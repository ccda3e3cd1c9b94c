"""CI guard: no ray_tpu module initializes a JAX backend at import time.
An accelerator belongs to one process, so an import-time `jax.devices()`
takes the chip from the worker or replica meant to own it.
tools/check_import_safety.py runs the whole package under a bogus
JAX_PLATFORMS canary in a bounded subprocess."""

import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_import_time_backend_init():
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "check_import_safety.py")],
        capture_output=True,
        text=True,
        timeout=300,
        cwd=_ROOT,
    )
    assert proc.returncode == 0, f"\n{proc.stdout}\n{proc.stderr}"
    assert "import safety OK" in proc.stdout

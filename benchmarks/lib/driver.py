"""What every runner's driver side shares: the runtime, the chip count, the
plain-data config handed to the chip-owning process. Never touches a jax
backend: `backend_initialized()` must stay False in the driver process."""

from __future__ import annotations

import contextlib
import os
import sys
import time
from typing import Any, Dict, Iterator

from .spec import ROOT, Cell


def backend_initialized() -> bool:
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def wait_pid_gone(pid: int, timeout_s: float = 30.0) -> bool:
    """Waits for the chip's owner to exit, so that the chip is free again."""
    from ray_tpu.core.zygote import PidHandle

    proc, deadline = PidHandle(pid), time.monotonic() + timeout_s
    while proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.02)
    return proc.poll() is not None


@contextlib.contextmanager
def runtime(cell: Cell) -> Iterator[int]:
    """rt.init() ... rt.shutdown(). Yields the chips to ask for (0 in a CPU
    rehearsal: whatever devices the worker's jax finds). Exits non-zero,
    before anything is measured, when the node has fewer chips than the cell
    needs: no path falls back to a CPU."""
    import ray_tpu as rt
    from ray_tpu.utils import compile_cache

    # Workers import benchmarks.* by name: the checkout's root on their path.
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    compile_cache.configure()  # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR is set
    rt.init()
    try:
        have = int(rt.cluster_resources().get("TPU", 0))
        if cell.allow_cpu:
            yield 0
        elif have < cell.chips:
            print(
                f"benchmark: cell {cell.name} needs {cell.chips} TPU chip(s), this node registers {have} "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); it never runs on a CPU.",
                file=sys.stderr,
            )
            raise SystemExit(3)
        else:
            yield cell.chips
    finally:
        rt.shutdown()


def worker_config(cell: Cell) -> Dict[str, Any]:
    return {
        "cell": cell.name,
        "model": cell.config,
        "traffic": cell.traffic,
        "seed": cell.seed,
        "seconds": cell.seconds,
        "trace": cell.trace,
        "allow_cpu": cell.allow_cpu,
        "out_prefix": cell.out_prefix,
    }

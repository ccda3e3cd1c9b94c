"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh (mirrors the reference's
single-machine multi-node test strategy, reference:
python/ray/tests/conftest.py ray_start_cluster / cluster_utils.Cluster) so
multi-chip sharding logic is exercised without TPU hardware.
"""

import os

# Must run before jax is imported: jax reads JAX_PLATFORMS and XLA_FLAGS at
# import / first backend init. Every runtime process the suite boots
# (raylet, workers, zygote) inherits this environment, so no test process
# ever opens an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic: no executable compiled by an earlier run (utils/compile_cache
# keeps them under <checkout>/.jax_cache) is loaded into this one.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Arm the dynamic lock-order detector for every runtime process the suite
# boots (raylet/GCS/serve-controller daemons inherit the env): an AB/BA
# inversion or >1s hold anywhere in tier-1 lands in the flight recorder
# and raytpu_lock_order_violations_total instead of staying a latent
# deadlock. Disarmed processes pay nothing (plain threading.Lock).
os.environ.setdefault("RAY_TPU_LOCK_ORDER", "1")

import sys
import time

import pytest

from ray_tpu.observability import logs as _logs


def _print_inside_capture(line: str) -> None:
    """The driver re-prints worker output from a background thread. Between
    two test phases pytest's capture is off for a moment, and a line printed
    just then lands in the middle of the progress dots — which is what
    tier-1 counts. Hold the line until capture is back (bounded, so `-s`
    still prints)."""
    deadline = time.monotonic() + 0.2
    while sys.stdout is sys.__stdout__ and time.monotonic() < deadline:
        time.sleep(0.001)
    print(line, flush=True)


_dedup_init = _logs.DedupPrinter.__init__
_logs.DedupPrinter.__init__ = lambda self, print_fn=None, **kw: _dedup_init(
    self, print_fn or _print_inside_capture, **kw
)


@pytest.fixture
def rt_local():
    """An initialized local-mode runtime (analogue of ray_start_regular)."""
    import ray_tpu as rt

    rt.shutdown()
    rt.init(local_mode=True, num_cpus=8)
    yield rt
    rt.shutdown()


@pytest.fixture(scope="session")
def stream_next_counts():
    """Reader of raytpu_stream_next_total by `woken`, as counted in this
    process: the cumulative per-thread cells that a flush reports deltas of
    (a lane's own _delta() would race the background flusher)."""
    from ray_tpu.utils import internal_metrics as imet

    lanes = {w: imet.STREAM_NEXT.labels(woken=w) for w in imet.STREAM_NEXT_WOKEN}

    def read():
        with imet._lock:
            return {
                w: lane._retired + sum(c[0] for _, c in lane._cells)
                for w, lane in lanes.items()
            }

    return read


@pytest.fixture
def rt_cluster():
    """An initialized single-node multi-process cluster."""
    import ray_tpu as rt

    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    yield rt
    rt.shutdown()

"""Import-safety check: no ray_tpu module may initialize a JAX backend
(or do any other blocking accelerator discovery) at import time.

The class of bug this guards against: an accelerator belongs to ONE
process. A module touching `jax.devices()` on import opens the chip in
every process that imports it — the driver, the raylet, the zygote — and
the worker or replica meant to own the chip then fails or hangs at its
first jax call.

Mechanism: run with `JAX_PLATFORMS` pinned to a platform name that does
not exist. Importing jax (and using jax.numpy types in annotations etc.)
stays legal, but the first backend resolution raises immediately instead
of probing hardware — so any module that initializes a backend at import
time fails loudly here. Then double-check the canary actually fires.

Run directly (CI) or through tests/test_import_safety.py:

    python tools/check_import_safety.py
"""

from __future__ import annotations

import importlib
import os
import pkgutil
import subprocess
import sys

CANARY_PLATFORM = "ray_tpu_import_safety_canary"

# Running as `python tools/check_import_safety.py` puts tools/ (not the
# repo root) on sys.path; the package under test must resolve regardless.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# Modules whose import is legitimately side-effectful beyond python code
# (native build tooling); everything else in the package must import clean.
SKIP = {
    "ray_tpu.native.build",
}

# Subsystems the walk MUST cover: a packaging slip that hides one of these
# (missing __init__, renamed dir) would silently shrink the check to
# nothing for that layer. The compiled-graph data plane is listed
# explicitly — its modules run inside every participating actor, so an
# import-time backend init there would wedge whole gangs at compile time.
REQUIRED = {
    "ray_tpu.cgraph",
    "ray_tpu.cgraph.compile",
    "ray_tpu.cgraph.communicator",
    "ray_tpu.cgraph.executor",
    "ray_tpu.cgraph.plan",
    "ray_tpu.core.channel",
    "ray_tpu.collective",
    # The observability layer imports into EVERY runtime process (the
    # flight recorder is always on; tracing imports it at module load) —
    # an import-time backend init here would wedge the whole cluster.
    "ray_tpu.observability",
    "ray_tpu.observability.flight_recorder",
    "ray_tpu.observability.logs",
    "ray_tpu.observability.perfetto",
    "ray_tpu.observability.history",
    "ray_tpu.observability.watchdog",
    "ray_tpu.observability.goodput",
    "ray_tpu.tracing",
    "ray_tpu.utils.sampling_profiler",
    # The chaos controller imports into every worker/raylet (its
    # injection points live on the task/channel/collective hot paths);
    # a backend init here would wedge the cluster with chaos DISARMED.
    "ray_tpu.chaos",
    "ray_tpu.chaos.controller",
    # The partition layer imports into core/rpc.py — i.e. every process
    # that owns an RpcClient (all of them).
    "ray_tpu.chaos.net",
    "ray_tpu.utils.node_events",
    # The elastic-training modules import into every training worker
    # (ray_tpu.train re-exports them) and the cgraph elastic wrapper
    # into every gang driver — a backend init here would wedge restores.
    "ray_tpu.train.elastic_checkpoint",
    "ray_tpu.train.zero",
    "ray_tpu.cgraph.elastic",
    # The lock-order detector imports into the raylet, GCS, serve
    # controller, and driver at module load; a backend init here would
    # wedge every control plane at boot.
    "ray_tpu.utils.lock_order",
    # The sharded-GCS layer: gcs_shards imports into the GCS daemon at
    # boot (shard routing + WAL segments), heartbeat into EVERY raylet
    # (the delta codec runs on the 1 Hz beat path) — an import-time
    # backend init in either would wedge the control plane.
    "ray_tpu.core.gcs_shards",
    "ray_tpu.core.heartbeat",
    # The warm-pool layer: the zygote pre-imports the ENTIRE worker
    # stack before forking (an import-time backend init there would
    # wedge every pre-forked worker), and the pool manager imports into
    # every raylet.
    "ray_tpu.core.worker_pool",
    "ray_tpu.core.zygote",
    "ray_tpu.core.worker_proc",
    # Runs at every worker's start-up: it may point jax at a cache
    # directory but must never open the chip the worker might not own.
    "ray_tpu.utils.compile_cache",
    # The LLM serving stack: serve/__init__ lazy-loads it (PEP 562) so
    # plain serve users never import it, but LLM replicas import the
    # whole package at deployment build — an import-time backend init
    # here would wedge replica startup (jax use must stay inside the
    # PagedLM constructor, not at module scope).
    "ray_tpu.serve.llm",
    "ray_tpu.serve.llm.engine",
    "ray_tpu.serve.llm.kv_cache",
    "ray_tpu.serve.llm.model",
    "ray_tpu.serve.llm.deployment",
    "ray_tpu.serve.llm.feed",
    # The streaming data plane: executor + op_pool import into every
    # driver that iterates a Dataset, feed into every trainer worker /
    # serve replica consuming a channel split — an import-time backend
    # init in any of them would wedge ingest across the fleet.
    "ray_tpu.data.streaming",
    "ray_tpu.data.executor",
    "ray_tpu.data.op_pool",
    "ray_tpu.data.feed",
    "ray_tpu.serve.ingest",
}


def iter_module_names() -> list:
    import ray_tpu

    names = ["ray_tpu"]
    for info in pkgutil.walk_packages(ray_tpu.__path__, prefix="ray_tpu."):
        if info.name in SKIP or "._build" in info.name:
            continue
        names.append(info.name)
    return sorted(names)


def check() -> int:
    assert os.environ.get("JAX_PLATFORMS") == CANARY_PLATFORM, (
        "run me via main() — the canary platform must be set before "
        "any jax import"
    )
    names = iter_module_names()
    missing = REQUIRED - set(names)
    if missing:
        print(f"coverage hole: required modules not discovered: {sorted(missing)}")
        return 3
    failed = []
    for name in names:
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001
            failed.append((name, repr(e)))
    if failed:
        print("modules with import-time backend init (or import errors):")
        for name, err in failed:
            print(f"  {name}: {err}")
        return 1
    # Verify the canary is live: if jax resolved a backend anyway, the
    # whole check was vacuous (e.g. a future jax ignoring JAX_PLATFORMS).
    import jax

    try:
        jax.devices()
    except Exception:
        pass  # expected: unknown platform cannot initialize
    else:
        print("canary failed: jax.devices() succeeded under a bogus platform")
        return 2
    print(f"import safety OK: {len(iter_module_names())} modules, no backend init")
    return 0


def main() -> int:
    if os.environ.get("_RAY_TPU_IMPORT_SAFETY_CHILD") == "1":
        return check()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = CANARY_PLATFORM
    env["_RAY_TPU_IMPORT_SAFETY_CHILD"] = "1"
    # A hang IS the failure mode being guarded against: bound the child.
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env,
        timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())

"""Runner `train_steps`: the step loop on a fixed device-resident batch,
through rt.init() -> JaxTrainer.fit() -> build_train_step, one worker that
owns every chip of the cell. The driver side only: it never opens a jax
backend; the loop itself is benchmarks/lib/worker_train.train_loop."""

from __future__ import annotations

import shutil
import tempfile
from typing import Any, Dict

from ..lib import driver
from ..lib.spec import Cell
from ..lib.worker_train import train_loop


def run(cell: Cell) -> Dict[str, Any]:
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    with driver.runtime(cell) as chips:
        storage = tempfile.mkdtemp(prefix="bench_train_")
        try:
            result = JaxTrainer(
                train_loop,
                train_loop_config=driver.worker_config(cell),
                scaling_config=ScalingConfig(
                    num_workers=1,
                    mesh=MeshSpec(data=-1),
                    resources_per_worker={"CPU": 1, "TPU": chips} if chips else None,
                ),
                run_config=RunConfig(name="bench_" + cell.name, storage_path=storage, verbose=0),
            ).fit()
        finally:
            shutil.rmtree(storage, ignore_errors=True)
        if result.error is not None:
            raise result.error
        worker = dict(result.metrics["summary"])
        driver.wait_pid_gone(worker["pid"])
    steps_ms = sorted((t1 - t0) * 1e3 for _name, t0, t1, _args in worker["spans"])
    return {
        # a whole-host stall shows as ONE long step (PERF.md, PR 26): the facts line says so
        "load_facts": {"steps": len(steps_ms), "step_ms_p50": steps_ms[len(steps_ms) // 2], "step_ms_longest": steps_ms[-3:]},
        "cell": cell,
        "worker": worker,
        "window": worker["window"],
        "spans": worker["spans"],
        "attempted": worker["attempted"],
        "failed": worker["failed"],
        "correct": all(worker["checks"].values()),
        "checks": worker["checks"],
        "training": worker["training"],
        "compared": worker["compared"],
    }

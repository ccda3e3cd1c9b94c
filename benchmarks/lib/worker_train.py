"""The benchmark's train_loop_per_worker: runs in the worker that owns the
chip(s), through JaxTrainer.fit(). Everything that touches jax in a training
cell is here: seeded weights and batch made on the device, the correctness
check against the plain reference, warm-up, the measured window around
`block_until_ready`, CompileWatch around it, and (traced runs) a short
traced segment AFTER the window, so that the window's host-clock numbers
carry no tracing cost.
"""

from __future__ import annotations

import contextlib
import glob
import math
import os
import shutil
import time
from typing import Any, Dict, List


def seeded_key(seed: int):
    """A PRNG key from a seed wider than 31 bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF), int(seed) >> 31)


def cache_everything() -> None:
    """Every executable goes to the persistent cache, not only those that
    took over a second to compile: a later run's set-up then compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_facts(devices, allow_cpu: bool) -> Dict[str, Any]:
    """Platform, kind and count as jax reports them; refuses anything but a
    TPU whose kind is in the peaks table (tests and rehearsals pass allow_cpu)."""
    import jax

    from . import peaks

    d = devices[0]
    facts = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    if not allow_cpu:
        if d.platform != "tpu":
            raise RuntimeError(f"the benchmark measures only on a TPU; jax found {facts}")
        peaks.for_kind(d.device_kind)
    return facts


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def find_xplane(logdir: str) -> str:
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    return path


class FirstSteps:
    """What the program's first steps read, for `lib/correct.compare_training`:
    each step's loss; after the first, the gradient's norms as the optimizer
    got it (adam's first moment is then (1 - b1) x the gradient); after the
    last, the norms of the parameters' change from those the seed makes
    (`make_params`: made again for the subtraction and dropped, a few tenths
    of a second, rather than held through the run)."""

    STEPS = 3

    def __init__(self, make_params):
        import jax

        from . import correct

        self.make_params = make_params
        self.losses: List[float] = []
        self.grad_norms = self.change_norms = None
        self._norms, self._change = jax.jit(correct.leaf_norms), jax.jit(correct.change_norms)
        self._first_moment, self._b1 = correct.first_moment, correct.ADAMW["b1"]

    def after_step(self, number: int, loss: float, params, opt_state) -> None:
        import numpy as np

        if number <= self.STEPS:
            self.losses.append(loss)
        if number == 1:
            self.grad_norms = np.asarray(self._norms(self._first_moment(opt_state)), np.float64) / (1 - self._b1)
        if number == self.STEPS:
            self.change_norms = np.asarray(self._change(params, self.make_params()), np.float64)

    def readings(self) -> Dict[str, Any]:
        if self.change_norms is None:
            raise RuntimeError(f"the traffic file's warmup_steps must give at least {self.STEPS} steps before the window")
        return {"losses": self.losses, "grad_norms": self.grad_norms, "change_norms": self.change_norms}


def leaf_names(init, key) -> List[str]:
    import jax

    return [jax.tree_util.keystr(path) for path, _leaf in jax.tree_util.tree_flatten_with_path(jax.eval_shape(init, key))[0]]


def train_loop(config: Dict[str, Any]) -> None:
    t_enter = time.monotonic()
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train import zero
    from ray_tpu.utils import compile_cache

    from . import correct, spec

    watch = compile_cache.watch()
    cache_everything()
    traffic, seed = config["traffic"], config["seed"]
    mesh = train.get_mesh()
    devices = list(mesh.devices.flat)
    n = len(devices)
    device = device_facts(devices, config["allow_cpu"])
    t_devices = time.monotonic()

    seq, per_chip = int(traffic["seq_len"]), int(traffic["batch_per_chip"])
    arch = spec.load_arch(config["model"])
    cfg = arch.model_config(config["model"], max_seq_len=seq)
    lr = config["model"]["assumed"]["learning_rate"]["value"]
    zero_axis = "data" if n > 1 else None
    tx = optax.adamw(lr, **correct.ADAMW)
    _init_state, step = tfm.build_train_step(cfg, tx, mesh, zero_axis=zero_axis)

    # Weights and batch: one jitted call each, from the seed, on the device,
    # in the type they are trained in (init_state's eager init compiles a
    # program per random call: ~50 s cold, PERF.md).
    rep = NamedSharding(mesh, P())
    key = seeded_key(seed)
    init = jax.jit(lambda k: correct.init_weights(tfm, cfg, k), out_shardings=rep)
    params = init(key)
    if zero_axis is None:
        opt_state = jax.jit(tx.init, out_shardings=rep)(params)
    else:
        opt_state = zero.init_opt_state(tx, params, mesh, zero_axis)
    tokens = jax.jit(
        lambda k: jax.random.randint(k, (per_chip * n, seq), 0, cfg.vocab_size, jnp.int32),
        out_shardings=NamedSharding(mesh, P("data")),
    )(jax.random.fold_in(key, 1))

    t_init = time.monotonic()

    def one_step(i, traced):
        nonlocal params, opt_state
        ctx = jax.profiler.TraceAnnotation("bench.train_step", step=i, tokens=per_chip * n * seq) if traced else contextlib.nullcontext()
        t0 = time.monotonic()
        with ctx:
            params, opt_state, loss = step(params, opt_state, tokens)
            jax.block_until_ready(loss)
        return t0, time.monotonic(), loss

    # The first steps of the one compiled step and state that the window
    # then drives on, through the window's own call: their losses, the first
    # gradient's norms and the parameters' change are what `correct`
    # compares with the reference that trains (after the window).
    first = FirstSteps(lambda: init(key))
    warm: List[float] = []
    for i in range(1 + int(traffic["warmup_steps"])):  # the first call compiles or loads
        warm.append(float(one_step(i, False)[2]))
        first.after_step(i + 1, warm[-1], params, opt_state)
    t_warm = time.monotonic()

    before = watch.snapshot()
    spans, losses = [], []
    w0 = time.monotonic()
    while True:
        t0, t1, loss = one_step(len(spans), False)
        spans.append(["bench.train_step", t0, t1, {"tokens": per_chip * n * seq}])
        losses.append(loss)
        if t1 - w0 >= config["seconds"]:
            break
    w1 = spans[-1][2]
    after = watch.snapshot()
    losses = [float(x) for x in losses]

    trace_path = None
    if config["trace"]:
        logdir = config["out_prefix"] + "-trace"
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
        try:
            for i in range(int(traffic["trace_steps"])):
                one_step(i, True)
        finally:
            jax.profiler.stop_trace()
        trace_path = find_xplane(logdir)

    # The reference that trains, after the window and the traced segment,
    # with the peak read and the trained state freed, so that neither
    # setup_s nor a metric sees it: the same batch, the same first steps.
    peak_bytes = memory_peak_bytes(devices)
    n_params = tfm.param_count(params)
    t_check = time.monotonic()
    params = opt_state = None
    reference = correct.training_reference(arch, config["model"], mesh, lr, FirstSteps.STEPS)(lambda: init(key), tokens)
    compared, training = correct.compare_training(first.readings(), reference, traffic["correctness"], leaf_names(init, key))
    training["seconds"] = time.monotonic() - t_check
    checks = {name: value <= limit for name, (value, limit) in compared.items()}
    checks["losses_finite"] = all(math.isfinite(x) for x in warm + losses)
    train.report({"summary": {
        "pid": os.getpid(),
        "device": device,
        "chips": n,
        "zero_axis": zero_axis,
        "window": [w0, w1],
        "spans": spans,
        "tokens_per_step": per_chip * n * seq,
        "attempted": len(losses),
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "checks": checks,
        "compared": compared,
        "training": training,
        "loss": {"first_steps": warm, "reference": reference["losses"], "window_last": losses[-1]},
        "compile": {"before": before, "after": after},
        "memory_peak_bytes": peak_bytes,
        "trace_path": trace_path,
        "setup_parts_s": {
            "worker_start_to_devices": t_devices - t_enter,
            "init": t_init - t_devices,
            "first_steps": t_warm - t_init,
        },
        "n_params": n_params,
        "arch_file": os.path.relpath(arch.__file__, spec.ROOT),
    }})

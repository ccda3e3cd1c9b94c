"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two payload paths once, through the entry points a user calls, at
Llama-2-7B widths (d_model 4096, 32x128 heads, d_ff 11008, vocab 32000,
bf16, fused attention) cut only in depth, with seeded random weights:

  train leg  rt.init() -> JaxTrainer(...).fit(): one worker owning every
             chip of the host, tfm.build_train_step (ZeRO over "data" when
             the mesh has more than one device), a few steps on a fixed batch.
  serve leg  serve.run(llm_deployment(tiny_paged_lm, ...)): one replica
             owning one chip, streamed greedy requests of several lengths
             joining and leaving the decode batch, two sharing a prefix.

The process running this file never initializes a jax backend: a chip
belongs to one process, and that is the trainer's worker, then the replica.
Every check that fails ends the run non-zero. A successful run prints a
summary line of facts (it measures nothing that may be called a speed:
"claim": null) and then, as the LAST stdout line, the contract object
{"ok": true, "device": {"platform", "kind", "count"}} and nothing else in it.
Without a TPU neither line is printed.

The legs are plain functions of a config, so tests/test_chip_smoke.py runs
the same code at tfm.tiny width on the CPU; only main() insists on a chip.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List


class SmokeFailure(AssertionError):
    """A leg ran but one of its checks does not hold."""


def _require(leg: str, checks: Dict[str, bool], facts: Dict[str, Any]) -> None:
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        raise SmokeFailure(
            f"{leg} leg failed checks {failed}:\n{json.dumps(facts, indent=1, default=str)}"
        )


def driver_backend_initialized() -> bool:
    """True once THIS process has opened a jax backend (and so, on a TPU
    host, taken the chip from the process meant to own it)."""
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def wait_pid_gone(pid: int, timeout_s: float = 30.0) -> bool:
    """Waits for `pid` to exit (a zombie counts: it has closed its device)."""
    from ray_tpu.core.zygote import PidHandle

    proc, deadline = PidHandle(pid), time.monotonic() + timeout_s
    while proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.02)
    return proc.poll() is not None


# ------------------------------------------------------------------ train


def full_train_config() -> Dict[str, Any]:
    """Llama-2-7B widths, 4 of 32 layers: 1.07 B params, what one v5e chip
    holds with adamw state at batch 2 x 2048 (PERF.md, cells)."""
    from ray_tpu.models import transformer as tfm

    return {
        "cfg": tfm.llama2_7b(n_layers=4, max_seq_len=2048, remat_policy="hot"),
        "batch_per_chip": 2,
        "seq": 2048,
        "steps": 4,
        "lr": 1e-4,
        "seed": 0,
    }


def _train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: runs in the worker that owns the chip(s)."""
    import os
    import time

    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu import train
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import shard_batch
    from ray_tpu.train import zero
    from ray_tpu.utils import compile_cache

    watch = compile_cache.watch()
    mesh = train.get_mesh()
    devices = list(mesh.devices.flat)
    n = len(devices)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }
    if config["require_tpu"] and device["platform"] != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; the worker's jax found {device} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). No leg runs on CPU."
        )
    cfg = config["cfg"]
    per_chip, seq = config["batch_per_chip"], config["seq"]
    zero_axis = "data" if n > 1 else None
    init_state, step = tfm.build_train_step(
        cfg, optax.adamw(config["lr"]), mesh, zero_axis=zero_axis
    )
    t0 = time.perf_counter()
    params, opt_state = init_state(jax.random.PRNGKey(config["seed"]))
    jax.block_until_ready((params, opt_state))
    init_s = time.perf_counter() - t0
    n_params = tfm.param_count(params)
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))

    host_tokens = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (per_chip * n, seq), dtype=np.int32
    )
    tokens = shard_batch({"tokens": host_tokens}, mesh)["tokens"]

    # Reference for step-0 loss: forward only, unfused attention, ONE
    # device, the global batch in per-chip pieces. On one chip it checks
    # the fused kernel against the reference; on several it is also "the
    # one-chip value for the same global batch".
    ref_cfg = cfg.replace(attn_impl="naive", remat=False)
    ref_fn = jax.jit(lambda p, t: tfm.next_token_loss(p, t, ref_cfg, None))
    ref_loss = float(
        np.mean(
            [
                float(ref_fn(params, host_tokens[i * per_chip : (i + 1) * per_chip]))
                for i in range(n)
            ]
        )
    )

    mosaic_calls = step.lower(params, opt_state, tokens).as_text().count("tpu_custom_call")
    before_step = watch.snapshot()
    losses: List[float] = []
    step_s: List[float] = []
    for i in range(config["steps"] + 1):  # the first call compiles
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready((params, opt_state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        if i == 0:
            after_first = watch.snapshot()
        train.report({"step": i, "loss": losses[-1], "step_s": step_s[-1]})
    step_compile_s = after_first["compile_s"] - before_step["compile_s"]

    mem = [d.memory_stats() or {} for d in devices]
    summary: Dict[str, Any] = {
        "owner_pid": os.getpid(),
        "device": device,
        "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1} or {"data": 1},
        "zero_axis": zero_axis,
        "n_layers": cfg.n_layers,
        "n_params": n_params,
        "global_batch": per_chip * n,
        "seq": seq,
        "init_s": round(init_s, 3),
        "mosaic_custom_calls_lowered": mosaic_calls,
        "first_step_s": round(step_s[0], 3),
        "step_compile_s": round(step_compile_s, 3),
        "step_s": [round(s, 4) for s in step_s[1:]],
        "compiles_after_first_step": watch.compiles - after_first["compiles"],
        "losses": [round(x, 4) for x in losses],
        "ref_loss_one_device": round(ref_loss, 4),
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
        "compile": watch.snapshot(),
    }
    if n > 1:
        replicated = NamedSharding(mesh, PartitionSpec())
        vectors = [
            x for x in jax.tree_util.tree_leaves(opt_state) if getattr(x, "ndim", 0) == 1
        ]
        per_dev = [zero.per_device_bytes(opt_state, d) for d in devices]
        summary["zero"] = {
            "opt_state_bytes_total": sum(
                x.nbytes for x in jax.tree_util.tree_leaves(opt_state)
            ),
            "opt_state_bytes_per_device": per_dev,
            "param_bytes": param_bytes,
            "opt_vectors_on_all_devices": all(
                len({s.device for s in x.addressable_shards}) == n
                and all(s.data.shape[0] * n == x.shape[0] for s in x.addressable_shards)
                for x in vectors
            ),
            "params_replicated": all(
                x.sharding.is_equivalent_to(replicated, x.ndim)
                for x in jax.tree_util.tree_leaves(params)
            ),
        }
    train.report({"summary": summary})


def train_leg(
    config: Dict[str, Any], *, num_tpus: int = 0, require_tpu: bool = False
) -> Dict[str, Any]:
    """JaxTrainer(...).fit() with one worker that owns `num_tpus` chips (0:
    whatever devices the worker's jax finds, e.g. the CPU mesh of a test).
    Needs rt.init() in cluster mode. Returns the worker's facts; raises
    SmokeFailure when a check does not hold."""
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={**config, "require_tpu": require_tpu},
            scaling_config=ScalingConfig(
                num_workers=1,
                mesh=MeshSpec(data=-1),
                resources_per_worker={"CPU": 1, "TPU": num_tpus} if num_tpus else None,
            ),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        ).fit()
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    if result.error is not None:
        raise result.error
    facts = dict(result.metrics["summary"])
    facts["driver_backend_initialized"] = driver_backend_initialized()
    facts["owner_exited"] = wait_pid_gone(facts["owner_pid"])

    losses, vocab = facts["losses"], config["cfg"].vocab_size
    # Unit-variance logits at init put the loss at ln(vocab) + 1/2.
    checks = {
        "driver_never_opened_a_backend": not facts["driver_backend_initialized"],
        "chip_owner_exited_after_fit": facts["owner_exited"],
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss0_near_ln_vocab": abs(losses[0] - (math.log(vocab) + 0.5)) < 0.5,
        "loss0_matches_one_device_reference": abs(losses[0] - facts["ref_loss_one_device"]) < 0.05,
        "loss_fell": losses[-1] < losses[0],
        "no_compile_after_first_step": facts["compiles_after_first_step"] == 0,
    }
    if require_tpu:
        checks["platform_is_tpu"] = facts["device"]["platform"] == "tpu"
        checks["flash_kernel_compiled_by_mosaic"] = facts["mosaic_custom_calls_lowered"] >= 1
        checks["peak_hbm_reported"] = all(facts["peak_bytes_in_use"])
    z = facts.get("zero")
    if z is not None:
        n = len(z["opt_state_bytes_per_device"])
        checks["opt_state_sharded_over_all_devices"] = z["opt_vectors_on_all_devices"]
        checks["opt_state_per_device_is_a_share"] = all(
            b <= 1.05 * z["opt_state_bytes_total"] / n for b in z["opt_state_bytes_per_device"]
        )
        checks["params_replicated"] = z["params_replicated"]
        in_use = facts["bytes_in_use"]
        if all(in_use):
            checks["no_device_holds_the_whole_job"] = max(in_use) <= 1.5 * min(in_use)
    facts["checks"] = checks
    _require("train", checks, facts)
    return facts


# ------------------------------------------------------------------ serve


def full_serve_config() -> Dict[str, Any]:
    """Llama-2-7B widths, 8 of 32 layers (3.8 GB bf16) and a 1024-page pool
    (2.1 GB): both paged steps hold a second copy of the pool as temp, so
    the pool may take about half of what the weights leave (PERF.md)."""
    from ray_tpu.models import transformer as tfm

    return {
        "cfg": tfm.llama2_7b(n_layers=8),
        "num_pages": 1024,
        "page_tokens": 16,
        "max_slots": 16,
        "max_pages_per_seq": 128,
        "seed": 0,
        # (prompt length, max_new_tokens): three prefill buckets (4, 16 and
        # 32 pages). "long" decodes alone first; the rest join while it
        # runs and leave at different steps. "long_shared" repeats its
        # first 160 tokens (10 full pages) under another tail.
        "requests": {
            "long": (200, 48),
            "long_shared": (200, 32),
            "short_a": (40, 16),
            "short_b": (50, 24),
            "longest": (450, 32),
        },
        "shared_prefix_tokens": 160,
    }


def _prompts(config: Dict[str, Any]) -> Dict[str, List[int]]:
    import numpy as np

    rng = np.random.default_rng(config["seed"] + 1)
    vocab = config["cfg"].vocab_size
    prompts = {
        name: rng.integers(1, vocab, length).tolist()
        for name, (length, _new) in config["requests"].items()
    }
    k = config["shared_prefix_tokens"]
    prompts["long_shared"][:k] = prompts["long"][:k]
    return prompts


def serve_leg(
    config: Dict[str, Any], *, num_tpus: int = 0, require_tpu: bool = False
) -> Dict[str, Any]:
    """serve.run(llm_deployment(tiny_paged_lm, ...)) and streamed greedy
    requests through the deployment handle. Needs rt.init() in cluster
    mode. Returns the replica's facts; raises SmokeFailure when a check
    does not hold. Any per-request error fails the leg even though the
    engine loop survives it."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import EngineConfig, llm_deployment, tiny_paged_lm

    requests, prompts = config["requests"], _prompts(config)
    app = llm_deployment(
        tiny_paged_lm,
        name="chip_smoke_llm",
        model_kwargs={
            "cfg": config["cfg"],
            "seed": config["seed"],
            "num_pages": config["num_pages"],
            "page_tokens": config["page_tokens"],
            "max_slots": config["max_slots"],
            "max_pages_per_seq": config["max_pages_per_seq"],
        },
        engine_config=EngineConfig(
            page_tokens=config["page_tokens"], pool_pages=config["num_pages"]
        ),
        ray_actor_options={"num_tpus": num_tpus} if num_tpus else None,
    )
    t0 = time.perf_counter()
    handle = serve.run(app, name="chip_smoke_llm", http_port=None)
    stream = handle.options(stream=True)
    stats_of = handle.options(method_name="engine_stats")
    tokens: Dict[str, List[int]] = {}
    errors: Dict[str, str] = {}
    answering = threading.Event()  # some request has a token (or has failed)

    def ask(name: str, prompt_name: str) -> None:
        got: List[int] = []
        try:
            for tok in stream.remote(prompts[prompt_name], requests[prompt_name][1]):
                got.append(int(tok))
                answering.set()
        except Exception as e:  # noqa: BLE001 - recorded; any error fails the leg below
            errors[name] = f"{type(e).__name__}: {e}"
        finally:
            answering.set()
        tokens[name] = got

    def run(names: Dict[str, str]) -> List[threading.Thread]:
        threads = [
            threading.Thread(target=ask, args=(name, prompt_name), daemon=True)
            for name, prompt_name in names.items()
        ]
        for t in threads:
            t.start()
        return threads

    try:
        # "long" first and alone: its prompt must be committed to the prefix
        # index before "long_shared" is admitted, and its first token is
        # where the prefill and decode executables get compiled.
        threads = run({"long": "long"})
        if not answering.wait(timeout=900):
            raise SmokeFailure("serve leg: no first token within 900 s")
        first_token_s = time.perf_counter() - t0
        threads += run({n: n for n in requests if n != "long"})
        for t in threads:
            t.join(timeout=900)
        # The same prompt again, alone: greedy decoding must repeat itself.
        for t in run({"short_a_again": "short_a"}):
            t.join(timeout=900)
        stats = stats_of.remote().result(timeout=60)
    finally:
        serve.shutdown()
    model = stats.pop("model")
    facts: Dict[str, Any] = {
        "owner_pid": model["pid"],
        "device": {
            "platform": model["platform"],
            "kind": model["device_kind"],
            "count": model["device_count"],
        },
        "n_layers": config["cfg"].n_layers,
        "pool_pages": config["num_pages"],
        "page_tokens": config["page_tokens"],
        "max_slots": config["max_slots"],
        "max_pages_per_seq": config["max_pages_per_seq"],
        "first_token_s": round(first_token_s, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
        "tokens_returned": {name: len(toks) for name, toks in tokens.items()},
        "errors": errors,
        "engine": stats,
        "peak_bytes_in_use": model["peak_bytes_in_use"],
        "compile": model["compile"],
        "driver_backend_initialized": driver_backend_initialized(),
        "owner_exited": wait_pid_gone(model["pid"]),
    }
    expected = {name: new for name, (_len, new) in requests.items()}
    expected["short_a_again"] = requests["short_a"][1]
    vocab = config["cfg"].vocab_size
    checks = {
        "driver_never_opened_a_backend": not facts["driver_backend_initialized"],
        "chip_owner_exited_after_shutdown": facts["owner_exited"],
        "no_request_errors": not errors,
        "every_request_returned_max_new_tokens": facts["tokens_returned"] == expected,
        "tokens_in_vocab": all(0 <= t < vocab for toks in tokens.values() for t in toks),
        "nothing_shed": stats["shed_total"] == 0,
        "engine_not_failed": stats["failed"] is None,
        "decode_steps_ran": stats["decode_steps"] > 0,
        "prefix_hit_recorded": stats["kv"]["prefix_hits"] >= 1,
        "same_prompt_same_tokens": tokens.get("short_a") == tokens.get("short_a_again"),
        "all_pages_released": stats["kv"]["used_pages"] == 0,
    }
    if require_tpu:
        checks["platform_is_tpu"] = facts["device"]["platform"] == "tpu"
        checks["peak_hbm_reported"] = all(facts["peak_bytes_in_use"])
    facts["checks"] = checks
    _require("serve", checks, facts)
    return facts


# ------------------------------------------------------------------- main


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if not name.startswith("."))
    except OSError:
        return 0


def contract_line(device: Dict[str, Any]) -> str:
    """The last stdout line of a successful run: exactly these keys, the
    device as the chip's owner got it from jax."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"]),
            },
        }
    )


def main() -> int:
    t_start = time.perf_counter()
    import ray_tpu as rt
    from ray_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()
    entries_before = _cache_entries(cache_dir)
    rt.init()  # the multi-process cluster runtime; chips are auto-detected
    try:
        chips = int(rt.cluster_resources().get("TPU", 0))
        if chips < 1:
            print(
                "chip_smoke: no TPU on this node (cluster resources: "
                f"{rt.cluster_resources()}, JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}); it never runs on CPU.",
                file=sys.stderr,
            )
            return 2
        print(f"chip_smoke: node registers TPU={chips}; compile cache at {cache_dir} "
              f"({entries_before} entries)", flush=True)

        train = train_leg(full_train_config(), num_tpus=chips, require_tpu=True)
        print("chip_smoke: train leg ok " + json.dumps(train, default=str), flush=True)

        serve = serve_leg(full_serve_config(), num_tpus=1, require_tpu=True)
        print("chip_smoke: serve leg ok " + json.dumps(serve, default=str), flush=True)
    finally:
        rt.shutdown()

    summary = {
        "device": train["device"],
        "driver_backend_initialized": driver_backend_initialized(),
        "chip_owners": {"train_worker_pid": train["owner_pid"], "serve_replica_pid": serve["owner_pid"]},
        "wall_s": round(time.perf_counter() - t_start, 1),
        "cache": {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": _cache_entries(cache_dir),
            "train": train["compile"],
            "serve": serve["compile"],
        },
        "train": {k: train[k] for k in (
            "mesh", "n_layers", "n_params", "global_batch", "seq", "losses",
            "ref_loss_one_device", "first_step_s", "step_compile_s", "step_s",
            "mosaic_custom_calls_lowered", "peak_bytes_in_use")},
        "serve": {k: serve[k] for k in (
            "device", "n_layers", "pool_pages", "max_slots", "first_token_s",
            "wall_s", "tokens_returned", "peak_bytes_in_use")},
        "claim": None,
    }
    if summary["driver_backend_initialized"]:
        raise SmokeFailure("the driver process initialized a jax backend")
    print("chip_smoke: summary " + json.dumps(summary), flush=True)
    print(contract_line(train["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The two rehearsals that cost no chip time (on-chip-measurement guide, section 2).

    python3 benchmarks/rehearse.py cpu [cell ...]   each cell end to end on the CPU at tiny
                                                    widths (4 virtual devices for a 4-chip cell)
    python3 benchmarks/rehearse.py aot [cell ...]   each cell's step programs compiled at the
                                                    published widths for v5e:2x2, memory_analysis()

Neither is a measurement: a CPU run gives no device number, and a compile
that passes is not a chip run. They find wrong paths, meshes and shapes that
do not fit before a chip-minute is spent on them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GIB = 1024**3


def _cells(names):
    from benchmarks.lib import spec

    all_names = [w["name"] for w in spec.benchmark_json()["workloads"]]
    return [spec.find_cell(n) for n in (names or all_names)]


# ----------------------------------------------------------------- cpu


def rehearse_cpu(names) -> int:
    """Runs each cell through run.main() in a process of its own: the same
    runner, worker code, readers and last line as on the chip, with the
    configuration's widths replaced by its architecture file's TINY ones."""
    failed = 0
    for cell in _cells(names):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={max(cell.chips, 1)}"
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache", "cpu_rehearsal")
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"), "one", "--workload", cell.name,
               "--seed", "3000000019", "--seconds", "3", "--trace", "1"]
        t0 = time.monotonic()
        p = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
        last = (p.stdout.strip().splitlines() or [""])[-1]
        ok = p.returncode == 0 and last.startswith("{")
        print(f"[cpu] {cell.name}: rc={p.returncode} {time.monotonic() - t0:.0f}s {last[:600]}")
        if not ok:
            failed += 1
            print(p.stdout[-3000:], p.stderr[-2500:])
    return failed


# ----------------------------------------------------------------- aot


def _topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    return {
        "args_GiB": round(m.argument_size_in_bytes / GIB, 2),
        "out_GiB": round(m.output_size_in_bytes / GIB, 2),
        "temp_GiB": round(m.temp_size_in_bytes / GIB, 2),
        "alias_GiB": round(m.alias_size_in_bytes / GIB, 2),
        "total_GiB": round(total / GIB, 2),
    }


def _force_mosaic():
    """The flash kernel picks interpret mode from THIS process's backend
    (CPU here); compile it for the described TPU instead."""
    import ray_tpu.ops.flash_attention  # noqa: F401

    sys.modules["ray_tpu.ops.flash_attention"]._auto_interpret = lambda: False


def aot_train(cell, batch_per_chip=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer as tfm
    from ray_tpu.train import zero

    topo = _topology()
    _force_mosaic()
    n = cell.chips
    mesh = Mesh(np.array(topo.devices[:n]), ("data",))
    cfg = cell.arch.model_config(cell.config, max_seq_len=cell.traffic["seq_len"])
    tx = optax.adamw(1e-4)
    zero_axis = "data" if n > 1 else None
    _init, step = tfm.build_train_step(cfg, tx, mesh, zero_axis=zero_axis)
    rep = NamedSharding(mesh, P())
    abstract = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), abstract)
    if zero_axis is None:
        opt = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), jax.eval_shape(tx.init, abstract)
        )
    else:
        sharder = zero.ZeroSharder(abstract, mesh, zero_axis)
        local = jax.eval_shape(tx.init, sharder.shard_struct())
        specs = sharder.opt_specs(local)
        opt = jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                (x.shape[0] * n,) if s == P(zero_axis) else x.shape, x.dtype, sharding=NamedSharding(mesh, s)
            ),
            local, specs,
        )
    b = batch_per_chip or cell.traffic["batch_per_chip"]
    tokens = jax.ShapeDtypeStruct((b * n, cell.traffic["seq_len"]), jnp.int32, sharding=NamedSharding(mesh, P("data")))
    t0 = time.monotonic()
    compiled = step.lower(params, opt, tokens).compile()
    text = compiled.as_text()
    yield {
        "program": f"train step, batch {b}/chip x {cell.traffic['seq_len']}, {n} chip(s)",
        "compile_s": round(time.monotonic() - t0, 1),
        "mosaic_custom_calls": text.count("tpu_custom_call"),
        "collectives": {k: text.count(k + "(") + text.count(k + "-start(") for k in ("reduce-scatter", "all-gather", "all-reduce")},
        **_mem(compiled),
    }
    # The reference that trains (lib/correct.training_reference) runs on the same chips after the window: it has to fit too.
    from benchmarks.lib import correct

    t0 = time.monotonic()
    reference = correct.training_reference(cell.arch, cell.config, mesh, 1e-4, 3).step
    compiled = reference.lower(params, params, params, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep), tokens).compile()
    yield {"program": "the reference's training step, same batch", "compile_s": round(time.monotonic() - t0, 1), **_mem(compiled)}


def aot_serve(cell) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import traffic as traffic_lib
    from ray_tpu.models import transformer as tfm

    topo = _topology()
    _force_mosaic()
    one = SingleDeviceSharding(topo.devices[0])
    cfg = cell.arch.model_config(cell.config)
    eng = {k: v["value"] for k, v in cell.config["assumed"].items()}
    T, P_, B, N = eng["page_tokens"], eng["max_pages_per_seq"], eng["max_slots"], eng["pool_pages"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    )
    kv = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(lambda: tfm.init_kv_pages(cfg, N, T)))

    def decode(params, tokens, positions, kv, bts):
        logits, kv = tfm.forward_decode(params, tokens, positions, cfg, kv, bts)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), kv

    def prefill(params, tokens, kv, bt, length, write_from):
        logits, kv = tfm.forward_prefill(params, tokens, cfg, kv, bt, length, write_from)
        return jnp.argmax(logits[0], axis=-1).astype(jnp.int32), kv

    rows = []
    t0 = time.monotonic()
    c = jax.jit(decode, donate_argnums=(3,)).lower(
        params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, P_), jnp.int32)
    ).compile()
    rows.append({"program": f"decode, {B} slots x {P_} pages, pool {N}", "compile_s": round(time.monotonic() - t0, 1), **_mem(c)})
    for bucket in traffic_lib.prefill_buckets(cell.traffic, T, P_):
        t0 = time.monotonic()
        c = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, sds((1, bucket * T), jnp.int32), kv, sds((bucket,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)
        ).compile()
        rows.append({
            "program": f"prefill bucket {bucket} pages ({bucket * T} tokens)",
            "compile_s": round(time.monotonic() - t0, 1),
            "mosaic_custom_calls": c.as_text().count("tpu_custom_call"),
            **_mem(c),
        })
    return rows


def rehearse_aot(names, batches=None) -> int:
    for cell in _cells(names):
        kind = cell.traffic["runner"]
        if kind == "train_steps":
            for b in batches or [None]:
                try:
                    for row in aot_train(cell, b):
                        print(f"[aot] {cell.name}: {json.dumps(row)}", flush=True)
                except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the result
                    print(f"[aot] {cell.name}: batch {b}: REFUSED {type(e).__name__}: {str(e)[:400]}", flush=True)
        else:
            for row in aot_serve(cell):
                print(f"[aot] {cell.name}: {json.dumps(row)}", flush=True)
    return 0


def main(argv) -> int:
    if len(argv) >= 2 and argv[1] == "one":
        from benchmarks import run
        from benchmarks.lib import rehearsal

        return run.main(argv[2:], prepare=rehearsal.shrink)
    if len(argv) < 2 or argv[1] not in ("cpu", "aot"):
        print(__doc__)
        return 2
    names = [a for a in argv[2:] if not a.startswith("--batch=")]
    batches = [int(x) for a in argv[2:] if a.startswith("--batch=") for x in a[8:].split(",")]
    if argv[1] == "cpu":
        return 1 if rehearse_cpu(names) else 0
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    return rehearse_aot(names, batches or None)


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Headline benchmark: flagship-model training-step MFU on one TPU chip.

Runs on a TPU only: on any other platform, or on a device_kind the peak
table does not list, it exits non-zero naming what it found. Prints ONE
JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

The reference publishes no LLM throughput numbers (BASELINE.md); the
north-star target is >=35% MFU for Llama-family fine-tuning (BASELINE.json),
so vs_baseline is measured MFU / 0.35. The workload is a full training step
(forward, backward, adamw update) on a ~350M-param Llama-style model in
bfloat16 with remat, batch sized to fill a single v5e chip.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s of `device` from the one table the runtime keeps
    (observability/goodput.PEAK_FLOPS_PER_CHIP). A platform that is not a
    TPU, or a device_kind the table does not list, ends the run: an MFU
    against a made-up denominator is worse than no MFU."""
    from ray_tpu.observability.goodput import PEAK_FLOPS_PER_CHIP

    kind = str(getattr(device, "device_kind", ""))
    if device.platform != "tpu":
        sys.exit(
            f"bench.py measures a TPU; jax found platform {device.platform!r} "
            f"(device_kind {kind!r}). No CPU fallback."
        )
    key = kind.lower().replace(" ", "")
    for name, peak in PEAK_FLOPS_PER_CHIP.items():
        if name in key:
            return peak
    sys.exit(
        f"bench.py has no peak FLOP/s for device_kind {kind!r}; add it to "
        "ray_tpu/observability/goodput.PEAK_FLOPS_PER_CHIP with its source."
    )


def _aot_7b(args) -> None:
    """AOT-compiles the llama-2-7B train step for a v5e-64 mesh
    (fsdp=16 x tensor=4, batch 64, seq 4096) via the TPU topology API and
    prints the standard one-line JSON with the per-device HBM estimate
    (AOT_7B_r05.json recorded 13.99 GB/device — the compiler's figure, not
    a run)."""
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import sharding as shr

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:8x8", num_slices=1
    )
    mesh = Mesh(np.array(topo.devices).reshape(16, 4), ("fsdp", "tensor"))
    cfg = tfm.llama2_7b(dtype=jnp.bfloat16, remat=True, remat_policy="hot")
    abstract = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    shardings = shr.tree_shardings(abstract, mesh, shr.TRANSFORMER_RULES)
    tx = optax.adamw(1e-4)
    batch, seq = 64, 4096

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(tfm.next_token_loss)(params, tokens, cfg, mesh)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    params_sds = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract,
        shardings,
    )
    opt_sds = jax.eval_shape(tx.init, params_sds)  # GSPMD propagates shardings
    tok_sds = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=NamedSharding(mesh, P("fsdp", None))
    )
    compiled = (
        jax.jit(train_step, donate_argnums=(0, 1))
        .lower(params_sds, opt_sds, tok_sds)
        .compile()
    )
    ma = compiled.memory_analysis()
    per_dev = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
        + ma.generated_code_size_in_bytes
        - getattr(ma, "alias_size_in_bytes", 0)
    ) / (1 << 30)
    print(
        json.dumps(
            {
                "metric": "llama7b_aot_v5e64_hbm_per_device",
                "value": round(per_dev, 3),
                "unit": "GB",
                "vs_baseline": round(per_dev / 16.0, 4),  # <1.0 = fits
                "mesh": {"fsdp": 16, "tensor": 4},
                "batch": batch,
                "seq": seq,
                "note": (
                    "AOT cross-compile of the full 7B train step (fwd+bwd+"
                    "adamw, hot selective remat) for a v5e-64 topology; "
                    "value is the per-device HBM requirement vs 16 GB/chip"
                ),
            }
        )
    )


def main() -> None:
    import argparse

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer as tfm

    ap = argparse.ArgumentParser()
    # "hot" saves only a named bf16 frontier and recomputes norms + gate/up
    # dots; "dots" saves fp32 dot outputs and exceeded HBM at this size.
    # Chosen by a sweep that predates PR 1 — not measured on today's code.
    ap.add_argument("--remat-policy", default="hot", choices=["none", "dots", "attn", "hot"])
    ap.add_argument("--no-remat", action="store_true", help="disable jax.checkpoint entirely (activations must fit HBM)")
    ap.add_argument("--heads", type=int, default=8)  # head_dim 128 = MXU/VPU lane width
    ap.add_argument("--batch", type=int, default=6)  # same pre-PR-1 sweep
    ap.add_argument("--attn", default="full", choices=["full", "naive", "ring", "ulysses"])
    # Long-context mode: --seq 32k runs the flagship at that context with
    # batch 1 (tokens/s + MFU at long context; pairs with --attn ring to
    # exercise the sequence-parallel path end to end). Accepts "32k"/"32768".
    ap.add_argument("--seq", default=None)
    ap.add_argument("--steps", type=int, default=40)
    # 350m fits (with optimizer state) on ONE v5e chip; 7b needs a sharded
    # mesh — params+adam alone are ~84 GB fp32-equivalent vs 16 GB HBM —
    # so --model 7b is the AOT compile below, not a single-chip run.
    ap.add_argument("--model", default="350m", choices=["350m", "1b", "7b"])
    # Debug ablations for step-time attribution (not a benchmark mode):
    # "attn" replaces attention with identity; "head" replaces the
    # lm_head+cross-entropy with a mean over the final hidden states.
    ap.add_argument("--ablate", default=None, choices=[None, "attn", "head"])
    args = ap.parse_args()

    dev = jax.devices()[0]
    peak = _peak_flops(dev)  # exits non-zero off-TPU / on an unlisted kind

    if args.model == "7b" and len(jax.devices()) < 8:
        # Single chip cannot hold 7B (params+opt ~40 GB sharded): the 7B
        # artifact is an AOT cross-compile of the REAL training step over
        # a v5e-64 topology (no chips needed), recording the per-device
        # HBM requirement — the precompiled proof the multi-chip run fits
        # (north star: BASELINE.json llama-2-7b on v5e-64).
        _aot_7b(args)
        return

    model_shapes = {
        #        d_model n_layers n_heads  d_ff   vocab
        "350m": (1024,   16,      args.heads, 4096, 32768),
        "1b":   (2048,   16,      16,      8192,  32768),
        "7b":   (4096,   32,      32,      11008, 32000),  # Llama-2-7B shape
    }
    if args.model != "350m" and args.heads != 8:
        print(
            f"warning: --heads is fixed by the {args.model} architecture; ignoring",
            file=sys.stderr,
        )
    d_model, n_layers, n_heads, d_ff, vocab = model_shapes[args.model]

    def parse_seq(s):
        s = s.lower().strip()
        return int(s[:-1]) * 1024 if s.endswith("k") else int(s)

    long_ctx = args.seq is not None
    seq = parse_seq(args.seq) if long_ctx else 2048
    cfg = tfm.TransformerConfig(
        vocab_size=vocab,
        d_model=d_model,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_heads,
        d_ff=d_ff,
        max_seq_len=seq,
        dtype=jnp.bfloat16,
        remat=not args.no_remat,
        remat_policy=None if args.remat_policy == "none" else args.remat_policy,
        attn_impl=args.attn,
    )
    batch = 1 if (long_ctx and args.batch == 4) else args.batch
    steps, warmup = args.steps, 2

    # Sequence-parallel attention runs over a "seq" mesh axis spanning all
    # visible devices (one chip -> degenerate 1-ring, still the flash path).
    mesh = None
    if args.attn in ("ring", "ulysses"):
        import numpy as _np
        from jax.sharding import Mesh

        devs = _np.array(jax.devices())
        mesh = Mesh(devs.reshape(-1), ("seq",))

    if args.ablate == "attn":
        import ray_tpu.models.transformer as _t

        _t._attention = lambda q, k, v, cfg, mesh: q  # identity: no attn compute
    loss_fn = tfm.next_token_loss
    if args.ablate == "head":
        def loss_fn(params, tokens, cfg_, mesh_=None, **kw):
            x = tfm.forward_hidden(params, tokens, cfg_, mesh_)
            return jnp.mean(jnp.square(x.astype(jnp.float32)))

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(1e-4)
    opt_state = jax.jit(tx.init)(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size)

    # Donation: params/opt_state buffers are reused in place, halving HBM
    # traffic and footprint for the update.
    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    for _ in range(warmup):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = train_step(params, opt_state, tokens)
    final_loss = float(loss)  # sync point ending the timed region
    dt = time.perf_counter() - t0

    tokens_per_s = batch * seq * steps / dt
    mfu = tokens_per_s * tfm.flops_per_token(cfg, seq) / peak
    print(
        json.dumps(
            {
                "metric": (
                    f"llama{args.model}_train_mfu_{seq//1024}k_{args.attn}"
                    if long_ctx
                    else f"llama{args.model}_train_mfu_1chip"
                ),
                "value": round(mfu, 4),
                "unit": "mfu_fraction",
                "vs_baseline": round(mfu / 0.35, 4),
                "tokens_per_s": round(tokens_per_s, 1),
                "step_ms": round(1000 * dt / steps, 2),
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "device_count": len(jax.devices()),
                "loss": final_loss,
            }
        )
    )


if __name__ == "__main__":
    main()

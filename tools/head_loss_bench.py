"""The training step's head and loss alone: `transformer.head_loss` against the plain expression it replaced.

    chiprun -- python3 tools/head_loss_bench.py [--shapes mistral,olmoe] [--budgets-mb 256,512,1024] [--forms plain,saved,carried] [--reps 10]
    python3 tools/head_loss_bench.py --aot 1 [...]      # here, no chip: compile for a described v5e, print what the compiler says

For each training cell's shape (Mistral: 3 x 4096 rows, d 4096, vocab 32 768;
OLMoE: 4 x 4096, d 2048, vocab 50 304; bfloat16), loss and the gradients of
the final hidden states and of the head, in one jit, in three forms:

- `plain`: `log_softmax` of the float32 logits, differentiated by jax (what
  `next_token_loss` was before PR 60; it lives on in tests/test_head_loss.py);
- `saved`: `transformer.head_loss` as the program runs it, at each
  `HEAD_LOSS_CHUNK_BYTES` of `--budgets-mb`: the chunks' bfloat16 dlogits
  written into one [rows, vocab] buffer, each backward product once;
- `carried`: the same chunk body, but a chunk's two backward products made
  right behind its dlogits and the head's gradient carried through the scan
  in float32 [d, vocab] (no [rows, vocab] buffer at all). An experiment of
  this file: the program does not run it.

Milliseconds a call beside 3 products' time at the chip's peak
(benchmarks/lib/peaks.json, keyed by device kind), and the largest difference
of each result from `plain`'s. `--aot 1`: temporaries, the `dot`s' operand
types and the float32 [rows, vocab]-sized buffers of the optimized program; a
compiler's estimate, not a measurement. Between two processes the same form
read up to 4 ms apart (PR 60: 58.4 and 60.8); compare forms inside one call. Refuses to time off a TPU: a CPU time
is not a device number. A builder's tool; no test and no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPES = {"mistral": (3, 4096, 4096, 32768), "olmoe": (4, 4096, 2048, 50304)}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,olmoe")
    ap.add_argument("--budgets-mb", default="256,512,1024")
    ap.add_argument("--forms", default="plain,saved,carried")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--aot", type=int, default=0)
    args = ap.parse_args(argv)

    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.lib import peaks
    from ray_tpu.models import transformer as tfm

    bf16, f32 = jnp.bfloat16, jnp.float32

    def plain(x, head, targets, weights):
        logits = jnp.einsum("...d,dv->...v", x, head, preferred_element_type=f32)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weights)

    def carried(x, head, targets, weights):
        """(loss, dx, dhead) with no derivative taken: the chunk's products behind its dlogits."""
        rows, (d, vocab) = targets.size, head.shape
        chunk = tfm._loss_chunk_rows(rows, vocab)

        def step(carry, xs):
            loss, dhead = carry
            part, _nll, dlogits = tfm._head_loss_rows(xs[0], head, *xs[1:])
            dx = jnp.einsum("cv,dv->cd", dlogits, head, preferred_element_type=f32).astype(x.dtype)
            return (loss + part, dhead + jnp.einsum("cd,cv->dv", xs[0], dlogits, preferred_element_type=f32)), dx

        chunked = tuple(a.reshape(rows // chunk, chunk, *a.shape[targets.ndim :]) for a in (x, targets, weights))
        (loss, dhead), dx = lax.scan(step, (jnp.zeros((), f32), jnp.zeros((d, vocab), f32)), chunked)
        return loss, (dx.reshape(x.shape), dhead.astype(head.dtype))

    forms = {
        "plain": jax.value_and_grad(plain, argnums=(0, 1)),
        "saved": jax.value_and_grad(tfm.head_loss, argnums=(0, 1)),
        "carried": carried,
    }

    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        where = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
        peak = None
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print("head_loss_bench: no TPU; a CPU time is not a device number", file=sys.stderr)
            return 3
        peak = peaks.for_kind(dev.device_kind)["bf16_flops_per_s"]

    for shape in args.shapes.split(","):
        b, s, d, v = SHAPES[shape]
        want = None
        for form in args.forms.split(","):
            for mb in [0] if form == "plain" else [int(m) for m in args.budgets_mb.split(",")]:
                tfm.HEAD_LOSS_CHUNK_BYTES = mb << 20 or tfm.HEAD_LOSS_CHUNK_BYTES
                fact = {"shape": shape, "form": form, "budget_mb": mb, "chunk_rows": tfm._loss_chunk_rows(b * s, v) if mb else b * s}
                run = jax.jit(forms[form])
                if args.aot:
                    shapes = [jax.ShapeDtypeStruct(sh, dt, sharding=where) for sh, dt in (((b, s, d), bf16), ((d, v), bf16), ((b, s), jnp.int32), ((b, s), f32))]
                    compiled = run.lower(*shapes).compile()
                    text, mem = compiled.as_text(), compiled.memory_analysis()
                    whole = re.findall(rf"= f32\[(?:{b},{s}|{b * s}),{v}\]", text)
                    made = dict(re.findall(r"(%[\w.-]+) = \(?(\w+)\[", text))  # an instruction's (first) element type
                    dots = re.findall(r"= (\w+)\[[^\]]*\]\S* (?:convolution|dot)\((%[\w.-]+), (%[\w.-]+)\)", text)
                    fact.update(temp_gib=round(mem.temp_size_in_bytes / 2**30, 3), f32_rows_by_vocab=len(whole), products=[f"{made[a]}x{made[c]}->{o}" for o, a, c in dots])
                    out = os.path.join(ROOT, "chiprun_out", "head_loss_aot")
                    os.makedirs(out, exist_ok=True)
                    with open(os.path.join(out, f"{shape}-{form}-{mb}.hlo.txt"), "w") as f:
                        f.write(text)
                else:
                    keys = jax.random.split(jax.random.PRNGKey(60), 4)
                    x = jax.random.normal(keys[0], (b, s, d), f32).astype(bf16)
                    head = (jax.random.normal(keys[1], (d, v), f32) / d**0.5).astype(bf16)
                    targets = jax.random.randint(keys[2], (b, s), 0, v)
                    weights = jnp.full((b, s), 1.0 / (b * s), f32)
                    got = jax.block_until_ready(run(x, head, targets, weights))
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        out = run(x, head, targets, weights)
                    jax.block_until_ready(out)
                    ms = (time.perf_counter() - t0) / args.reps * 1e3
                    got = [jnp.asarray(a, f32) for a in jax.tree_util.tree_leaves(got)]
                    want = want or got
                    fact.update(
                        ms=round(ms, 3), ms_at_peak=round(3 * 2 * b * s * d * v / peak * 1e3, 3), loss=float(got[0]),
                        gap=[float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w))) for g, w in zip(got, want)],
                        peak_gib=round(dev.memory_stats()["peak_bytes_in_use"] / 2**30, 3),
                    )
                print("head_loss_bench: " + json.dumps(fact), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

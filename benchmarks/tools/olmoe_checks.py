"""The builder's instrument for what a forward computes: the per-position
relative error of the logits (`lib/correct.logit_relative_errors`) between
the program's own forward functions and the plain reference, for any cell of
BENCHMARK.json, with the reference in fp8 precision and, for a routed model,
the nearest wrong programs beside it. It says WHERE a model departs (which
positions, whether the router's near-ties explain it) before a cell is
sized; no cell's `correct` rests on it (that compares what the timed
executables emit: `lib/correct.py`, `tools/control.py`). Never part of a
check: the driver runs benchmarks/run.py.

    chiprun -- python3 benchmarks/tools/olmoe_checks.py --workload <cell> --seeds 11,2147483659

For each seed, weights and tokens are made exactly as the cell's worker makes
them (`lib/correct.init_weights`: the program's init, then every norm's scale
drawn, without which a model that leaves a norm out passes). Against the
float32 "highest" reference of the configuration's architecture file, with
the statistic, the quantiles and the fp8 rounding of `lib/correct.py`:

- **A, `program`**: what the cell's own check reads. A training cell: per
  position of every sequence of the batch, the relative error of
  `tfm.forward`'s logits (on the weights as drawn; the cell reads them after
  its window's steps). A serving cell: `paged_probe_logits` below, over a
  pool and an allocator of the configuration's own sizes at `--probes` lengths.
- **B, `reference_in_fp8`**: the same statistic for the reference itself
  with every weight matrix rounded to float8_e4m3's 3 mantissa bits: the
  mildest form of the precision below the configuration's bfloat16.
- a routed model (`n_experts` > 0) also: top-(k-1), renormalised top-k, and
  no q/k-norm where the model has one; `agreeing` = over the positions whose
  token took the reference's SET of experts in every layer, where a
  difference is arithmetic and not a router's near-tie resolved the other
  way; and `paged`, the serve path's functions on a 1 021-token prompt at
  16-token pages, which no cell of the benchmark runs for this model yet.
- `loss`: the batch's mean next-token loss less the reference's, the other
  comparison a training cell makes (blind to the layers below a random head:
  PERF.md section 4).

"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


PAGED_DECODE_STEPS = 16  # or one page, where a page is longer: the steps of a probe always cross into a new page


def paged_probe_logits(lm, alloc, seed: int, prompt_lengths) -> Dict[str, Any]:
    """The logits that the serve path's own functions produce, through the
    paged cache of `lm` (a PagedLM: its pool, page size, slots and bucket
    rule) on pages taken from `alloc` and given back. For each probe, a
    prompt of that many tokens drawn from the seed (no probe or request
    shares them, so nothing is cached): the last prompt position from a cold
    `forward_prefill`; the same position again from a prefill with
    `write_from` at about half the prompt, which reads the pages below it as
    the first call wrote them (the suffix path of a prefix hit); then
    `forward_decode` steps, teacher-forced on drawn tokens, all probes
    together in the last slots of the one decode batch, the other slots
    inactive. Returns, per probe, its tokens, the positions whose
    next-token logits were taken and the logits [2 + steps, vocab], and the
    allocator's free pages before and after.

    The jitted steps here return logits where PagedLM's return the argmax:
    other executables over the same `forward_prefill` / `forward_decode`,
    cached like every other."""
    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.kv_cache import TRASH_PAGE

    from benchmarks.lib import traffic as traffic_lib

    cfg, T, B, P = lm.cfg, lm.page_tokens, lm.max_slots, lm.max_pages_per_seq
    steps = max(PAGED_DECODE_STEPS, T)
    if len(prompt_lengths) > B or min(prompt_lengths) <= T:
        raise ValueError(f"{len(prompt_lengths)} probes of {list(prompt_lengths)} tokens: at most {B} (slots), each longer than a page ({T})")
    prefill = jax.jit(
        lambda params, tokens, kv, bt, length, write_from: tfm.forward_prefill(params, tokens, cfg, kv, bt, length, write_from),
        donate_argnums=lm._donate((2,)))
    decode = jax.jit(
        lambda params, tokens, positions, kv, bts: tfm.forward_decode(params, tokens, positions, cfg, kv, bts),
        donate_argnums=lm._donate((3,)))

    free_before = alloc.free_pages()
    probes, seqs = [], []
    try:
        for i, n in enumerate(prompt_lengths):
            tokens = traffic_lib.segment_tokens(seed, f"paged{i}", n + steps, lm.vocab)
            seq = alloc.allocate(tokens.tolist())
            seqs.append(seq)
            if seq.cached_tokens:
                raise RuntimeError("a paged probe's drawn prompt was found in the prefix cache")
            n_pages = -(-n // T)
            bucket = lm._bucket_pages(n_pages)
            padded = np.zeros((1, bucket * T), np.int32)
            padded[0, :n] = tokens[:n]
            bt = np.full((bucket,), TRASH_PAGE, np.int32)
            bt[:n_pages] = seq.pages[:n_pages]
            cached = max(T, n // 2 // T * T)
            rows = [
                lm._run_step(lambda kv: prefill(lm.params, padded, kv, bt, np.int32(n), np.int32(w)), "bench.check.prefill")[0]
                for w in (0, cached)
            ]
            probes.append({"tokens": tokens, "prompt_tokens": n, "cached_tokens": cached, "bucket_pages": bucket,
                           "positions": [n - 1, n - 1] + list(range(n, n + steps)), "logits": rows})
        slots = range(B - len(probes), B)
        bts = np.full((B, P), TRASH_PAGE, np.int32)
        for slot, seq in zip(slots, seqs):
            bts[slot, : len(seq.pages)] = seq.pages
        for j in range(steps):
            toks, pos = np.zeros((B,), np.int32), np.full((B,), -1, np.int32)
            for slot, probe in zip(slots, probes):
                toks[slot], pos[slot] = probe["tokens"][probe["prompt_tokens"] + j], probe["prompt_tokens"] + j
            out = lm._run_step(lambda kv: decode(lm.params, toks, pos, kv, bts), "bench.check.decode")
            for slot, probe in zip(slots, probes):
                probe["logits"].append(out[slot])
    finally:
        for seq in seqs:
            alloc.release(seq)
    for probe in probes:
        probe["logits"] = np.stack(probe["logits"])
    return {"probes": probes, "free_pages": [free_before, alloc.free_pages()], "decode_steps": steps}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-train-seq4k-1chip")
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--probes", default="300,1500", help="a serving cell's probe prompt lengths")
    ap.add_argument("--tiny", type=int, default=0, help="TINY widths on whatever backend there is (a rehearsal of this tool)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import correct, driver, rehearsal, spec
    from benchmarks.lib.worker_serve import BenchModel
    from benchmarks.lib.worker_train import cache_everything, seeded_key
    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.kv_cache import PagedKVAllocator
    from ray_tpu.serve.llm.model import PagedLM

    cell = spec.find_cell(args.workload)
    if args.tiny:
        rehearsal.shrink(cell)
    cache_everything()
    config, arch = cell.config, cell.arch
    device = jax.devices()[0]
    fp8 = jax.jit(correct.in_fp8)

    def paged_readings(lm, alloc, params8, seed, prompt_lengths):
        got = paged_probe_logits(lm, alloc, seed, prompt_lengths)
        a, b = [], []
        for p in got["probes"]:
            want, want8 = (correct.reference_logits(arch, w, p["tokens"], p["positions"], config) for w in (lm.params, params8))
            a.append(np.asarray(correct.logit_relative_errors(jnp.asarray(p["logits"]), want)))
            b.append(np.asarray(correct.logit_relative_errors(want8, want)))
        return {
            "program": correct.error_quantiles(np.concatenate(a)), "reference_in_fp8": correct.error_quantiles(np.concatenate(b)),
            "by_probe": [{"prompt_tokens": p["prompt_tokens"], "cached_tokens": p["cached_tokens"], "cold": float(e[0]),
                          "suffix": float(e[1]), "decode": [float(x) for x in e[2:]]} for p, e in zip(got["probes"], a)],
            "free_pages": got["free_pages"],
        }

    def serve_cell(seed):
        cell.seed = seed
        model = BenchModel(driver.worker_config(cell))
        eng = {k: v["value"] for k, v in config["assumed"].items()}
        alloc = PagedKVAllocator(eng["pool_pages"], eng["page_tokens"])
        return paged_readings(model.lm, alloc, fp8(model.lm.params), seed, [int(x) for x in args.probes.split(",")])

    def train_cell(seed):
        seq, per_chip = int(cell.traffic["seq_len"]), int(cell.traffic["batch_per_chip"])
        cfg = arch.model_config(config, max_seq_len=seq)
        key = seeded_key(seed)
        params = jax.jit(lambda k: correct.init_weights(tfm, cfg, k))(key)
        tokens = jax.jit(lambda k: jax.random.randint(k, (per_chip * cell.chips, seq), 0, cfg.vocab_size, jnp.int32))(jax.random.fold_in(key, 1))
        params8 = fp8(params)
        programs = {"program": cfg}
        if cfg.n_experts:
            k = cfg.n_experts_per_tok
            programs[f"top-{k - 1}"] = cfg.replace(n_experts_per_tok=k - 1)
            programs["renormalised"] = cfg.replace(norm_topk_prob=not cfg.norm_topk_prob)
        if cfg.qk_norm:
            programs["no-qk-norm"] = cfg.replace(qk_norm=False)

        def nll(z, s):
            return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(z[:-1], axis=-1), s[1:, None], axis=-1))

        @jax.jit
        def reference(p, s):
            z = arch.logits_at(p, s, jnp.arange(seq), config)
            experts = jnp.sort(arch.routed_experts(p, s, config), axis=-1) if cfg.n_experts else None
            return z, experts, nll(z, s)

        def run_of(c):
            @jax.jit
            def run(p, s, z_ref):
                z = tfm.forward(p, s[None], c)[0]
                return correct.logit_relative_errors(z, z_ref), nll(z, s)

            return run

        runs = {name: run_of(c) for name, c in programs.items()}
        names = list(runs) + ["reference_in_fp8"]
        err, loss, same = {n: [] for n in names}, {n: [] for n in names + ["reference"]}, {"program": [], "reference_in_fp8": []}
        for s in tokens:
            z_ref, e_ref, l_ref = reference(params, s)
            loss["reference"].append(float(l_ref))
            for name, run in runs.items():
                e, l = run(params, s, z_ref)
                err[name].append(np.asarray(e))
                loss[name].append(float(l))
            z8, e8, l8 = reference(params8, s)
            err["reference_in_fp8"].append(np.asarray(correct.logit_relative_errors(z8, z_ref)))
            loss["reference_in_fp8"].append(float(l8))
            if cfg.n_experts:
                got = jnp.sort(tfm.routing_stats(params, s[None], cfg)["experts"], axis=-1)
                same["program"].append(np.asarray(jnp.all(got == e_ref, axis=(0, 2))))
                same["reference_in_fp8"].append(np.asarray(jnp.all(e8 == e_ref, axis=(0, 2))))
        ref_loss = float(np.mean(loss["reference"]))
        out = {
            "batch": int(tokens.shape[0]), "seq_len": seq,
            "loss_minus_reference": {n: float(np.mean(v)) - ref_loss for n, v in loss.items() if n != "reference"},
        }
        if cell.chips > 1:  # the cell judges each chip's own sequences and takes the worst chip: the same groups here
            out["by_chip"] = {n: [correct.error_quantiles(np.concatenate(v[c * per_chip:(c + 1) * per_chip])) for c in range(cell.chips)]
                              for n, v in err.items()}
        for n, v in err.items():
            out[n] = correct.error_quantiles(np.concatenate(v))
        if cfg.n_experts:
            for n, v in same.items():
                agree = np.concatenate(v)
                out[n].update(agreeing=correct.error_quantiles(np.concatenate(err[n])[agree]), agreeing_share=float(agree.mean()))
            prompt = 29 if args.tiny else min(1021, seq - 17)
            pages = -(-(prompt + 16) // 16) + 2
            lm = PagedLM(cfg, params, num_pages=pages, page_tokens=16, max_slots=2, max_pages_per_seq=-(-seq // 16))
            out["paged"] = paged_readings(lm, PagedKVAllocator(pages, 16), params8, seed, [prompt])
        return out

    read = train_cell if cell.traffic["runner"] == "train_steps" else serve_cell
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "olmoe_checks.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = json.dumps(dict({"workload": cell.name, "seed": seed, "device": f"{device.platform} {device.device_kind}",
                                    "widths": "TINY" if args.tiny else "published"}, **read(seed)))
            print("olmoe_checks: " + line, flush=True)
            f.write(line + "\n")
            gc.collect()  # the last seed's weights and pool go before the next are made
    return 0


if __name__ == "__main__":
    sys.exit(main())

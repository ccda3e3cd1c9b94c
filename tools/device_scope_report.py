"""Where a traced program's device time went, by the program's own names.

    python3 tools/device_scope_report.py <trace.xplane.pb[.gz] | a directory holding one> [--by scope|phase|category|source]

Reads the per-op metadata a jax profiler trace carries (benchmarks/lib/
xplane_meta.py): every op's `tf_op` path, which holds the `jax.named_scope`s
of `ray_tpu.models.transformer.SCOPES` and the transformation that made the
op, its `hlo_category`, XLA's `model_flops` and `bytes_accessed`, its `source`
line. One row a (scope, phase) by default: device seconds, share of the
chip's busy time, executed ops, XLA's FLOPs and the FLOP/s they ran at as a
share of the chip's peak, bytes and bytes/s as a share of the HBM peak
(benchmarks/lib/peaks.json), and the three largest ops under the row with
their source lines. A custom call (a Pallas kernel) carries no XLA count: its
row prints time and share and leaves the other columns empty. Then the
asynchronous ops (collectives, copies) with the seconds they were in flight
and the seconds of those in which nothing else ran on the chip.

Rows hold the ops of the `XLA Ops` line, control-flow containers left out (a
`while` spans its body's ops, which are listed); what a container holds
beyond its body's ops is the row `(control flow)`, so the rows' seconds sum
to the busy time. A fusion carries one `tf_op`, its root's: an op XLA fused
across a scope's border counts whole where its root lies. Phases: forward
(`jvp(`), backward (`transpose(jvp(`), recompute (`rematted_computation`),
update (neither: the optimizer, ZeRO); a program without a gradient reads
forward throughout. On several chips, seconds are the mean over the chips.
The window is the span of the trace's `bench.*` host spans where it holds
any (a benchmark run), else the whole trace (`JaxTrainer`'s own).

    chiprun -- python3 tools/device_scope_report.py --record chiprun_out/recorded/tiny_v5e_moe_scopes.xplane.pb.gz

records three steps of a small routed model's train step on the chip: the
file benchmarks/tests/test_scope_readers.py reads the `moe.*` rows from.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BY = ("scope", "phase", "category", "source")
CONTROL = "(control flow)"


def find_trace(path: str) -> str:
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb*"), recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        return found[-1]
    return path


def load(path: str):
    """(the ops inside the window, the window's host spans)."""
    from benchmarks.lib import trace, xplane_meta as xm

    tr = trace.Trace(path)
    return xm.OpTable(path, tr.window(), tr.skew_s), tr.spans


def rows_of(table, by):
    """{key: [ops]} over the `XLA Ops` line; `by` a tuple of BY."""
    key_of = {
        "scope": table.scope_of,
        "phase": table.phase_of,
        "category": lambda op: op.category or "(none)",
        "source": lambda op: os.path.relpath(op.source, ROOT) if op.source.startswith(ROOT) else op.source or "(none)",
    }
    rows = defaultdict(list)
    for op in table.sync:
        rows[tuple(key_of[b](op) for b in by)].append(op)
    return rows


def control_flow_s(table) -> float:
    """Busy seconds in which a container ran and none of the listed ops did."""
    return table.busy_s() - table.seconds(table.sync)


def _short(hlo: str) -> str:
    return hlo.partition(" = ")[0].strip().lstrip("%")


def _line_of(op) -> str:
    return os.path.basename(op.source) if op.source else "-"


def report(table, spans, by, peaks, out=None) -> float:
    """Prints the table; returns the seconds its rows sum to."""
    out = out or sys.stdout
    busy, chips = table.busy_s(), max(1, len(table.chips))
    steps = len(spans)
    print(f"chips {chips}, busy {busy:.6f} s a chip in the window" + (f", {steps} host spans ({1e3 * busy / steps:.3f} ms busy a span)" if steps else ""), file=out)
    head = " | ".join(by)
    print(f"| {head} | s | % busy | ops | XLA TFLOP | % peak FLOP/s | GB accessed | % peak HBM | largest ops (ms, source) |", file=out)
    print("|" + " --- |" * (len(by) + 8), file=out)
    total = 0.0
    rows = rows_of(table, by)
    for key, ops in sorted(rows.items(), key=lambda kv: -sum(op.seconds for op in kv[1])):
        seconds = table.seconds(ops)
        total += seconds
        counted = [op for op in ops if not op.custom_call]
        counted_s = sum(op.seconds for op in counted)
        flops, nbytes = sum(op.model_flops for op in counted), sum(op.bytes_accessed for op in counted)
        by_name = defaultdict(float)
        for op in ops:
            by_name[(_short(op.hlo), _line_of(op))] += op.seconds / chips
        largest = "; ".join(f"{n} {1e3 * s:.3f} {src}" for (n, src), s in sorted(by_name.items(), key=lambda kv: -kv[1])[:3])
        if counted_s and flops + nbytes:
            numbers = f"{flops / chips / 1e12:.4f} | {100 * flops / (counted_s * peaks['bf16_flops_per_s']):.1f} | {nbytes / chips / 1e9:.4f} | {100 * nbytes / (counted_s * peaks['hbm_bytes_per_s']):.1f}"
        else:
            numbers = " | | | "
        print(f"| {' | '.join(key)} | {seconds:.6f} | {100 * seconds / busy:.2f} | {len(ops) // chips} | {numbers} | {largest} |", file=out)
    control = control_flow_s(table)
    total += control
    print(f"| {' | '.join([CONTROL] + [''] * (len(by) - 1))} | {control:.6f} | {100 * control / busy:.2f} | | | | | | |", file=out)
    print(f"rows sum to {total:.6f} s of {busy:.6f} s busy", file=out)

    flying = defaultdict(list)
    for op in table.flying:
        flying[(table.scope_of(op), op.category or "(none)")].append(op)
    if flying:
        print("\n| asynchronous ops: scope | category | n | s in flight | s exposed (nothing else on the chip) | largest (ms) |", file=out)
        print("| --- | --- | --- | --- | --- | --- |", file=out)
        for (sc, cat), ops in sorted(flying.items(), key=lambda kv: -table.exposed_s(kv[1])):
            by_name = defaultdict(float)
            for op in ops:
                by_name[_short(op.hlo)] += op.seconds / chips
            largest = "; ".join(f"{n} {1e3 * s:.3f}" for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:3])
            print(f"| {sc} | {cat} | {len(ops) // chips} | {table.seconds(ops):.6f} | {table.exposed_s(ops):.6f} | {largest} |", file=out)
    return total


def record(dst: str) -> int:
    """Three traced train steps of a small routed model (head_dim 128, a
    sequence the flash kernels tile), under `bench.train_step` spans."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from ray_tpu.models import transformer as tfm

    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=256, max_seq_len=512,
        remat_policy="hot", n_experts=4, n_experts_per_tok=2, qk_norm=True,
    )
    init_state, step = tfm.build_train_step(cfg, optax.adamw(1e-4), Mesh(np.array(jax.devices()[:1]), ("data",)))
    params, opt = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 512), 0, cfg.vocab_size, jnp.int32)
    for _ in range(2):
        params, opt, loss = step(params, opt, tokens)
        jax.block_until_ready(loss)
    logdir = os.path.join(os.path.dirname(os.path.abspath(dst)), "tb_scopes")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.train_step", step=i, tokens=1024):
            params, opt, loss = step(params, opt, tokens)
            jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    with open(path, "rb") as f, gzip.open(dst, "wb", 9) as g:
        g.write(f.read())
    shutil.rmtree(logdir, ignore_errors=True)
    print("recorded", dst, os.path.getsize(dst), "bytes", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", help="an .xplane.pb[.gz], or a directory holding one")
    ap.add_argument("--by", default="scope,phase", help=f"comma-separated, of {', '.join(BY)} (default scope,phase)")
    ap.add_argument("--kind", default="TPU v5 lite", help="the chip's `device_kind`, a key of benchmarks/lib/peaks.json")
    ap.add_argument("--record", metavar="DST", help="record a small routed model's train step on this machine's chip to DST (.xplane.pb.gz), then report it")
    args = ap.parse_args(argv)
    by = tuple(args.by.split(","))
    if not by or any(b not in BY for b in by):
        ap.error(f"--by takes {', '.join(BY)}")
    if args.record:
        os.makedirs(os.path.dirname(os.path.abspath(args.record)), exist_ok=True)
        record(args.record)
        args.trace = args.record
    if not args.trace:
        ap.error("a trace, or --record")

    from benchmarks.lib import peaks

    table, spans = load(find_trace(args.trace))
    if not table.chips:
        raise SystemExit("the trace holds no /device:TPU plane with an `XLA Ops` line")
    report(table, spans, by, peaks.for_kind(args.kind))
    return 0


if __name__ == "__main__":
    sys.exit(main())

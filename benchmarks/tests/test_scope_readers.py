"""The reader of a trace's per-op metadata (benchmarks/lib/xplane_meta.py), the
three scope readers and their metric files, on traces recorded on a v5e:

    tiny_v5e_scopes.xplane.pb.gz      benchmarks/tools/record_trace.py on the scoped program (dense: 3 train
                                      steps, a prefill, 4 decode steps)
    tiny_v5e_moe_scopes.xplane.pb.gz  tools/device_scope_report.py --record (a routed model's 3 train steps:
                                      record_trace.py takes no config)
    tiny_v5e.xplane.pb.gz             the parent's program: no scope anywhere
"""

import collections
import glob
import gzip
import json
import os
import shutil

import pytest

from benchmarks.lib import evidence as on_disk, spec, trace, xplane_meta as xm
from benchmarks.readers import trace_scope_exposed, trace_scope_mxu, trace_scope_share
from benchmarks.tools import scope_rows

RECORDED = os.path.join(spec.BENCH_DIR, "recorded")
DENSE, ROUTED, PARENT = (os.path.join(RECORDED, f"tiny_v5e{tag}.xplane.pb.gz") for tag in ("_scopes", "_moe_scopes", ""))
TRAIN = "jit(train_step)/"

# The rows that partition a training cell's `XLA Ops` time by scope (ISSUE 56's table; since PR 58 the benchmark holds
# all nine as `train_scope_share_pct.<row>`, tools/device_scope_report.py prints a row a scope): every name of the table in exactly one.
PARTITION = {
    "attn_proj": ["attn.qkv", "attn.qk_norm", "attn.rope", "attn.gate", "attn.out", "attn.mla.q", "attn.mla.kv_down", "attn.mla.expand",
                  "attn.mla.absorb", "attn.mla.out", "kda.gates", "kda.out"],
    "attn_core": ["attn.core", "attn.window", "kda.conv", "kda.chunk", "kda.step", "retention.chunk"],
    "ffn": ["ffn"],
    "moe_routing": ["moe.router", "moe.route.groups", "moe.dispatch", "moe.combine"],
    "moe_experts": ["moe.experts", "moe.shared"],
    "head_loss": ["head", "loss"],
    "optimizer": ["optimizer", "zero.update", "zero.grad_scatter", "zero.param_gather"],
    "norm_embed": ["norm", "embed", "residual"],
    "unscoped": [xm.UNSCOPED],
}


def evidence_of(path):
    cell = spec.find_cell("mistral7b-train-seq4k-1chip")
    return {"worker": {"trace_path": path, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}, "cell": cell}


@pytest.fixture(scope="module")
def dense():
    return evidence_of(DENSE)


def test_phase_on_the_four_forms_of_a_path():
    body = "while/body/closed_call/"
    assert xm.phase(f"{TRAIN}jvp()/{body}attn.qkv/dot_general") == "forward"
    assert xm.phase(f"{TRAIN}transpose(jvp())/{body}checkpoint/attn.qkv/dot_general") == "backward"
    assert xm.phase(f"{TRAIN}transpose(jvp())/{body}checkpoint/rematted_computation/attn.qkv/dot_general") == "recompute"
    assert xm.phase(f"{TRAIN}transpose(jvp(loss))/mul") == "backward" and xm.phase(f"{TRAIN}jvp(loss)/mul") == "forward"
    assert xm.phase(f"{TRAIN}optimizer/add:") == "update" and xm.phase("") == "update"
    assert xm.phase("jit(llm_decode)/while/body/attn.core/paged_attention_decode/pallas_call:") == "update"  # `phase_of` reads a program without a gradient `forward`


def test_scope_is_the_last_name_of_the_table_on_the_path():
    names = xm.program_scopes()
    assert xm.scope(f"{TRAIN}jvp()/while/body/closed_call/attn.core/attn.window/dot_general:", names) == "attn.window"
    assert xm.scope(f"{TRAIN}transpose(jvp(loss))/jit(log_softmax)/mul:", names) == "loss"
    assert xm.scope(f"{TRAIN}jvp(head)/...d,dv->...v/dot_general:", names) == "head"
    assert xm.scope(f"{TRAIN}transpose(jvp())/while/body/dynamic_slice:", names) == xm.UNSCOPED
    assert xm.scope("", names) == xm.UNSCOPED and xm.scope("jit(f)/normalize/mul", names) == xm.UNSCOPED  # whole components only


def test_three_ops_against_their_stats_counted_by_hand():
    """`fusion.376`, the FFN's recomputed gate and up products with the SwiGLU between them, 1 024 rows x 256 into
    2 x 512: the products 2 x 1 024 x 256 x 512 = 268 435 456 FLOPs as XLA counts the fused pair, and 6 elementwise
    ops over [1 024, 1 024] = 6 291 456; a flash kernel, which carries no count; one of adam's fusions."""
    ops = {op.hlo.partition(" = ")[0]: op for op in xm.read_ops(DENSE)}
    up = ops["%fusion.376"]
    assert up.category == "convolution fusion" and up.flops == up.model_flops == 2 * 1024 * 256 * 512 + 6 * 1024 * 1024
    assert up.bytes_accessed == 4_980_736 and up.source.endswith("ray_tpu/models/transformer.py:1515")
    assert up.tf_op == f"{TRAIN}transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/ffn/bsd,df->bsf/dot_general:"
    fwd = ops["%flash_attention_fwd.6"]
    assert fwd.custom_call and fwd.flops == fwd.model_flops == fwd.bytes_accessed == 0  # a custom call carries no XLA count
    assert fwd.tf_op == f"{TRAIN}jvp()/while/body/closed_call/attn.core/flash_attention_fwd/pallas_call:"
    adam = ops["%fusion.206"]
    assert adam.category == "loop fusion" and adam.tf_op == f"{TRAIN}optimizer/add:" and adam.bytes_accessed == 3_670_020
    assert up.program_id == fwd.program_id == adam.program_id == "16614154980389473013"
    assert 0 < up.seconds < 1e-4 and up.line == "XLA Ops" and up.plane == "/device:TPU:0"


@pytest.mark.parametrize("path", [DENSE, PARENT], ids=["scoped", "parent"])
def test_the_decoder_reads_what_xplane_pb2_reads(path):
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with gzip.open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name not in xm.OP_LINES:
                continue
            for ev in line.events:
                meta = plane.event_metadata[ev.metadata_id]
                stats = {}
                for s in meta.stats:
                    kind = s.WhichOneof("value")
                    stats[names[s.metadata_id]] = names[s.ref_value] if kind == "ref_value" else getattr(s, kind)
                start = (line.timestamp_ns * 1000 + ev.offset_ps) * 1e-12
                want.append((plane.name, line.name, meta.name, start, start + ev.duration_ps * 1e-12, str(stats.get("tf_op", "")),
                             str(stats.get("hlo_category", "")), float(stats.get("model_flops", 0)), float(stats.get("flops", 0)),
                             float(stats.get("bytes_accessed", 0)), str(stats.get("program_id", "")), str(stats.get("source", ""))))
    got = xm.read_ops(path)
    assert len(got) == len(want) > 2000
    assert [tuple(op) for op in got] == want


def test_busy_time_is_the_traces(dense):
    table = xm.table_of(dense)
    busy = trace.Trace(DENSE).busy_s()
    assert abs(table.busy_s() - busy) < 1e-3 * busy  # ProfileData rounds an event to whole nanoseconds
    assert xm.table_of(dense) is table  # decoded once a run


@pytest.mark.parametrize("path", [DENSE, ROUTED], ids=["dense", "routed"])
def test_the_share_rows_partition_the_ops(path):
    everything = sorted(s for row in PARTITION.values() for s in row)
    assert everything == sorted([*xm.program_scopes(), xm.UNSCOPED])  # each scope of the table in exactly one row
    ev = evidence_of(path)
    table = xm.table_of(ev)
    rows = {name: trace_scope_share.read(ev, {"scopes": scopes}) for name, scopes in PARTITION.items()}
    listed = 100.0 * table.seconds(table.sync) / table.busy_s()  # the rest of the busy time: a `while` between its body's ops
    assert abs(sum(rows.values()) - listed) < 1e-6 and 85 < listed <= 100
    phases = [trace_scope_share.read(ev, {"phase": p}) for p in xm.PHASES]
    assert abs(sum(phases) - listed) < 1e-6 and all(p > 0 for p in phases)
    routed = path == ROUTED
    if routed:
        # The experts' products themselves are NOT under `moe.experts`: the TPU compiler turns `lax.ragged_dot` into kernels
        # of its own (`ragged-dot-none.N`) whose `tf_op` is that name and nothing of the program's path: they read `unscoped`.
        ragged = 100.0 * table.seconds([op for op in table.sync if op.tf_op == "ragged-dot-none:"]) / table.busy_s()
        assert rows["moe_routing"] > 20 and rows["moe_experts"] > 1 and rows["ffn"] == 0 and 5 < ragged < rows["unscoped"] < 35
    else:
        assert rows["ffn"] > 10 and rows["moe_experts"] == rows["moe_routing"] == 0 and 0 < rows["unscoped"] < 25
    assert rows["attn_core"] > 3 and rows["head_loss"] > 3 and rows["optimizer"] > 1


@pytest.mark.parametrize("path", [DENSE, ROUTED], ids=["dense", "routed"])
def test_the_nine_metric_files_are_the_partition_and_the_tool_adds_them_up(path, tmp_path, capsys):
    """PR 58 entered all nine rows: `tools/scope_rows.py` reads them from a traced run's evidence on disk
    (lib/evidence.py), through the metric files, and holds their sum to the listed share of the busy time."""
    ev = evidence_of(path)
    ev["cell"].bench_dir = str(tmp_path)
    os.makedirs(os.path.join(str(tmp_path), "out", f"{ev['cell'].name}-0-trace", "plugins", "profile", "recorded"))
    shutil.copy(path, os.path.join(ev["cell"].out_prefix + "-trace", "plugins", "profile", "recorded", "tiny.xplane.pb.gz"))
    on_disk.write(ev["cell"], ev)
    assert scope_rows.main([ev["cell"].out_prefix]) == 0
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["rows"] == {name: trace_scope_share.read(evidence_of(path), {"scopes": scopes}) for name, scopes in PARTITION.items()}
    assert said["every_scope_in_one_row"] and abs(said["sum"] - said["listed_share_of_busy_pct"]) < 1e-6
    assert sorted(said["in_cell"]) == sorted(set(PARTITION) - {"moe_routing", "moe_experts"})  # the Mistral cell lists the dense rows


METRIC_FILES = sorted(p for p in glob.glob(os.path.join(spec.BENCH_DIR, "metrics", "*.json")) if json.load(open(p))["reader"].startswith("trace_scope_"))


@pytest.mark.parametrize("path", METRIC_FILES, ids=lambda p: os.path.basename(p)[:-5])
def test_a_scope_metric_reads_the_scoped_trace_and_nothing_of_the_parents(path, dense):
    name = os.path.basename(path)[:-5]
    cell = dense["cell"]
    entry = next(m for m in spec.benchmark_json()["per_layer"] if m["name"] == name)
    if name.startswith("train_scope_share_pct."):  # a row of the partition, with the row's scopes, in the cells where it is not nought
        assert spec.metric_file(cell, name)["args"] == {"scopes": PARTITION[name.rpartition(".")[2]]}
    routed_only = all("olmoe" in c for c in entry["workloads"])  # the routed step's rows read 0 on a dense step, and `ffn` 0 on a routed one
    value = spec.read_metric(cell, name, evidence_of(ROUTED) if routed_only else dense)
    if name.startswith("zero_exposed_ms"):
        assert value is None  # one chip: no collective
    else:
        assert 0 < value < 100
    assert spec.read_metric(cell, name, evidence_of(PARENT)) is None


def test_no_reader_reads_a_parents_trace_as_anything():
    ev = evidence_of(PARENT)
    for args in ({"scopes": [xm.UNSCOPED]}, {"phase": "recompute"}, {"categories": list(xm.COPY_CATEGORIES)}, {"scopes": ["ffn"]}):
        assert trace_scope_share.read(ev, args) is None  # `unscoped` is not 100 there: the instrument is absent, not empty
    assert trace_scope_mxu.read(ev, {"scopes": ["ffn"]}) is None and trace_scope_exposed.read(ev, {"scopes": ["zero.param_gather"]}) is None
    assert not xm.table_of(ev).scoped() and xm.table_of(ev).busy_s() > 0


def test_the_mxu_share_divides_xlas_flops_by_the_time_of_the_ops_that_ran_them(dense):
    table = xm.table_of(dense)
    ffn = [op for op in table.sync if table.scope_of(op) == "ffn"]
    want = 100.0 * sum(op.model_flops for op in ffn) / (sum(op.seconds for op in ffn) * 197e12)
    assert trace_scope_mxu.read(dense, {"scopes": ["ffn"]}) == pytest.approx(want) and 30 < want < 100
    assert trace_scope_mxu.read(dense, {"scopes": ["attn.core"]}) < 5  # the kernels are left out: XLA counted nothing for them


def test_a_serving_program_reads_forward_throughout(dense):
    table = xm.table_of(dense)
    by_program = collections.defaultdict(set)
    for op in table.sync:
        if op.tf_op:
            by_program[op.tf_op.split("/")[0]].add(table.phase_of(op))
    assert by_program["jit(llm_decode)"] == {"forward"} and by_program["jit(llm_prefill_p8)"] == {"forward"}
    assert by_program["jit(train_step)"] == set(xm.PHASES)


def test_a_parents_routed_step_is_read_as_a_program_without_the_table():
    """Before PR 56 a routed layer already opened `moe.*` (and `attn.qk_norm`) with nothing round them: by the
    table 84 % of the parent's OLMoE step would read `unscoped` and `attn_core` 0 (my chip run, PR 56). The
    instrument is present only where a scope that EVERY forward opens is (`lib/scopes.json`'s `every_program`)."""
    op = xm.Op("/device:TPU:0", "XLA Ops", "%fusion.1 = f32[] fusion()", 1.0, 2.0, "jit(train_step)/jvp()/while/body/closed_call/moe.router/top_k:", "", 0, 0, 0, "1", "")
    table = xm.OpTable.__new__(xm.OpTable)
    table.names, table._scopes, table.chips, table.sync = frozenset(xm.program_scopes()), {}, [op.plane], [op]
    assert table.scope_of(op) == "moe.router" and not table.scoped()
    table.sync = [op, op._replace(tf_op="jit(train_step)/jvp(norm)/mul:")]
    assert table.scoped()


def test_exposed_seconds_are_what_no_listed_op_covers():
    op = xm.Op("/device:TPU:0", "XLA Ops", "%fusion.1 = f32[] fusion()", 1.0, 2.0, "", "loop fusion", 0, 0, 0, "", "")
    flying = op._replace(line="Async XLA Ops", hlo="%all-gather-start.1", category="all-gather-start", start=1.5, end=3.0)
    alone = op._replace(hlo="%all-reduce.5", category="all-reduce", start=4.0, end=4.5)  # as the four-chip step's are: on the `XLA Ops` line
    table = xm.OpTable.__new__(xm.OpTable)
    table.chips, table.sync = ["/device:TPU:0"], [op, alone]
    assert flying.collective and alone.collective and not op.collective
    assert table.exposed_s([flying]) == pytest.approx(1.0) and table.exposed_s([alone]) == pytest.approx(0.5) and table.exposed_s([]) == 0.0

"""The program's device-side names (`transformer.SCOPES`): the table against
the source, the traced and the compiled steps against the table, the flash
kernels' names, and the operator's tool on a trace recorded on a v5e.

Two views of a step. The JAXPR holds every equation with the name stack it
was traced under: there EVERY equation of a train step lies under a scope of
the table (but a cotangent's sum over uses in different scopes, `add_any`),
and every equation of a serving scan's body too (but the layer's place in
its stack, `sub`). The COMPILED text (CPU) holds what XLA made of them, an
`op_name` an instruction: there each scope the config runs must hold an
instruction in each phase it runs in. It is not asked that every compiled
instruction has a scope: XLA's own plumbing of a scan (`dynamic_slice`,
`dynamic_update_slice`, the carry's `add`, copies, bitcasts) has none, and a
fusion over a scope's border gets the CPU compiler's common prefix of its
parts. That the lowered text WITHOUT names is the parent's is
tests/test_power_retention.py's table of digests, which this PR left as it was.
"""

import collections
import functools
import glob
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from benchmarks.lib import xplane_meta as xm
from ray_tpu.models import transformer as tfm

fa = importlib.import_module("ray_tpu.ops.flash_attention")  # `ray_tpu.ops` exports the function under the module's name
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOURCES = sorted(glob.glob(os.path.join(ROOT, "ray_tpu", "**", "*.py"), recursive=True))
OPENED = {path: re.findall(r"named_scope\(([^)]*)\)", open(path).read()) for path in SOURCES}
OPENED = {os.path.relpath(path, ROOT): found for path, found in OPENED.items() if found}


# ------------------------------------------------- the table and the source


@pytest.mark.parametrize("path", sorted(OPENED))
def test_every_scope_a_file_opens_is_a_literal_of_the_table(path):
    for arg in OPENED[path]:
        assert re.fullmatch(r'"[a-z_.]+"', arg), f"{path}: named_scope({arg}) is no literal"
        assert arg.strip('"') in tfm.SCOPES, f"{path}: {arg} is not in transformer.SCOPES"


@pytest.mark.parametrize("name", list(tfm.SCOPES))
def test_every_name_of_the_table_is_opened_somewhere(name):
    assert any(f'"{name}"' in found for found in OPENED.values())
    assert tfm.SCOPES[name] and name != xm.UNSCOPED and "/" not in name


def test_the_benchmarks_copy_of_the_table_is_the_programs():
    assert xm.program_scopes() == list(tfm.SCOPES)
    assert set(xm.program_scopes("every_program")) == {"norm", "embed", "head"} <= set(tfm.SCOPES)


METRICS = sorted(glob.glob(os.path.join(ROOT, "benchmarks", "metrics", "*.json")))
SCOPE_METRICS = [p for p in METRICS if json.load(open(p))["reader"].startswith("trace_scope_")]


@pytest.mark.parametrize("path", SCOPE_METRICS, ids=os.path.basename)
def test_a_metric_file_names_scopes_of_the_table(path):
    args = json.load(open(path))["args"]
    assert set(args.get("scopes", [])) <= set(tfm.SCOPES) | {xm.UNSCOPED}
    assert args.get("phase") in (None, *xm.PHASES)
    assert args.get("scopes") or args.get("phase") or args.get("categories")


# ------------------------------------------------------ the traced program


def leaves(jaxpr, prefix="", in_scan=False):
    """(inside a scan's body, primitive, name-stack path) of every equation that holds no other."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        path = f"{prefix}/{stack}" if stack else prefix
        inner = []
        for value in eqn.params.values():
            for x in value if isinstance(value, (list, tuple)) else [value]:
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    inner.append(x)
        for sub in inner:
            yield from leaves(sub, f"{path}/{eqn.primitive.name}", in_scan or eqn.primitive.name == "scan")
        if not inner:
            yield in_scan, eqn.primitive.name, path


def unscoped(jaxpr, only_scans=False):
    found = collections.Counter()
    for in_scan, primitive, path in leaves(jaxpr.jaxpr):
        if (in_scan or not only_scans) and xm.scope(path, tfm.SCOPES) == xm.UNSCOPED:
            found[primitive] += 1
    return found


HOT = dict(remat=True, remat_policy="hot", max_seq_len=128)
ROUTED = dict(n_experts=4, n_experts_per_tok=2, qk_norm=True)
TRAINED = {
    "dense": tfm.tiny(**HOT),
    "routed": tfm.tiny(**ROUTED, **HOT),
    "windowed": tfm.tiny(windows=(16, 0), attn_impl="naive", **HOT),
    "afmoe": tfm.tiny(
        **ROUTED, qk_norm_per_head=True, router_score="sigmoid", d_ff_shared=64, n_dense_layers=1, d_ff_dense=128, windows=(16, 0),
        rope_layers=(True, False), attn_gate=True, post_norms=True, embed_scale=True, attn_impl="naive", **HOT,
    ),
}
BLOCK = ("norm", "attn.qkv", "attn.rope", "attn.core", "attn.out", "residual")
EXPECTED = {
    "dense": (*BLOCK, "ffn"),
    "routed": (*BLOCK, "attn.qk_norm", "moe.router", "moe.dispatch", "moe.experts", "moe.combine"),
    "windowed": (*BLOCK, "attn.window", "ffn"),
    "afmoe": (*BLOCK, "attn.qk_norm", "attn.window", "attn.gate", "ffn", "moe.router", "moe.dispatch", "moe.experts", "moe.combine", "moe.shared"),
}
TOKENS = jax.ShapeDtypeStruct((4, 128), jnp.int32)


@functools.lru_cache(maxsize=None)
def train_step(name, chips=1):
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    init_state, step = tfm.build_train_step(TRAINED[name], optax.adamw(1e-3), mesh, zero_axis="data" if chips > 1 else None)
    params, opt = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    return step, (params, opt, TOKENS)


@functools.lru_cache(maxsize=None)
def compiled_ops(name, chips=1):
    """{(scope, phase): instructions} of the compiled step's `op_name`s."""
    step, args = train_step(name, chips)
    text = step.lower(*args).compile().as_text()
    found = collections.Counter()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found[xm.scope(op_name, tfm.SCOPES), xm.phase(op_name)] += 1
    return found


@pytest.mark.parametrize("name", list(TRAINED))
def test_every_equation_of_a_train_step_lies_under_a_scope(name):
    step, args = train_step(name)
    left = unscoped(jax.make_jaxpr(step)(*args))
    # afmoe: the zeros that stand for the gradient of the router's bias, which selects and never weighs
    assert set(left) <= ({"add_any", "broadcast_in_dim"} if name == "afmoe" else {"add_any"}), left


@pytest.mark.parametrize("name", list(TRAINED))
def test_the_compiled_step_holds_every_scope_in_its_phases(name):
    ops = compiled_ops(name)
    for scope in EXPECTED[name]:
        # an add's backward is its cotangent handed on: no instruction
        assert ops[scope, "forward"] and (ops[scope, "backward"] or scope == "residual"), (scope, {k: v for k, v in ops.items() if k[0] == scope})
    # remat_policy "hot" recomputes the norms and what lies between a saved value and its uses
    assert ops["norm", "recompute"] and any(ops[s, "recompute"] for s in ("ffn", "moe.experts"))
    for scope in ("embed", "head", "loss"):
        assert ops[scope, "forward"] and ops[scope, "backward"], scope
    assert ops["optimizer", "update"] and not ops["optimizer", "forward"] and not ops["optimizer", "backward"]
    assert not any(ops[s, p] for s in ("zero.grad_scatter", "zero.update", "zero.param_gather") for p in xm.PHASES)


@pytest.mark.parametrize("scope", ["zero.grad_scatter", "zero.update", "zero.param_gather"])
def test_the_zero_step_on_four_devices_holds_its_scopes(scope):
    ops = compiled_ops("dense", 4)
    assert ops[scope, "update"] and not ops["optimizer", "update"]
    assert ops["loss", "forward"] and ops["ffn", "recompute"]


def test_every_equation_of_the_zero_step_lies_under_a_scope():
    step, args = train_step("dense", 4)
    assert not unscoped(jax.make_jaxpr(step)(*args))


# ----------------------------------------------------- the serving forwards

PAGED = tfm.tiny(**ROUTED, windows=(16, 0), attn_impl="naive")


def _serving(which):
    i32 = jnp.int32
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), PAGED))
    pool = jax.eval_shape(lambda: tfm.init_kv_pages(PAGED, 16, 16))
    if which == "prefill":
        fn = lambda p, t, kv, bt, n, w: tfm.forward_prefill(p, t, PAGED, kv, bt, n, w)  # noqa: E731
        return fn, (params, jax.ShapeDtypeStruct((1, 64), i32), pool, jax.ShapeDtypeStruct((4,), i32), jax.ShapeDtypeStruct((), i32), jax.ShapeDtypeStruct((), i32))
    fn = lambda p, t, pos, kv, bt: tfm.forward_decode(p, t, pos, PAGED, kv, bt, stats=True)  # noqa: E731
    return fn, (params, jax.ShapeDtypeStruct((4,), i32), jax.ShapeDtypeStruct((4,), i32), pool, jax.ShapeDtypeStruct((4, 4), i32))


@pytest.mark.parametrize("which", ["prefill", "decode"])
def test_a_serving_forward_gets_the_blocks_scopes_with_no_line_of_its_own(which):
    fn, args = _serving(which)
    jaxpr = jax.make_jaxpr(fn)(*args)
    assert set(unscoped(jaxpr, only_scans=True)) <= {"sub"}
    text = jax.jit(fn).lower(*args).compile().as_text()
    ops = collections.Counter((xm.scope(n, tfm.SCOPES), xm.phase(n)) for n in re.findall(r'op_name="([^"]*)"', text))
    for scope in (*BLOCK, "attn.qk_norm", "attn.window", "moe.router", "moe.experts", "moe.combine", "embed", "head"):
        assert ops[scope, "update"], scope  # no gradient in the program: neither `jvp(` nor `transpose(`; a table reads it `forward`
    assert not any(phase != "update" for _scope, phase in ops)


# -------------------------------------------------------- the flash kernels


@pytest.mark.parametrize("name", [fa.FWD_KERNEL_NAME, fa.DQ_KERNEL_NAME, fa.DKV_KERNEL_NAME])
def test_the_flash_kernels_carry_their_names(name):
    q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 1, 128), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, interpret=True).astype(jnp.float32)), argnums=(0, 1, 2))
    assert name in (fa.FWD_KERNEL_NAME, fa.DQ_KERNEL_NAME, fa.DKV_KERNEL_NAME) and name.startswith("flash_attention_")
    assert f"name={name}" in str(jax.make_jaxpr(grad)(q, kv, kv))


# --------------------------------------------------------- the tool's table

RECORDED = os.path.join(ROOT, "benchmarks", "recorded", "tiny_v5e_scopes.xplane.pb.gz")


@pytest.mark.parametrize("by", ["scope,phase", "category", "source", "phase"])
def test_the_tools_rows_sum_to_the_busy_time(by, capsys):
    from benchmarks.lib import peaks
    from benchmarks.lib.trace import Trace
    from tools import device_scope_report as tool

    table, spans = tool.load(RECORDED)
    total = tool.report(table, spans, tuple(by.split(",")), peaks.for_kind("TPU v5 lite"))
    busy = Trace(RECORDED).busy_s()
    assert busy > 0 and abs(total - busy) <= 1e-3 * busy
    out = capsys.readouterr().out
    assert tool.CONTROL in out and "asynchronous ops" in out
    if by == "scope,phase":
        for row in ("| ffn | recompute |", "| attn.core | backward |", "| optimizer | update |", "| loss | forward |"):
            assert row in out, row
        assert "flash_attention_dkv" in out  # the kernels by their names, among a row's largest ops


def test_the_chunked_head_and_loss_lie_under_their_scopes(monkeypatch):
    """`head_loss` over several chunks (PR 60): the scan's slices and the hand-written backward read `head` / `loss`, in both phases."""
    cfg = TRAINED["dense"]
    monkeypatch.setattr(tfm, "HEAD_LOSS_CHUNK_BYTES", 4 * cfg.vocab_size * 128)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    grad = jax.jit(jax.grad(lambda p, t: tfm.next_token_loss(p, t, cfg)))
    jaxpr = jax.make_jaxpr(grad)(params, TOKENS)
    assert any(in_scan and primitive == "dot_general" and "(loss)/scan/head/" in path for in_scan, primitive, path in leaves(jaxpr.jaxpr))
    assert set(unscoped(jaxpr)) <= {"add_any"}
    ops = collections.Counter((xm.scope(n, tfm.SCOPES), xm.phase(n)) for n in re.findall(r'op_name="([^"]*)"', grad.lower(params, TOKENS).compile().as_text()))
    assert all(ops[scope, phase] for scope in ("head", "loss") for phase in ("forward", "backward")), ops

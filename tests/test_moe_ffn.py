"""The routed FFN of models/transformer.py (`_ffn` with cfg.n_experts):
dropless top-k over SwiGLU experts behind the one FFN call, against a
per-token loop over experts and against the benchmark's plain reference
(benchmarks/archs/olmoe.py), on the CPU in float32 at tiny widths."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.archs import dense_decoder, olmoe
from benchmarks.lib import spec
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import moe_rows
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import TRANSFORMER_RULES, spec_for_path

D, F, E, K = 64, 32, 8, 2


def moe_cfg(**kw):
    return tfm.tiny(n_kv_heads=4, d_ff=F, n_experts=E, n_experts_per_tok=K, qk_norm=True, dtype=jnp.float32, **kw)


def one_layer_ffn(seed, cfg):
    """One layer's FFN weights and an input [2, 7, d]."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    mp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(seed + 100), (2, 7, D), jnp.float32)
    return h, mp


def loop_ffn(h, mp, experts, renorm=False):
    """Token by token, expert by expert; `experts` [n, k] are concrete
    (chosen outside, so the loop is differentiable in h and the weights)."""
    x = h.reshape(-1, h.shape[-1])
    probs = jax.nn.softmax(x @ mp["router"], axis=-1)
    rows = []
    for t in range(x.shape[0]):
        p = jnp.stack([probs[t, e] for e in experts[t]])
        if renorm:
            p = p / jnp.sum(p)
        out = 0.0
        for j, e in enumerate(experts[t]):
            out = out + p[j] * ((jax.nn.silu(x[t] @ mp["w_gate"][e]) * (x[t] @ mp["w_up"][e])) @ mp["w_down"][e])
        rows.append(out)
    return jnp.stack(rows).reshape(h.shape)


def chosen(h, mp):
    probs = jax.nn.softmax(h.reshape(-1, D) @ mp["router"], axis=-1)
    return np.argsort(-np.asarray(probs), axis=-1, kind="stable")[:, :K]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ffn_equals_the_per_token_loop_in_value_and_in_every_gradient(seed):
    cfg = moe_cfg()
    h, mp = one_layer_ffn(seed, cfg)
    experts = chosen(h, mp)
    np.testing.assert_allclose(tfm._ffn(h, mp, cfg), loop_ffn(h, mp, experts), atol=2e-6)

    def scalar(fn):
        return lambda h, mp: jnp.sum(jnp.sin(3.0 * fn(h, mp)))

    got = jax.grad(scalar(lambda h, mp: tfm._ffn(h, mp, cfg)), argnums=(0, 1))(h, mp)
    want = jax.grad(scalar(lambda h, mp: loop_ffn(h, mp, experts)), argnums=(0, 1))(h, mp)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = dict(jax.tree_util.tree_leaves_with_path(want))[path]
        assert float(jnp.max(jnp.abs(w))) > 1e-3, path  # the router's gradient too: the weights are not constants
        np.testing.assert_allclose(g, w, atol=5e-6, err_msg=str(path))


@pytest.mark.parametrize("renorm", [False, True])
def test_norm_topk_prob_both_ways(renorm):
    cfg = moe_cfg(norm_topk_prob=renorm)
    h, mp = one_layer_ffn(3, cfg)
    experts = chosen(h, mp)
    got = tfm._ffn(h, mp, cfg)
    np.testing.assert_allclose(got, loop_ffn(h, mp, experts, renorm=renorm), atol=2e-6)
    # and the other way is another function: the weights of top-2 of 8 sum to well under 1
    assert float(jnp.max(jnp.abs(got - loop_ffn(h, mp, experts, renorm=not renorm)))) > 1e-2


def test_dropless_under_the_worst_imbalance():
    """A router that sends every token to the same k experts: nothing is
    dropped, shapes do not change, the output still equals the loop."""
    cfg = moe_cfg()
    params = tfm.init_params(jax.random.PRNGKey(4), cfg)
    # all-equal logits: top_k takes the lowest indices for every token
    params["blocks"]["mlp"]["router"] = jnp.zeros((cfg.n_layers, D, E))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, cfg.vocab_size)
    stats = tfm.routing_stats(params, tokens, cfg)
    load = np.asarray(stats["tokens_per_expert"])
    assert load.shape == (cfg.n_layers, E) and (load.sum(axis=1) == 2 * 16 * K).all()
    assert (load[:, :K] == 2 * 16).all() and (load[:, K:] == 0).all()
    assert np.asarray(stats["gap"]).max() == 0.0
    mp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 7, D), jnp.float32)
    experts = np.tile(np.arange(K), (14, 1))
    np.testing.assert_allclose(tfm._ffn(h, mp, cfg), loop_ffn(h, mp, experts), atol=2e-6)
    assert bool(jnp.isfinite(tfm.next_token_loss(params, tokens, cfg)))


def test_routing_stats_counts_every_pair_and_agrees_with_the_reference():
    config = dict(olmoe.TINY, rope_theta=10000.0, rms_norm_eps=1e-5, torch_dtype="float32")
    cfg = olmoe.model_config(config, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (3, 24), 0, cfg.vocab_size)
    stats = tfm.routing_stats(params, tokens, cfg)
    assert stats["experts"].shape == (2, 72, K) and stats["gap"].shape == (2, 72)
    assert (np.asarray(stats["tokens_per_expert"]).sum(axis=1) == 72 * K).all()
    assert float(jnp.min(stats["gap"])) >= 0.0
    for b in range(3):
        want = olmoe.routed_experts(params, tokens[b], config)
        np.testing.assert_array_equal(np.asarray(stats["experts"]).reshape(2, 3, 24, K)[:, b], want)


# ------------------------------------------------ the train step, and wrong models


def olmoe_tiny(**changed):
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", "olmoe-1b-7b-0125-L2.json"))
    config.update(olmoe.TINY, torch_dtype="float32", **changed)
    return config


def test_train_step_step0_loss_is_the_references_and_the_loss_falls():
    config = olmoe_tiny()
    cfg = olmoe.model_config(config, max_seq_len=32)
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices("cpu")[:1])
    _init, step = tfm.build_train_step(cfg, optax.adamw(1e-2), mesh, donate=False)
    params = tfm.init_params(jax.random.PRNGKey(9), cfg)
    opt_state = optax.adamw(1e-2).init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(10), (4, 32), 0, cfg.vocab_size)
    want = float(jnp.mean(jnp.stack([olmoe.sequence_nll(params, t, config) for t in tokens])))
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert abs(losses[0] - want) <= 1e-5, (losses[0], want)
    assert losses[-1] < losses[0] - 0.05


WRONG_MODELS = {
    "top-7": lambda cfg: cfg.replace(n_experts_per_tok=cfg.n_experts_per_tok - 1),
    "renormalised": lambda cfg: cfg.replace(norm_topk_prob=True),
    "no-qk-norm": lambda cfg: cfg.replace(qk_norm=False),
}
PARITY_TOLERANCE = 1e-4  # tests/test_parity.py


@pytest.mark.parametrize("wrong", sorted(WRONG_MODELS))
def test_a_nearby_wrong_model_fails_the_parity_tolerance(wrong):
    """top-1 of 2 in place of top-2, renormalised weights, q/k-norm left out:
    each computes logits far outside the tolerance that the right program holds."""
    config = olmoe_tiny()
    cfg = olmoe.model_config(config, remat=False)
    params = tfm.init_params(jax.random.PRNGKey(11), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (19,), 0, cfg.vocab_size)
    want = olmoe.logits_at(params, tokens, jnp.arange(19), config)
    assert float(jnp.max(jnp.abs(tfm.forward(params, tokens[None], cfg)[0] - want))) <= PARITY_TOLERANCE
    off = float(jnp.max(jnp.abs(tfm.forward(params, tokens[None], WRONG_MODELS[wrong](cfg))[0] - want)))
    assert off > 100 * PARITY_TOLERANCE, off


def test_reference_refuses_what_it_does_not_compute():
    for key, value in (("clip_qkv", 8.0), ("attention_bias", True), ("rope_scaling", {"type": "linear", "factor": 2.0})):
        with pytest.raises(ValueError, match=key):
            olmoe.dims(olmoe_tiny(**{key: value}))


# ------------------------------------------------ counts, sharding, the dense path


def test_flops_per_token_counts_the_active_experts():
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", "olmoe-1b-7b-0125-L2.json"))
    cfg = olmoe.model_config(config)
    d, f, L, V = 2048, 1024, 2, 50304
    per_layer = 4 * d * d + d * 64 + 8 * 3 * d * f
    want = 6.0 * (L * per_layer + 2 * d * V) + 12 * L * d * 2048
    assert tfm.flops_per_token(cfg, 4096) == want  # the program counts the embedding too, as for dense models
    assert olmoe.train_flops_per_token(config, 4096) == want - 6.0 * d * V
    all_experts = 6.0 * L * 56 * 3 * d * f
    assert tfm.flops_per_token(cfg.replace(n_experts_per_tok=64), 4096) == want + all_experts
    # one dense SwiGLU of the same width, for scale: 8 experts and a router more
    dense = cfg.replace(n_experts=0, n_experts_per_tok=0)
    assert tfm.flops_per_token(cfg, 4096) - tfm.flops_per_token(dense, 4096) == 6.0 * L * (7 * 3 * d * f + d * 64)


def test_olmoe_counts_at_the_published_widths():
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", "olmoe-1b-7b-0125-L2.json"))
    cfg = olmoe.model_config(config)
    abstract = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert tfm.param_count(abstract) == 1_045_186_560
    assert abstract["blocks"]["mlp"]["w_gate"].shape == (2, 64, 2048, 1024)
    assert abstract["blocks"]["mlp"]["router"].shape == (2, 2048, 64)
    assert abstract["blocks"]["attn"]["q_norm"]["scale"].shape == (2, 2048)
    kinds = olmoe.kernels(config, 3, 4096)
    assert set(kinds) == {"fwd", "dq", "dkv", *olmoe.GROUPED_MATMULS}
    assert kinds["rows_x_f"][0] == 2.0 * 3 * 4096 * 8 * 2048 * 1024
    assert kinds["fwd"] == dense_decoder.flops.flash_kernels(16, 16, 128, 3, 4096)["fwd"]
    # a decode step reads an eighth of the experts for one sequence, nearly all for 32
    one, many = olmoe.decode_step_min_bytes(config, 1, 0), olmoe.decode_step_min_bytes(config, 32, 0)
    shared = 2 * olmoe.shared_matmul_params(config)
    assert one == shared + 2 * 2 * 8 * olmoe.expert_params(config)
    assert shared + 0.98 * 2 * 2 * 64 * olmoe.expert_params(config) < many < shared + 2 * 2 * 64 * olmoe.expert_params(config)


def test_new_leaves_resolve_under_the_transformer_rules():
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), moe_cfg()))
    from jax.sharding import PartitionSpec as P

    want = {
        "blocks.mlp.router": P(),
        "blocks.mlp.w_gate": P(("fsdp",), "tensor"),
        "blocks.mlp.w_down": P("tensor", ("fsdp",)),
        "blocks.attn.q_norm.scale": P(),
        "blocks.attn.k_norm.scale": P(),
    }
    for path, spec_ in want.items():
        assert spec_for_path(path, TRANSFORMER_RULES) == spec_, path
    assert set(params["blocks"]["mlp"]) == {"router", "w_gate", "w_up", "w_down"}


def _old_mlp(h, mp, cfg):
    """The dense MLP as `_layer`, `forward_prefill` and `forward_decode` each
    spelled it before they shared `_ffn` (PR 27)."""
    up = jnp.einsum("bsd,df->bsf", h, mp["w_up"], preferred_element_type=jnp.float32)
    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", h, mp["w_gate"], preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up).astype(cfg.dtype)
    else:
        act = jax.nn.gelu(up).astype(cfg.dtype)
    return jnp.einsum("bsf,fd->bsd", act, mp["w_down"], preferred_element_type=jnp.float32).astype(cfg.dtype)


DENSE = {
    "llama": lambda: tfm.tiny(),
    "gptj": lambda: tfm.tiny(mlp_act="gelu", parallel_block=True, rotary_dim=8, norm_type="layer", rope_style="interleaved", n_kv_heads=4),
}


@pytest.mark.parametrize("family", sorted(DENSE))
def test_dense_params_tree_and_ffn_are_what_they_were(family):
    """The dense configurations draw the same weights from a key as before
    the routed leaves existed (same tree, same random stream), and `_ffn` is
    bit for bit the MLP the three block copies spelled."""
    cfg = DENSE[family]()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    mlp = {"w_gate", "w_up", "w_down"} if cfg.mlp_act == "swiglu" else {"w_up", "w_down"}
    assert set(params["blocks"]["mlp"]) == mlp and set(params["blocks"]["attn"]) == {"wq", "wk", "wv", "wo"}
    keys = jax.random.split(jax.random.PRNGKey(0), 16)
    L, d, f = cfg.n_layers, cfg.d_model, cfg.d_ff
    down = keys[7] if cfg.mlp_act == "swiglu" else keys[6]
    want = (jax.random.normal(down, (L, f, d), jnp.float32) / np.sqrt(f)).astype(cfg.dtype)
    np.testing.assert_array_equal(np.asarray(params["blocks"]["mlp"]["w_down"], np.float32), np.asarray(want, np.float32))
    head = keys[8] if cfg.mlp_act == "swiglu" else keys[7]
    want = (jax.random.normal(head, (d, cfg.vocab_size), jnp.float32) / np.sqrt(d)).astype(cfg.dtype)
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32), np.asarray(want, np.float32))
    mp = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 9, d), jnp.float32).astype(cfg.dtype)
    np.testing.assert_array_equal(
        np.asarray(tfm._ffn(h, mp, cfg), np.float32), np.asarray(_old_mlp(h, mp, cfg), np.float32))


@pytest.mark.parametrize("axes", [dict(data=2, tensor=2), dict(fsdp=2, tensor=2)])
def test_loss_and_gradients_under_the_transformer_rules_shardings(axes):
    """Expert weights sharded like the dense MLP's (tensor-parallel inside
    each expert, fsdp over d): the partitioned program computes the same loss
    and the same gradients as one device."""
    from ray_tpu.parallel import shard_batch, shard_tree

    cfg = moe_cfg(attn_impl="naive")
    params = tfm.init_params(jax.random.PRNGKey(13), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (4, 16), 0, cfg.vocab_size)
    want, want_g = jax.value_and_grad(tfm.next_token_loss)(params, tokens, cfg)
    mesh = build_mesh(MeshSpec(**axes), devices=jax.devices("cpu")[:4])
    sharded = shard_tree(params, mesh)
    assert sharded["blocks"]["mlp"]["w_gate"].sharding.spec[-1] == "tensor"
    got, got_g = jax.jit(jax.value_and_grad(lambda p, t: tfm.next_token_loss(p, t, cfg, mesh)))(sharded, shard_batch(tokens, mesh))
    assert abs(float(got) - float(want)) <= 1e-5
    for g, w in zip(jax.tree_util.tree_leaves(got_g), jax.tree_util.tree_leaves(want_g)):
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_zero_sharded_step_equals_the_plain_step():
    """build_train_step's ZeRO path (shard_map over data, the loss mesh-free
    per shard) runs the routed FFN on each shard's rows: same loss, same update."""
    cfg = moe_cfg(attn_impl="naive")
    tx = optax.sgd(0.1)  # an update proportional to the gradient: rounding stays rounding
    mesh1 = build_mesh(MeshSpec(data=1), devices=jax.devices("cpu")[:1])
    mesh4 = build_mesh(MeshSpec(data=4), devices=jax.devices("cpu")[:4])
    tokens = jax.random.randint(jax.random.PRNGKey(15), (4, 16), 0, cfg.vocab_size)
    out = []
    for mesh, axis in ((mesh1, None), (mesh4, "data")):
        init, step = tfm.build_train_step(cfg, tx, mesh, zero_axis=axis, donate=False)
        params, opt_state = init(jax.random.PRNGKey(16))
        params, _, loss = step(params, opt_state, tokens)
        out.append((float(loss), np.asarray(params["blocks"]["mlp"]["w_down"]), np.asarray(params["blocks"]["mlp"]["router"])))
    assert abs(out[0][0] - out[1][0]) <= 1e-5
    np.testing.assert_allclose(out[0][1], out[1][1], atol=1e-5)
    np.testing.assert_allclose(out[0][2], out[1][2], atol=1e-5)


# ------------------------------------------------ the row movements' hand-written backward


def plain_routed_ffn(h, mp, cfg):
    """`tfm._routed_ffn` with plain `jnp.take` for both row movements: what
    autodiff's backward (two scatter-adds) is the reference for."""
    b, s, d = h.shape
    n, k = b * s, cfg.n_experts_per_tok
    x = h.reshape(n, d)
    probs = tfm._router_probs(x, mp, cfg)[0]
    top_e = jax.lax.top_k(probs, k)[1]
    top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    flat_e = top_e.reshape(n * k)
    order = jnp.argsort(flat_e)
    inverse = jnp.argsort(order)
    group_sizes = tfm._tokens_per_expert(flat_e, cfg.n_experts)
    xs = jnp.take(x, order // k, axis=0)
    gate = jax.lax.ragged_dot(xs, mp["w_gate"], group_sizes, preferred_element_type=cfg.dtype)
    up = jax.lax.ragged_dot(xs, mp["w_up"], group_sizes, preferred_element_type=cfg.dtype)
    act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(cfg.dtype)
    ys = jax.lax.ragged_dot(act, mp["w_down"], group_sizes, preferred_element_type=cfg.dtype)
    ys = jnp.take(ys, inverse, axis=0).reshape(n, k, d)
    out = jnp.sum(ys.astype(jnp.float32) * top_p[..., None], axis=1)
    return out.astype(cfg.dtype).reshape(b, s, d)


def _scalar(fn, cfg):
    return lambda h, mp: jnp.sum(jnp.sin(3.0 * fn(h, mp, cfg).astype(jnp.float32)))


@pytest.mark.parametrize("routing", ["random", "one-group"])
@pytest.mark.parametrize("renorm", [False, True])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_every_gradient_equals_autodiff_through_plain_takes(k, renorm, routing):
    cfg = moe_cfg(norm_topk_prob=renorm).replace(n_experts=16, n_experts_per_tok=k)
    h, mp = one_layer_ffn(20 + k, cfg)
    if routing == "one-group":  # all-equal logits: every token takes experts 0..k-1, the worst imbalance
        mp = dict(mp, router=jnp.zeros_like(mp["router"]))
    np.testing.assert_array_equal(tfm._routed_ffn(h, mp, cfg), plain_routed_ffn(h, mp, cfg))
    got = jax.grad(_scalar(tfm._routed_ffn, cfg), argnums=(0, 1))(h, mp)
    want = jax.grad(_scalar(plain_routed_ffn, cfg), argnums=(0, 1))(h, mp)
    assert set(got[1]) == {"router", "w_gate", "w_up", "w_down"}
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        if not (renorm and k == 1):  # a renormalised single weight is the constant 1: no router gradient
            assert float(jnp.max(jnp.abs(w))) > 1e-3, path
        np.testing.assert_allclose(g, w, atol=5e-6, err_msg=str(path))


def _row_scatters(jaxpr, width):
    """Every scatter of the (closed) jaxpr, sub-jaxprs included, whose
    operand's rows are `width` wide."""
    found = []
    for eqn in jaxpr.eqns:
        if "scatter" in eqn.primitive.name and eqn.invars[0].aval.shape[-1:] == (width,):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _row_scatters(sub, width)
    return found


def test_the_gradient_scatters_no_rows():
    """Of `_routed_ffn` alone: dispatch and combine are permutations, so
    their transposes are gathers. (The probabilities' `take_along_axis`
    keeps its scatter into [n, E]; E is not D here.)"""
    cfg = moe_cfg()
    h, mp = one_layer_ffn(30, cfg)
    ours = jax.make_jaxpr(jax.grad(_scalar(tfm._routed_ffn, cfg), argnums=(0, 1)))(h, mp)
    plain = jax.make_jaxpr(jax.grad(_scalar(plain_routed_ffn, cfg), argnums=(0, 1)))(h, mp)
    assert len(_row_scatters(plain.jaxpr, D)) == 2  # what the detector is for: autodiff's two
    assert _row_scatters(ours.jaxpr, D) == []


def test_bf16_dispatch_gradient_sums_the_k_copies_in_float32():
    """dx of dispatch rounds once, after a float32 sum over a token's k
    copies: at least as close to the exact sum as autodiff's scatter-add,
    which rounds to bf16 after every row it adds."""
    n, k, d = 64, 8, D
    order = jnp.argsort(jax.random.randint(jax.random.PRNGKey(40), (n * k,), 0, E))
    inverse = jnp.argsort(order)
    x = jax.random.normal(jax.random.PRNGKey(41), (n, d), jnp.float32).astype(jnp.bfloat16)
    dxs = jax.random.normal(jax.random.PRNGKey(42), (n * k, d), jnp.float32).astype(jnp.bfloat16)
    got = jax.vjp(lambda x: moe_rows.dispatch_rows(x, order, inverse, k), x)[1](dxs)[0]
    old = jax.vjp(lambda x: jnp.take(x, order // k, axis=0), x)[1](dxs)[0]
    exact = np.zeros((n, d), np.float64)
    np.add.at(exact, np.asarray(order) // k, np.asarray(dxs, np.float64))
    assert got.dtype == old.dtype == jnp.bfloat16
    err_got = np.abs(np.asarray(got, np.float64) - exact)
    err_old = np.abs(np.asarray(old, np.float64) - exact)
    assert err_got.max() <= err_old.max() and err_got.mean() <= err_old.mean()
    # one rounding of the exact sum: half a bf16 ulp of the result
    assert (err_got <= 2.0**-8 * np.abs(exact) + 1e-30).all()


def test_remat_hot_gives_the_gradients_of_the_step_without_remat():
    """Under remat_policy "hot" the hand-written backward reads the saved
    `moe_route` indices and the recomputed sorted rows: same gradients."""
    cfg = moe_cfg(attn_impl="naive")
    params = tfm.init_params(jax.random.PRNGKey(50), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(51), (2, 16), 0, cfg.vocab_size)
    want_loss, want = jax.value_and_grad(tfm.next_token_loss)(params, tokens, cfg)
    hot = cfg.replace(remat=True, remat_policy="hot")
    got_loss, got = jax.jit(jax.value_and_grad(lambda p, t: tfm.next_token_loss(p, t, hot)))(params, tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-6, err_msg=str(path))
    assert float(jnp.max(jnp.abs(want["blocks"]["mlp"]["router"]))) > 1e-4


# ------------------------------------------- one chip's share of the experts


def share_of(full_cfg, mp_full, rank, held):
    """Rank `rank`'s config and its slice of a full layer's weights: the
    router and its bias whole, the expert stacks' rows [rank x held, ...)."""
    cfg = full_cfg.replace(n_experts_held=held, first_expert=rank * held)
    here = slice(rank * held, (rank + 1) * held)
    return cfg, dict(mp_full, **{name: mp_full[name][here] for name in tfm.EXPERT_WEIGHTS})


@pytest.mark.parametrize("router", ["softmax_renormalised", "sigmoid_with_a_selecting_bias_and_a_shared_expert"])
@pytest.mark.parametrize("form", ["grouped", "every_expert", "grouped_in_place"])
def test_the_eight_shares_add_up_to_the_uncut_layer(form, router):
    """The model-configs guide's share test. A layer of 16 experts, top-4, cut
    eight ways (2 experts a rank): every rank routes over all 16, renormalises
    over the 4 chosen whether held or not, and computes its own experts' part;
    the eight parts, the shared expert counted once, are the uncut layer's
    result. All three forms of the routed FFN: training's grouped one
    (`lax.ragged_dot`), a decode step's every-expert one, and a prefill
    chunk's, grouped on the stack in place (ops/grouped_matmul.py: a call of
    GROUPED_FROM_ROWS rows), which must also agree with training's share by share."""
    kw = dict(norm_topk_prob=True) if router.startswith("softmax") else dict(router_score="sigmoid", norm_topk_prob=True, route_scale=1.7, d_ff_shared=F)
    full = tfm.tiny(n_kv_heads=4, d_ff=F, n_experts=16, n_experts_per_tok=4, dtype=jnp.float32, **kw)
    mp = jax.tree_util.tree_map(lambda a: a[0], tfm.init_params(jax.random.PRNGKey(3), full)["blocks"]["mlp"])
    rows = tfm.GROUPED_FROM_ROWS // 2 if form == "grouped_in_place" else 9
    h = jax.random.normal(jax.random.PRNGKey(4), (2, rows, D), jnp.float32)
    assert tfm.experts_grouped_at(2 * rows) == (form == "grouped_in_place")

    def ffn(cfg, mp):
        if form == "grouped":
            return tfm._routed_ffn(h, mp, cfg)
        stack = {name: mp[name][None] for name in tfm.EXPERT_WEIGHTS}
        riding = {name: w for name, w in mp.items() if name not in tfm.EXPERT_WEIGHTS}
        return tfm._routed_ffn(h, riding, cfg, experts=(stack, jnp.int32(0)))

    with jax.default_matmul_precision("highest"):
        whole = ffn(full, mp)
        shared = tfm._ffn(h, mp["shared"], full) if "shared" in mp else 0.0
        parts = [ffn(*share_of(full, mp, rank, 2)) for rank in range(8)]
        other = [tfm._routed_ffn(h, share_of(full, mp, rank, 2)[1], share_of(full, mp, rank, 2)[0]) for rank in (0, 5)]
    np.testing.assert_allclose(sum(parts) - 7 * shared, whole, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(parts[0] - shared))) > 1e-3  # a share is a part, not nothing
    for rank, grouped in zip((0, 5), other):
        np.testing.assert_allclose(parts[rank], grouped, rtol=2e-5, atol=2e-6)


def test_a_share_keeps_the_routers_width_and_its_gradients_reach_only_the_held_experts():
    full = moe_cfg(norm_topk_prob=True)
    cfg = full.replace(n_experts_held=2, first_expert=4)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    mlp = params["blocks"]["mlp"]
    assert mlp["router"].shape == (2, D, E) and mlp["w_gate"].shape == (2, 2, D, F) and mlp["w_down"].shape == (2, 2, F, D)
    with pytest.raises(ValueError, match="not among the router's"):
        tfm.init_params(jax.random.PRNGKey(0), full.replace(n_experts_held=4, first_expert=6))
    mp = jax.tree_util.tree_map(lambda a: a[0], mlp)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 7, D), jnp.float32)
    out, counts = tfm._routed_ffn(h, mp, cfg, counts=True)
    assert counts.shape == (E,) and int(counts.sum()) == 14 * K  # every pick of the router's, held or not
    grads = jax.grad(lambda mp: jnp.sum(tfm._routed_ffn(h, mp, cfg) ** 2))(mp)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))
    picked = np.asarray(counts[4:6]) > 0
    assert np.array_equal(np.asarray(jnp.any(grads["w_down"] != 0, axis=(1, 2))), picked)
    # the held share of a token's picks is what flops_per_token counts: 2 of 8 experts, so a quarter of k
    dense_part = tfm.flops_per_token(cfg.replace(n_experts_per_tok=0), 128)
    assert tfm.flops_per_token(cfg, 128) - dense_part == pytest.approx((tfm.flops_per_token(full, 128) - tfm.flops_per_token(full.replace(n_experts_per_tok=0), 128)) / 4)


# ------------------------------------------------ group-limited selection


def grouped_router_cfg(**kw):
    """The published router's shape at a toy width: 256 experts in 8 groups of 32, 4 groups kept, top-8."""
    return tfm.tiny(n_kv_heads=4, d_ff=16, n_experts=256, n_experts_per_tok=8, router_score="sigmoid", norm_topk_prob=True,
                    route_scale=2.5, d_ff_shared=16, n_group=8, topk_group=4, dtype=jnp.float32, **kw)


def loop_selection(ranked, n_group, topk_group, k):
    """Group-limited selection written out for ONE token: a group's score is
    the sum of its two largest entries; the best groups stay (the lower index
    on a tie); the k largest entries among them, best first."""
    per = len(ranked) // n_group
    scores = [sum(sorted(ranked[g * per:(g + 1) * per])[-2:]) for g in range(n_group)]
    kept = sorted(range(n_group), key=lambda g: (-scores[g], g))[:topk_group]
    allowed = [e for g in kept for e in range(g * per, (g + 1) * per)]
    return sorted(allowed, key=lambda e: (-ranked[e], e))[:k]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_selection_is_the_loop_written_one_where_plain_top_k_differs(seed):
    cfg = grouped_router_cfg()
    mp = jax.tree_util.tree_map(lambda a: a[0], tfm.init_params(jax.random.PRNGKey(seed), cfg)["blocks"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 50), (24, D), jnp.float32)
    scores, ranked = tfm._router_probs(x, mp, cfg)
    plain_scores, plain = tfm._router_probs(x, mp, cfg.replace(n_group=1, topk_group=1))
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(plain_scores))  # the groups select; they never weigh
    got = np.asarray(jax.lax.top_k(ranked, 8)[1])
    want = np.asarray([loop_selection([float(v) for v in row], 8, 4, 8) for row in np.asarray(plain)])
    np.testing.assert_array_equal(got, want)
    unlimited = np.asarray(jax.lax.top_k(plain, 8)[1])
    assert (np.sort(unlimited, -1) != np.sort(want, -1)).any(axis=-1).sum() >= 12  # plain top-8 would choose otherwise for most tokens
    assert all(len({e // 32 for e in row}) <= 4 for row in got)


def test_one_group_is_todays_router_bit_for_bit():
    """n_group 1: `_router_probs` returns what it returned, and the routed FFN lowers to the text it lowered to."""
    cfg = moe_cfg(router_score="sigmoid", norm_topk_prob=True, d_ff_shared=F)
    assert (cfg.n_group, cfg.topk_group) == (1, 1)
    h, mp = one_layer_ffn(0, cfg)
    scores, ranked = tfm._router_probs(h.reshape(-1, D), mp, cfg)
    logits = jnp.einsum("nd,de->ne", h.reshape(-1, D), mp["router"], precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(jax.nn.sigmoid(logits)))
    np.testing.assert_array_equal(np.asarray(ranked), np.asarray(jax.nn.sigmoid(logits) + mp["router_bias"]))
    text = jax.jit(lambda h, mp: tfm._routed_ffn(h, mp, cfg)).lower(h, mp).as_text()
    assert "moe.route.groups" not in text and text.count("top_k") + text.count("topk") <= 2
    grouped = jax.jit(lambda h, mp: tfm._routed_ffn(h, mp, cfg.replace(n_group=2, topk_group=1))).lower(h, mp).as_text(debug_info=True)
    assert "moe.route.groups" in grouped
    with pytest.raises(ValueError, match="group-limited selection"):
        tfm.init_params(jax.random.PRNGKey(0), moe_cfg(n_group=2))  # a softmax router has no groups


# The published routers cut sixteen ways: dots.vlm1's and GigaChat3.5's shape (8 groups of 32, 4 kept, a shared expert), and
# MiMo-V2's (benchmarks/configs/mimo-v2.5-L7.json: one group, no shared expert to count once, the weights times 1).
SIXTEEN_WAYS = {
    "group_limited_with_a_shared_expert": {},
    "mimo_v2_one_group_no_shared_expert": dict(n_group=1, topk_group=1, d_ff_shared=0, route_scale=1.0),
}


@pytest.mark.parametrize("router", sorted(SIXTEEN_WAYS))
@pytest.mark.parametrize("form", ["grouped", "every_expert"])
def test_the_sixteen_shares_of_a_group_limited_layer_add_up_to_the_uncut_layer(form, router):
    """The model-configs guide's share test at the published router's shape:
    256 experts (in 8 groups, 4 kept; or in one), top-8, cut sixteen ways (16
    experts a rank): every rank routes over all 256, renormalises over the 8
    chosen whether held or not, and computes its own experts' part; the
    sixteen parts, the shared expert (where the model has one) counted once,
    are the uncut layer's result."""
    full = grouped_router_cfg().replace(**SIXTEEN_WAYS[router])
    mp = jax.tree_util.tree_map(lambda a: a[0], tfm.init_params(jax.random.PRNGKey(3), full)["blocks"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, D), jnp.float32)

    def ffn(cfg, mp):
        if form == "grouped":
            return tfm._routed_ffn(h, mp, cfg)
        stack = {name: mp[name][None] for name in tfm.EXPERT_WEIGHTS}
        riding = {name: w for name, w in mp.items() if name not in tfm.EXPERT_WEIGHTS}
        return tfm._routed_ffn(h, riding, cfg, experts=(stack, jnp.int32(0)))

    with jax.default_matmul_precision("highest"):
        whole, shared = ffn(full, mp), (tfm._ffn(h, mp["shared"], full) if "shared" in mp else 0.0)
        parts = [ffn(*share_of(full, mp, rank, 16)) for rank in range(16)]
    assert ("shared" in mp) == (router == "group_limited_with_a_shared_expert")
    np.testing.assert_allclose(sum(parts) - 15 * shared, whole, rtol=2e-5, atol=2e-6)
    assert sum(float(jnp.max(jnp.abs(p - shared))) > 1e-3 for p in parts) >= 8  # shares are parts, not nothing

"""Model-family tests on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from ray_tpu import models
from ray_tpu.models import transformer as tfm
from ray_tpu.models import mlp
from ray_tpu.parallel import MeshSpec, build_mesh, shard_tree, shard_batch
from ray_tpu.parallel.sharding import TRANSFORMER_RULES


def test_param_shapes_and_count():
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert params["blocks"]["attn"]["wq"].shape == (2, 64, 64)
    assert params["blocks"]["attn"]["wk"].shape == (2, 64, 32)  # GQA kv heads
    assert tfm.param_count(params) > 0


def test_forward_shapes_fp32_logits():
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_loss_finite_and_decreases_with_sgd():
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)

    loss0 = tfm.next_token_loss(params, tokens, cfg)
    assert bool(jnp.isfinite(loss0))

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(tfm.next_token_loss)(p, tokens, cfg)
        return l, jax.tree_util.tree_map(lambda w, gw: w - 0.1 * gw.astype(w.dtype), p, g)

    p = params
    losses = []
    for _ in range(5):
        l, p = step(p)
        losses.append(float(l))
    assert losses[-1] < losses[0]


def test_causality():
    """Future tokens must not affect current logits."""
    cfg = tfm.tiny(remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[:, 10:].set((t1[:, 10:] + 7) % cfg.vocab_size)
    l1 = tfm.forward(params, t1, cfg)
    l2 = tfm.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[:, :10]), np.asarray(l2[:, :10]), atol=1e-4)


def test_sharded_forward_matches_single_device():
    """Full pjit path: params sharded fsdp+tensor over 8 devices."""
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    cfg = tfm.tiny(remat=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)

    expected = tfm.forward(params, tokens, cfg)

    sparams = shard_tree(params, mesh)
    stokens = shard_batch({"tokens": tokens}, mesh)["tokens"]
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(sparams, stokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=3e-2, rtol=3e-2)


def test_ring_attention_model_matches_full():
    """Sequence-parallel model == full-attention model."""
    devs = jax.devices("cpu")[:4]
    mesh = build_mesh(MeshSpec(data=1, seq=4), devices=devs)
    # fp32 so the comparison is exact; in bf16 the two orderings differ by
    # ~4e-2 of pure rounding noise.
    cfg_full = tfm.tiny(remat=False, dtype=jnp.float32)
    cfg_ring = tfm.tiny(remat=False, attn_impl="ring", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_full)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg_full.vocab_size)

    expected = tfm.forward(params, tokens, cfg_full)
    got = tfm.forward(params, tokens, cfg_ring, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=1e-3, rtol=1e-3)


def test_stacked_param_sharding_right_aligned():
    mesh = build_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    cfg = tfm.tiny()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sp = shard_tree(params, mesh)
    wq = sp["blocks"]["attn"]["wq"]  # [L, d, hd*nh] -> (None, fsdp, tensor)
    assert wq.sharding.spec == PartitionSpec(None, ("fsdp",), "tensor")


def test_mlp_learns_xor_ish():
    cfg = mlp.MLPConfig(in_dim=2, hidden=(16,), n_classes=2)
    params = mlp.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.array([[0, 0], [0, 1], [1, 0], [1, 1]], jnp.float32)
    y = jnp.array([0, 1, 1, 0], jnp.int32)
    batch = {"x": x, "y": y}

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(mlp.loss_fn)(p, batch)
        return l, jax.tree_util.tree_map(lambda w, gw: w - 0.5 * gw, p, g)

    p = params
    for _ in range(200):
        _, p = step(p)
    assert float(mlp.accuracy(p, batch)) == 1.0


def test_paged_decode_matches_full_forward():
    """The serving decode path (paged KV cache, one compiled step per
    batch composition) must be NUMERICALLY the same model as training
    `forward`: greedy decode token-for-token, including a prefix-cached
    second sequence (its prefill skips re-writing shared pages) and an
    inactive batch slot (position -1, writes redirected to the trash
    page)."""
    from ray_tpu.serve.llm.kv_cache import PagedKVAllocator
    from ray_tpu.serve.llm.model import PagedLM

    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, remat=False)
    T = 8
    lm = PagedLM(cfg, seed=0, num_pages=32, page_tokens=T, max_slots=2,
                 max_pages_per_seq=8)
    alloc = PagedKVAllocator(32, T)

    def gold(prompt, n):
        seq = list(prompt)
        out = []
        for _ in range(n):
            logits = tfm.forward(lm.params, jnp.asarray([seq], jnp.int32), cfg)
            nxt = int(jnp.argmax(logits[0, len(seq) - 1]))
            out.append(nxt)
            seq.append(nxt)
        return out

    def paged(prompt, n, sp, slot, co_pos=None, co_tok=None, co_pages=None):
        """Decode `n` tokens for `sp` in `slot`; the other slot either
        idles (position -1) or replays a fixed co-resident sequence."""
        got = [lm.prefill(prompt, sp.pages, sp.cached_tokens)]
        alloc.commit(sp, prompt)
        while len(got) < n:
            pos = len(prompt) + len(got) - 1
            if pos >= sp.num_pages * T:
                alloc.extend(sp)
            toks = [0, 0]
            poss = [-1, -1]
            tabs = [[], []]
            toks[slot], poss[slot], tabs[slot] = got[-1], pos, sp.pages
            got.append(int(lm.decode(toks, poss, tabs)[slot]))
        return got

    # 13-token prompt: crosses a page boundary mid-prompt AND during
    # decode (position 16 needs a third page via alloc.extend).
    p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]
    assert paged(p1, 8, alloc.allocate(p1), slot=0) == gold(p1, 8)

    # Prefix-cached sequence in the OTHER slot: shares p1's first full
    # page physically (prefill skips re-writing it), must still match.
    p2 = p1[:T] + [7, 7]
    sp2 = alloc.allocate(p2)
    assert sp2.cached_tokens == T  # radix hit on the committed page
    assert paged(p2, 5, sp2, slot=1) == gold(p2, 5)


ONE_BLOCK = {
    "dense_gqa": dict(),
    "parallel_block": dict(
        n_kv_heads=4, mlp_act="gelu", parallel_block=True, rotary_dim=8, norm_type="layer",
        rope_style="interleaved",
    ),
    "routed_qk_norm": dict(
        n_kv_heads=4, d_ff=32, n_experts=8, n_experts_per_tok=2, qk_norm=True,
    ),
}


@pytest.mark.parametrize("name", list(ONE_BLOCK))
def test_train_prefill_and_decode_run_the_one_block(name, monkeypatch):
    """`forward`, `forward_prefill` and `forward_decode` each trace
    `_block` and nothing beside it (so a change to the block is a change to
    all three, and the training step cannot drift from what is served), and
    the paged pair computes what `forward` computes."""
    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, **ONE_BLOCK[name])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    traced = []
    block = tfm._block
    monkeypatch.setattr(
        tfm, "_block", lambda *a, **kw: traced.append(1) or block(*a, **kw)
    )

    T, S, n = 8, 16, 11  # page, prefill bucket, prompt length
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab_size, jnp.int32)
    full = tfm.forward(params, tokens, cfg)
    assert len(traced) == 1  # the layer scan traces its body once

    kv = tfm.init_kv_pages(cfg, 8, T)
    table = jnp.asarray([1, 2], jnp.int32)
    prompt = tokens.at[0, n:].set(0)  # what lies beyond the length is padding
    logits, kv = tfm.forward_prefill(
        params, prompt, cfg, kv, table, jnp.int32(n), jnp.int32(0)
    )
    assert len(traced) == 2
    np.testing.assert_allclose(logits[0], full[0, n - 1], rtol=2e-4, atol=2e-4)

    # slot 0 takes the prompt's next token at position n; slot 1 is inactive
    logits, kv = tfm.forward_decode(
        params, jnp.asarray([tokens[0, n], 0], jnp.int32), jnp.asarray([n, -1], jnp.int32),
        cfg, kv, jnp.asarray([[1, 2], [0, 0]], jnp.int32),
    )
    assert len(traced) == 3
    np.testing.assert_allclose(logits[0], full[0, n], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("heads", [4, 2, 1], ids=["mha", "gqa_kv", "one_head"])
@pytest.mark.parametrize("per_row", [False, True], ids=["s_r", "b_s_r"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "style,rotary_dim", [("half", None), ("interleaved", None), ("half", 8), ("interleaved", 8)]
)
def test_flat_rope_equals_the_head_view_bit_for_bit(style, rotary_dim, dtype, per_row, heads):
    """apply_rope on a projection before its split into heads (what `_block`
    passes while a call has fewer rows than the weight) against apply_rope
    on the head view (a training batch): the same float32 products and sum,
    so equal."""
    cfg = tfm.tiny(rope_style=style, rotary_dim=rotary_dim)
    b, s, hd = 3, 12, cfg.head_dim
    x = jax.random.normal(jax.random.PRNGKey(heads), (b, s, heads * hd), jnp.float32).astype(dtype)
    cos, sin = tfm.rope_tables(cfg, 64)
    if per_row:  # each row its own positions, as decode takes them
        pos = jax.random.randint(jax.random.PRNGKey(7), (b, s), 0, 64)
        cos, sin = cos[pos], sin[pos]
    else:
        cos, sin = cos[:s], sin[:s]
    want = tfm.apply_rope(x.reshape(b, s, heads, hd), cos, sin, cfg).reshape(x.shape)
    # op by op: one compiled program may contract a product and the sum into
    # one rounding, and need not choose the same product in both spellings
    got = tfm.apply_rope(x, cos, sin, cfg)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize(
    "style,rotary_dim", [("half", None), ("interleaved", None), ("half", 8), ("interleaved", 8)]
)
def test_rope_per_row_tables_match_per_sequence_tables(style, rotary_dim):
    """One rope for both table shapes: [s, r] (every row at positions 0..s)
    and [b, s, r] (each row its own positions, decode's one position a row
    among them)."""
    cfg = tfm.tiny(rope_style=style, rotary_dim=rotary_dim)
    b, s = 3, 12
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, cfg.n_heads * cfg.head_dim), jnp.float32)
    cos, sin = tfm.rope_tables(cfg, s)
    want = tfm.apply_rope(x, cos, sin, cfg)
    rows = lambda t: jnp.broadcast_to(t, (b, *t.shape))
    np.testing.assert_array_equal(tfm.apply_rope(x, rows(cos), rows(sin), cfg), want)
    pos = jnp.asarray([0, 5, 11])  # one position a row, as decode takes them
    one = tfm.apply_rope(
        x[jnp.arange(b), pos][:, None], cos[pos][:, None, :], sin[pos][:, None, :], cfg
    )
    np.testing.assert_array_equal(one[:, 0], want[jnp.arange(b), pos])
    if rotary_dim is not None:  # what lies beyond rotary_dim passes through
        heads = lambda t: t.reshape(b, s, cfg.n_heads, cfg.head_dim)
        np.testing.assert_array_equal(heads(want)[..., rotary_dim:], heads(x)[..., rotary_dim:])


@pytest.mark.parametrize("heads", [4, 1], ids=["heads", "one_head"])
def test_per_head_rms_norm_on_the_flat_projection_equals_the_head_view(heads):
    """The per-head qk-norm takes only its statistic from a head view: the
    numbers of rms_norm over [.., heads, head_dim], bit for bit."""
    hd, eps = 16, 1e-5
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, heads * hd), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (hd,), jnp.float32)
    want = tfm.rms_norm(x.reshape(2, 5, heads, hd), scale, eps).reshape(x.shape)
    np.testing.assert_array_equal(tfm._rms_norm_per_head(x, scale, eps, hd), want)


@pytest.mark.parametrize("style,rotary_dim", [("half", None), ("interleaved", 8)])
def test_block_gives_the_same_numbers_on_both_sides_of_its_row_rule(style, rotary_dim):
    """`_block` splits q and k into heads before rope while a call has at
    least d_model rows and after it while it has fewer: one sequence alone
    (32 rows of a 64-wide model) against the same sequence in a batch of
    four (128 rows)."""
    cfg = tfm.tiny(rope_style=style, rotary_dim=rotary_dim)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    assert tokens[:1].size < cfg.d_model <= tokens.size
    alone, batched = tfm.forward(params, tokens[:1], cfg), tfm.forward(params, tokens, cfg)
    np.testing.assert_allclose(alone[0], batched[0], rtol=1e-5, atol=1e-5)

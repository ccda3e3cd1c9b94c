"""`transformer.head_loss`: the head and the loss as one function with a hand-written derivative, held to the plain expression
it replaced (`log_softmax` of `_logits`' float32 logits, differentiated by jax)."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm


def plain_loss(params, tokens, cfg, mask=None):
    """`next_token_loss` as it was before PR 60."""
    logits = tfm.forward(params, tokens, cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    s = tokens.shape[1]
    m = jnp.broadcast_to(jnp.arange(s)[None, :] < s - 1, nll.shape).astype(nll.dtype)
    if mask is not None:
        m = m * jnp.roll(mask, -1, axis=1).astype(nll.dtype)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


# name -> (batch, seq), the rows a chunk may hold (None: the module's budget, so one chunk), then what else the case turns on
CASES = {
    "several_chunks": ((4, 16), 8, {}),
    "rows_the_budget_does_not_divide": ((3, 10), 8, {}),  # 30 rows in chunks of 6
    "one_chunk": ((4, 16), None, {}),
    "a_mask": ((4, 16), 8, {"mask": True}),
    "a_tied_head": ((4, 16), 8, {"tied": True}),
    "a_cotangent_of_2.5": ((4, 16), 8, {"scale": 2.5}),
    "chunks_of_one_row": ((1, 7), 2, {}),  # a prime: no divisor under the budget but 1
    "more_chunks_than_are_laid_out": ((4, 16), 2, {}),  # 32 > HEAD_LOSS_CHUNKS_LAID_OUT: the scan stays a loop
}
# of a leaf's largest entry: float32 differs by the order of its sums; in bfloat16 dlogits is rounded once more than jax's
# float32 cotangent (2**-9 an entry) before every gradient is rounded to the leaf's 8 bits
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 4e-2}


@pytest.mark.parametrize("dtype", list(TOLERANCE), ids=lambda d: d.__name__)
@pytest.mark.parametrize("case", list(CASES))
def test_loss_and_every_leafs_gradient_are_the_plain_expressions(case, dtype, monkeypatch):
    (b, s), chunk_rows, turns = CASES[case]
    cfg = tfm.tiny(dtype=dtype, attn_impl="naive", n_layers=1, tie_embeddings=turns.get("tied", False))
    if chunk_rows:
        monkeypatch.setattr(tfm, "HEAD_LOSS_CHUNK_BYTES", 4 * cfg.vocab_size * chunk_rows)
    assert (tfm._loss_chunk_rows(b * s, cfg.vocab_size) == b * s) == (chunk_rows is None)
    assert (b * s // tfm._loss_chunk_rows(b * s, cfg.vocab_size) > tfm.HEAD_LOSS_CHUNKS_LAID_OUT) == (case == "more_chunks_than_are_laid_out")
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    assert ("lm_head" in params) != cfg.tie_embeddings
    tokens = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0, cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.PRNGKey(5), (b, s)) < 0.6) if turns.get("mask") else None
    scale = turns.get("scale", 1.0)
    want, want_g = jax.value_and_grad(lambda p: scale * plain_loss(p, tokens, cfg, mask))(params)
    got, got_g = jax.jit(jax.value_and_grad(lambda p: scale * tfm.next_token_loss(p, tokens, cfg, mask=mask)))(params)
    assert got.dtype == jnp.float32 and abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want_g)[0], jax.tree_util.tree_leaves(got_g)):
        assert g.dtype == w.dtype == dtype and g.shape == w.shape
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        assert np.abs(w).max() > 0 and np.abs(g - w).max() <= TOLERANCE[dtype] * np.abs(w).max(), jax.tree_util.keystr(path)


def test_the_weights_of_the_mean_get_their_derivative_too():
    """The function is differentiable in all it takes: a row's weight by the row's loss."""
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    x, head = jax.random.normal(keys[0], (2, 6, 16)), jax.random.normal(keys[1], (16, 40))
    targets, weights = jax.random.randint(keys[2], (2, 6), 0, 40), jax.random.uniform(keys[3], (2, 6))

    def plain(x, head, weights):
        return jnp.sum(-jnp.take_along_axis(jax.nn.log_softmax(x @ head), targets[..., None], axis=-1)[..., 0] * weights)

    want = jax.grad(plain, argnums=(0, 1, 2))(x, head, weights)
    for chunked in (True, False):
        got = jax.grad(lambda x, head, weights: tfm.head_loss(x, head, targets, weights, chunked), argnums=(0, 1, 2))(x, head, weights)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5)


def test_the_lowered_gradient_holds_no_float32_logits_and_multiplies_in_the_parameters_dtype(monkeypatch):
    """At a shape of several chunks, the text `jax.grad(next_token_loss)` lowers to: no float32 value as large as
    [batch x seq, vocab]; the head's three products (one forward in the scan's body, two backward, none a second
    time) take bfloat16 on both sides and accumulate in float32."""
    cfg = tfm.tiny(vocab_size=384, attn_impl="naive", n_layers=1)  # 384: no other width of the model
    b, s, v = 4, 32, cfg.vocab_size
    monkeypatch.setattr(tfm, "HEAD_LOSS_CHUNK_BYTES", 4 * v * 32)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    text = jax.jit(jax.grad(lambda p, t: tfm.next_token_loss(p, t, cfg))).lower(params, jax.ShapeDtypeStruct((b, s), jnp.int32)).as_text()
    sizes = [math.prod(int(n) for n in dims.split("x") if n) for dims in re.findall(r"tensor<((?:\d+x)*)f32>", text)]
    assert sizes and max(sizes) < b * s * v, max(sizes)
    assert f"tensor<{b}x{s}x{v}xbf16>" in text  # dlogits whole, in the parameters' dtype
    products = re.findall(r"stablehlo\.dot_general.*: \((tensor<[^>]*>), (tensor<[^>]*>)\) -> (tensor<[^>]*>)", text)
    heads = [p for p in products if any(f"{v}x" in t or f"x{v}x" in t for t in p)]
    assert len(heads) == 3, heads
    for left, right, out in heads:
        assert left.endswith("xbf16>") and right.endswith("xbf16>") and out.endswith("xf32>"), (left, right, out)

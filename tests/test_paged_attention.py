"""The paged-attention decode kernel (ray_tpu/ops/paged_attention.py) against
the kept XLA expression (transformer.paged_attention_gather), in pallas
interpret mode on the CPU at small tileable shapes: B 4, P 8, T 16,
head_dim 128. The tiny CPU widths of tests/test_parity.py and
test_models.py (head_dim 16, 8-token pages) cannot be tiled and stay on
the XLA expression; the last tests hold that choice and what describe()
says of it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm.kv_cache import TRASH_PAGE

B, P, T, HD, N = 4, 8, 16, 128, 40

# Lengths a slot can have: one token, a page boundary -1 / +0 / +1, a block
# boundary (pages_per_block 2: 32 tokens), a full table, an inactive slot.
LENGTHS = {
    "one_and_page_boundary": (1, T - 1, T, T + 1),
    "full_table_inactive_block_boundary": (P * T, 0, 2 * T + 1, P * T - 1),
    "all_inactive_but_one": (0, 0, 5, 0),
}
HEADS = {"mha": (4, 4), "gqa4to1": (8, 2)}


def _inputs(dtype, heads, lengths, seed=0):
    """Pool of two layers, q, and block tables whose rows past a slot's
    length point at the trash page (as the engine builds them)."""
    H, G = heads
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((2, N, T, G * HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((2, N, T, G * HD)), dtype)
    q = jnp.asarray(rng.standard_normal((B, H, HD)), dtype)
    bt = np.full((B, P), TRASH_PAGE, np.int32)
    free = iter(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // T)):
            bt[b, j] = next(free)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)


def _both(q, kp, vp, bt, lengths, G, layer=1, **kw):
    out = pa.paged_attention(q, kp, vp, layer, bt, lengths, n_kv_heads=G, **kw)
    ref = tfm.paged_attention_gather(q, kp[layer], vp[layer], bt, jnp.maximum(lengths, 1), G)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("lengths", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_kernel_matches_the_xla_expression(dtype, heads, lengths):
    """float32 pool: the same numbers to 1e-5 (float32 products, statistics
    and accumulator; only the order of the sums differs). bf16 pool: K and V
    are read as stored and the q.K products still accumulate in float32, so
    the one thing the kernel rounds that the expression does not is the
    probabilities, to V's dtype for the P.V product (flash_attention.py does
    the same): at most 2^-9 of max|V|, plus the output's own bf16 rounding."""
    q, kp, vp, bt, lens = _inputs(dtype, heads, lengths)
    out, ref = _both(q, kp, vp, bt, lens, heads[1], pages_per_block=2)
    live = np.asarray(lens) > 0
    if dtype == jnp.float32:
        tol = 1e-5
    else:
        tol = 2.0 ** -9 * float(jnp.max(jnp.abs(vp.astype(jnp.float32)))) + 2.0 ** -8 * np.abs(ref).max()
    assert np.abs(out[live] - ref[live]).max() < tol
    assert not out[~live].any(), "an inactive slot walks no page and returns zeros"


def test_kernel_reads_nothing_past_the_live_length():
    """Every position past each slot's length (the rest of its last page,
    every page it does not hold, the trash page) is NaN: the output is the
    same finite numbers, because the read is bounded by the length."""
    lengths = (1, T + 3, 0, P * T - 5)
    q, kp, vp, bt, lens = _inputs(jnp.float32, (4, 4), lengths)
    clean, _ = _both(q, kp, vp, bt, lens, 4, pages_per_block=2)
    held = np.zeros((N, T), bool)
    for b, n in enumerate(lengths):
        for t in range(n):
            held[int(bt[b, t // T]), t % T] = True
    poison = jnp.where(jnp.asarray(held)[None, :, :, None], kp, jnp.nan)
    poison_v = jnp.where(jnp.asarray(held)[None, :, :, None], vp, jnp.nan)
    assert bool(jnp.isnan(poison[1, TRASH_PAGE]).all())
    got, _ = _both(q, poison, poison_v, bt, lens, 4, pages_per_block=2)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def test_kernel_clamps_indices_like_a_gather():
    """A length past the table and a page index past the pool are clamped
    (what an XLA gather does with them), never a DMA outside the pool."""
    q, kp, vp, bt, lens = _inputs(jnp.float32, (4, 4), (P * T, P * T, 3, 0))
    want, _ = _both(q, kp, vp, bt.at[1, 0].set(N - 1), lens, 4, pages_per_block=3)
    got, _ = _both(q, kp, vp, bt.at[1, 0].set(N + 7), lens.at[0].set(P * T + 40), 4, pages_per_block=3)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ppb", [1, 3, 8])
def test_kernel_block_size_does_not_change_the_result(ppb):
    """pages_per_block is a tiling, not a parameter of the mathematics (3
    does not divide the table: the last block of a full slot is short)."""
    q, kp, vp, bt, lens = _inputs(jnp.float32, (8, 2), (P * T, 37, 0, 16))
    out, ref = _both(q, kp, vp, bt, lens, 2, layer=0, pages_per_block=ppb)
    live = np.asarray(lens) > 0
    assert np.abs(out[live] - ref[live]).max() < 1e-5


def test_one_decode_executable_serves_every_batch_mix():
    """Three batch compositions and lengths (one slot; three slots across a
    page boundary; the middle slot alone), ONE compile of the decode step,
    and the served tokens are the full forward's."""
    from ray_tpu.serve.llm.model import PagedLM

    cfg = tfm.tiny(d_model=256, n_heads=2, n_kv_heads=1, attn_impl="naive", dtype=jnp.float32)
    lm = PagedLM(cfg, seed=0, num_pages=24, page_tokens=T, max_slots=3, max_pages_per_seq=4)
    assert lm.describe()["decode_attention"] == "paged_kernel"
    prompts = {0: [3, 1, 4, 1, 5], 1: list(range(7, 7 + 15)), 2: [9] * 20}
    pages = {0: [1, 2], 1: [3, 4], 2: [5, 6]}
    seqs = {s: list(p) + [lm.prefill(p, pages[s][: -(-len(p) // T)], 0)] for s, p in prompts.items()}

    def step(slots):
        toks, poss, tabs = [0] * 3, [-1] * 3, [[] for _ in range(3)]
        for s in slots:
            toks[s], poss[s], tabs[s] = seqs[s][-1], len(seqs[s]) - 1, pages[s]
        out = lm.decode(toks, poss, tabs)
        for s in slots:
            seqs[s].append(out[s])

    step([0])
    after_first = lm.describe()["compile"]["compiles"]
    step([0, 1, 2])  # slot 1 writes position 16: its second page
    step([1])
    step([0, 2])
    assert lm.describe()["compile"]["compiles"] == after_first
    assert lm._decode_jit._cache_size() == 1
    for s, p in prompts.items():
        seq = list(p)
        while len(seq) < len(seqs[s]):
            logits = tfm.forward(lm.params, jnp.asarray([seq], jnp.int32), lm.cfg)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert seq == seqs[s], s


@pytest.mark.parametrize(
    "head_dim,page_tokens,dtype,path",
    [
        (128, 16, jnp.bfloat16, "paged_kernel"),  # the serving cells, OLMoE, Mistral
        (256, 16, jnp.bfloat16, "paged_kernel"),  # GPT-J
        (128, 8, jnp.float32, "paged_kernel"),
        (128, 8, jnp.bfloat16, "xla_gather"),  # a bf16 page of 8 tokens is half a tile
        (16, 8, jnp.float32, "xla_gather"),  # tfm.tiny: tests/test_parity.py, test_models.py
        (64, 16, jnp.bfloat16, "xla_gather"),
    ],
)
def test_path_follows_the_shape(head_dim, page_tokens, dtype, path):
    cfg = tfm.tiny(d_model=2 * head_dim, n_heads=2, n_kv_heads=2, dtype=dtype)
    assert tfm.paged_attention_path(cfg, page_tokens) == path
    assert pa.can_tile(page_tokens, head_dim, dtype) == (path == "paged_kernel")


def test_tiny_widths_stay_on_the_xla_expression_and_describe_says_so():
    from ray_tpu.serve.llm.model import PagedLM

    lm = PagedLM(seed=0, num_pages=8, page_tokens=8, max_slots=2, max_pages_per_seq=2)
    assert lm.describe()["decode_attention"] == "xla_gather"
    q, kp, vp, bt, lens = _inputs(jnp.float32, (4, 4), (1, 1, 1, 1))
    with pytest.raises(ValueError, match="cannot tile"):
        pa.paged_attention(q[..., :16], kp[..., : 4 * 16], vp[..., : 4 * 16], 0, bt, lens, n_kv_heads=4)


def test_interpret_follows_the_backend_like_the_flash_kernels(monkeypatch):
    """Off a TPU the kernel is interpreted; an ahead-of-time compile for a
    described TPU steers flash_attention._auto_interpret and gets both."""
    import sys

    assert jax.default_backend() == "cpu" and pa._auto_interpret() is True
    monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"], "_auto_interpret", lambda: False)
    assert pa._auto_interpret() is False


# ------------------------------------------------- the prefill kernel
#
# One chunk of one prompt over the prompt's pages: C rows at positions
# start.., a table of P = 8 pages. name: (chunk rows, start, length). A
# hit's chunks start where the cache ends: `start` is a page multiple and
# need be no multiple of the chunk nor of block_q (two pages here), and the
# last chunk's rows may run past the table.
PREFILL_CHUNKS = {
    "whole_prompt_in_one_chunk": (P * T, 0, P * T),
    "first_chunk": (2 * T, 0, 5 * T + 3),
    "a_hits_last_chunk_ending_inside_it": (2 * T, 4 * T, 5 * T + 3),
    "last_chunk_of_a_full_table": (4 * T, 4 * T, P * T),
    "one_page_one_token": (T, 0, 1),
    "a_hits_chunk_starting_at_an_odd_page": (2 * T, 3 * T, 5 * T + 3),
    "a_hits_chunk_starting_at_an_odd_page_and_running_past_the_table": (4 * T, 5 * T, P * T),
}


def _prefill_inputs(dtype, heads, C, length, seed=0):
    H, G = heads
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.standard_normal((2, N, T, G * HD)), dtype)
    vp = jnp.asarray(rng.standard_normal((2, N, T, G * HD)), dtype)
    q = jnp.asarray(rng.standard_normal((C, H, HD)), dtype)
    bt = np.full((P,), TRASH_PAGE, np.int32)
    n = -(-length // T)
    bt[:n] = rng.permutation(np.arange(1, N))[:n]
    return q, kp, vp, jnp.asarray(bt)


def _prefill_both(q, kp, vp, bt, start, length, G, layer=1, **kw):
    """Kernel and expression, the rows that are positions of the prompt."""
    out = pa.paged_prefill_attention(q, kp, vp, layer, bt, start, length, n_kv_heads=G, **kw)
    ref = tfm.paged_prefill_attention_gather(q, kp[layer], vp[layer], bt, start, G)
    rows = max(0, min(q.shape[0], length - start))
    return np.asarray(out, np.float32)[:rows], np.asarray(ref, np.float32)[:rows]


@pytest.mark.parametrize("chunk", PREFILL_CHUNKS.values(), ids=PREFILL_CHUNKS.keys())
@pytest.mark.parametrize("heads", HEADS.values(), ids=HEADS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_prefill_kernel_matches_the_xla_expression(dtype, heads, chunk):
    """As for decode: float32 to 1e-5; a bf16 pool differs by the
    probabilities' rounding to V's dtype and the output's own."""
    C, start, length = chunk
    q, kp, vp, bt = _prefill_inputs(dtype, heads, C, length)
    out, ref = _prefill_both(q, kp, vp, bt, start, length, heads[1], block_q=min(C, 2 * T), pages_per_block=3)
    if dtype == jnp.float32:
        tol = 1e-5
    else:
        tol = 2.0 ** -9 * float(jnp.max(jnp.abs(vp.astype(jnp.float32)))) + 2.0 ** -8 * np.abs(ref).max()
    assert out.shape[0] == min(C, length - start) and np.abs(out - ref).max() < tol


def test_prefill_kernel_reads_no_page_past_the_prompts_last():
    """Pages the prompt does not hold (the trash page its table names past
    its length, every other page of the pool) are NaN: the prompt's rows
    are the same finite numbers, and a block of rows wholly past the
    length returns zeros."""
    C, start, length = 4 * T, 2 * T, 3 * T + 5
    q, kp, vp, bt = _prefill_inputs(jnp.float32, (4, 4), C, length)
    clean, _ = _prefill_both(q, kp, vp, bt, start, length, 4, block_q=T, pages_per_block=3)
    held = np.zeros((N,), bool)
    held[np.asarray(bt[: -(-length // T)])] = True
    poison_k = jnp.where(jnp.asarray(held)[None, :, None, None], kp, jnp.nan)
    poison_v = jnp.where(jnp.asarray(held)[None, :, None, None], vp, jnp.nan)
    out = pa.paged_prefill_attention(q, poison_k, poison_v, 1, bt, start, length, n_kv_heads=4, block_q=T, pages_per_block=3)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[: length - start], clean)
    assert np.isfinite(out).all() and not out[2 * T :].any()


@pytest.mark.parametrize("block_q,ppb", [(T, 1), (2 * T, 8), (4 * T, 5)])
def test_prefill_kernel_tiling_does_not_change_the_result(block_q, ppb):
    q, kp, vp, bt = _prefill_inputs(jnp.float32, (8, 2), 4 * T, P * T)
    out, ref = _prefill_both(q, kp, vp, bt, 4 * T, P * T, 2, layer=0, block_q=block_q, pages_per_block=ppb)
    assert np.abs(out - ref).max() < 1e-5


# ------------------------------------------------- attention windows
#
# A window layer sees the last `window` positions only: both kernels start
# their page walk at the page that holds the first visible key and mask
# inside it, and the gather expressions mask the whole row. name: window,
# against contexts of up to P * T = 128 positions in 16-token pages.
WINDOWS = {
    "shorter_than_a_page": 5,
    "edge_inside_a_page": 2 * T + 5,
    "a_whole_number_of_pages": 3 * T,
    "equal_to_the_longest_context": P * T,
    "longer_than_every_context": P * T + 9,
    "no_window_as_data": tfm.NO_WINDOW,
}


def _window_tol(dtype, vp, ref):
    if dtype == jnp.float32:
        return 1e-5
    return 2.0 ** -9 * float(jnp.max(jnp.abs(vp.astype(jnp.float32)))) + 2.0 ** -8 * np.abs(ref).max()


@pytest.mark.parametrize("window", WINDOWS.values(), ids=WINDOWS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_windowed_decode_kernel_matches_the_windowed_expression(dtype, window):
    """Ragged lengths on both sides of the window; the window rides as a
    traced scalar, as it does in forward_decode's layer scan."""
    lengths = (P * T, 2 * T + 6, 3, 0)
    q, kp, vp, bt, lens = _inputs(dtype, (8, 2), lengths)
    out = jax.jit(lambda w: pa.paged_attention(q, kp, vp, 1, bt, lens, n_kv_heads=2, window=w, pages_per_block=2))(jnp.int32(window))
    ref = tfm.paged_attention_gather(q, kp[1], vp[1], bt, jnp.maximum(lens, 1), 2, window=window)
    out, ref, live = np.asarray(out, np.float32), np.asarray(ref, np.float32), np.asarray(lens) > 0
    assert np.abs(out[live] - ref[live]).max() < _window_tol(dtype, vp, ref)
    assert not out[~live].any()
    if window >= P * T:  # a window that reaches past every context is no window
        full = np.asarray(pa.paged_attention(q, kp, vp, 1, bt, lens, n_kv_heads=2, pages_per_block=2), np.float32)
        np.testing.assert_array_equal(out, full)


def test_windowed_decode_kernel_reads_no_page_below_the_window():
    """Every page wholly below a slot's window is NaN: the same finite
    numbers come out, so those pages were neither copied nor multiplied."""
    window, lengths = 2 * T + 5, (P * T, 5 * T + 1, 3, 0)
    q, kp, vp, bt, lens = _inputs(jnp.float32, (8, 2), lengths)
    clean = pa.paged_attention(q, kp, vp, 1, bt, lens, n_kv_heads=2, window=window, pages_per_block=2)
    below = np.zeros((N,), bool)
    for b, n in enumerate(lengths):
        below[np.asarray(bt[b, : max(0, n - window) // T])] = True
    assert below.sum() == 5 + 2
    poison = [jnp.where(jnp.asarray(below)[None, :, None, None], jnp.nan, x) for x in (kp, vp)]
    out = pa.paged_attention(q, *poison, 1, bt, lens, n_kv_heads=2, window=window, pages_per_block=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


PREFILL_WINDOW_CHUNKS = {  # (chunk rows, start, length): the cached part ends before, inside and beyond the window
    "first_chunk": (2 * T, 0, 5 * T + 3),
    "later_chunk_ending_inside_it": (2 * T, 4 * T, 5 * T + 3),
    "last_chunk_of_a_full_table": (4 * T, 4 * T, P * T),
    "chunk_starting_at_an_odd_page": (2 * T, 3 * T, 5 * T + 3),
    "chunk_starting_at_an_odd_page_and_running_past_the_table": (4 * T, 5 * T, P * T),
}


@pytest.mark.parametrize("chunk", PREFILL_WINDOW_CHUNKS.values(), ids=PREFILL_WINDOW_CHUNKS.keys())
@pytest.mark.parametrize("window", WINDOWS.values(), ids=WINDOWS.keys())
def test_windowed_prefill_kernel_matches_the_windowed_expression(window, chunk):
    """Blocks of rows smaller than the window and larger than it (block_q 32
    against windows of 5 to 137): a row whose first K/V block lies wholly
    below its own reach must come out as if it had never seen it."""
    C, start, length = chunk
    q, kp, vp, bt = _prefill_inputs(jnp.float32, (8, 2), C, length)
    out = jax.jit(lambda w: pa.paged_prefill_attention(
        q, kp, vp, 1, bt, start, length, n_kv_heads=2, window=w, block_q=min(C, 2 * T), pages_per_block=1))(jnp.int32(window))
    ref = tfm.paged_prefill_attention_gather(q, kp[1], vp[1], bt, start, 2, window=window)
    rows = min(C, length - start)
    assert np.abs(np.asarray(out)[:rows] - np.asarray(ref)[:rows]).max() < 1e-5
    if window >= P * T:
        full = pa.paged_prefill_attention(q, kp, vp, 1, bt, start, length, n_kv_heads=2, block_q=min(C, 2 * T), pages_per_block=1)
        np.testing.assert_array_equal(np.asarray(out)[:rows], np.asarray(full)[:rows])


def test_windowed_prefill_kernel_reads_no_page_below_the_window():
    C, start, length, window = 2 * T, 6 * T, P * T, T + 3
    q, kp, vp, bt = _prefill_inputs(jnp.float32, (8, 2), C, length)
    kw = dict(n_kv_heads=2, window=window, block_q=T, pages_per_block=2)
    clean = pa.paged_prefill_attention(q, kp, vp, 1, bt, start, length, **kw)
    below = np.zeros((N,), bool)
    below[np.asarray(bt[: (start - window + 1) // T])] = True  # below the reach of the chunk's first row
    assert below.sum() == 4
    poison = [jnp.where(jnp.asarray(below)[None, :, None, None], jnp.nan, x) for x in (kp, vp)]
    out = pa.paged_prefill_attention(q, *poison, 1, bt, start, length, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


# ------------------------------------------------- the latent kernels


# ---- K heads wider than V heads (MiMo-V2's global layers: 192 beside 128). A 192-wide K head lies in the pages padded
# with zeros to 256 lanes (transformer.kv_page_widths), q is padded alike where it meets them, the scores keep 192^-0.5.

HK, HK_STORED, HV = 192, 256, 128


def _wide_key_pool(dtype, G, seed=0):
    """(K pool with every head's lanes past 192 zero, V pool of 128-wide heads, the scale)."""
    rng = np.random.default_rng(seed)
    kp = rng.standard_normal((2, N, T, G, HK_STORED))
    kp[..., HK:] = 0.0
    return jnp.asarray(kp.reshape(2, N, T, G * HK_STORED), dtype), jnp.asarray(rng.standard_normal((2, N, T, G * HV)), dtype), HK**-0.5


def _padded_q(rng, dtype, rows, H):
    q = rng.standard_normal((rows, H, HK_STORED))
    q[..., HK:] = 0.0
    return jnp.asarray(q, dtype)


@pytest.mark.parametrize("lengths", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_decode_kernel_at_a_key_width_that_is_not_the_value_width(dtype, lengths):
    """16:4 heads... at test size 8:2: the kernel's result is a V head wide,
    and equal to the expression's over the same padded pool AND to plain
    attention over the unpadded 192-wide heads."""
    H, G = 8, 2
    kp, vp, scale = _wide_key_pool(dtype, G)
    rng = np.random.default_rng(1)
    q = _padded_q(rng, dtype, B, H)
    bt = np.full((B, P), TRASH_PAGE, np.int32)
    free = iter(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // T)):
            bt[b, j] = next(free)
    bt, lens = jnp.asarray(bt), jnp.asarray(lengths, jnp.int32)
    out = np.asarray(pa.paged_attention(q, kp, vp, 1, bt, lens, n_kv_heads=G, scale=scale, pages_per_block=2), np.float32)
    ref = np.asarray(tfm.paged_attention_gather(q, kp[1], vp[1], bt, jnp.maximum(lens, 1), G, scale=scale), np.float32)
    assert out.shape == (B, H, HV)
    live = np.asarray(lens) > 0
    assert np.abs(out[live] - ref[live]).max() < _window_tol(dtype, vp, ref) and not out[~live].any()
    # the unpadded heads through the same expression at its own scale (192^-0.5 from q's width): the padding changes nothing
    unpadded = tfm.paged_attention_gather(q[..., :HK], kp[1].reshape(N, T, G, HK_STORED)[..., :HK].reshape(N, T, G * HK), vp[1], bt, jnp.maximum(lens, 1), G)
    assert np.abs(ref[live] - np.asarray(unpadded, np.float32)[live]).max() < (1e-5 if dtype == jnp.float32 else 2.0 ** -7 * np.abs(ref).max())


@pytest.mark.parametrize("chunk", PREFILL_CHUNKS.values(), ids=PREFILL_CHUNKS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_prefill_kernel_at_a_key_width_that_is_not_the_value_width(dtype, chunk):
    C, start, length = chunk
    H, G = 8, 2
    kp, vp, scale = _wide_key_pool(dtype, G)
    rng = np.random.default_rng(2)
    q = _padded_q(rng, dtype, C, H)
    bt = np.full((P,), TRASH_PAGE, np.int32)
    n = -(-length // T)
    bt[:n] = rng.permutation(np.arange(1, N))[:n]
    bt = jnp.asarray(bt)
    out = pa.paged_prefill_attention(q, kp, vp, 1, bt, start, length, n_kv_heads=G, scale=scale, block_q=min(C, 2 * T), pages_per_block=3)
    ref = tfm.paged_prefill_attention_gather(q, kp[1], vp[1], bt, start, G, scale=scale)
    rows = max(0, min(C, length - start))
    out, ref = np.asarray(out, np.float32)[:rows], np.asarray(ref, np.float32)[:rows]
    assert out.shape == (rows, H, HV) and np.abs(out - ref).max() < _window_tol(dtype, vp, ref)


def test_a_key_head_of_192_lies_in_256_lanes_and_the_kernels_take_it():
    cfg = tfm.tiny(d_head=192, v_head_dim=128, n_heads=8, n_kv_heads=2, dtype=jnp.bfloat16)
    assert tfm.kv_page_widths(cfg) == (256, 128) and tfm.paged_attention_path(cfg, 16) == "paged_kernel"
    assert tfm.kv_page_widths(tfm.tiny(d_head=128)) == (128, 128) and tfm.kv_page_widths(tfm.tiny(d_head=24, v_head_dim=16)) == (24, 16)
    assert pa.can_tile(16, 256, jnp.bfloat16, 128) and not pa.can_tile(16, 256, jnp.bfloat16, 64) and not pa.can_tile(16, 192, jnp.bfloat16, 128)
    with pytest.raises(ValueError, match="V's 301"):  # V's row is no whole number of heads
        pa.paged_attention(jnp.zeros((1, 8, 256)), jnp.zeros((1, 4, 16, 512)), jnp.zeros((1, 4, 16, 301)), 0, jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32), n_kv_heads=2)
    with pytest.raises(ValueError, match="cannot tile"):  # a V head of 150 lanes
        pa.paged_attention(jnp.zeros((1, 8, 256)), jnp.zeros((1, 4, 16, 512)), jnp.zeros((1, 4, 16, 300)), 0, jnp.zeros((1, 2), jnp.int32), jnp.ones((1,), jnp.int32), n_kv_heads=2)


def _latent_inputs(dtype, lengths, seed=0, heads=16, c=128, rope=64):
    """A latent pool of two layers (rows [c_kv | k_r] padded to whole lane
    tiles), absorbed queries of the same width, and block tables as `_inputs` builds them."""
    from ray_tpu.ops import latent_attention as la

    W = la.row_width(c, rope)
    rng = np.random.default_rng(seed)
    pool = np.zeros((2, N, T, W), np.float32)
    pool[..., : c + rope] = rng.standard_normal((2, N, T, c + rope))
    q = np.zeros((B, heads, W), np.float32)
    q[..., : c + rope] = rng.standard_normal((B, heads, c + rope))
    bt = np.full((B, P), TRASH_PAGE, np.int32)
    free = iter(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // T)):
            bt[b, j] = next(free)
    return jnp.asarray(q, dtype), jnp.asarray(pool, dtype), jnp.asarray(bt), jnp.asarray(lengths, jnp.int32), c


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_latent_decode_kernel_is_its_gather_expression(lengths, dtype, tol):
    """`paged_latent_attention` in interpret mode against
    `latent_attention_gather`: one key/value row for 16 heads, the value a
    prefix of the key; an inactive slot returns zeros."""
    from ray_tpu.ops import latent_attention as la

    q, pool, bt, n, c = _latent_inputs(dtype, LENGTHS[lengths])
    got = la.paged_latent_attention(q, pool, jnp.int32(1), bt, n, scale=0.11, v_width=c, pages_per_block=2)
    want = la.latent_attention_gather(q, pool[1], bt, jnp.maximum(n, 1), scale=0.11, v_width=c)
    live = np.asarray(n) > 0
    assert got.shape == (B, 16, c) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], atol=tol, rtol=tol)
    assert not np.asarray(got, np.float32)[~live].any()


@pytest.mark.parametrize("start,length,positions", [(0, 20, 4), (32, 64, 4), (48, 70, 2), (64, 8 * 16, 8), (16, 17, 4)])
def test_latent_prefill_kernel_is_its_gather_expression(start, length, positions):
    """`paged_latent_prefill_attention` against `latent_prefill_attention_gather`
    at a `start` on a page's border: rows below the length agree, blocks of
    rows wholly past it return zeros, and nothing is NaN whatever stale rows a
    block's buffer held."""
    from ray_tpu.ops import latent_attention as la

    _q, pool, bt, _n, c = _latent_inputs(jnp.float32, (P * T, 0, 0, 0), seed=3)
    C = 32
    q = jnp.asarray(np.random.default_rng(4).standard_normal((C, 16, pool.shape[-1])), jnp.float32)
    got = la.paged_latent_prefill_attention(q, pool, jnp.int32(0), bt[0], jnp.int32(start), jnp.int32(length), scale=0.11, v_width=c,
                                            positions_per_block=positions, pages_per_block=2)
    want = la.latent_prefill_attention_gather(q, pool[0], bt[0], start, scale=0.11, v_width=c)
    rows = max(0, min(C, length - start))
    np.testing.assert_allclose(np.asarray(got)[:rows], np.asarray(want)[:rows], atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    past = -(-rows // positions) * positions  # the first block of rows wholly past the length
    assert not np.asarray(got)[past:].any()


def test_latent_kernels_refuse_what_they_cannot_tile_and_the_model_then_gathers():
    from ray_tpu.ops import latent_attention as la

    assert la.row_width(512, 64) == 640 and la.row_width(32, 8) == 128
    assert la.can_tile(128, 128, 512, jnp.bfloat16) and not la.can_tile(8, 4, 32, jnp.float32)
    q, pool, bt, n, _c = _latent_inputs(jnp.float32, LENGTHS["all_inactive_but_one"], heads=4, c=32, rope=8)
    with pytest.raises(ValueError, match="gather expression"):
        la.paged_latent_attention(q, pool, jnp.int32(0), bt, n, scale=1.0, v_width=32)

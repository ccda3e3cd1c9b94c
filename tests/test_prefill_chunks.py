"""A prefill chunk sized by the weights it has to read (PR 62).

`transformer.prefill_chunk_rows(cfg)`: rows at the ridge times (the bytes of
the layers' weights one pass reads) / (the bytes one row multiplies), between
PREFILL_CHUNK_TOKENS and PREFILL_CHUNK_CAP. `forward_prefill` with `big_chunks`
walks that many big chunks (`prefill_big_chunk_tokens`) from `write_from` on;
PagedLM has a long span's head walked so by ONE executable for all buckets, and
what is left of the span (the span's last big chunk, whole or not, which holds
the last position) by the bucket's own executable in small chunks, from where
the big ones ended. Here, on the CPU: the rule at the published widths of
BENCHMARK.json's configurations (shapes alone), and at each architecture's TINY
widths in float32 that big chunks and a tail give what small chunks alone give
and what the whole-sequence forward gives, for every row of `KINDS` that pages
positions or keeps a slot, with the counts PagedLM and the engine report.
"""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, rehearsal, spec
from ray_tpu import tracing
from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import PagedLM, PromptTokens

TOLERANCE = 2e-4  # float32 at matmul precision "highest", as tests/test_mimo_v2.py
T = 8  # positions a page
FLOOR, CAP = 16, 32  # PREFILL_CHUNK_TOKENS and PREFILL_CHUNK_CAP in these tests: a small chunk is two pages, a big one four (the TINY stacks read 1.65 to 1.92 times what a row multiplies: 27 to 31 rows)
BUCKET = 16  # pages: 128 positions, every case's bucket, so a kind compiles one executable a chunking
SLOT = 2
# kind of cache -> a cell of BENCHMARK.json whose configuration keeps it, taken at its architecture's TINY widths; each a routed model
CELLS = {
    "softmax_pages": "trinitymini-serve-agent-turns",  # K/V pages, windows riding the scan
    "window_rings": "mimov25-serve-longctx-batch",  # K/V pages of the global layers beside rings
    "latent_pages": "dotsvlm1-serve-longdoc-batch",
    "delta_rule_slots": "solaropen2-serve-reasoning-batch",  # K/V pages beside state slots
    "latent_pages_and_delta_rule_slots": "gigachat35-serve-longanswer-batch",
}
SHARES_PREFIX = {"softmax_pages", "latent_pages"}  # the others keep a state: no page of one prompt serves another


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def chunking(floor, cap):
    """The module's two constants for the length of a `with`: what a trace made inside it reads."""
    return mock.patch.multiple(tfm, PREFILL_CHUNK_TOKENS=floor, PREFILL_CHUNK_CAP=cap)


@functools.lru_cache(maxsize=None)
def seeded(kind):
    cell = rehearsal.shrink(spec.find_cell(CELLS[kind]))
    cfg = cell.arch.model_config(cell.config, dtype=jnp.float32, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(3))


def tokens_of(cfg, n):
    return jax.random.randint(jax.random.PRNGKey(11), (n,), 1, cfg.vocab_size, jnp.int32)


@functools.lru_cache(maxsize=None)
def prefill_program(kind, big):
    """forward_prefill of the kind's model over BUCKET pages with chunks of FLOOR rows, or (`big`) its `big_chunks` form, compiled."""
    cfg, params = seeded(kind)
    slot = (jnp.int32(SLOT),) if "slot" in tfm.cache_layout(cfg).indexed.values() else ()
    pool = tfm.init_kv_pages(cfg, 2 * BUCKET + 2, T, 4)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    with chunking(FLOOR, CAP):
        rows = tfm.prefill_big_chunk_tokens(cfg, T) if big else tfm.prefill_chunk_tokens(cfg, BUCKET, T)[0]
        fn = jax.jit(lambda p, t, kv, bt, n, w, chunks: tfm.forward_prefill(p, t, cfg, kv, bt, n, w, *slot, big_chunks=chunks if big else None)).lower(
            params, jax.ShapeDtypeStruct((1, BUCKET * T), jnp.int32), pool, jax.ShapeDtypeStruct((BUCKET,), jnp.int32), i32, i32, i32).compile()
    return fn, pool, rows


@functools.lru_cache(maxsize=None)
def whole_program(kind):
    cfg, params = seeded(kind)
    return jax.jit(lambda p, t: tfm.forward(p, t, cfg))


def spans_of(kind, big):
    """name -> (length, cached): exactly two big chunks (one walked big, the last in small ones); a big chunk and one row (one
    small chunk behind it); under one big chunk behind a prefix hit of three pages (no multiple of a chunk), or from nothing where
    nothing is shared: small chunks alone."""
    cached = 3 * T if kind in SHARES_PREFIX else 0
    return {"two_big_chunks": (2 * big, 0), "a_big_chunk_and_one_row": (big + 1, 0), "under_a_big_chunk_behind_the_cache": (cached + big - 3, cached)}


def walk(kind, big_chunks, tokens, length, cached, table, pool):
    """One prompt's span from `cached` on: as PagedLM.prefill walks it with big chunks, or in small chunks alone."""
    _, params = seeded(kind)
    small_fn, _, small = prefill_program(kind, False)
    padded = jnp.zeros((1, BUCKET * T), jnp.int32).at[0, :length].set(tokens[:length])
    n_big = 0
    if big_chunks:
        big_fn, _, big = prefill_program(kind, True)
        start, count = tfm.prefill_chunk_span(length, cached, big, T)
        n_big = count - 1
        if n_big:
            _, pool = big_fn(params, padded, pool, table, jnp.int32(length), jnp.int32(cached), jnp.int32(n_big))
            cached = start + n_big * big
    logits, pool = small_fn(params, padded, pool, table, jnp.int32(length), jnp.int32(cached), jnp.int32(0))
    return logits[0], pool, n_big


def prefill(kind, big_chunks, tokens, length, cached):
    """The prompt's owner prefills it whole into pages 1.., then (a hit) the same prompt again with its first `cached`
    positions in the owner's pages and the rest in pages of its own. -> (logits, pool, the table, big chunks walked)."""
    table = jnp.arange(1, BUCKET + 1, dtype=jnp.int32)
    logits, pool, n_big = walk(kind, big_chunks, tokens, length, 0, table, prefill_program(kind, False)[1])
    if cached:
        table = jnp.concatenate([table[: cached // T], jnp.arange(BUCKET + 1, 2 * BUCKET + 1 - cached // T, dtype=jnp.int32)])
        logits, pool, n_big = walk(kind, big_chunks, tokens, length, cached, table, pool)
    return logits, pool, table, n_big


@pytest.mark.parametrize("span", ["two_big_chunks", "a_big_chunk_and_one_row", "under_a_big_chunk_behind_the_cache"])
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_big_chunks_and_a_tail_give_what_small_chunks_alone_and_the_whole_forward_give(kind, span):
    cfg, params = seeded(kind)
    big, small = prefill_program(kind, True)[2], prefill_program(kind, False)[2]
    assert small == FLOOR and big > small and big % small == 0 and 2 * big <= BUCKET * T
    length, cached = spans_of(kind, big)[span]
    tokens = tokens_of(cfg, BUCKET * T)
    want = whole_program(kind)(params, tokens[None, :length])[0, length - 1]
    logits, pool, table, n_big = prefill(kind, True, tokens, length, cached)
    in_small, pool_small, _, none = prefill(kind, False, tokens, length, cached)
    assert (n_big, none) == ({"two_big_chunks": 1, "a_big_chunk_and_one_row": 1, "under_a_big_chunk_behind_the_cache": 0}[span], 0)
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    assert float(jnp.max(jnp.abs(logits - want))) <= TOLERANCE * scale and float(jnp.max(jnp.abs(in_small - want))) <= TOLERANCE * scale
    layout = tfm.cache_layout(cfg)
    for name, indexed in layout.indexed.items():
        if indexed == "slot":  # the sequence's slot, whole: a state, a convolution's tail, a ring's rows
            got, ref = pool[name][:, SLOT], pool_small[name][:, SLOT]
            assert float(jnp.max(jnp.abs(ref))) > 0, name
        else:  # its pages' positions below the length: what lies behind them in the last page is padding's
            got, ref = (p[name][:, table].reshape(p[name].shape[0], BUCKET * T, -1)[:, cached:length] for p in (pool, pool_small))
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))) <= TOLERANCE * max(1.0, float(jnp.max(jnp.abs(ref)))), name


def test_a_ring_shorter_than_a_chunk_is_attended_a_band_of_rows_at_a_time():
    """The window layers' chunk form at a big chunk of four windows: every block of rows beside the window below it.
    What the bands leave out, no row sees: the whole masked product gives the same rows."""
    cfg, _ = seeded("window_rings")
    ring, rows, heads, kvh, hd = max(cfg.windows), 64, cfg.n_heads, cfg.window_kv_heads, cfg.head_dim
    assert rows == 4 * ring
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = {"ring_k": jax.random.normal(ks[0], (1, 3, ring, kvh * hd)), "ring_v": jax.random.normal(ks[1], (1, 3, ring, kvh * cfg.value_dim))}
    q, k, v = jax.random.normal(ks[2], (1, rows, heads, hd)), jax.random.normal(ks[3], (1, rows, kvh, hd)), jax.random.normal(ks[4], (1, rows, kvh, cfg.value_dim))
    for c0, length in ((0, 64), (40, 104), (40, 90)):  # the prompt's first chunk; one behind a ring that holds 40 positions; one that ends inside
        ctx = dict(slot=1, c0=jnp.int32(c0), rows=rows, length=jnp.int32(length))
        with chunking(FLOOR, CAP):
            o, (rk, rv) = tfm._ring_chunk(cfg, ctx)(tfm.LayerPlace(0, None, None), pool)(q, k, v)
        with chunking(rows, CAP):  # a block as long as the chunk: the one masked product
            o_whole, (rk_whole, rv_whole) = tfm._ring_chunk(cfg, ctx)(tfm.LayerPlace(0, None, None), pool)(q, k, v)
        valid = length - c0
        np.testing.assert_allclose(o[0, :valid], o_whole[0, :valid], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(rk, rk_whole)
        np.testing.assert_array_equal(rv, rv_whole)


@pytest.mark.parametrize("span", ["two_big_chunks", "a_big_chunk_and_one_row", "under_a_big_chunk_behind_the_cache"])
@pytest.mark.parametrize("kind", ["softmax_pages", "window_rings"])
def test_paged_lm_walks_a_long_spans_head_in_big_chunks_and_serves_the_token_small_chunks_serve(kind, span, monkeypatch):
    """PagedLM's two calls (the big chunks' executable, one for every bucket, then the bucket's own from where they
    ended) give the first token that small chunks alone give; its counts are the span's big chunks but the last and
    the small chunks of the rest, and the rows are what a walk in small chunks alone computes."""
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", FLOOR)
    served = {}
    for cap in (CAP, FLOOR):
        monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", cap)
        cfg, params = seeded(kind)
        lm = PagedLM(cfg, params, num_pages=2 * BUCKET + 2, page_tokens=T, max_slots=3, max_pages_per_seq=BUCKET)
        big = lm._big_chunk or FLOOR
        length, cached = spans_of(kind, prefill_program(kind, True)[2])[span]
        prompt = [int(t) for t in tokens_of(cfg, length)]
        pages = list(range(1, -(-length // T) + 1))
        if cached:
            lm.prefill(prompt, pages, 0)
            pages = pages[: cached // T] + list(range(BUCKET + 1, BUCKET + 1 + len(pages) - cached // T))
        tok = lm.prefill(PromptTokens(prompt, lambda: None, slot=0) if lm.layout.state else prompt, pages, cached)
        served[cap] = (int(tok), tok.counters["prefill_chunks"], tok.computed_tokens)
        n_big = (length - cached - 1) // big if lm._big_chunk else 0
        rest = length - cached - n_big * big
        assert tok.counters["prefill_chunks"] == {"big": n_big, "small": -(-rest // FLOOR), "big_rows": n_big * big, "rows": tok.computed_tokens}
        assert tok.computed_tokens == -(-(length - cached) // FLOOR) * FLOOR
        assert tok.counters["prefill_experts"]["chunks"] == n_big + -(-rest // FLOOR)
        assert tok.counters["prefill_experts"]["rows"] == tok.computed_tokens * (cfg.n_layers - cfg.n_dense_layers)
        assert (n_big > 0) == (cap == CAP and span != "under_a_big_chunk_behind_the_cache") and (cap == CAP or lm._prefill_big_jit is None)
    assert served[CAP][0] == served[FLOOR][0] and served[CAP][2] == served[FLOOR][2]


def test_the_engine_reports_the_chunks_a_prefill_walked(monkeypatch):
    """`stats()["clocks"]["prefill_chunks"]` adds up what PagedLM says of each prefill (`prefill_big_chunk_pct` is
    big_rows / rows), and the request's `llm.prefill` span carries its own count of big and of small chunks; a
    dense model walks small chunks alone."""
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", FLOOR)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", CAP)
    served = {}
    for name, (cfg, params) in {"routed": seeded("softmax_pages"), "dense": (tfm.tiny(attn_impl="naive", dtype=jnp.float32, remat=False), None)}.items():
        lm = PagedLM(cfg, params, num_pages=48, page_tokens=T, max_slots=2, max_pages_per_seq=BUCKET)
        big, small = lm._big_chunk or FLOOR, tfm.prefill_chunk_tokens(cfg, BUCKET, T)[0]
        exp = tracing.InMemoryExporter()
        tracing.enable(exp)
        eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=48), name=f"t-chunks-{name}")
        try:
            for n in (big + 5, 9):  # a big chunk and a small one; one small chunk (two pages: a bucket of its size)
                list(eng.generate([1 + i % 200 for i in range(n)], max_new_tokens=2))
            served[name] = (big, small, eng.stats()["clocks"]["prefill_chunks"], [s["attrs"] for s in exp.spans if s["name"] == "llm.prefill"])
        finally:
            eng.close()
            tracing.disable()
    big, small, clock, spans = served["routed"]
    assert big > small == FLOOR and clock == {"big": 1, "small": 2, "big_rows": big, "rows": big + 2 * FLOOR}
    assert [(a["big_chunks"], a["small_chunks"], a["computed_tokens"]) for a in spans] == [(1, 1, big + FLOOR), (0, 1, FLOOR)]
    big, small, clock, spans = served["dense"]
    assert (big, small) == (FLOOR, FLOOR) and clock == {"big": 0, "small": 3, "big_rows": 0, "rows": 3 * FLOOR}
    assert [(a["big_chunks"], a["small_chunks"]) for a in spans] == [(0, 2), (0, 1)]


# (cell, rows of a big chunk at its published widths or 0, what a pass reads over what a row multiplies)
PUBLISHED = [
    ("dsllm7b-serve-chat-steady", 0, 1.0),
    ("mistral7b-train-seq4k-1chip", 0, 1.0),
    ("brumby14b-serve-longgen-batch", 0, 1.0),
    ("olmoe-train-seq4k-1chip", 1024, 6.24),
    ("trinitymini-serve-agent-turns", 1024, 8.52),
    ("solaropen2-serve-reasoning-batch", 1024, 4.76),
    ("dotsvlm1-serve-longdoc-batch", 0, 2.70),  # 692 rows asked for: three small chunks, under the cap
    ("gigachat35-serve-longanswer-batch", 0, 2.54),  # 651
    ("mimov25-serve-longctx-batch", 1024, 3.51),  # 898: four small chunks
]


@pytest.mark.parametrize("cell_name,big,ratio", PUBLISHED)
def test_the_rows_of_a_chunk_follow_from_the_weights_a_pass_reads_and_a_row_multiplies(cell_name, big, ratio):
    """A dense stack and a state-only layout have no big chunk (their programs are what they were); a routed stack, whole or
    one chip's share, reads 2.5 to 8.5 times what a row multiplies: the cap where the rows it asks for, in whole small
    chunks, reach it, and small chunks alone where they do not. Shapes alone: nothing is drawn."""
    cell = spec.find_cell(cell_name)
    cfg = cell.arch.model_config(cell.config)
    read, multiplied = tfm._layer_weight_bytes(cfg)
    assert abs(read * (cfg.n_experts or 1) / multiplied - ratio) < 0.01
    asked = tfm.prefill_chunk_rows(cfg)
    assert asked == min(tfm.PREFILL_CHUNK_CAP, -(-256 * read * (cfg.n_experts or 1) // multiplied))
    assert big == (tfm.PREFILL_CHUNK_CAP if tfm.cache_layout(cfg).kv and asked > tfm.PREFILL_CHUNK_CAP - tfm.PREFILL_CHUNK_TOKENS else 0)
    assert [tfm.prefill_big_chunk_tokens(cfg, page) for page in (16, 128, 256, 512)] == [big] * 4
    assert tfm.prefill_big_chunk_tokens(cfg, 1024) == 0  # a page of 1 024 positions is a small chunk of as many
    # forward_prefill's own chunk is what it was, whatever the weights
    if tfm.cache_layout(cfg).kv:
        assert tfm.prefill_chunk_tokens(cfg, 64, 128) == (256, 128) and tfm.prefill_chunk_tokens(cfg, 1, 128) == (128, 128)


def test_a_state_only_layout_has_no_big_chunk_whatever_its_weights_ask():
    """Retention's chunked form takes the chunk as its algorithm's block (`flops_per_token` counts it so): a routed
    retention stack, if there were one, would still walk its page in PREFILL_CHUNK_TOKENS rows."""
    cfg = tfm.tiny(retention_degree=2, n_experts=8, n_experts_per_tok=2, d_ff=32)
    assert tfm.prefill_chunk_rows(cfg) > tfm.PREFILL_CHUNK_TOKENS and not tfm.cache_layout(cfg).kv
    assert tfm.prefill_big_chunk_tokens(cfg, 4096) == 0 and tfm.prefill_chunk_tokens(cfg, 1, 4096) == (256, 1)

"""Latent attention (MLA) on the normal path: the "latent" row of
`transformer.KINDS` against the plain reference of
benchmarks/archs/dots_vlm.py at its TINY widths, on seeded weights.

The program serves the ABSORBED form out of latent pages (a prefill in
chunks, then decode steps), the reference computes the EXPANDED form token
against token without a cache: every comparison here is of logits, in float32
at matmul precision "highest", where two orders of the same float32 sums
differ by ~1e-5 at these widths (TOLERANCE 1e-4, as tests/test_kda.py)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.archs import dots_vlm
from benchmarks.lib import correct
from benchmarks.tools import wrong_dots_vlm
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import latent_attention as la
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM

TOLERANCE = 1e-4
CHUNK = 16  # PREFILL_CHUNK_TOKENS in these tests: two pages of 8
CONFIG = dict(dots_vlm.TINY)
T = 8  # positions a latent page


@pytest.fixture(autouse=True)
def small_chunks_at_highest_precision(monkeypatch):
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", CHUNK)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", CHUNK)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def seeded(seed):
    cfg = dots_vlm.model_config(CONFIG, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(seed))


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def reference(arch, params, tokens, positions):
    return correct.reference_logits(arch, params, tokens, positions, CONFIG)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def prefill(cfg, params, pool, tokens, n, table, write_from=0):
    padded = jnp.zeros((1, len(table) * T), jnp.int32).at[0, :n].set(tokens[:n])
    return jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, jnp.asarray(table), n, write_from))(params, padded, pool)


# ------------------------------------------------------------ (a) the rope


def test_yarn_frequencies_and_scale_are_the_closed_form_past_the_original_context():
    """The published numbers: 64 rope dims, theta 10000, factor 40 over 4096:
    pairs 0..10 turn as plain rope, pairs 23..31 forty times slower, a linear
    ramp between; cos and sin unscaled (mscale = mscale_all_dim); the softmax
    scale 192^-0.5 x (0.1 ln 40 + 1)^2 = 0.07217 x 1.8739."""
    cfg = tfm.TransformerConfig(d_head=192, kv_lora_rank=512, q_lora_rank=1536, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                                rope_scaling=("yarn", 40.0, 4096.0, 32.0, 1.0, 1.0, 1.0))
    d = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(10000.0))  # noqa: E731
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    want = []
    for i in range(32):
        f, ramp = 10000.0 ** (-2 * i / 64), min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f / 40 * ramp + f * (1 - ramp))
    np.testing.assert_allclose(np.asarray(tfm._rope_freqs(cfg)), want, rtol=1e-6)
    positions = np.array([0, 1, 4095, 4096, 8191, 24703])
    cos, sin = tfm.rope_at(cfg, jnp.asarray(positions))
    angles = positions[:, None].astype(np.float64) * np.asarray(want, np.float64)[None, :]
    # float32 angles of ~2e4 radians carry ~2e-3 of error; the table's rows are the same function of the same float32 products
    np.testing.assert_allclose(np.asarray(cos), np.cos(angles), atol=4e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(angles), atol=4e-3)
    table_cos, table_sin = tfm.rope_tables(cfg, 24704)
    np.testing.assert_array_equal(np.asarray(table_cos[positions]), np.asarray(cos))
    np.testing.assert_array_equal(np.asarray(table_sin[positions]), np.asarray(sin))
    assert abs(tfm.latent_softmax_scale(cfg) - 0.07217 * 1.8739) < 2e-5
    assert tfm._rope_magnitude(cfg) == 1.0
    plain = cfg.replace(rope_scaling=())
    np.testing.assert_allclose(np.asarray(tfm._rope_freqs(plain)), [10000.0 ** (-2 * i / 64) for i in range(32)], rtol=1e-6)
    assert abs(tfm.latent_softmax_scale(plain) - 192 ** -0.5) < 1e-9


def test_a_latent_config_on_the_flash_path_or_half_given_is_refused_loudly():
    cfg, params = seeded(0)
    with pytest.raises(ValueError, match="attn_impl='naive'"):
        tfm.forward(params, tokens_of(0, 8)[None], cfg.replace(attn_impl="full"))
    with pytest.raises(ValueError, match="latent attention needs"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(q_lora_rank=0))
    with pytest.raises(ValueError, match="rope_scaling"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(rope_scaling=("linear", 2.0)))


# ------------------------------------------------- (b) one layer's three forms


def layer_inputs(seed, n, cfg):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    H, nope, rope, c, v = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank, cfg.v_head_dim
    return (jax.random.normal(ks[0], (1, n, H, nope)), jax.random.normal(ks[1], (1, n, H, rope)), jax.random.normal(ks[2], (1, n, c)),
            jax.random.normal(ks[3], (1, n, rope)), jax.random.normal(ks[4], (H, nope, c)) / math.sqrt(c), jax.random.normal(ks[5], (H, c, v)) / math.sqrt(c))


@pytest.mark.parametrize("n", [16, 29, 32, 33])
def test_absorbed_over_the_pages_is_expanded_over_the_sequence_on_one_layer(n):
    """One layer's mixer between its projections: the whole-sequence form
    expands every position's latent into every head's keys and values; a
    prefill chunk and a decode step apply `w_uk` to the query and `w_uv` to
    the output and attend over the latent rows in the pages. The same numbers,
    at lengths on and beside a chunk's border."""
    cfg, _ = seeded(0)
    q_nope, q_rope, c_kv, k_r, w_uk, w_uv = layer_inputs(n, 48, cfg)
    where = tfm.LayerPlace(jnp.int32(1), None, None)
    want = tfm.KINDS["latent"].whole(cfg, None, where)(q_nope[:, :n], q_rope[:, :n], c_kv[:, :n], k_r[:, :n], w_uk, w_uv)[0][0]
    pool = {"ckv": tfm.init_kv_pages(cfg.replace(n_layers=2), 12, T)["ckv"] + 7.0}  # another owner's leftovers
    table, got = jnp.array([5, 3, 9, 2, 7, 0]), []
    for c0 in range(0, n, CHUNK):
        ctx = dict(block_table=table, dest_table=jnp.pad(table, (0, CHUNK // T)), length=n, write_from=0, slot=0, rows=CHUNK, page_tokens=T, c0=c0)
        rows = slice(c0, c0 + CHUNK)
        o, (leaf,) = tfm.KINDS["latent"].chunk(cfg, ctx)(where, pool)(q_nope[:, rows], q_rope[:, rows], c_kv[:, rows], k_r[:, rows], w_uk, w_uv)
        pool = {"ckv": leaf}
        got.append(o[0])
    assert worst(jnp.concatenate(got)[:n], want) <= TOLERANCE
    assert bool(jnp.all(pool["ckv"][0] == 7.0))  # layer 0 of the pool is not this layer's
    # one more position, as a decode step of three rows of which the middle one is live
    pos = jnp.array([0, n, 0])
    ctx = dict(block_tables=jnp.zeros((3, 6), jnp.int32).at[1].set(table), pos=pos, active=jnp.array([False, True, False]), page_tokens=T)
    step = lambda t: jnp.stack([t[0, :1], t[0, n : n + 1], t[0, :1]])  # noqa: E731
    o, _ = tfm.KINDS["latent"].step(cfg, ctx)(where, pool)(step(q_nope), step(q_rope), step(c_kv), step(k_r), w_uk, w_uv)
    want = tfm.KINDS["latent"].whole(cfg, None, where)(q_nope[:, : n + 1], q_rope[:, : n + 1], c_kv[:, : n + 1], k_r[:, : n + 1], w_uk, w_uv)[0][0, n]
    assert worst(o[1, 0], want) <= TOLERANCE


# ------------------------------------------- (c) through the latent pages


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed):
    cfg, params = seeded(seed)
    tokens = tokens_of(seed, 70)
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0]
    assert worst(got, reference(dots_vlm, params, tokens, np.arange(70))) <= TOLERANCE


def paged_lm(cfg, params, slots=3, pages=24):
    return PagedLM(cfg, params, num_pages=pages, page_tokens=T, max_slots=slots, max_pages_per_seq=8)


@pytest.mark.parametrize("seed,n", [(0, 41), (1, 32), (2, 33), (3, 47), (4, 48)])
def test_prefill_then_decode_through_the_latent_pages_matches_the_reference_logits(seed, n):
    """A prompt prefilled in chunks of two pages (lengths inside a chunk, on
    its border, one past it, on and before a page's end) into pages 3.. of a
    pool that holds another sequence's leftovers, then tokens teacher-forced
    through decode steps as row 1 beside two inactive rows: every logit the
    reference's full forward's."""
    cfg, params = seeded(seed)
    tokens = tokens_of(seed + 10, 56)
    want = reference(dots_vlm, params, tokens, np.arange(56))
    lm = paged_lm(cfg, params)
    assert set(lm.kv) == {"ckv"} and lm.kv["ckv"].shape == (3, 24, T, 128)
    pool = {"ckv": lm.kv["ckv"] + 100.0}  # the plain gather multiplies a masked position's row by a weight of exactly 0
    table = [3, 4, 5, 6, 7, 8, 9, 0]
    logits, pool = prefill(cfg, params, pool, tokens, n, table)
    assert worst(logits[0], want[n - 1]) <= TOLERANCE
    tables = jnp.zeros((3, 8), jnp.int32).at[1].set(jnp.asarray(table))
    decode = jax.jit(lambda p, t, pos, kv: tfm.forward_decode(p, t, pos, cfg, kv, tables, stats=True))
    for i in range(n, 56):
        logits, pool, stats = decode(params, jnp.array([0, tokens[i], 0]), jnp.array([-1, i, -1]), pool)
        assert worst(logits[1], want[i]) <= TOLERANCE, i
    assert set(stats) == {"experts_touched", "held_picks"}
    # pages 1, 2 and 10.. were nobody's: untouched
    assert bool(jnp.all(pool["ckv"][:, 1:3] == 100.0)) and bool(jnp.all(pool["ckv"][:, 10:] == 100.0))
    # a row as stored: [c_kv | k_r | zeros to a whole lane tile]
    assert bool(jnp.all(pool["ckv"][:, 3, :, 40:] == 0.0)) and bool(jnp.any(pool["ckv"][:, 3, :, :40] != 0.0))


@pytest.mark.parametrize("shared_pages,suffix", [(2, 5), (4, 1), (4, 16), (5, 9), (3, 20)])
def test_a_prefix_hits_suffix_prefill_is_the_miss_and_leaves_the_owners_pages(shared_pages, suffix):
    """Behind a prefix hit the chunks start where the cache ends: the suffix's
    rows attend over the owner's latent pages and give the logits a miss of
    the whole prompt gives; the owner's pages hold the bytes they held."""
    cfg, params = seeded(5)
    doc, n = tokens_of(50, 64), shared_pages * T + suffix
    prompt = jnp.concatenate([doc[: shared_pages * T], tokens_of(51 + suffix, suffix)])
    want = reference(dots_vlm, params, prompt, np.array([n - 1]))[0]
    empty = tfm.init_kv_pages(cfg, 24, T)
    _, pool = prefill(cfg, params, empty, doc, 64, [1, 2, 3, 4, 5, 6, 7, 8])  # the owner: eight full pages
    owner = np.asarray(pool["ckv"][:, 1:9])
    table = [1, 2, 3, 4, 5, 6, 7, 8][:shared_pages] + [11, 12, 13, 14, 15, 16, 17, 18][: 8 - shared_pages]
    hit, pool = prefill(cfg, params, pool, prompt, n, table, write_from=shared_pages * T)
    miss, _ = prefill(cfg, params, empty, prompt, n, [11, 12, 13, 14, 15, 16, 17, 18])
    assert worst(hit[0], want) <= TOLERANCE and worst(miss[0], want) <= TOLERANCE
    np.testing.assert_array_equal(np.asarray(pool["ckv"][:, 1:9]), owner)


def test_paged_lm_names_the_latent_path_and_counts_what_it_read():
    cfg, params = seeded(3)
    tokens = [int(t) for t in tokens_of(30, 37)]
    want = reference(dots_vlm, params, jnp.asarray(tokens), np.array([29, 30]))
    lm = paged_lm(cfg, params)
    first = lm.prefill(tokens[:30], [1, 2, 3, 4], 0)
    assert int(first) == int(jnp.argmax(want[0])) and first.computed_tokens == 32
    layers, row = 3, (32 + 8) * 4  # a cached position of a layer, unpadded: [c_kv | k_r] float32
    assert first.counters["prefill_latent"] == {"pairs": layers * 30 * 31 // 2, "calls": 1}
    again = lm.prefill(tokens[:30], [1, 2, 3, 4], 16)  # behind a hit of two pages: rows 16..29 against the positions below each
    assert int(again) == int(first) and again.counters["prefill_latent"]["pairs"] == layers * (30 * 31 - 16 * 17) // 2
    out = lm.decode([0, tokens[30]], [-1, 30], [[], [1, 2, 3, 4]])
    assert out[1] == int(jnp.argmax(want[1]))
    assert isinstance(out, DecodeTokens) and set(out.counters) == {"decode_experts", "decode_latent"}
    assert out.counters["decode_latent"] == {"bytes": 31 * layers * row, "positions": 31, "steps": 1}
    assert tfm.latent_position_bytes(cfg) == row and dots_vlm.latent_bytes_per_token_layer(CONFIG) == row
    said = lm.describe()
    assert said["cache"] == {"kind": "kv_pages", "page_bytes": lm.page_bytes} and said["decode_attention"] == "xla_gather"
    assert lm.shares_prefix_pages is True and lm.page_bytes == 3 * T * 128 * 4
    assert tfm.decode_paths(cfg.replace(n_heads=16, kv_lora_rank=128, dtype=jnp.bfloat16), 16) == {"decode_attention": "latent_kernel"}
    assert tfm.cache_layout(cfg).paged == "ckv" and tfm.cache_layout(tfm.tiny()).paged == "k"


# ---------------------------------------------------- (d) the wrong models


def served_margins(arch, params, tokens, served):
    logits = reference(arch, params, tokens, np.arange(len(tokens)))
    return np.asarray(jnp.max(logits, -1) - jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0])


@functools.lru_cache(maxsize=None)
def served_by_the_program():
    cfg, params = seeded(4)
    out = []
    for seed in (40, 41, 42):
        # positions 100..: past TINY's original context of 64, where YaRN's ramp and plain rope have parted
        tokens = tokens_of(seed, 170)
        served = jnp.argmax(jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0], -1)
        out.append((tokens, served, served_margins(dots_vlm, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_dots_vlm.WRONG))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 170: against
    the right reference every margin is 0 to rounding; against each wrong
    model's (one line of the reference altered, and the fp8-precision control)
    the 90th percentile, which a routed cell's limit names, is far over it."""
    _cfg, params = seeded(4)
    wrong = wrong_dots_vlm.load("dots_vlm", name)
    right = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()]))
    margins = np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()])
    wrong = correct.error_quantiles(np.where(np.isfinite(margins), margins, np.inf))
    assert right["q100"] <= 1e-3
    assert wrong["q90"] > 0.02 and wrong["q90"] > 20 * max(right["q100"], 1e-3), (right, wrong)


# ----------------------------------------------------------- (e) the engine


def greedy(cfg, params, prompt, n):
    """An engine-free greedy loop: the whole-sequence forward at one padded length."""
    fwd = jax.jit(lambda p, t: tfm.forward(p, t, cfg))
    tokens = np.zeros((1, len(prompt) + n), np.int32)
    tokens[0, : len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        tokens[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(tokens))[0, i - 1]))
    return [int(t) for t in tokens[0, len(prompt):]]


def test_questions_on_one_document_share_its_latent_pages_and_are_served_what_they_are_served_alone():
    """Three questions on one 40-token document and one on another, through
    the engine with two slots: the later questions hit the first one's five
    full pages, and every request is served the tokens the whole-sequence
    forward's greedy loop gives it."""
    cfg, params = seeded(6)
    doc, other = ([int(t) for t in tokens_of(60 + i, 40)] for i in range(2))
    prompts = [doc + [int(t) for t in tokens_of(70 + i, 3 + i)] for i in range(3)] + [other + [5, 6, 7]]
    eng = InferenceEngine(paged_lm(cfg, params, slots=2, pages=40), EngineConfig(page_tokens=T, pool_pages=40), name="t-mla")
    try:
        first = list(eng.generate(prompts[0], 6))  # finished, so its pages are indexed before the others ask
        streams = [eng.generate(p, 6) for p in prompts[1:]]
        got = [first] + [list(s) for s in streams]
        stats = eng.stats()
    finally:
        eng.close()
    assert got == [greedy(cfg, params, p, 6) for p in prompts]
    assert stats["kv"]["prefix_hits"] == 2 * 5 and stats["clocks"]["prefill_latent"]["calls"] == 4
    latent = stats["clocks"]["decode_latent"]
    assert latent["steps"] == stats["clocks"]["decode"]["n"] and latent["bytes"] == latent["positions"] * 3 * 160
    assert stats["clocks"]["prefill_latent"]["pairs"] > 0


# ------------------------------------------------ (f) what the others keep


@pytest.mark.parametrize("preset", ["llama2_7b", "llama2_13b", "gpt_j_6b", "tiny"])
def test_every_preset_has_no_latent_no_rope_scaling_and_one_group(preset):
    cfg = getattr(tfm, preset)()
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.rope_scaling, cfg.n_group, cfg.topk_group) == (0, 0, 0, 0, 0, (), 1, 1)
    assert [kind for kind, _ in tfm.cache_layout(cfg.replace(n_layers=2)).kinds] == ["softmax"]


def test_counts_at_the_published_widths():
    """The issue's arithmetic: 4.566 B parameters in the cut, 1 152 B a cached
    position a layer (1 280 as stored), 242 FLOP a byte at decode."""
    from benchmarks.lib import spec

    config = spec.find_cell("dotsvlm1-serve-longdoc-batch").config
    cfg = dots_vlm.model_config(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert abs(tfm.param_count(shapes) / 1e9 - 4.566) < 0.001
    assert abs(dots_vlm.attention_params(config) / 1e6 - 187.1) < 0.05
    assert abs(dots_vlm.matmul_params(config) + 16160 * 7168 - tfm.param_count(shapes)) < 2e6  # norms, the selecting bias
    pool = jax.eval_shape(lambda: tfm.init_kv_pages(cfg, 6241, 128))
    assert pool["ckv"].shape == (5, 6241, 128, 640) and dots_vlm.latent_bytes_per_token_layer(config) == 1152
    flops, nbytes = dots_vlm.latent_decode_work(config, 32, 32 * 15000)
    assert abs(flops / nbytes - 2 * 128 * (576 + 512) / 1152) < 1e-6 and 241 < flops / nbytes < 243
    # a 14.9k-token miss: expanded, 45 TFLOP of pairs and 2.5 of expansion over five layers; behind a hit absorbed
    miss, hit = dots_vlm.latent_prefill_work(config, 14900, 0)[0], dots_vlm.latent_prefill_work(config, 14980, 14848)[0]
    assert 45e12 < miss < 50e12 and abs(hit / (5 * (14980 * 14981 - 14848 * 14849) / 2 * 278528) - 1) < 1e-9
    assert tfm.decode_paths(cfg, 128) == {"decode_attention": "latent_kernel"}
    # the program's count takes the embedding for a matmul (6 N), the architecture file's does not (a gather)
    assert abs((tfm.flops_per_token(cfg, 4096) - 6 * 16160 * 7168) / dots_vlm.train_flops_per_token(config, 4096) - 1) < 0.01

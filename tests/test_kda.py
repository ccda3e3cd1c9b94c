"""Solar-Open2 (`model_type: solar_open2`) on the normal path, at
`archs/solar_open2.TINY` widths on the CPU (two periods of a gated no-rope
GQA layer and three KDA layers, 8 of 16 experts held), float32, seeded random
weights with every norm's scale drawn: the KDA layer's three forms against
each other and against the reference's token-by-token recurrence, the kernel
in interpret mode, the whole-sequence forward and the paged path with BOTH
caches against the plain reference of `benchmarks/archs/solar_open2.py`, the
wrong models, the engine's slots, and what the other models keep.

TOLERANCE is tests/test_parity.py's: both sides compute in float32, the
reference at matmul precision "highest". Read over these cases (PR 44, CPU):
the largest difference 3e-5 on logits up to 4 in size.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.archs import solar_open2
from benchmarks.lib import correct
from benchmarks.tools import wrong_reference
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import kda
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM

TOLERANCE = 1e-4
CHUNK = 16  # PREFILL_CHUNK_TOKENS in these tests: a 41-token prompt walks three chunks, the last one padded
CONFIG = dict(solar_open2.TINY, gqa_interval=3, rms_norm_eps=1e-5, n_shared_experts=1, routed_scaling_factor=1, use_rope=False)
T = 8  # positions a K/V page


@pytest.fixture(autouse=True)
def small_chunks_at_highest_precision(monkeypatch):
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", CHUNK)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", CHUNK)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def seeded(seed):
    cfg = solar_open2.model_config(CONFIG, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(seed))


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def reference(arch, params, tokens, positions):
    return correct.reference_logits(arch, params, tokens, positions, CONFIG)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ------------------------------------------------- (a) the layer's three forms

H, DK = 2, 16


def layer_inputs(seed, n, kind):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = kda.qk_norms(*(jax.random.normal(ks[i], (n, H, DK)) for i in range(2)))
    v = jax.random.normal(ks[2], (n, H, DK))
    log_rate = {"seeded": (-6.0, 0.0), "strong_decay": (1.0, 3.0), "near_one": (-9.0, -7.0)}.get(kind, (-6.0, 0.0))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, H, DK), minval=log_rate[0], maxval=log_rate[1]))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (n, H)) + (6.0 if kind == "beta_near_2" else 0.0))
    return q, k, v, g, beta


@pytest.mark.parametrize("kind", ["seeded", "strong_decay", "near_one", "beta_near_2"])
@pytest.mark.parametrize("form", ["chunk_whole", "chunks_split_anywhere", "padded_last_chunk", "one_token_a_step"])
def test_the_three_forms_give_the_references_recurrence(form, kind):
    """150 tokens of one sequence from a random state: the chunked form in one
    call (sub-chunks of 64, the last one padded), in chunks that split the
    rows anywhere with the state handed on, with a last chunk padded by rows
    that must leave the state alone, and the one-token step, each against the
    architecture file's token-by-token recurrence (`_delta_rule`, from a zero
    state: so the program's runs start there too but for the split forms'
    later chunks). Decays to exp(-20) a token underflow no exponent."""
    n = 150
    q, k, v, g, beta = layer_inputs(7, n, kind)
    zero = jnp.zeros((H, DK, DK))
    want = solar_open2._delta_rule(q, k, v, g, beta)
    if form == "chunk_whole":
        got, _ = kda.kda_chunk(q, k, v, g, beta, zero)
    elif form == "one_token_a_step":
        got, _ = kda.kda_recurrence(q, k, v, g, beta, zero)
    else:
        cuts = [0, 1, 70, 134, n] if form == "chunks_split_anywhere" else [0, 64, n]
        outs, s = [], zero
        for a, b in zip(cuts, cuts[1:]):
            rows = [t[a:b] for t in (q, k, v, g, beta)]
            valid = None
            if form == "padded_last_chunk" and b == n:  # 86 rows padded to 128 with rows that would wreck the state
                pad = 128 - (b - a)
                rows = [jnp.concatenate([t, 5.0 * jnp.ones((pad, *t.shape[1:]))]) for t in rows]
                rows[3] = -jnp.abs(rows[3])
                valid = jnp.arange(128) < b - a
            o, s = kda.kda_chunk(*rows, s, valid)
            outs.append(o[: b - a])
        got = jnp.concatenate(outs)
        np.testing.assert_allclose(s, kda.kda_recurrence(q, k, v, g, beta, zero)[1], rtol=1e-4, atol=1e-5)
    assert worst(got, want) <= TOLERANCE * max(1.0, float(jnp.max(jnp.abs(want))))


def head_wise_inputs(seed, n, kind, value_heads=4, key_heads=2):
    """The gated-delta-rule form's inputs (GigaChat3.5's layer): ONE decay a
    value head, beta in (0, 1), `key_heads` key heads each read by value_heads
    / key_heads value heads (value head j reads key head j // their ratio)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = kda.qk_norms(*(jax.random.normal(ks[i], (n, key_heads, DK)) for i in range(2)))
    q, k = (jnp.repeat(t, value_heads // key_heads, axis=1) for t in (q, k))
    v = jax.random.normal(ks[2], (n, value_heads, DK))
    log_rate = {"seeded": (-6.0, 0.0), "strong_decay": (1.0, 3.0), "near_one": (-9.0, -7.0)}[kind]
    a = jax.random.uniform(ks[3], (n, value_heads), minval=log_rate[0], maxval=log_rate[1])
    # softplus(a + dt_bias) = exp(a) with A_log = 0: the same rates as the per-channel cases draw
    g, beta = kda.gates_a_head(jnp.log(jnp.expm1(jnp.exp(a))), jnp.zeros((value_heads,)), jnp.zeros((value_heads,)), jax.random.normal(ks[4], (n, value_heads)), DK)
    return q, k, v, g, beta


@pytest.mark.parametrize("kind", ["seeded", "strong_decay", "near_one"])
@pytest.mark.parametrize("form", ["chunk_whole", "chunks_split_anywhere", "padded_last_chunk", "one_token_a_step"])
def test_the_three_forms_agree_for_a_decay_a_head_and_shared_key_heads(form, kind):
    """The same three forms with the decay ONE number a head, broadcast over
    its channels (`gates_a_head`), beta in (0, 1) and two key heads under four
    value heads, against archs/gigachat3_5.py's token-by-token recurrence,
    which takes the decay a head as a scalar and never broadcasts it."""
    from benchmarks.archs import gigachat3_5

    n = 150
    q, k, v, g, beta = head_wise_inputs(11, n, kind)
    assert g.shape == (n, 4, DK) and bool(jnp.all(g[..., :1] == g)) and bool(jnp.all((beta > 0) & (beta < 1)))
    zero = jnp.zeros((4, DK, DK))
    want = gigachat3_5._delta_rule(q, k, v, g[..., 0], beta)
    if form == "chunk_whole":
        got, _ = kda.kda_chunk(q, k, v, g, beta, zero)
    elif form == "one_token_a_step":
        got, _ = kda.kda_recurrence(q, k, v, g, beta, zero)
    else:
        cuts = [0, 1, 70, 134, n] if form == "chunks_split_anywhere" else [0, 64, n]
        outs, s = [], zero
        for a, b in zip(cuts, cuts[1:]):
            rows = [t[a:b] for t in (q, k, v, g, beta)]
            valid = None
            if form == "padded_last_chunk" and b == n:
                pad = 128 - (b - a)
                rows = [jnp.concatenate([t, 5.0 * jnp.ones((pad, *t.shape[1:]))]) for t in rows]
                rows[3] = -jnp.abs(rows[3])
                valid = jnp.arange(128) < b - a
            o, s = kda.kda_chunk(*rows, s, valid)
            outs.append(o[: b - a])
        got = jnp.concatenate(outs)
        np.testing.assert_allclose(s, kda.kda_recurrence(q, k, v, g, beta, zero)[1], rtol=1e-4, atol=1e-5)
    assert worst(got, want) <= TOLERANCE * max(1.0, float(jnp.max(jnp.abs(want))))


def test_the_decode_kernel_takes_a_decay_a_head_broadcast_over_its_channels():
    """`kda_decode` in interpret mode under the head-wise form: 8 value heads
    over 4 key heads of 128, the decay a head on all 128 of its channels."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q, k = kda.qk_norms(*(jax.random.normal(ks[i], (2, 4, 128)) for i in range(2)))
    q, k = (jnp.repeat(t, 2, axis=1) for t in (q, k))
    v = jax.random.normal(ks[2], (2, 8, 128))
    g, beta = kda.gates_a_head(jax.random.normal(ks[3], (2, 8)), jnp.zeros((8,)), jnp.zeros((8,)), jax.random.normal(ks[4], (2, 8)), 128)
    pool = jax.random.normal(ks[5], (1, 3, 8, 128, 128))
    slots, live = jnp.array([2, 1]), jnp.array([True, True])
    o, after = kda.kda_decode(q, k, v, g, beta, pool, 0, slots, live, interpret=True)
    want_o, want_s = kda.kda_step(q, k, v, g, beta, pool[0, slots])
    assert worst(o, want_o) <= 1e-5 and worst(after[0, slots], want_s) <= 1e-5


def test_a_state_not_handed_on_shows():
    q, k, v, g, beta = layer_inputs(8, 64, "near_one")
    zero = jnp.zeros((H, DK, DK))
    whole, _ = kda.kda_chunk(q, k, v, g, beta, zero)
    lost, _ = kda.kda_chunk(*(t[32:] for t in (q, k, v, g, beta)), zero)
    assert worst(whole[32:], lost) > 0.05


def test_the_short_convolution_hands_its_tail_on_and_stops_it_at_the_valid_rows():
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x, w = jax.random.normal(ks[0], (20, 6)), jax.random.normal(ks[1], (6, 4))
    whole, tail = kda.short_conv(x, w, jnp.zeros((3, 6)))
    np.testing.assert_allclose(whole, solar_open2._short_conv(x, w), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail, x[17:])
    first, tail = kda.short_conv(jnp.pad(x[:11], ((0, 5), (0, 0)), constant_values=9.0), w, jnp.zeros((3, 6)), n_valid=11)
    np.testing.assert_array_equal(tail, x[8:11])
    second, _ = kda.short_conv(x[11:], w, tail)
    np.testing.assert_allclose(jnp.concatenate([first[:11], second]), whole, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ (b) the kernel


@pytest.mark.parametrize("heads", [8, 16])
def test_the_decode_kernel_in_interpret_mode_is_kda_step_in_place(heads):
    """Three rows, the middle one not live, over a pool of two layers and five
    slots: the live rows' states of layer 1 are `kda_step`'s, every other
    slot but the trash slot is untouched, bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(heads), 6)
    q, k = kda.qk_norms(*(jax.random.normal(ks[i], (3, heads, 128)) for i in range(2)))
    v = jax.random.normal(ks[2], (3, heads, 128))
    g = -jnp.exp(jax.random.uniform(ks[3], (3, heads, 128), minval=-6.0, maxval=2.0))
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (3, heads)))
    pool = jax.random.normal(ks[5], (2, 5, heads, 128, 128))
    slots, live = jnp.array([3, 1, 4]), jnp.array([True, False, True])
    assert kda.can_tile(heads, 128, 128) and not kda.can_tile(4, 16, 16) and not kda.can_tile(12, 128, 128)
    o, after = kda.kda_decode(q, k, v, g, beta, pool, 1, slots, live, interpret=True)
    want_o, want_s = kda.kda_step(q, k, v, g, beta, pool[1, slots])
    rows = jnp.array([0, 2])
    assert worst(o[rows], want_o[rows]) <= 1e-5 and worst(after[1, slots][rows], want_s[rows]) <= 1e-5
    touched = jnp.array([3, 4, 0])
    assert bool(jnp.all(after.at[1, touched].set(pool[1, touched]) == pool))


# ------------------------------------------- (c) the whole-sequence forward


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed):
    """70 tokens through two periods (a GQA layer and three KDA layers each)
    with 8 of 16 experts held (rank 1): router over all 16, the held ones'
    terms summed, in program and reference alike."""
    cfg, params = seeded(seed)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.kda_per_period) == (16, 8, 8, 3)
    tokens = tokens_of(seed, 70)
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0]
    assert worst(got, reference(solar_open2, params, tokens, np.arange(70))) <= TOLERANCE


def test_a_kda_config_on_the_flash_path_or_with_rope_is_refused_loudly():
    cfg, params = seeded(0)
    with pytest.raises(ValueError, match="naive"):
        tfm.forward(params, tokens_of(0, 16)[None], cfg.replace(attn_impl="full"))
    with pytest.raises(ValueError, match="periods"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(rope_layers=()))
    with pytest.raises(ValueError, match="periods"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(n_layers=6, rope_layers=(False,) * 6))
    with pytest.raises(ValueError, match="solar_open2 does not compute"):
        solar_open2.dims(dict(CONFIG, kda_allow_neg_eigval=False))
    with pytest.raises(ValueError, match="gqa_layers"):
        solar_open2.dims(dict(CONFIG, gqa_layers=[0, 5]))


# ------------------------------------------------------ (d) both caches


def hybrid_lm(cfg, params, slots=3, pages=24):
    return PagedLM(cfg, params, num_pages=pages, page_tokens=T, max_slots=slots, max_pages_per_seq=8)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_both_caches_matches_the_reference_logits(seed):
    """A 41-token prompt prefilled in three chunks (the last one padded) into
    pages 3.. and state slot 2 of a pool that holds another sequence's
    leftovers, then nine tokens teacher-forced through decode steps as row 1
    (so state slot 2) beside two inactive rows: every logit the reference's."""
    cfg, params = seeded(seed)
    tokens = tokens_of(seed + 10, 50)
    want = reference(solar_open2, params, tokens, np.arange(50))
    lm = hybrid_lm(cfg, params)
    assert set(lm.kv) == {"k", "v", "s", "tail"}
    assert lm.kv["k"].shape[0] == 2 and lm.kv["s"].shape[:2] == (6, 4) and lm.kv["tail"].shape[:2] == (6, 4)
    # What a slot's or a page's last owner left must not leak. In the state slots NaN, which does not fade; in the
    # pages numbers (the plain gather expression multiplies a masked position's V by a weight of exactly 0).
    # The trash slot holds numbers too: what an inactive row makes of it lands in the trash page, which the gather reads.
    pool = {name: (leaf + jnp.nan).at[:, 0].set(0.0) if name in ("s", "tail") else leaf + 100.0 for name, leaf in lm.kv.items()}
    n = 41
    table = jnp.array([3, 4, 5, 6, 7, 8, 9, 0])
    padded = jnp.zeros((1, 64), jnp.int32).at[0, :n].set(tokens[:n])
    logits, pool = jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, table, n, 0, 2))(params, padded, pool)
    assert worst(logits[0], want[n - 1]) <= TOLERANCE
    tables = jnp.zeros((3, 8), jnp.int32).at[1].set(table)
    decode = jax.jit(lambda p, t, pos, kv: tfm.forward_decode(p, t, pos, cfg, kv, tables))
    for i in range(n, 50):
        logits, pool = decode(params, jnp.array([0, tokens[i], 0]), jnp.array([-1, i, -1]), pool)
        assert worst(logits[1], want[i]) <= TOLERANCE, i
    # state slots 1 and 3 were nobody's: untouched; slot 2 is the sequence's
    for name in ("s", "tail"):
        assert bool(jnp.all(jnp.isnan(pool[name][:, 1]))) and bool(jnp.all(jnp.isnan(pool[name][:, 3])))
        assert bool(jnp.all(jnp.isfinite(pool[name][:, 2])))


def test_paged_lm_keeps_a_rows_state_by_the_engines_slot_and_a_bare_list_writes_the_trash_slot():
    from ray_tpu.serve.llm.model import PromptTokens

    cfg, params = seeded(3)
    tokens = [int(t) for t in tokens_of(30, 37)]
    want = reference(solar_open2, params, jnp.asarray(tokens), np.array([29, 30]))
    lm = hybrid_lm(cfg, params)
    before = jax.tree_util.tree_map(np.asarray, lm.kv)
    first = lm.prefill(tokens[:30], [1, 2, 3, 4], 0)  # a caller's bare list: the trash slot, as its pages could be the trash page
    assert int(first) == int(jnp.argmax(want[0]))
    after = jax.tree_util.tree_map(np.asarray, lm.kv)
    assert np.array_equal(after["s"][:, 1:], before["s"][:, 1:]) and not np.array_equal(after["s"][:, 0], before["s"][:, 0])
    prompt = PromptTokens(tokens[:30])
    prompt.slot = 1  # the engine's admission: decode row 1, so state slot 2
    assert int(lm.prefill(prompt, [1, 2, 3, 4], 0)) == int(first)
    assert np.any(np.asarray(lm.kv["s"])[:, 2] != 0) and not np.any(np.asarray(lm.kv["s"])[:, 1] != 0)
    out = lm.decode([0, tokens[30]], [-1, 30], [[], [1, 2, 3, 4]])
    assert out[1] == int(jnp.argmax(want[1]))
    assert isinstance(out, DecodeTokens) and set(out.counters) == {"decode_experts", "decode_state", "decode_kv"}
    assert out.counters["decode_kv"] == {"bytes": 31 * lm.page_bytes // T, "tokens": 31, "steps": 1}
    assert out.counters["decode_state"] == {"bytes": 2 * lm.state_bytes, "live_slots": 1, "steps": 1}
    experts = out.counters["decode_experts"]
    assert experts["held"] == 8 * 8 and experts["picks"] == 8 * 3 * 4 and 0 < experts["held_picks"] < experts["picks"]
    assert experts["touched"] <= min(experts["held"], experts["held_picks"])
    said = lm.describe()
    assert said["cache"] == {"kind": "state+kv_pages", "state_bytes": lm.state_bytes, "page_bytes": lm.page_bytes}
    assert said["decode_state"] == "xla_step" and lm.shares_prefix_pages is False
    assert lm.page_bytes == 2 * 2 * T * 2 * 16 * 4 and lm.state_bytes == 6 * (4 * 16 * 16 * 4 + 9 * 64 * 4)
    with pytest.raises(ValueError, match="cached_tokens"):
        lm.prefill(tokens[:20], [1, 2, 3], 8)


# ---------------------------------------------------- (e) the wrong models


def served_margins(arch, params, tokens, served):
    logits = reference(arch, params, tokens, np.arange(len(tokens)))
    return np.asarray(jnp.max(logits, -1) - jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0])


@functools.lru_cache(maxsize=None)
def served_by_the_program():
    cfg, params = seeded(4)
    out = []
    for seed in (40, 41, 42):
        tokens = tokens_of(seed, 70)
        served = jnp.argmax(jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0], -1)
        out.append((tokens, served, served_margins(solar_open2, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_reference.WRONG["solar_open2"]))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 70: against the
    right reference every margin is 0 to rounding; against each wrong model's
    (one line of the reference altered, and the fp8-precision control) the
    90th percentile, which a routed cell's limit names, is far over it."""
    _cfg, params = seeded(4)
    wrong = wrong_reference.load("solar_open2", name)
    right = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()]))
    margins = np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()])
    wrong = correct.error_quantiles(np.where(np.isfinite(margins), margins, np.inf))
    assert right["q100"] <= 1e-3
    assert wrong["q90"] > 0.02 and wrong["q90"] > 20 * max(right["q100"], 1e-3), (right, wrong)


# ----------------------------------------------------------- (f) the engine


def greedy(cfg, params, prompt, n):
    """An engine-free greedy loop: the whole-sequence forward at one padded length."""
    fwd = jax.jit(lambda p, t: tfm.forward(p, t, cfg))
    tokens = np.zeros((1, len(prompt) + n), np.int32)
    tokens[0, : len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        tokens[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(tokens))[0, i - 1]))
    return tokens[0, len(prompt):].tolist()


def test_twice_as_many_requests_as_slots_queue_and_each_is_served_the_tokens_it_is_served_alone():
    """Four prompts over two slots (so two wait, and each takes the row, the
    state slot and some pages another left), of different lengths and answer
    lengths, at once: each gets the tokens of an engine-free greedy loop.
    Nothing enters the prefix index, and the counters of both caches add up."""
    cfg, params = seeded(5)
    prompts = [[int(t) for t in tokens_of(50 + i, n)] for i, n in enumerate((45, 18, 33, 45))]
    prompts[3] = prompts[0]  # the same prompt again: no hit
    answers = (12, 19, 7, 12)
    want = [greedy(cfg, params, p, n) for p, n in zip(prompts, answers)]
    lm = hybrid_lm(cfg, params, slots=2, pages=33)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=33, prefill_token_budget=64), name="t-kda")
    got = [None] * 4

    def client(i):
        got[i] = list(eng.generate(prompts[i], answers[i]))

    try:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        stats = eng.stats()
    finally:
        eng.close()
    assert got == want
    assert stats["kv"]["indexed_pages"] == 0 and stats["kv"]["prefix_hits"] == 0 and stats["kv"]["used_pages"] == 0
    clocks = stats["clocks"]
    chunks = sum(-(-len(p) // CHUNK) for p in prompts)
    assert clocks["prefill_state"] == {"chunks": chunks, "carried_in": chunks - 4}
    # every layer of a KDA stack is routed; chunks of CHUNK rows, under the cut: none through the grouped product
    assert clocks["prefill_experts"] == {"rows": chunks * CHUNK * cfg.n_layers, "grouped_rows": 0, "chunks": chunks}
    state, kv, experts = clocks["decode_state"], clocks["decode_kv"], clocks["decode_experts"]
    assert state["steps"] == kv["steps"] == experts["steps"] == clocks["decode"]["n"]
    assert state["live_slots"] == sum(answers) - 4 and state["bytes"] == state["live_slots"] * 2 * lm.state_bytes
    # a decode step's row reads its prompt and what it has been served so far, the token it appends included
    assert kv["tokens"] == sum(sum(range(len(p) + 1, len(p) + n)) for p, n in zip(prompts, answers))
    assert experts["picks"] == experts["steps"] * 8 * 2 * 4 and 0 < experts["held_picks"] < experts["picks"]


def test_a_slot_reused_after_a_cancel_starts_clean():
    """One slot. A first request is cancelled part-way through its answer (its
    consumer goes away), leaving its state and tails in the slot and its K/V
    in its pages; the next request on that slot is served the tokens a fresh
    engine serves it."""
    cfg, params = seeded(6)
    first, second = ([int(t) for t in tokens_of(60 + i, n)] for i, n in enumerate((40, 25)))

    def serve(cancel_first):
        eng = InferenceEngine(hybrid_lm(cfg, params, slots=1, pages=17), EngineConfig(page_tokens=T, pool_pages=17), name="t-kda-reuse")
        try:
            if cancel_first:
                stream = eng.generate(first, 20)
                for _ in range(5):
                    next(stream)
                stream.close()  # the consumer drops: the engine reaps the slot and the pages
            return list(eng.generate(second, 10))
        finally:
            eng.close()

    assert serve(True) == serve(False)


# ------------------------------------------------ (g) what the others keep


@pytest.mark.parametrize("preset", ["llama2_7b", "llama2_13b", "gpt_j_6b", "tiny"])
def test_every_preset_holds_all_its_experts_has_no_kda_layer_and_draws_the_weights_it_drew(preset):
    """The share and the period are off by default: a preset's config says so,
    and its parameters at toy sizes are, leaf for leaf, those of the same
    config with the share spelled out as all of them."""
    cfg = getattr(tfm, preset)()
    assert (cfg.n_experts_held, cfg.first_expert, cfg.kda_per_period, cfg.state_slots) == (0, 0, 0, 0) and cfg.experts_held == cfg.n_experts
    small = cfg.replace(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=48, rotary_dim=None, mlp_act="swiglu",
                        n_experts=4, n_experts_per_tok=2)
    a, b = (tfm.init_params(jax.random.PRNGKey(1), c) for c in (small, small.replace(n_experts_held=4)))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b) and "kda_blocks" not in a
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert a["blocks"]["mlp"]["w_gate"].shape == (2, 4, 32, 48)
    pool = tfm.init_kv_pages(small, 4, 8)
    assert set(pool) == {"k", "v"} and pool["k"].shape[0] == 2


def test_counts_at_the_published_widths():
    """The issue's arithmetic: 3.308 B parameters in the cut, a state of 12.58
    MB and 4 096 B of K/V a token a sequence, 250 B in the whole model."""
    from benchmarks.lib import spec

    config = spec.find_cell("solaropen2-serve-reasoning-batch").config
    cfg = solar_open2.model_config(config)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert abs(tfm.param_count(shapes) / 1e9 - 3.308) < 0.001
    assert abs(solar_open2.matmul_params(config) + 24576 * 4096 - tfm.param_count(shapes)) < 2e6  # norms, biases, convolutions
    pool = jax.eval_shape(lambda: tfm.init_kv_pages(cfg, 2561, 128))
    assert pool["s"].shape == (3, 65, 64, 128, 128) and pool["k"].shape == (1, 2561, 128, 1024)
    assert solar_open2.decode_state_bytes(config, 1) == 2 * 3 * 64 * 128 * 128 * 4 and solar_open2.decode_kv_bytes(config, 1) == 4096
    whole = dict(config, num_hidden_layers=48, gqa_layers=list(range(0, 48, 4)), n_routed_experts=320, vocab_size=196608)
    whole["assumed"] = dict(config["assumed"], expert_rank={"value": 0})
    assert abs((solar_open2.matmul_params(whole) + 196608 * 4096) / 1e9 - 250.3) < 0.2
    # the program's count takes the embedding for a matmul (6 N), the architecture file's does not (a gather)
    assert abs((tfm.flops_per_token(cfg, 4096) - 6 * 24576 * 4096) / solar_open2.train_flops_per_token(config, 4096) - 1) < 0.01


def test_routing_stats_walks_a_kda_stack_in_published_order_and_agrees_with_the_references_router():
    """`routing_stats` walks the stack's plan like every other forward: a KDA
    stack's routers come back [n_layers, ...] in published order (the softmax
    layer of a period, then its KDA layers), every (token, choice) pair
    counted, the same experts as the plain reference's router chooses on the
    reference's own hidden states."""
    cfg, params = seeded(3)
    n, k, m = 24, cfg.n_experts_per_tok, solar_open2.dims(CONFIG)
    tokens = tokens_of(3, n)
    stats = tfm.routing_stats(params, tokens[None], cfg)
    assert stats["experts"].shape == (cfg.n_layers, n, k) and stats["gap"].shape == (cfg.n_layers, n)
    np.testing.assert_array_equal(np.asarray(stats["tokens_per_expert"]).sum(-1), np.full(cfg.n_layers, n * k))
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    for layer in range(cfg.n_layers):
        p, j = divmod(layer, m["per"])
        group, index = ("blocks", (p,)) if j == 0 else ("kda_blocks", (p, j - 1))
        stacks = {name: params[group]["mlp"][name] for name in tfm.EXPERT_WEIGHTS}
        w = jax.tree_util.tree_map(lambda a: a[index], dict(params[group], mlp={name: a for name, a in params[group]["mlp"].items() if name not in stacks}))
        mixer = solar_open2._kda_mixer if j else solar_open2._gqa_mixer
        mid = x + mixer(solar_open2._rms_norm(x, w["attn_norm"]["scale"], m["eps"]), w["attn"], m)
        chosen = np.asarray(solar_open2._router_weights(solar_open2._rms_norm(mid, w["mlp_norm"]["scale"], m["eps"]), w["mlp"], m) > 0)
        got = np.zeros_like(chosen)
        np.put_along_axis(got, np.asarray(stats["experts"][layer]), True, axis=-1)
        clear = np.asarray(stats["gap"][layer]) > 1e-5  # a token whose k-th and (k+1)-th scores all but tie may fall either way
        assert clear.sum() >= n - 2
        np.testing.assert_array_equal(got[clear], chosen[clear], err_msg=f"layer {layer}")
        x = solar_open2._layer(x, w, m, stacks, index)

"""Power retention (Brumby-14B-Base, `model_type: brumby`) on the normal path,
at `archs/brumby.TINY` widths on the CPU, float32, seeded random weights with
every norm's scale drawn: the three forms of the layer (quadratic, chunked
through a state, one token a step), the whole-sequence forward and the paged
path whose page is a sequence's state against the plain reference of
`benchmarks/archs/brumby.py`, the wrong models, the engine, the kernel in
interpret mode, and what the other models keep.

TOLERANCE is tests/test_parity.py's: both sides compute in float32, the
reference at matmul precision "highest". Read over these cases (PR 42, CPU):
the largest difference 7e-6 on logits up to 4 in size.
"""

import functools
import hashlib
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from benchmarks.archs import brumby
from benchmarks.lib import correct, rehearsal, spec
from benchmarks.tools import wrong_retention
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import power_retention
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.kv_cache import PagedKVAllocator
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM
from ray_tpu.train import zero

TOLERANCE = 1e-4
CHUNK = 16  # PREFILL_CHUNK_TOKENS in these tests: a 70-token prompt walks five chunks
CONFIG = dict(brumby.TINY, rope_theta=1000000, rms_norm_eps=1e-6, torch_dtype="float32")
CONFIG.pop("assumed")
PAGE = 128  # positions a sequence may reach


@pytest.fixture(autouse=True)
def small_chunks_at_highest_precision(request, monkeypatch):
    if "lower_to_the_text_they_did" in request.node.name:  # (f) reads the program as it ships
        yield
        return
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", CHUNK)
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def seeded(seed):
    cfg = brumby.model_config(CONFIG, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(seed))


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def reference(arch, params, tokens, positions):
    return correct.reference_logits(arch, params, tokens, positions, CONFIG)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ------------------------------------------------- (a) the layer's three forms

H, KV, HD = 4, 2, 16


def layer_inputs(seed, n, gates):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(ks[i], (n, heads, HD)) for i, heads in enumerate((H, KV, KV)))
    if gates == "near_one":  # l in [-0.01, 0]: the past never fades, so a lost carry shows at every later position
        log_g = -0.01 * jax.random.uniform(ks[3], (n, KV))
    else:
        log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (n, KV)))
    return q, k, v, log_g


def quadratic(q, k, v, log_g):
    """ISSUE 42's step 3 as it stands: every pair s <= t, no state."""
    n = q.shape[0]
    r = H // KV
    k, v, L = jnp.repeat(k, r, 1), jnp.repeat(v, r, 1), jnp.repeat(jnp.cumsum(log_g, 0), r, 1).T
    scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(HD)
    seen = np.arange(n)[:, None] >= np.arange(n)[None, :]
    a = jnp.where(seen, jnp.exp(jnp.where(seen, L[:, :, None] - L[:, None, :], 0.0)) * scores**2, 0.0)
    return jnp.einsum("hts,shd->thd", a, v) / (jnp.sum(a, -1).T[..., None] + tfm.RETENTION_EPS)


def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k():
    for hd in (2, 16, 128):
        x, y = jax.random.normal(jax.random.PRNGKey(hd), (2, 7, hd))
        px, py = tfm.retention_phi(x), tfm.retention_phi(y)
        assert px.shape == (7, tfm.retention_state_dim(hd)) and tfm.retention_state_dim(hd) == (hd // 2 + 1) * hd
        # (x . y)^2 cancels where x . y is small beside |x| |y|: the float32 products are held to the terms' size
        size = np.sum(np.asarray(x) ** 2, -1) * np.sum(np.asarray(y) ** 2, -1)
        assert np.all(np.abs(np.sum(np.asarray(px * py, np.float64), -1) - np.sum(np.asarray(x * y, np.float64), -1) ** 2) <= 1e-6 * size)


@pytest.mark.parametrize("gates", ["near_one", "seeded"])
@pytest.mark.parametrize("form", ["whole_in_chunks_of_16", "whole_in_chunks_of_100", "one_token_a_step", "chunks_from_an_arbitrary_position"])
def test_the_three_forms_give_one_result_over_1k_tokens(form, gates):
    n = 1000
    q, k, v, log_g = layer_inputs(3, n, gates)
    want = quadratic(q, k, v, log_g)
    zero = (jnp.zeros((KV, HD, tfm.retention_state_dim(HD))), jnp.zeros((KV, tfm.retention_state_dim(HD))))
    if form.startswith("whole"):
        got = tfm.retention_whole(q[None], k[None], v[None], log_g[None], chunk=int(form.rsplit("_", 1)[1]))[0]
    elif form == "one_token_a_step":
        def step(state, x):
            y, s, z = tfm.retention_step(*(t[None] for t in x), state[0][None], state[1][None])
            return (s[0], z[0]), y[0]

        got = jax.lax.scan(step, zero, (q, k, v, log_g))[1]
    else:  # chunk borders wherever a cache happened to end: 0, 37, 37 + 256, 900, with padding past the end
        state, outs, borders = zero, [], [0, 37, 293, 900, n]
        for a, b in zip(borders, borders[1:]):
            pad = lambda t: jnp.pad(t[a:b], [(0, 5)] + [(0, 0)] * (t.ndim - 1), constant_values=3.0)  # noqa: E731
            y, *state = tfm.retention_chunk(pad(q), pad(k), pad(v), pad(log_g), *state, valid=jnp.arange(b - a + 5) < b - a)
            outs.append(y[: b - a])
        got = jnp.concatenate(outs)
    # phi(q) . S sums terms of both signs far larger than (q . k)^2 where q . k is small: read 6e-5 of the outputs' size
    scale = float(jnp.max(jnp.abs(want)))
    assert worst(got, want) <= 2e-4 * max(scale, 1.0), (worst(got, want), scale)


def test_a_lost_carry_shows_with_gates_near_one():
    """The check above has teeth: the chunked form with the state dropped at one border is far off."""
    q, k, v, log_g = layer_inputs(3, 64, "near_one")
    want = quadratic(q, k, v, log_g)
    D = tfm.retention_state_dim(HD)
    zero = (jnp.zeros((KV, HD, D)), jnp.zeros((KV, D)))
    second_half = tfm.retention_chunk(q[32:], k[32:], v[32:], log_g[32:], *zero)[0]
    assert worst(second_half, want[32:]) > 0.05


# --------------------------------------------- (b) parity with the reference


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed):
    cfg, params = seeded(seed)
    tokens = tokens_of(seed, 70)
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0]
    assert worst(got, reference(brumby, params, tokens, np.arange(70))) <= TOLERANCE


def test_a_retention_config_on_the_flash_path_is_refused_loudly():
    cfg, params = seeded(0)
    for impl in ("full", "ring", "ulysses"):
        with pytest.raises(ValueError, match="retention"):
            tfm.forward(params, tokens_of(0, 16)[None], cfg.replace(attn_impl=impl))
    with pytest.raises(ValueError, match="retention_degree"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(retention_degree=3))


def state_lm(cfg, params, slots=3):
    return PagedLM(cfg, params, num_pages=slots + 1, page_tokens=PAGE, max_slots=slots, max_pages_per_seq=1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_the_state_matches_the_reference_logits(seed):
    """A 41-token prompt prefilled in three chunks into slot 2 of a pool whose
    slots hold another sequence's leftovers, then nine tokens teacher-forced
    through decode steps beside an inactive row: every logit the reference's."""
    cfg, params = seeded(seed)
    tokens = tokens_of(seed + 10, 50)
    want = reference(brumby, params, tokens, np.arange(50))
    lm = state_lm(cfg, params)
    # What a slot's last owner left must not leak. NaN, not numbers: gates drawn from a seed forget within a few
    # tokens, and a leftover that fades before the prompt ends would pass unseen.
    lm.kv = jax.tree_util.tree_map(lambda a: a + jnp.nan, lm.kv)
    n = 41
    padded = jnp.zeros((1, PAGE), jnp.int32).at[0, :n].set(tokens[:n])
    logits, kv = jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, jnp.array([2]), n, 0))(params, padded, lm.kv)
    assert worst(logits[0], want[n - 1]) <= TOLERANCE
    decode = jax.jit(lambda p, t, pos, kv: tfm.forward_decode(p, t, pos, cfg, kv, jnp.array([[0], [2], [0]])))
    for i in range(n, 50):
        logits, kv = decode(params, jnp.array([0, tokens[i], 0]), jnp.array([-1, i, -1]), kv)
        assert worst(logits[1], want[i]) <= TOLERANCE, i
    # slots 1 and 3 were nobody's: untouched
    assert all(bool(jnp.all(jnp.isnan(kv[name][:, 1]))) and bool(jnp.all(jnp.isfinite(kv[name][:, 2]))) for name in ("s", "z"))


@pytest.mark.parametrize("case", [(70, 32), (70, 23), (37, 36)], ids=["at_a_chunk_border", "at_an_arbitrary_position", "one_token_left"])
def test_a_prefill_that_starts_from_the_slots_state_gives_the_whole_prompts_logits(case):
    """`write_from` w > 0: the slot's state holds positions [0, w) and the
    chunks start at w, wherever that lies; w = 0 starts from nothing."""
    length, w = case
    cfg, params = seeded(3)
    tokens = tokens_of(20, length)
    want = reference(brumby, params, tokens, np.array([w - 1, length - 1]))
    lm = state_lm(cfg, params)
    prefill = jax.jit(lambda p, t, kv, n, w: tfm.forward_prefill(p, t, cfg, kv, jnp.array([1]), n, w))
    padded = jnp.zeros((1, PAGE), jnp.int32).at[0, :length].set(tokens)
    first, kv = prefill(params, padded, lm.kv, w, 0)
    last, kv = prefill(params, padded, kv, length, w)
    assert worst(first[0], want[0]) <= TOLERANCE and worst(last[0], want[1]) <= TOLERANCE
    whole, kv_whole = prefill(params, padded, lm.kv, length, 0)
    assert worst(whole[0], want[1]) <= TOLERANCE
    for name in ("s", "z"):
        np.testing.assert_allclose(kv[name][:, 1], kv_whole[name][:, 1], rtol=1e-4, atol=1e-5)


# ---------------------------------------------------- (c) the wrong models


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
        out.append((tokens, served, served_margins(brumby, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_retention.WRONG))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 70: against the
    right reference every margin is 0 to rounding; against each wrong model's
    (one line of the reference altered, and the fp8-precision control) the
    99th percentile is far over any limit between."""
    _cfg, params = seeded(4)
    wrong = wrong_retention.load(name)
    right = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()]))
    wrong = correct.error_quantiles(np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()]))
    assert right["q100"] <= 1e-3
    assert wrong["q99"] > 0.02 and wrong["q99"] > 20 * max(right["q100"], 1e-3), (right, wrong)


# ----------------------------------------------------------- (d) the engine


def _collect(engine, prompt, n):
    return list(engine.generate(prompt, n))


def greedy(cfg, params, prompt, n):
    """An engine-free greedy loop: the whole-sequence forward at one padded length."""
    fwd = jax.jit(lambda p, t: tfm.forward(p, t, cfg))
    tokens = np.zeros((1, len(prompt) + n), np.int32)
    tokens[0, : len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        tokens[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(tokens))[0, i - 1]))
    return tokens[0, len(prompt):].tolist()


def test_the_engine_serves_sequences_that_come_and_go_the_tokens_each_is_served_alone():
    """Four prompts over two slots (so two wait, and each takes the slot and
    the state another left), of different lengths and answer lengths, at
    once: each gets the tokens of an engine-free greedy loop. No page of the
    model ever enters the prefix index, the same prompt twice is computed
    twice, and the state counters add up."""
    cfg, params = seeded(5)
    prompts = [[int(t) for t in tokens_of(50 + i, n)] for i, n in enumerate((45, 18, 33, 45))]
    prompts[3] = prompts[0]  # the same prompt again: no hit
    answers = (12, 20, 7, 12)
    want = [greedy(cfg, params, p, n) for p, n in zip(prompts, answers)]
    # a waiting sequence holds its page, its state's slot, from submit on: four pages and the trash page, two rows a step
    lm = PagedLM(cfg, params, num_pages=5, page_tokens=PAGE, max_slots=2, max_pages_per_seq=1)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=PAGE, pool_pages=5, prefill_token_budget=64), name="t-retention")
    got = [None] * 4

    def client(i):
        got[i] = _collect(eng, prompts[i], answers[i])

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
    assert clocks["prefill"]["computed_tokens"] == chunks * CHUNK
    state = clocks["decode_state"]
    assert state["steps"] == clocks["decode"]["n"] and state["live_slots"] == sum(answers) - 4
    assert state["bytes"] == state["live_slots"] * 2 * lm.page_bytes
    D = tfm.retention_state_dim(cfg.head_dim)
    assert lm.page_bytes == cfg.n_layers * cfg.n_kv_heads * D * (cfg.head_dim + 1) * 4
    said = lm.describe()
    assert said["cache"] == {"kind": "state", "page_bytes": lm.page_bytes} and said["decode_attention"] == "xla_step"
    out = lm.decode([1], [3], [[1]])
    assert isinstance(out, DecodeTokens) and set(out.counters) == {"decode_state"}


def test_a_slot_reused_after_release_gives_the_tokens_of_a_fresh_engine():
    cfg, params = seeded(6)
    first, second = ([int(t) for t in tokens_of(60 + i, n)] for i, n in enumerate((40, 25)))

    def serve(prompts):
        eng = InferenceEngine(state_lm(cfg, params, slots=1), EngineConfig(page_tokens=PAGE, pool_pages=2), name="t-retention-reuse")
        try:
            return [_collect(eng, p, 10) for p in prompts]
        finally:
            eng.close()

    assert serve([first, second])[1] == serve([second])[0]


def test_no_page_of_a_state_model_is_shared_and_a_cached_prefix_is_refused():
    """The engine asks the model (`shares_prefix_pages`); an allocator told so
    neither indexes nor matches, even a prompt that fills its page; and
    PagedLM refuses a prefill that claims cached tokens."""
    alloc = PagedKVAllocator(4, 8, share_prefixes=False)
    seq = alloc.allocate(list(range(8)))
    alloc.commit(seq, list(range(8)))
    again = alloc.allocate(list(range(8)))
    assert again.cached_tokens == 0 and again.pages != seq.pages and alloc.stats()["indexed_pages"] == 0
    cfg, params = seeded(0)
    lm = state_lm(cfg, params)
    assert lm.shares_prefix_pages is False and PagedLM(max_slots=2).shares_prefix_pages is True
    with pytest.raises(ValueError, match="cached_tokens"):
        lm.prefill([1, 2, 3], [1], 2)
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        PagedLM(cfg, params, num_pages=4, page_tokens=PAGE, max_slots=2, max_pages_per_seq=2)


def test_a_dense_paged_model_keeps_no_state_clock():
    eng = InferenceEngine(PagedLM(max_slots=2), EngineConfig(), name="t-dense-no-state")
    try:
        _collect(eng, [1, 2, 3], 4)
        stats = eng.stats()
    finally:
        eng.close()
    assert not {"decode_state", "prefill_state"} & set(stats["clocks"])
    assert PagedLM(max_slots=2).describe()["cache"]["kind"] == "kv_pages"


# ------------------------------------------------------------ (e) the kernel


@pytest.mark.parametrize("heads", [(10, 2), (4, 4)], ids=["five_query_heads_a_kv_head", "one"])
def test_the_decode_kernel_in_interpret_mode_is_retention_step_in_place(heads):
    """Three rows against a pool of two layers and four slots: the live rows'
    states advance as `retention_step` advances them, their outputs are its,
    and every other layer and slot is bit for bit what it was."""
    n_heads, n_kv = heads
    hd, L, N, B = 128, 2, 4, 3
    assert power_retention.can_tile(n_heads, n_kv, hd) and not power_retention.can_tile(4, 2, 16)
    assert not power_retention.can_tile(12, 2, 128)  # six query heads a K/V head do not fit the tile
    D = tfm.retention_state_dim(hd)
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q, k, v = (jax.random.normal(ks[i], (B, h, hd)) for i, h in enumerate((n_heads, n_kv, n_kv)))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, n_kv)))
    s, z = jax.random.normal(ks[4], (L, N, n_kv, hd, D)), jnp.abs(jax.random.normal(ks[5], (L, N, n_kv, D)))
    slots, live, layer = jnp.array([2, 1, 3]), jnp.array([True, False, True]), 1
    y, s2, z2 = jax.jit(lambda *a: power_retention.power_retention_decode(*a, eps=tfm.RETENTION_EPS, interpret=True))(
        q, k, v, log_g, s, z, layer, slots, live)
    want_y, want_s, want_z = tfm.retention_step(q, k, v, log_g, s[layer, slots], z[layer, slots])
    for b in (0, 2):
        assert worst(y[b], want_y[b]) <= 1e-4 * float(jnp.max(jnp.abs(want_y[b])))
        np.testing.assert_allclose(s2[layer, slots[b]], want_s[b], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(z2[layer, slots[b]], want_z[b], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s2[0], s[0])
    np.testing.assert_array_equal(s2[1, 1], s[1, 1])  # the slot of the row that is not live
    np.testing.assert_array_equal(z2[1, 1], z[1, 1])


def chunk_inputs(seed, c, heads, gates):
    """A chunk's operands at the kernel's head width, and a pool of two layers
    and three slots that holds another sequence's leftovers everywhere."""
    n_heads, n_kv = heads
    hd, D = 128, tfm.retention_state_dim(128)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (c, h, hd)) for i, h in enumerate((n_heads, n_kv, n_kv)))
    log_g = {"seeded": jax.nn.log_sigmoid(jax.random.normal(ks[3], (c, n_kv))), "near_zero": -1e-3 * jax.random.uniform(ks[3], (c, n_kv)),
             "strongly_negative": -8.0 - 8.0 * jax.random.uniform(ks[3], (c, n_kv))}[gates]
    s, z = jax.random.normal(ks[4], (2, 3, n_kv, hd, D)), jnp.abs(jax.random.normal(ks[5], (2, 3, n_kv, D)))
    return q, k, v, log_g, s, z


# (q, k, v, log_g, s, z, layer, slot, carried, valid): ONE jitted function, so that the cases of one shape share its compilation
prefill_kernel = jax.jit(functools.partial(power_retention.power_retention_prefill, eps=tfm.RETENTION_EPS, interpret=True))


PREFILL_CASES = {  # name: (rows, of them valid, (n_heads, n_kv_heads), the gates, whether the slot's state is carried in)
    "from_nothing_whatever_the_slot_holds": (16, 16, (4, 2), "seeded", False),
    "from_the_slots_state": (16, 16, (4, 2), "seeded", True),
    "a_padded_last_chunk": (16, 13, (4, 2), "seeded", True),
    "a_padded_first_chunk": (16, 5, (4, 2), "seeded", False),
    "gates_near_zero": (16, 16, (4, 2), "near_zero", True),
    "gates_strongly_negative": (16, 16, (4, 2), "strongly_negative", True),
    "five_query_heads_a_kv_head": (8, 8, (10, 2), "seeded", True),
}


@pytest.mark.parametrize("case", sorted(PREFILL_CASES))
def test_the_prefill_kernel_in_interpret_mode_is_retention_chunk_in_place(case):
    """One chunk into (layer 1, slot 2) of a pool of two layers and three
    slots: the valid rows' outputs and the state after them are
    `retention_chunk`'s from the slot's state, or from nothing where none is
    carried (the slot then holds NaN: nothing of it may be read into the
    result), and every other page of the pool is bit for bit what it was."""
    c, n_valid, heads, gates, carried = PREFILL_CASES[case]
    q, k, v, log_g, s, z = chunk_inputs(7, c, heads, gates)
    layer, slot, valid = 1, 2, jnp.arange(c) < n_valid
    assert power_retention.can_tile_prefill(c, *heads, 128) and not power_retention.can_tile_prefill(12, *heads, 128)
    assert not power_retention.can_tile_prefill(16, 4, 2, 16)
    if not carried:
        s, z = s.at[layer, slot].set(jnp.nan), z.at[layer, slot].set(jnp.nan)
    y, s2, z2 = prefill_kernel(q, k, v, log_g, s, z, layer, slot, carried, valid)
    zero = lambda a: a if carried else jnp.zeros_like(a)  # noqa: E731
    want_y, want_s, want_z = tfm.retention_chunk(q, k, v, log_g, zero(s[layer, slot]), zero(z[layer, slot]), valid)
    assert worst(y[:n_valid], want_y[:n_valid]) <= 1e-4 * float(jnp.max(jnp.abs(want_y[:n_valid])))
    np.testing.assert_allclose(s2[layer, slot], want_s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z2[layer, slot], want_z, rtol=1e-5, atol=1e-5)
    for before, after in ((s, s2), (z, z2)):
        np.testing.assert_array_equal(after[0], before[0])
        np.testing.assert_array_equal(after[1, :2], before[1, :2])


def test_two_chunks_through_the_prefill_kernel_leave_the_state_and_give_the_outputs_of_the_whole():
    """40 rows as a chunk of 24 from nothing and a padded chunk of 24 from
    what the first left in the slot: the outputs are `retention_whole`'s over
    the 40 rows, and the slot's state is what the plain chunks leave."""
    n, c = 40, 24
    q, k, v, log_g, s, z = chunk_inputs(8, 2 * c, (4, 2), "near_zero")
    layer, slot = 0, 1
    want = tfm.retention_whole(q[None, :n], k[None, :n], v[None, :n], log_g[None, :n], chunk=c)[0]
    state = (jnp.zeros_like(s[layer, slot]), jnp.zeros_like(z[layer, slot]))
    got = []
    for i in range(2):
        rows, valid = slice(i * c, (i + 1) * c), jnp.arange(i * c, (i + 1) * c) < n
        y, s, z = prefill_kernel(q[rows], k[rows], v[rows], log_g[rows], s, z, layer, slot, i > 0, valid)
        _, *state = tfm.retention_chunk(q[rows], k[rows], v[rows], log_g[rows], *state, valid)
        got.append(y)
    assert worst(jnp.concatenate(got)[:n], want) <= 1e-4 * float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(s[layer, slot], state[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z[layer, slot], state[1], rtol=1e-5, atol=1e-5)


def test_a_prefill_at_the_kernels_head_width_goes_through_it_and_says_so():
    """`forward_prefill` at a head of 128 lanes picks the kernel by the shapes
    (describe() says so), at TINY widths the plain expression; a prompt of
    three chunks, the last padded, into a slot that held NaN ends at the
    logits of the whole-sequence forward, which never meets the kernel."""
    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, retention_degree=2, n_heads=2, n_kv_heads=1, d_head=128, n_layers=2)
    assert tfm.prefill_paths(cfg, PAGE) == {"prefill_attention": "retention_kernel"} and tfm.prefill_paths(seeded(0)[0], PAGE) == {"prefill_attention": "xla_chunk"}
    assert tfm.prefill_paths(tfm.tiny(), 16) == {}
    params = tfm.init_params(jax.random.PRNGKey(2), cfg)
    lm = PagedLM(cfg, params, num_pages=3, page_tokens=PAGE, max_slots=2, max_pages_per_seq=1)
    assert lm.describe()["prefill_attention"] == "retention_kernel" and lm.describe()["decode_attention"] == "retention_kernel"
    n, tokens = 41, jax.random.randint(jax.random.PRNGKey(3), (41,), 1, cfg.vocab_size, jnp.int32)
    kv = jax.tree_util.tree_map(lambda a: a + jnp.nan, lm.kv)
    padded = jnp.zeros((1, PAGE), jnp.int32).at[0, :n].set(tokens)
    logits, kv = jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, jnp.array([2]), n, 0))(params, padded, kv)
    want = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0, n - 1]
    assert worst(logits[0], want) <= TOLERANCE
    assert all(bool(jnp.all(jnp.isnan(kv[name][:, 1]))) and bool(jnp.all(jnp.isfinite(kv[name][:, 2]))) for name in ("s", "z"))


# ------------------------------------------- (f) what the other models keep

# sha256 (first 16 hex digits), taken at the parent commit of PR 48 and PR 49 (28daf63, before the four forwards walked one plan),
# of each configuration of BENCHMARK.json at its architecture's TINY widths: init_params' leaves (paths, dtypes, numbers),
# and the lowered text of forward_decode, of forward_prefill, of forward_decode with `stats` as PagedLM asks it of a routed
# model, of the whole-sequence `forward`, of the training loss's gradient where the configuration has a training cell, and
# for Mistral of the ZeRO train step over four devices (the four-chip cell's program, where PR 48's benchmark run failed).
# The first four cells' first three digests are what PR 42 recorded before the state's path existed. `prefill_long` (PR 62): forward_prefill
# over a bucket of 2 048 positions, eight chunks of 256 rows where `prefill`'s 64 positions are one chunk whatever a chunk's size, taken at the parent of PR 62 (d329e78).
PARENT = {
    "mistral7b-train-seq4k-1chip": dict(
        prefill_long="81d58ff5d6cb5866", params="00146abe9d7f8cbe", decode="5b205ddfdad764ec", prefill="acca1b330f25d98a", forward="cab927e22fe03603", grad="90e3cc1409de622b",
        zero_step="dc207b56dddcc81b"),
    "dsllm7b-serve-chat-steady": dict(
        prefill_long="ad8d462a1b39b2b5", params="00146abe9d7f8cbe", decode="c7f2192c2a02f3b2", prefill="114685abbe26da57", forward="1e18187b873812c1"),
    "olmoe-train-seq4k-1chip": dict(
        prefill_long="08233f552c9add63", params="9fc2e6536f721f59", decode="125d9f6811579117", prefill="cd4027dbb914c125", decode_stats="dd2fac39bb166a76",
        forward="e26fcc5eff7c9a50", grad="1b95257862785e87"),
    "trinitymini-serve-agent-turns": dict(
        prefill_long="a3c3d5e02da7ad31", params="dd98a4937de87222", decode="cd393207dca6cc51", prefill="ec3e421d2af401fa", decode_stats="46c3d2dbe563e6ee",
        forward="bb34c9f1b88a8e6a"),
    "brumby14b-serve-longgen-batch": dict(
        prefill_long="23473e22899dcdc0", params="0dbcfc63962591b0", decode="75b94718751bcdc8", prefill="3153248bbbc0868d", forward="304722bdd829cd50"),
    "solaropen2-serve-reasoning-batch": dict(
        prefill_long="883b5337800e6080", params="fa24a5892e238707", decode="4940be13c58a600f", prefill="1e82441d7698dd38", decode_stats="19e20912f9c8350d",
        forward="2c99e380bd5aba4c"),
}

# The digests that a later PR moved on purpose, (cell, program) -> (the digest since, why), PARENT keeping what they were. For each of the refactor's (PR 48's, landed by PR 49),
# the chip's optimised program was compared at published widths (v5e, compiled here; CHANGES.md, PR 49): but for the Mosaic
# kernels' serialised bodies (transformer.py's line numbers) the same instructions in the same order, the same bytes.
MOVED = {
    ("trinitymini-serve-agent-turns", "decode"): ("41882a5b82c625bb", "the experts' index (layer - first) is taken before the layer's rope switch, as prefill took it: two scalar ops change places"),
    ("trinitymini-serve-agent-turns", "decode_stats"): ("0f9d81c8ca7b3ff3", "as decode"),
    ("solaropen2-serve-reasoning-batch", "decode_stats"): ("d44acfaf0881fb72", "the step's two counters are summed a member of a segment, as every other routed model's, not over one concatenation"),
    # PR 55, the whole-sequence attention of the configurations whose `forward` is flash (attn_impl "full"); every decode, prefill,
    # decode_stats and params digest holds, and so do Trinity's (naive, windows), Brumby's and Solar-Open2's forwards.
    ("dsllm7b-serve-chat-steady", "forward"): ("06d1b3f2a254b9fb", "PR 55: the causal flash kernels (ops/flash_attention.py) walk a diagonal block in sub-tiles, mask only there, name the block in VMEM on a skipped step and keep row statistics, lse and delta a row's value in every lane"),
    ("mistral7b-train-seq4k-1chip", "forward"): ("cede48bbb077cf14", "as above"),
    ("olmoe-train-seq4k-1chip", "forward"): ("244f65fe8fddd677", "as above"),
    # PR 60, by design and the three training programs alone (every params, decode, prefill, decode_stats and forward digest of every cell holds):
    # `next_token_loss` no longer differentiates through `forward()`'s float32 [batch, seq, vocab] logits; head and loss are `transformer.head_loss`,
    # a custom_vjp that forms dlogits once in the parameters' dtype and whose two backward products take that dtype on both sides.
    ("mistral7b-train-seq4k-1chip", "grad"): ("d7e149197e7d0038", "PR 60: head and loss as one differentiated function; before it 44d8f3d8e3cadcac (PR 55: the causal flash kernels and their two backward kernels)"),
    ("mistral7b-train-seq4k-1chip", "zero_step"): ("6e5af522eb057c88", "PR 60, as grad: the four-chip cell's program calls the same loss inside its shard_map; before it e52ebc15bf0b5625 (PR 57: a chip's ZeRO shard is a "
                                                   "slice along the leaf's last inner dimension divisible by n, the gradient reduce-scattered in its own shape, the UPDATES gathered in the leaf's shape), "
                                                   "before that 5552f02e7829f78d (PR 55), PARENT's the one before that"),
    ("olmoe-train-seq4k-1chip", "grad"): ("db0b6aae5e506878", "PR 60, as Mistral's grad; before it 6adc8fe8eff1fe69 (PR 55)"),
}


def _digest(text: bytes) -> str:
    return hashlib.sha256(text).hexdigest()[:16]


def _zero_step_on_four_devices(cfg):
    """The four-chip training cell's program (benchmarks/lib/worker_train.py: `build_train_step(..., zero_axis="data")`
    over data=4), lowered on four of conftest's CPU devices: the step with its optimizer state sharded as
    `zero.init_opt_state` shards it and the batch split over the axis."""
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    tx = optax.adamw(1e-3)
    _init, step = tfm.build_train_step(cfg, tx, mesh, zero_axis="data", donate=False)
    abstract = lambda tree: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), tree)  # noqa: E731
    params = jax.device_put(tfm.init_params(jax.random.PRNGKey(0), cfg), NamedSharding(mesh, PartitionSpec()))
    opt_state = zero.init_opt_state(tx, params, mesh, "data")
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32, sharding=NamedSharding(mesh, PartitionSpec("data")))
    return step.lower(abstract(params), abstract(opt_state), tokens)


@pytest.mark.parametrize("cell_name", sorted(PARENT))
def test_the_accepted_architectures_draw_the_weights_and_lower_to_the_text_they_did(cell_name):
    """A refactor of the forwards leaves every configuration's programs what
    they were: `init_params` draws the same leaves, and the paged executables,
    the whole-sequence forward and the training gradient lower to the same
    text, letter for letter, as at the commit the digests were taken at. Each
    cache with the operands it needs: a retention model's block table is one
    page a sequence, a KDA stack's pool takes its state slots and its prefill
    the sequence's slot."""
    cell = rehearsal.shrink(spec.find_cell(cell_name))
    cfg = cell.arch.model_config(cell.config)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tfm.init_params(jax.random.PRNGKey(5), cfg))[0]:
        h.update(jax.tree_util.keystr(path).encode() + str(leaf.dtype).encode() + np.asarray(leaf.astype(jnp.float32)).tobytes())
    T, N, B, S = 16, 8, 4, 64
    P = 1 if cfg.retention_degree else 4
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = jax.eval_shape(lambda: tfm.init_kv_pages(cfg, N, T, B + 1))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    slot = (i32(),) if cfg.kda_per_period else ()

    def decode(stats):
        return jax.jit(lambda p, t, pos, kv, bt: tfm.forward_decode(p, t, pos, cfg, kv, bt, stats=stats)).lower(params, i32(B), i32(B), kv, i32(B, P))

    lowered = {
        "decode": lambda: decode(False),
        "prefill": lambda: jax.jit(lambda p, t, kv, bt, n, w, *s: tfm.forward_prefill(p, t, cfg, kv, bt, n, w, *s)).lower(
            params, i32(1, S), kv, i32(P), i32(), i32(), *slot),
        "prefill_long": lambda: jax.jit(lambda p, t, kv, bt, n, w, *s: tfm.forward_prefill(p, t, cfg, kv, bt, n, w, *s)).lower(
            params, i32(1, 2048), kv, i32(1 if cfg.retention_degree else 128), i32(), i32(), *slot),
        "decode_stats": lambda: decode(True),
        "forward": lambda: jax.jit(lambda p, t: tfm.forward(p, t, cfg)).lower(params, i32(2, 32)),
        "grad": lambda: jax.jit(jax.grad(lambda p, t: tfm.next_token_loss(p, t, cfg))).lower(params, i32(2, 32)),
        "zero_step": lambda: _zero_step_on_four_devices(cfg),
    }
    want = {**PARENT[cell_name], **{program: since for (name, program), (since, _why) in MOVED.items() if name == cell_name}}
    assert ("decode_stats" in want) == bool(cfg.n_experts) and ("grad" in want) == (cell.traffic["runner"] == "train_steps")
    got = {"params": h.hexdigest()[:16], **{name: _digest(lowered[name]().as_text().encode()) for name in want if name != "params"}}
    assert got == want


# What each configuration of BENCHMARK.json caches, as the benchmark's readers know it: describe()["cache"]["kind"], whether a
# full page may serve another prompt, the decode executable's name, and the pool's leaves in order with their indexing.
CACHES = {
    "mistral7b-train-seq4k-1chip": ("kv_pages", True, "llm_decode", {"k": "page", "v": "page"}),
    "dsllm7b-serve-chat-steady": ("kv_pages", True, "llm_decode", {"k": "page", "v": "page"}),
    "olmoe-train-seq4k-1chip": ("kv_pages", True, "llm_decode", {"k": "page", "v": "page"}),
    "trinitymini-serve-agent-turns": ("kv_pages", True, "llm_decode", {"k": "page", "v": "page"}),
    "brumby14b-serve-longgen-batch": ("state", False, "llm_decode_state", {"s": "page", "z": "page"}),
    "solaropen2-serve-reasoning-batch": ("state+kv_pages", False, "llm_decode_hybrid", {"k": "page", "v": "page", "s": "slot", "tail": "slot"}),
    # PR 61: the global layers' K/V pages beside the window layers' rings, one slot a sequence as a state's
    "mimov25-serve-longctx-batch": ("state+kv_pages", False, "llm_decode_hybrid", {"k": "page", "v": "page", "ring_k": "slot", "ring_v": "slot"}),
}


@pytest.mark.parametrize("cell_name", sorted(CACHES))
def test_one_layout_says_what_a_model_caches_and_paged_lm_reads_it(cell_name):
    """`cache_layout(cfg)` is the pool: its leaves are `init_kv_pages`' keys
    in order, each indexed by page or by slot as its second axis says; and
    what PagedLM tells the engine and the benchmark of the cache (the kind, the
    prefix sharing, the executables' names, describe()'s keys) is what it
    told them before it read the layout."""
    kind, shares, decode_name, indexed = CACHES[cell_name]
    cell = rehearsal.shrink(spec.find_cell(cell_name))
    cfg = cell.arch.model_config(cell.config)
    layout = tfm.cache_layout(cfg)
    pages, slots = 6, 3
    pool = tfm.init_kv_pages(cfg, pages, 16, slots)  # not through eval_shape: a dict comes back from it with its keys sorted
    assert list(pool) == list(layout.names) == list(indexed) and layout.indexed == indexed
    assert {name: leaf.shape[1] for name, leaf in pool.items()} == {name: {"page": pages, "slot": slots}[by] for name, by in indexed.items()}
    assert sum(layers for _, layers in layout.kinds) == cfg.n_layers and (layout.state, layout.kv) == (not shares, "k" in indexed)
    lm = PagedLM(cfg, num_pages=pages, page_tokens=16, max_slots=slots - 1, max_pages_per_seq=4 if layout.kv else 1)
    described = lm.describe()
    assert described["cache"]["kind"] == kind and lm.shares_prefix_pages is shares
    assert set(described["cache"]) == {"kind", "page_bytes"} | ({"state_bytes"} if "slot" in indexed.values() else set())
    assert set(described) == {"pid", "platform", "device_kind", "device_count", "cache", "decode_attention", "peak_bytes_in_use", "compile"} | (
        {"decode_window" if "ring_k" in indexed else "decode_state"} if "slot" in indexed.values() else set()) | ({"prefill_attention"} if kind == "state" else set())
    assert lm._get_decode().__name__ == decode_name and lm._get_prefill(2 if layout.kv else 1).__name__ == decode_name.replace("decode", "prefill") + ("_p2" if layout.kv else "_p1")
    assert lm.page_bytes == sum(math.prod(pool[name].shape) * pool[name].dtype.itemsize for name, by in indexed.items() if by == "page") // pages

"""MiMo-V2 (`model_type: mimo_v2`) on the normal path, at
`archs/mimo_v2.TINY` widths on the CPU (a dense global layer, then two periods
of two window layers and a global layer; window 16; 8 query heads over 2 K/V
heads global and 4 window; keys 24 wide, values 16; 8 of 16 experts held),
float32, seeded random weights: the stack's plan and the cache's layout, the
whole-sequence forward and the paged path through BOTH caches (K/V pages of
the global layers, rings of the window layers) against the plain reference of
`benchmarks/archs/mimo_v2.py`, across the ring's wrap and chunk borders, the
shares of a routed layer, the wrong models, the engine's slots, and the
refusals that are left.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.archs import mimo_v2
from benchmarks.lib import correct
from benchmarks.tools import wrong_mimo_v2
from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM, PromptTokens

TOLERANCE = 2e-4
CONFIG = mimo_v2.TINY
W = CONFIG["sliding_window"]  # 16: the ring
T = 8  # positions a K/V page


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def small_chunks(monkeypatch, chunk):
    """PREFILL_CHUNK_TOKENS in a test: a chunk is then `chunk // T` pages."""
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", chunk)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", chunk)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's


@functools.lru_cache(maxsize=None)
def seeded(seed):
    cfg = mimo_v2.model_config(CONFIG, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(seed))


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def reference(arch, params, tokens, positions, config=CONFIG):
    return correct.reference_logits(arch, params, tokens, positions, config)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ------------------------------------------------- (a) the plan and the layout


def test_the_stack_is_a_dense_global_layer_then_periods_of_window_layers_that_end_on_a_global_one():
    cfg, params = seeded(0)
    plan = tfm.stack_plan(cfg)
    assert plan == (
        (1, (tfm.StackMember("softmax", "dense_blocks", 1, 0),)),
        (2, (tfm.StackMember("window", "window_blocks", 2, 0), tfm.StackMember("softmax", "blocks", 1, 1))),
    )
    # the places in their kinds' cache leaves, in published order: window layers 0..3, global layers 0 (dense), 1, 2
    assert [plan[1][1][0].place(r, j) for r in range(2) for j in range(2)] == [0, 1, 2, 3]
    assert [plan[1][1][1].place(r) for r in range(2)] == [1, 2]
    layout = tfm.cache_layout(cfg)
    assert layout.kinds == (("softmax", 3), ("window", 4)) and layout.names == ("k", "v", "ring_k", "ring_v")
    assert layout.indexed == {"k": "page", "v": "page", "ring_k": "slot", "ring_v": "slot"} and layout.state and layout.kv and layout.paged == "k"
    assert set(params) == {"embed", "dense_blocks", "blocks", "window_blocks", "final_norm", "lm_head"}
    dense, glob, win = (params[name]["attn"] for name in ("dense_blocks", "blocks", "window_blocks"))
    # q 8 x 24; k 2 x 24 global and 4 x 24 window; v 2 x 16 and 4 x 16; o from 8 x 16; a sink logit a query head, float32
    assert dense["wq"].shape == (1, 64, 192) and dense["wk"].shape == (1, 64, 48) and dense["wv"].shape == (1, 64, 32) and dense["wo"].shape == (1, 128, 64)
    assert glob["wk"].shape == (2, 64, 48) and "sink" not in glob and "sink" not in dense
    assert win["wk"].shape == (2, 2, 64, 96) and win["wv"].shape == (2, 2, 64, 64) and win["sink"].shape == (2, 2, 8) and win["sink"].dtype == jnp.float32
    assert 0.5 < float(jnp.std(win["sink"])) < 1.5  # drawn, not zeros: zeros would hide the term from every check
    assert "router" not in params["dense_blocks"]["mlp"] and params["window_blocks"]["mlp"]["w_gate"].shape == (2, 2, 8, 64, 32)
    pool = tfm.init_kv_pages(cfg, 24, T, 4)
    assert pool["k"].shape == (3, 24, T, 2 * 24) and pool["v"].shape == (3, 24, T, 2 * 16)
    assert pool["ring_k"].shape == (4, 4, W, 4 * 24) and pool["ring_v"].shape == (4, 4, W, 4 * 16)


def test_the_plan_of_the_published_cut_and_its_counts():
    """The issue's arithmetic as `init_params` counts it: 3 429.9 M
    parameters; a position costs 2 x 2 560 B in the global layers' pages as
    needed (3 072 as stored: a 192-wide K head lies in 256 lanes) and a ring
    128 x 5 120 B a window layer; ~9.4 GB a decode step at 64 x 7.5 k."""
    from benchmarks.lib import spec

    config = spec.find_cell("mimov25-serve-longctx-batch").config
    cfg = mimo_v2.model_config(config)
    assert tfm.stack_plan(cfg) == (
        (1, (tfm.StackMember("softmax", "dense_blocks", 1, 0),)),
        (1, (tfm.StackMember("window", "window_blocks", 5, 0), tfm.StackMember("softmax", "blocks", 1, 1))),
    )
    assert tfm.cache_layout(cfg).kinds == (("softmax", 2), ("window", 5)) and cfg.windows == (0, 128, 128, 128, 128, 128, 0)
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.window_rope_theta, cfg.value_scale, cfg.window_kv_heads, cfg.n_kv_heads) == (64, 1e7, 1e4, 0.707, 8, 4)
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert abs(tfm.param_count(shapes) / 1e6 - 3429.96) < 0.01
    assert abs(mimo_v2.matmul_params(config) + 19072 * 4096 - tfm.param_count(shapes)) < 1e5  # norms, sinks, the selecting bias
    pool = jax.eval_shape(lambda: tfm.init_kv_pages(cfg, 8705, 128))
    assert pool["k"].shape == (2, 8705, 128, 4 * 256) and pool["v"].shape == (2, 8705, 128, 4 * 128)
    assert pool["ring_k"].shape == (5, 65, 128, 8 * 192) and pool["ring_v"].shape == (5, 65, 128, 8 * 128)
    assert tfm.kv_page_widths(cfg) == (256, 128) and tfm.decode_paths(cfg, 128) == {"decode_attention": "paged_kernel", "decode_window": "xla_ring"}
    assert mimo_v2.decode_kv_bytes(config, 1) == 2 * 2560 and mimo_v2.decode_state_bytes(config, 1) == 5 * 128 * 5120
    # a window layer counts at no more than 128 positions a row, however long the rows are
    long, short = mimo_v2.decode_step_min_bytes(config, 64, 64 * 7500), mimo_v2.decode_step_min_bytes(config, 64, 64 * 100)
    assert abs(long / 1e9 - 9.37) < 0.01
    assert long - mimo_v2.decode_step_min_bytes(config, 64, 64 * 7400) == 64 * 100 * 2 * 2560
    assert short == mimo_v2.decode_step_min_bytes(config, 0, 0) + 64 * 100 * (2 * 2560 + 5 * 5120)
    # the three shares of readers/counter_cache_bytes_share.py add up to a step's bytes
    weights = mimo_v2.decode_step_min_bytes(config, 0, 0)
    assert weights + mimo_v2.decode_state_bytes(config, 64) + mimo_v2.decode_kv_bytes(config, 64 * 7500) == long
    flops, nbytes = mimo_v2.wide_key_decode_work(config, live=64, kv_tokens=64 * 7500)
    assert nbytes == 64 * 7500 * 5120 and flops == 2 * 64 * 7500 * 2 * 64 * 320


def test_what_init_params_still_refuses_says_what_is_left():
    cfg, _ = seeded(0)
    key = jax.random.PRNGKey(0)
    for windows in ((16, 16, 0, 16, 16, 0, 0), (0, 16, 16, 0, 16, 16, 16), (0, 16, 16, 0, 16, 8, 0), (0, 16, 0, 16, 16, 0, 0)):
        with pytest.raises(ValueError, match="global first"):
            tfm.init_params(key, cfg.replace(windows=windows))
    with pytest.raises(ValueError, match="global first"):
        tfm.init_params(key, cfg.replace(n_dense_layers=2))
    with pytest.raises(ValueError, match="q/k-norm, gate"):
        tfm.init_params(key, cfg.replace(attn_gate=True))
    with pytest.raises(ValueError, match="window rings"):
        tfm.init_params(key, tfm.tiny(value_scale=0.5))
    with pytest.raises(ValueError, match="window rings"):
        tfm.init_params(key, tfm.tiny(v_head_dim=8))
    with pytest.raises(ValueError, match="naive"):
        tfm.forward(seeded(0)[1], tokens_of(0, 16)[None], cfg.replace(attn_impl="full"))
    with pytest.raises(ValueError, match="mimo_v2 does not compute"):
        mimo_v2.dims(dict(CONFIG, add_full_attention_sink_bias=True))
    with pytest.raises(ValueError, match="attention_chunk_size == sliding_window"):
        mimo_v2.dims(dict(CONFIG, attention_chunk_size=32))
    with pytest.raises(ValueError, match="whole periods"):
        mimo_v2.dims(dict(CONFIG, hybrid_layer_pattern=[0, 1, 1, 0, 1, 0, 0]))


# ------------------------------------------- (b) the whole-sequence forward


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed):
    cfg, params = seeded(seed)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.window_kv_heads, cfg.head_dim, cfg.value_dim, cfg.rotary_dim) == (16, 8, 8, 4, 24, 16, 8)
    tokens = tokens_of(seed, 70)
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0]
    assert worst(got, reference(mimo_v2, params, tokens, np.arange(70))) <= TOLERANCE


# ------------------------------------------------------ (c) both caches


def ring_lm(cfg, params, slots=3, pages=40):
    return PagedLM(cfg, params, num_pages=pages, page_tokens=T, max_slots=slots, max_pages_per_seq=16)


# (prompt length, chunk tokens): a prompt that ends inside the ring's first lap, exactly at the window, and after the
# ring has wrapped several times; chunk borders inside a window (8-token chunks: two to a window) and outside (32: two windows)
LAPS = [(11, 8), (16, 8), (16, 32), (17, 8), (53, 8), (53, 16), (53, 32), (100, 8), (100, 32), (64, 64)]


@pytest.mark.parametrize("n,chunk", LAPS)
def test_prefill_in_chunks_then_decode_through_both_caches_matches_the_reference(monkeypatch, n, chunk):
    """A prompt prefilled in chunks into K/V pages 3.. and ring slot 2 of a
    pool that holds another sequence's leftovers, then twenty tokens (a lap of
    the ring and more) teacher-forced through decode steps as row 1 (so ring
    slot 2) beside two inactive rows: every logit the reference's full
    forward's."""
    small_chunks(monkeypatch, chunk)
    cfg, params = seeded(n % 3)
    total = n + 20
    tokens = tokens_of(n + chunk, total)
    want = reference(mimo_v2, params, tokens, np.arange(total))
    lm = ring_lm(cfg, params)
    assert set(lm.kv) == {"k", "v", "ring_k", "ring_v"}
    # What a slot's or a page's last owner left must not leak: numbers in both (a masked key's row is multiplied by a weight of exactly 0).
    pool = {name: leaf + 100.0 for name, leaf in lm.kv.items()}
    table = jnp.arange(3, 19)
    padded = jnp.zeros((1, 128), jnp.int32).at[0, :n].set(tokens[:n])
    logits, pool = jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, table, n, 0, 2))(params, padded, pool)
    assert worst(logits[0], want[n - 1]) <= TOLERANCE
    # the ring holds the prompt's last 16 positions, position p in row p % 16, and nothing of the padding
    tables = jnp.zeros((3, 16), jnp.int32).at[1].set(table)
    decode = jax.jit(lambda p, t, pos, kv: tfm.forward_decode(p, t, pos, cfg, kv, tables))
    for i in range(n, total):
        logits, pool = decode(params, jnp.array([0, tokens[i], 0]), jnp.array([-1, i, -1]), pool)
        assert worst(logits[1], want[i]) <= TOLERANCE, i
    # ring slots 1 and 3 were nobody's: untouched; the trash slot took the inactive rows' writes
    for name in ("ring_k", "ring_v"):
        assert bool(jnp.all(pool[name][:, 1] == 100.0)) and bool(jnp.all(pool[name][:, 3] == 100.0))


def test_a_rings_slot_is_cleared_where_a_new_prompt_takes_it(monkeypatch):
    """A long prompt and its decode steps fill ring slot 1 (several laps);
    then a SHORT prompt (inside the ring's first lap) takes the same slot and
    the same pages: it and its decode steps read nothing of what the slot
    held, the first owner's rows that lie past the new prompt's positions
    included."""
    small_chunks(monkeypatch, 8)
    cfg, params = seeded(1)
    lm = ring_lm(cfg, params, slots=1, pages=20)
    first, second = [int(t) for t in tokens_of(70, 60)], tokens_of(71, 20)
    prompt = PromptTokens(first)
    prompt.slot = 0
    lm.prefill(prompt, list(range(1, 9)), 0)
    lm.decode([first[-1]], [60], [list(range(1, 9))])
    assert bool(jnp.all(lm.kv["ring_k"][:, 1] != 0))  # every row of the ring written
    want = reference(mimo_v2, params, second, np.arange(20))
    prompt = PromptTokens([int(t) for t in second[:9]])
    prompt.slot = 0
    assert int(lm.prefill(prompt, [1, 2], 0)) == int(jnp.argmax(want[8]))
    for i in range(9, 20):
        assert lm.decode([int(second[i])], [i], [[1, 2, 3]])[0] == int(jnp.argmax(want[i])), i


def test_paged_lm_says_both_paths_and_counts_both_caches(monkeypatch):
    small_chunks(monkeypatch, 16)
    cfg, params = seeded(3)
    tokens = [int(t) for t in tokens_of(30, 37)]
    want = reference(mimo_v2, params, jnp.asarray(tokens), np.array([29, 30]))
    lm = ring_lm(cfg, params)
    prompt = PromptTokens(tokens[:30])
    prompt.slot = 1  # the engine's admission: decode row 1, so ring slot 2
    first = lm.prefill(prompt, [1, 2, 3, 4], 0)
    assert int(first) == int(jnp.argmax(want[0]))
    assert set(first.counters) == {"prefill_chunks", "prefill_state", "prefill_experts"}
    assert first.counters["prefill_state"] == {"chunks": 2, "carried_in": 1}
    assert first.counters["prefill_experts"]["rows"] == 2 * 16 * 6  # two chunks through the six routed layers
    assert np.any(np.asarray(lm.kv["ring_k"])[:, 2] != 0) and not np.any(np.asarray(lm.kv["ring_k"])[:, 1] != 0)
    out = lm.decode([0, tokens[30]], [-1, 30], [[], [1, 2, 3, 4]])
    assert out[1] == int(jnp.argmax(want[1]))
    assert isinstance(out, DecodeTokens) and set(out.counters) == {"decode_experts", "decode_window", "decode_state", "decode_kv"}
    # a ring is read whole and takes one row: one pass, where a recurrent state is read and written
    assert out.counters["decode_state"] == {"bytes": lm.state_bytes, "live_slots": 1, "steps": 1}
    assert out.counters["decode_kv"] == {"bytes": 31 * lm.page_bytes // T, "tokens": 31, "steps": 1}
    # of 31 live positions a window layer reads 16: (3 x 31 + 4 x 16) of 7 x 31
    assert out.counters["decode_window"] == {"kv_read": 3 * 31 + 4 * 16, "kv_live": 7 * 31}
    said = lm.describe()
    assert said["cache"] == {"kind": "state+kv_pages", "state_bytes": lm.state_bytes, "page_bytes": lm.page_bytes}
    assert said["decode_window"] == "xla_ring" and said["decode_attention"] == "xla_gather" and "decode_state" not in said and lm.shares_prefix_pages is False
    assert lm.page_bytes == 3 * T * 2 * (24 + 16) * 4 and lm.state_bytes == 4 * W * 4 * (24 + 16) * 4
    assert lm._get_decode().__name__ == "llm_decode_hybrid"
    with pytest.raises(ValueError, match="cached_tokens"):
        lm.prefill(tokens[:20], [1, 2, 3], 8)


# ------------------------------------------ (d) a routed layer's shares: tests/test_moe_ffn.py, parametrised with this config


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
        out.append((tokens, served, served_margins(mimo_v2, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_mimo_v2.WRONG))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 70: against the
    right reference every margin is 0 to rounding; against each wrong model's
    (one line of the reference altered, and the fp8-precision control) the
    90th percentile, which a routed cell's limit names, is far over it."""
    _cfg, params = seeded(4)
    wrong = wrong_mimo_v2.load("mimo_v2", name)
    right = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()]))
    margins = np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()])
    wrong = correct.error_quantiles(np.where(np.isfinite(margins), margins, np.inf))
    assert right["q100"] <= 2e-3
    assert wrong["q90"] > 0.02 and wrong["q90"] > 10 * max(right["q100"], 2e-3), (right, wrong)


# ----------------------------------------------------------- (f) the engine


def greedy(cfg, params, prompt, n):
    """An engine-free greedy loop: the whole-sequence forward at one padded length."""
    fwd = jax.jit(lambda p, t: tfm.forward(p, t, cfg))
    tokens = np.zeros((1, len(prompt) + n), np.int32)
    tokens[0, : len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        tokens[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(tokens))[0, i - 1]))
    return tokens[0, len(prompt):].tolist()


def test_twice_as_many_requests_as_slots_queue_and_each_is_served_the_tokens_it_is_served_alone(monkeypatch):
    """Four prompts over two slots (so two wait, and each takes the row, the
    ring slot and some pages another left), of different lengths and answer
    lengths, at once: each gets the tokens of an engine-free greedy loop.
    Nothing enters the prefix index, and the counters of both caches add up."""
    small_chunks(monkeypatch, 16)
    cfg, params = seeded(5)
    prompts = [[int(t) for t in tokens_of(50 + i, n)] for i, n in enumerate((45, 18, 33, 45))]
    prompts[3] = prompts[0]  # the same prompt again: no hit
    answers = (12, 19, 7, 12)
    want = [greedy(cfg, params, p, n) for p, n in zip(prompts, answers)]
    lm = ring_lm(cfg, params, slots=2, pages=33)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=33, prefill_token_budget=64), name="t-mimo")
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
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert clocks["prefill_state"] == {"chunks": chunks, "carried_in": chunks - 4}
    state, kv = clocks["decode_state"], clocks["decode_kv"]
    assert state["steps"] == kv["steps"] == clocks["decode"]["n"]
    assert state["live_slots"] == sum(answers) - 4 and state["bytes"] == state["live_slots"] * lm.state_bytes
    assert kv["tokens"] == sum(sum(range(len(p) + 1, len(p) + n)) for p, n in zip(prompts, answers))

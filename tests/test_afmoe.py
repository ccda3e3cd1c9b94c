"""Trinity-Mini (`model_type: afmoe`) on the normal path, at `archs/afmoe.TINY`
widths on the CPU, float32, seeded random weights with every norm's scale
drawn and a non-zero selection bias (`init_params` draws it): a sigmoid-routed
top-k FFN with a shared expert, two leading dense layers before the routed
ones, window layers (16 positions) among full ones, an attention gate,
per-head q/k-norm, four norms a layer and a scaled embedding, against the
plain reference of `benchmarks/archs/afmoe.py`.

TOLERANCE is tests/test_parity.py's: both sides compute in float32, the
reference at matmul precision "highest". Read over these cases (PR 37, CPU):
the largest difference 6.0e-6 on logits up to 5 in size; the bfloat16 control
reads 3e-2 and more.
"""

import copy
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.archs import afmoe
from benchmarks.lib import correct
from benchmarks.tools import wrong_models
from ray_tpu.models import transformer as tfm
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM

TOLERANCE = 1e-4
T = 8  # page tokens
WINDOW = afmoe.TINY["sliding_window"]
CONFIG = dict(
    afmoe.TINY, rope_theta=10000, rms_norm_eps=1e-5, route_scale=2.826, route_norm=True, num_shared_experts=1,
    mup_enabled=True, score_func="sigmoid", global_attn_every_n_layers=4, torch_dtype="float32",
)


@functools.lru_cache(maxsize=None)
def seeded(seed, dtype=jnp.float32):
    cfg = afmoe.model_config(CONFIG, dtype=dtype, remat=False)
    key = jax.random.PRNGKey(seed)
    return cfg, correct.init_weights(tfm, cfg, key)


@functools.lru_cache(maxsize=None)
def jitted_forward(cfg):
    """`tfm.forward` compiled once a config and length: eager, every call
    compiles its layer scans again."""
    return jax.jit(lambda params, tokens: tfm.forward(params, tokens, cfg))


def forward(cfg, params, tokens):
    return jitted_forward(cfg)(params, tokens)


def reference(arch, params, tokens, positions):
    """The architecture file's float32 logits, jitted as a run of the cell jits them."""
    return correct.reference_logits(arch, params, tokens, positions, CONFIG)


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def assert_same_experts(cfg, params, tokens):
    got = tfm.routing_stats(params, tokens[None], cfg)["experts"]
    np.testing.assert_array_equal(np.sort(np.asarray(got), -1), np.sort(np.asarray(afmoe.routed_experts(params, tokens, CONFIG)), -1))


# ------------------------------------------------------------- (a) forward


@pytest.mark.parametrize("length", [3 * WINDOW, 5 * WINDOW - 3])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed, length):
    cfg, params = seeded(seed)
    tokens = tokens_of(seed, length)
    assert_same_experts(cfg, params, tokens)
    want = reference(afmoe, params, tokens, jnp.arange(length))
    assert worst(forward(cfg, params, tokens[None])[0], want) <= TOLERANCE
    cfg16, params16 = seeded(seed, jnp.bfloat16)  # the control: the nearest precision below must fail
    assert worst(forward(cfg16, params16, tokens[None])[0], want) > 10 * TOLERANCE


def test_the_selection_bias_changes_a_fair_share_of_the_choices_and_never_weighs():
    cfg, params = seeded(0)
    tokens = tokens_of(0, 64)
    bias = params["blocks"]["mlp"]["router_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.min(jnp.abs(bias))) > 0
    unbiased = copy.deepcopy(params)
    unbiased["blocks"]["mlp"]["router_bias"] = jnp.zeros_like(bias)
    a, b = (np.sort(np.asarray(tfm.routing_stats(p, tokens[None], cfg)["experts"]), -1) for p in (params, unbiased))
    assert 0.1 < (a != b).mean() < 0.6
    # a bias alike for every expert selects the same and must weigh nothing
    shifted = copy.deepcopy(params)
    shifted["blocks"]["mlp"]["router_bias"] = bias + 3.0
    assert worst(forward(cfg, shifted, tokens[None]), forward(cfg, params, tokens[None])) == 0.0


def test_a_window_layer_on_the_flash_path_is_refused_loudly():
    cfg, params = seeded(0)
    with pytest.raises(ValueError, match="attention window"):
        tfm.forward(params, tokens_of(0, 32)[None], cfg.replace(attn_impl="full"))
    with pytest.raises(ValueError, match="windows has 2 entries"):
        tfm.init_params(jax.random.PRNGKey(0), cfg.replace(windows=(4, 0)))


# ------------------------------------------- (b) prefill and decode, 64 slots

SLOTS, PAGES_PER_SEQ = 64, 16  # 128 positions a sequence
# Six live slots of 64 with ragged prompts; the others stay inactive. prompt length -> (slot, cached tokens of a second prefill)
LIVE = {37: (0, 0), 21: (5, 8), 51: (17, 24), 64: (30, 40), 9: (41, 8), 44: (63, 32)}
STEPS = 2 * WINDOW + 3


def test_prefill_hit_or_miss_then_decode_past_the_window_in_a_64_slot_batch(monkeypatch):
    """Each live slot: a cold prefill of its prompt into its own pages, the
    reference's logits at the last position; then the same prompt prefilled
    again over those pages with `write_from` > 0, the cached part ending
    before (8 of 21), inside (24 of 37-51: the window reaches back to 21-35)
    and beyond (40 of 64, 32 of 44) the last position's window, in 16-token
    chunks that start at the cached part's end (8, 24 and 40 are no multiples
    of 16; 64's second chunk and 9's only one run past their buckets); then 35 teacher-forced decode steps of the whole 64-slot batch,
    over two windows' worth and several page edges, every live row's logits
    against the reference's full forward."""
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", 2 * T)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", 2 * T)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    cfg, params = seeded(3)
    kv = tfm.init_kv_pages(cfg, 1 + len(LIVE) * PAGES_PER_SEQ, T)
    prefill = jax.jit(lambda tokens, kv, table, length, write_from: tfm.forward_prefill(params, tokens, cfg, kv, table, length, write_from))
    decode = jax.jit(lambda tokens, positions, kv, tables: tfm.forward_decode(params, tokens, positions, cfg, kv, tables, stats=True))
    seqs, tables = {}, np.zeros((SLOTS, PAGES_PER_SEQ), np.int32)
    for i, (length, (slot, cached)) in enumerate(LIVE.items()):
        tokens = tokens_of(10 + i, max(LIVE) + STEPS)  # one shape for every reference call; a causal model ignores what follows
        seqs[slot] = (length, tokens, reference(afmoe, params, tokens, jnp.arange(length - 1, length + STEPS)))
        tables[slot] = 1 + i * PAGES_PER_SEQ + np.arange(PAGES_PER_SEQ)
        bucket = 1 << max(0, (-(-length // T) - 1).bit_length())
        padded = jnp.zeros((1, bucket * T), jnp.int32).at[0, :length].set(tokens[:length])
        for write_from in (0, cached):
            logits, kv = prefill(padded, kv, jnp.asarray(tables[slot, :bucket]), jnp.int32(length), jnp.int32(write_from))
            assert worst(logits[0], seqs[slot][2][0]) <= TOLERANCE, (length, write_from)
    for step in range(STEPS):
        toks, pos = np.zeros((SLOTS,), np.int32), np.full((SLOTS,), -1, np.int32)
        for slot, (length, tokens, _want) in seqs.items():
            toks[slot], pos[slot] = tokens[length + step], length + step
        logits, kv, stats = decode(jnp.asarray(toks), jnp.asarray(pos), kv, jnp.asarray(tables))
        for slot, (length, _tokens, want) in seqs.items():
            assert worst(logits[slot], want[1 + step]) <= TOLERANCE, (slot, step)
        # 64 rows x 2 picks over 8 experts of each of 4 routed layers: counted from what the routed FFN grouped
        assert 4 <= int(stats["experts_touched"]) <= 4 * 8


@pytest.mark.parametrize("case", [(60, 40), (51, 24), (56, 56)], ids=[
    "last_chunk_past_the_bucket", "two_chunks_inside_the_bucket", "the_cache_holds_the_whole_prompt"])
def test_a_hits_chunks_start_where_the_cache_ends_and_give_the_misss_logits(monkeypatch, case):
    """A prompt's owner prefills it as a miss into its own pages; the same
    prompt then hits the owner's first `cached` tokens (a page multiple, no
    multiple of the 16-token chunk) and computes the rest into pages of its
    own, in chunks laid from `cached` on: [40, 56) and [56, 72) of a
    64-token bucket, the second over padding made inside the step. The
    miss's logits; every page of the owner's byte for byte as it was; the
    hit's own pages what the owner's hold there. With the whole prompt
    cached the last position's page alone is computed and nothing written."""
    length, cached = case
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", 2 * T)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", 2 * T)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    cfg, params = seeded(7)
    tokens = tokens_of(40, length)
    bucket = 1 << (-(-length // T) - 1).bit_length()
    padded = jnp.zeros((1, bucket * T), jnp.int32).at[0, :length].set(tokens)
    prefill = jax.jit(lambda kv, table, write_from: tfm.forward_prefill(params, padded, cfg, kv, table, jnp.int32(length), write_from))
    owner_table = jnp.arange(1, 1 + bucket, dtype=jnp.int32)
    miss, owner_kv = prefill(tfm.init_kv_pages(cfg, 1 + 2 * bucket, T), owner_table, jnp.int32(0))
    assert worst(miss[0], reference(afmoe, params, tokens, jnp.asarray([length - 1]))[0]) <= TOLERANCE
    shared = cached // T
    table = jnp.concatenate([owner_table[:shared], jnp.arange(1 + bucket, 1 + 2 * bucket - shared, dtype=jnp.int32)])
    hit, kv = prefill(owner_kv, table, jnp.int32(cached))
    assert worst(hit[0], miss[0]) <= TOLERANCE
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(kv[name][:, 1:1 + bucket]), np.asarray(owner_kv[name][:, 1:1 + bucket]))
        for j in range(shared, -(-length // T)):
            live = min(T, length - j * T)
            assert worst(kv[name][:, table[j], :live], owner_kv[name][:, 1 + j, :live]) <= TOLERANCE, (name, j)
        if cached == length:
            assert not np.asarray(kv[name][:, 1 + bucket:]).any()


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "a_share_of_four_experts_from_the_third"])
@pytest.mark.parametrize("rows", [3, 64, tfm.GROUPED_FROM_ROWS - 1, tfm.GROUPED_FROM_ROWS, tfm.GROUPED_FROM_ROWS + 40])
def test_a_serving_step_picks_its_expert_products_by_its_rows(rows, share):
    """A serving step hands `_routed_ffn` the group's expert stack and the
    layer's place in it: under GROUPED_FROM_ROWS rows it multiplies every
    expert by every row, from there on each expert by its own rows where the
    stack lies (ops/grouped_matmul.py), and never through `lax.ragged_dot`.
    Both give what the layer's own matrices give grouped as training groups
    them, the same experts counted; under a share (Solar-Open2's way: 4 of
    the 8 experts held) the held experts' part of it."""
    cfg, params = seeded(6)
    riding, stack = tfm._experts_in_place(params["blocks"])
    assert tfm._experts_in_place(params["dense_blocks"]) == (params["dense_blocks"], None)
    assert set(stack) == set(tfm.EXPERT_WEIGHTS) and not set(riding["mlp"]) & set(stack)
    if share:
        cfg = cfg.replace(n_experts_held=4, first_expert=2)
        stack = {name: w[:, 2:6] for name, w in stack.items()}
    layer = 1
    of_layer = functools.partial(jax.tree_util.tree_map, lambda w: w[layer])
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, cfg.d_model), jnp.float32)
    want, want_counts = tfm._routed_ffn(h, dict(of_layer(riding["mlp"]), **of_layer(stack)), cfg, counts=True)
    served = lambda h: tfm._routed_ffn(h, of_layer(riding["mlp"]), cfg, counts=True, experts=(stack, jnp.int32(layer)))
    text = str(jax.make_jaxpr(served)(h))
    assert "ragged_dot" not in text
    assert ("pallas_call" in text) == tfm.experts_grouped_at(rows) == (rows >= tfm.GROUPED_FROM_ROWS)
    out, counts = served(h)
    assert worst(out, want) <= TOLERANCE
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))


# ------------------------------------------------------- (c) the wrong models


def served_margins(arch, params, tokens, served):
    """The statistic the cell's `correct` judges (lib/correct.served_margins):
    the reference's best logit less its logit of the token served, here for
    the program's greedy token after every position of `tokens`."""
    logits = reference(arch, params, tokens, jnp.arange(tokens.shape[0]))
    return np.asarray(jnp.max(logits, -1) - jnp.take_along_axis(logits, served[:, None], -1)[:, 0])


@functools.lru_cache(maxsize=None)
def served_by_the_program():
    """(tokens, the float32 program's greedy token after every position, its
    margins against the right reference) of 3 sequences of 5 windows: made
    once for all the wrong models."""
    cfg, params = seeded(4)
    out = []
    for s in range(3):
        tokens = tokens_of(20 + s, 5 * WINDOW)
        served = jnp.argmax(forward(cfg, params, tokens[None])[0], -1)
        out.append((tokens, served, served_margins(afmoe, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_models.WRONG))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 5 windows:
    against the right reference every margin is 0 to rounding; against each
    wrong model's (one line of the reference altered, and the fp8-precision
    control) the 90th percentile is far over any limit between."""
    _cfg, params = seeded(4)
    wrong = wrong_models.load(name)
    right, wrong = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()])), correct.error_quantiles(
        np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()]))
    assert right["q100"] <= 1e-3
    assert wrong["q90"] > 0.02 and wrong["q90"] > 20 * max(right["q99"], 1e-3), (right, wrong)


# ----------------------------------------------------------- (e) the engine


def _collect(engine, prompt, n):
    return list(engine.generate(prompt, n))


def greedy(cfg, params, prompt, n):
    """An engine-free greedy loop: the whole-sequence forward, token by token,
    at one padded length (the model is causal: what follows a position does
    not reach it), so that one executable serves every step."""
    tokens = np.zeros((1, len(prompt) + n), np.int32)
    tokens[0, :len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        tokens[0, i] = int(jnp.argmax(forward(cfg, params, jnp.asarray(tokens))[0, i - 1]))
    return tokens[0, len(prompt):].tolist()


def test_the_engine_serves_it_with_prefix_hits_and_counts_experts_and_windows(monkeypatch):
    """Two prompts that share 40 tokens (the second's cached part ends beyond
    its last position's window), through InferenceEngine + PagedLM: the tokens
    of an engine-free greedy loop, a prefix hit, and the two counters a decode
    step's router and windows feed."""
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", 2 * T)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", 2 * T)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    cfg, params = seeded(5)
    first = [int(t) for t in tokens_of(30, 45)]
    second = first[:40] + [int(t) for t in tokens_of(31, 19)]
    lm = PagedLM(cfg, params, num_pages=64, page_tokens=T, max_slots=4, max_pages_per_seq=12)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=64), name="t-afmoe")
    try:
        assert _collect(eng, first, 20) == greedy(cfg, params, first, 20)
        assert _collect(eng, second, 20) == greedy(cfg, params, second, 20)
        stats = eng.stats()
    finally:
        eng.close()
    assert stats["kv"]["prefix_hits"] == 5
    clocks = stats["clocks"]
    steps = clocks["decode"]["n"]
    assert clocks["decode_experts"]["steps"] == steps and clocks["decode_experts"]["held"] == steps * 4 * 8
    assert steps * 4 <= clocks["decode_experts"]["touched"] <= clocks["decode_experts"]["held"]
    # one live row a step at lengths 46..64 and 60..78: five of six layers see 16 positions, one all of them
    live = list(range(46, 46 + 19)) + list(range(60, 60 + 19))
    assert clocks["decode_window"] == {"kv_live": 6 * sum(live), "kv_read": sum(5 * WINDOW + n for n in live)}
    # 16-row chunks over 4 routed layers, under the cut: every row through the every-expert product
    computed = clocks["prefill"]["computed_tokens"]
    assert clocks["prefill_experts"] == {"rows": 4 * computed, "grouped_rows": 0, "chunks": computed // (2 * T)}
    out = lm.decode([1], [3], [[1]])
    assert isinstance(out, DecodeTokens) and len(out) == 4 and set(out.counters) == {"decode_experts", "decode_window"}


def test_a_prompt_whose_chunk_reaches_the_cut_is_served_through_the_grouped_product():
    """A prompt of 130 tokens lands in a bucket of 32 pages whose chunk is
    PREFILL_CHUNK_TOKENS = 256 rows, at and above GROUPED_FROM_ROWS: its four
    routed layers sort the chunk's 512 (row, expert) pairs to their experts
    (ops/grouped_matmul.py, interpreted) and the decode steps behind it
    multiply every expert. The tokens of an engine-free greedy loop, and the
    counter that says which rows went which way."""
    assert tfm.experts_grouped_at(tfm.PREFILL_CHUNK_TOKENS) and not tfm.experts_grouped_at(2)
    cfg, params = seeded(8)
    prompt = [int(t) for t in tokens_of(32, 130)]
    lm = PagedLM(cfg, params, num_pages=48, page_tokens=T, max_slots=2, max_pages_per_seq=40)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=48, prefill_token_budget=256), name="t-afmoe-grouped")
    try:
        assert _collect(eng, prompt, 6) == greedy(cfg, params, prompt, 6)
        clocks = eng.stats()["clocks"]
    finally:
        eng.close()
    assert clocks["prefill"]["computed_tokens"] == 256
    assert clocks["prefill_experts"] == {"rows": 4 * 256, "grouped_rows": 4 * 256, "chunks": 1}


def test_a_256_row_chunk_lowers_to_two_grouped_kernels_a_routed_group_and_a_decode_step_to_none():
    """The traced text of the two serving steps: a decode step of 64 rows
    holds no call of the grouped kernels and the every-expert products
    [E, rows, f]; a prefill bucket whose chunk is 256 rows holds the fused
    gate-up kernel and the down kernel once each (the routed group is one
    scan body), no `ragged_dot`, and no [E, rows, f] product of every expert."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg, params = seeded(0)
    E, f, pages = cfg.n_experts, cfg.d_ff, 32
    kv = tfm.init_kv_pages(cfg, 1 + pages, T)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    decode = str(jax.make_jaxpr(lambda t, pos, kv, bt: tfm.forward_decode(params, t, pos, cfg, kv, bt))(i32(SLOTS), i32(SLOTS), kv, i32(SLOTS, pages)))
    prefill = str(jax.make_jaxpr(lambda t, kv, bt, n: tfm.forward_prefill(params, t, cfg, kv, bt, n, 0))(i32(1, pages * T), kv, i32(pages), jnp.int32(130)))
    names = (gm.SWIGLU_KERNEL_NAME, gm.MATMUL_KERNEL_NAME)
    assert [decode.count(f"name={name}\n") for name in names] == [0, 0] and f"[{E},{SLOTS},{f}]" in decode
    assert [prefill.count(f"name={name}\n") for name in names] == [1, 1]
    assert "ragged_dot" not in prefill + decode and f"[{E},{tfm.PREFILL_CHUNK_TOKENS},{f}]" not in prefill


def test_a_dense_models_decode_returns_a_plain_list_and_no_new_clock():
    lm = PagedLM(max_slots=2)
    out = lm.decode([1], [3], [[1]])
    assert type(out) is list and len(out) == 2
    eng = InferenceEngine(PagedLM(max_slots=2), EngineConfig(), name="t-dense-clocks")
    try:
        _collect(eng, [1, 2, 3], 4)
        assert not {"decode_experts", "decode_window"} & set(eng.stats()["clocks"])
    finally:
        eng.close()


# ------------------------------------------- (f) what the other models keep

PRESETS = {
    "dense": tfm.tiny(),
    "gqa": tfm.tiny(n_kv_heads=2, tie_embeddings=True),
    "olmoe": tfm.tiny(n_experts=8, n_experts_per_tok=2, d_ff=32, qk_norm=True),
    "gptj": tfm.tiny(mlp_act="gelu", parallel_block=True, rotary_dim=8, norm_type="layer", rope_style="interleaved"),
}


def tree_print(params):
    return {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype), round(float(jnp.sum(a.astype(jnp.float32) ** 2)), 3))
            for p, a in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_the_other_presets_draw_the_weights_they_drew(name):
    """`init_params` gives the llama, OLMoE and GPT-J blocks the tree and the
    numbers it gave them before this model's leaves existed: the new leaves
    draw from a key stream of their own."""
    assert tree_print(tfm.init_params(jax.random.PRNGKey(5), PRESETS[name])) == PARENT_TREES[name]


def test_the_routed_ffn_of_olmoe_lowers_to_the_ops_it_had():
    """The OLMoE train step's routed FFN: three grouped matmuls forward, the
    sorts and the top-k once each, a softmax router and no sigmoid; counted in
    the lowered text, as on the parent commit."""
    import optax
    from jax.sharding import Mesh

    cfg = tfm.tiny(n_experts=8, n_experts_per_tok=2, d_ff=32, qk_norm=True, remat=True, remat_policy="hot")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    _init, step = tfm.build_train_step(cfg, optax.adamw(1e-3), mesh)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    opt = jax.eval_shape(optax.adamw(1e-3).init, params)
    text = step.lower(params, opt, jax.ShapeDtypeStruct((2, 64), jnp.int32)).as_text()
    assert {op: len(re.findall(re.escape(op) + r"\b", text)) for op in PARENT_OLMOE_OPS} == PARENT_OLMOE_OPS


# Read on the parent commit (PR 36's tree) with this file's own functions. PR 55 moved two counts, both inside the flash kernels the step inlines
# when interpreted (a body for the blocks below the diagonal and one for those on it, each with its products and exps: dot_general 42 -> 51,
# exponential 9 -> 13); with attn_impl="naive" the step reads 41 and 7 at PR 55 and at its parent alike: the routed FFN's own ops are unmoved.
# PR 60 took the LOSS's gather and its transpose's scatter out (`head_loss` picks the target's logit with a compare; each op's name stands twice in
# the text, once in its attribute: gather 18 -> 16, scatter 6 -> 4); the three products of the head and every op of the routed FFN are what they were.
PARENT_OLMOE_OPS = {'stablehlo.dot_general': 51, 'stablehlo.gather': 16, 'stablehlo.scatter': 4, 'stablehlo.sort': 2, 'chlo.top_k': 1, 'stablehlo.exponential': 13, 'stablehlo.logistic': 0}
PARENT_TREES = {'dense': {"['blocks']['attn']['wk']": ((2, 64, 32), 'bfloat16', 64.693),
           "['blocks']['attn']['wo']": ((2, 64, 64), 'bfloat16', 132.358),
           "['blocks']['attn']['wq']": ((2, 64, 64), 'bfloat16', 127.155),
           "['blocks']['attn']['wv']": ((2, 64, 32), 'bfloat16', 61.283),
           "['blocks']['attn_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
           "['blocks']['mlp']['w_down']": ((2, 128, 64), 'bfloat16', 127.847),
           "['blocks']['mlp']['w_gate']": ((2, 64, 128), 'bfloat16', 255.991),
           "['blocks']['mlp']['w_up']": ((2, 64, 128), 'bfloat16', 255.572),
           "['blocks']['mlp_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
           "['embed']['embedding']": ((256, 64), 'bfloat16', 252.266),
           "['final_norm']['scale']": ((64,), 'bfloat16', 64.0),
           "['lm_head']": ((64, 256), 'bfloat16', 258.02)},
 'gqa': {"['blocks']['attn']['wk']": ((2, 64, 32), 'bfloat16', 64.693),
         "['blocks']['attn']['wo']": ((2, 64, 64), 'bfloat16', 132.358),
         "['blocks']['attn']['wq']": ((2, 64, 64), 'bfloat16', 127.155),
         "['blocks']['attn']['wv']": ((2, 64, 32), 'bfloat16', 61.283),
         "['blocks']['attn_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
         "['blocks']['mlp']['w_down']": ((2, 128, 64), 'bfloat16', 127.847),
         "['blocks']['mlp']['w_gate']": ((2, 64, 128), 'bfloat16', 255.991),
         "['blocks']['mlp']['w_up']": ((2, 64, 128), 'bfloat16', 255.572),
         "['blocks']['mlp_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
         "['embed']['embedding']": ((256, 64), 'bfloat16', 252.266),
         "['final_norm']['scale']": ((64,), 'bfloat16', 64.0)},
 'olmoe': {"['blocks']['attn']['k_norm']['scale']": ((2, 32), 'bfloat16', 64.0),
           "['blocks']['attn']['q_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
           "['blocks']['attn']['wk']": ((2, 64, 32), 'bfloat16', 64.693),
           "['blocks']['attn']['wo']": ((2, 64, 64), 'bfloat16', 132.358),
           "['blocks']['attn']['wq']": ((2, 64, 64), 'bfloat16', 127.155),
           "['blocks']['attn']['wv']": ((2, 64, 32), 'bfloat16', 61.283),
           "['blocks']['attn_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
           "['blocks']['mlp']['router']": ((2, 64, 8), 'bfloat16', 16.093),
           "['blocks']['mlp']['w_down']": ((2, 8, 32, 64), 'bfloat16', 1026.478),
           "['blocks']['mlp']['w_gate']": ((2, 8, 64, 32), 'bfloat16', 513.561),
           "['blocks']['mlp']['w_up']": ((2, 8, 64, 32), 'bfloat16', 508.273),
           "['blocks']['mlp_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
           "['embed']['embedding']": ((256, 64), 'bfloat16', 252.266),
           "['final_norm']['scale']": ((64,), 'bfloat16', 64.0),
           "['lm_head']": ((64, 256), 'bfloat16', 254.925)},
 'gptj': {"['blocks']['attn']['wk']": ((2, 64, 32), 'bfloat16', 64.693),
          "['blocks']['attn']['wo']": ((2, 64, 64), 'bfloat16', 132.358),
          "['blocks']['attn']['wq']": ((2, 64, 64), 'bfloat16', 127.155),
          "['blocks']['attn']['wv']": ((2, 64, 32), 'bfloat16', 61.283),
          "['blocks']['attn_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
          "['blocks']['mlp']['w_down']": ((2, 128, 64), 'bfloat16', 127.786),
          "['blocks']['mlp']['w_up']": ((2, 64, 128), 'bfloat16', 255.991),
          "['blocks']['mlp_norm']['scale']": ((2, 64), 'bfloat16', 128.0),
          "['embed']['embedding']": ((256, 64), 'bfloat16', 252.266),
          "['final_norm']['scale']": ((64,), 'bfloat16', 64.0),
          "['lm_head']": ((64, 256), 'bfloat16', 255.703)}}

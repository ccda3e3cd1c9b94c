"""GigaChat3.5 (`model_type: gigachat3_5`) on the normal path, at
`archs/gigachat3_5.TINY` widths on the CPU (a dense gated-delta-rule layer,
then two periods of a gated latent-attention layer and three gated-delta-rule
layers, 8 of 16 experts held), float32, seeded random weights with every
norm's `w` drawn: the stack's plan and the cache's layout, the whole-sequence
forward and the paged path through BOTH caches (latent pages and state slots)
against the plain reference of `benchmarks/archs/gigachat3_5.py`, the sixteen
shares of a routed layer against the uncut layer, the latent kernels at 64
heads, the wrong models, the engine's slots, and the refusals that are left.

TOLERANCE: both sides compute in float32, the reference at matmul precision
"highest". Under `swiglu_limit` one column in sixteen of a SwiGLU is an outlier
channel (transformer.init_params), and at TINY widths that is 2 of an expert's
32 columns and 6 of the dense layer's 96, which carry most of the FFN's output:
the reference's own logits move by 5e-4 (median) to 6e-3 (worst) under a 1e-6
relative perturbation of its weights, three to ten times what they do with
plain columns (PR 59, CPU), and the two float32 programs differ by up to 1.9e-3
on logits up to ~6 over seeds 0-7 (4e-5 to 9e-5 with plain columns).
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.archs import gigachat3_5
from benchmarks.lib import correct
from benchmarks.tools import wrong_gigachat3_5
from ray_tpu.models import transformer as tfm
from ray_tpu.ops import latent_attention
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.llm.model import DecodeTokens, PagedLM, PromptTokens

TOLERANCE = 4e-3
CHUNK = 16  # PREFILL_CHUNK_TOKENS in these tests: a 41-token prompt walks three chunks, the last one padded
CONFIG = dict(gigachat3_5.TINY, rms_norm_eps=1e-6, routed_scaling_factor=2.5, layernorm_gating_weight=2, swiglu_limit=10,
              linear_sigmoid_gate_scale=2, linear_attn_o_norm_eps=1e-6, gated_attention=True, rope_interleave=True)
T = 8  # positions a latent page


@pytest.fixture(autouse=True)
def small_chunks_at_highest_precision(monkeypatch):
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", CHUNK)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_CAP", CHUNK)  # small chunks alone: big ones and a tail are tests/test_prefill_chunks.py's
    with jax.default_matmul_precision("highest"):
        yield


@functools.lru_cache(maxsize=None)
def seeded(seed):
    cfg = gigachat3_5.model_config(CONFIG, remat=False)
    return cfg, correct.init_weights(tfm, cfg, jax.random.PRNGKey(seed))


def tokens_of(seed, n):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 1), (n,), 1, CONFIG["vocab_size"], jnp.int32)


def reference(arch, params, tokens, positions, config=CONFIG):
    return correct.reference_logits(arch, params, tokens, positions, config)


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


# ------------------------------------------------- (a) the plan and the layout


def test_the_stack_is_a_dense_state_layer_then_periods_of_a_latent_layer_and_three_state_layers():
    cfg, params = seeded(0)
    plan = tfm.stack_plan(cfg)
    assert plan == (
        (1, (tfm.StackMember("kda", "dense_blocks", 1, 0),)),
        (2, (tfm.StackMember("latent", "blocks", 1, 0), tfm.StackMember("kda", "kda_blocks", 3, 1))),
    )
    # the state layers' places in their cache leaves, in published order: the dense one, then a period's three
    assert [plan[1][1][1].place(r, j) for r in range(2) for j in range(3)] == [1, 2, 3, 4, 5, 6]
    layout = tfm.cache_layout(cfg)
    assert layout.kinds == (("kda", 7), ("latent", 2)) and layout.names == ("s", "tail", "ckv")
    assert layout.indexed == {"s": "slot", "tail": "slot", "ckv": "page"} and layout.state and layout.kv and layout.paged == "ckv"
    assert set(params) == {"embed", "dense_blocks", "blocks", "kda_blocks", "final_norm", "lm_head"}
    dense, full, kda = (params[name]["attn"] for name in ("dense_blocks", "blocks", "kda_blocks"))
    assert dense["wq"].shape == (1, 64, 32) and dense["wv"].shape == (1, 64, 64) and dense["w_a"].shape == (1, 64, 4) and dense["w_z"].shape == (1, 64, 64)
    assert kda["wk"].shape == (2, 3, 64, 32) and kda["conv_v"].shape == (2, 3, 64, 4) and "w_fa" not in kda and "w_ga" not in kda
    assert full["wg"].shape == (2, 64, 4 * 16) and full["w_uk"].shape == (2, 4, 16, 32)
    assert "router" not in params["dense_blocks"]["mlp"] and params["kda_blocks"]["mlp"]["w_gate"].shape == (2, 3, 8, 64, 32)
    pool = tfm.init_kv_pages(cfg, 24, T, 4)
    # a slot's tails: 3 rows of q's 32, k's 32 and v's 64 channels, one after another
    assert pool["s"].shape == (7, 4, 4, 16, 16) and pool["tail"].shape == (7, 4, 1, 3 * 128) and pool["ckv"].shape == (2, 24, T, 128)


def test_the_plan_of_the_published_cut_and_its_counts():
    """The issue's arithmetic: 4.731 B parameters in the cut, 17.2 MB of state
    and tails and 1152 B of latent row a position a sequence, ~13.9 GB a step."""
    from benchmarks.lib import spec

    config = spec.find_cell("gigachat35-serve-longanswer-batch").config
    cfg = gigachat3_5.model_config(config)
    assert tfm.stack_plan(cfg) == (
        (1, (tfm.StackMember("kda", "dense_blocks", 1, 0),)),
        (1, (tfm.StackMember("latent", "blocks", 1, 0), tfm.StackMember("kda", "kda_blocks", 3, 1))),
    )
    assert tfm.cache_layout(cfg).kinds == (("kda", 4), ("latent", 1))
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    assert abs(tfm.param_count(shapes) / 1e9 - 4.731) < 0.001
    assert abs(gigachat3_5.matmul_params(config) + 16032 * 7168 - tfm.param_count(shapes)) < 2e6  # norms, biases, convolutions
    pool = jax.eval_shape(lambda: tfm.init_kv_pages(cfg, 3585, 128))
    assert pool["s"].shape == (4, 129, 64, 128, 128) and pool["tail"].shape == (4, 129, 16, 3072) and pool["ckv"].shape == (1, 3585, 128, 640)
    assert gigachat3_5.decode_state_bytes(config, 1) == 2 * 4 * 64 * 128 * 128 * 4 and gigachat3_5.decode_kv_bytes(config, 1) == 1152
    # every held expert counted: 13.85 GB; the 15.7 of 16 a layer that a uniform router's 128 rows are expected to touch: 13.76
    assert abs(gigachat3_5.decode_step_bytes(config, 128, 128 * 2200, 64) / 1e9 - 13.85) < 0.01
    assert abs(gigachat3_5.decode_step_min_bytes(config, 128, 128 * 2200) / 1e9 - 13.76) < 0.01
    assert tfm.decode_paths(cfg, 128) == {"decode_state": "kda_kernel", "decode_attention": "latent_kernel"}
    # the program's count takes the embedding for a matmul (6 N), the architecture file's does not (a gather)
    assert abs((tfm.flops_per_token(cfg, 2048) - 6 * 16032 * 7168) / gigachat3_5.train_flops_per_token(config, 2048) - 1) < 0.01


def test_the_grouped_products_of_a_decode_step_are_read_by_their_rows_and_the_touched_experts():
    """`readers/trace_grouped_products.py` by hand at the published cut: a step
    of 128 rows x 8 choices calls `grouped_swiglu` [1024, 2048] and
    `grouped_matmul` [1024, 7168] once a routed layer; 38.4 experts touched a
    step (60 % of 4 x 16) are 9.6 a call, 58.7 MB each for gate and up, 29.4
    MB for down; a prefill chunk's calls (2048 rows) and an op that only reads
    a product's result are left out."""
    from benchmarks.lib import spec
    from benchmarks.readers import trace_grouped_products as reader

    config = spec.find_cell("gigachat35-serve-longanswer-batch").config
    ops = [("%grouped_swiglu.14 = bf16[1024,2048]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %bitcast.1, bf16[1024,7168]{1,0} %xs)", 0.8e-3),
           ("%grouped_matmul.14 = bf16[1024,7168]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %bitcast.1, bf16[1024,2048]{1,0} %grouped_swiglu.14)", 0.4e-3),
           ("%grouped_swiglu.28 = bf16[2048,2048]{1,0} custom-call(s32[1]{0} %bitcast.2)", 5e-3),
           ("%broadcast_select_fusion.9 = bf16[1024,7168]{1,0} fusion(bf16[1024,7168]{1,0} %grouped_matmul.14)", 1e-3)]

    class Trace:
        def op_calls(self, pattern):
            import re
            return [(hlo, s) for hlo, s in ops if re.search(pattern, hlo)]

        def busy_s(self):
            return 12e-3

    def evidence(touched):
        marks = [{"engine": {"clocks": {"decode_experts": {"touched": 0, "steps": 0}}}}, {"engine": {"clocks": {"decode_experts": {"touched": touched, "steps": 10}}}}]
        return {"_trace": Trace(), "marks": marks, "worker": {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}}

    cell = type("Cell", (), {"arch": gigachat3_5, "config": config, "allow_cpu": False})()
    assert reader.read(evidence(384), {"stat": "time_share_pct", "cell": cell}) == pytest.approx(10.0)
    least = 9.6 * 3 * 7168 * 2048 * 2 / 819e9  # 1.03 ms
    assert reader.read(evidence(384), {"stat": "roofline", "cell": cell}) == pytest.approx(100.0 * least / 1.2e-3)  # 86 %
    assert reader.read(evidence(0), {"stat": "roofline", "cell": cell}) == pytest.approx(100.0 * 3 * 2 * 64 * 7168 * 2048 / 197e12 / 1.2e-3)  # no expert counted: the held picks' FLOPs
    assert reader.read(dict(evidence(384), marks=[]), {"stat": "roofline", "cell": cell}) is None
    assert reader.read(evidence(384), {"stat": "roofline", "cell": type("Cell", (), {"arch": object(), "config": config})()}) is None
    assert not hasattr(gigachat3_5, "decode_expert_products")  # a 128-row step has no every-expert product to read


def test_what_init_params_still_refuses_says_what_is_left():
    cfg, _ = seeded(0)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="no other list of full layers"):
        tfm.init_params(key, cfg.replace(full_layers=(2, 6)))  # a full layer inside a period
    with pytest.raises(ValueError, match="ends inside a period"):
        tfm.init_params(key, cfg.replace(n_layers=8, full_layers=(1, 5)))
    with pytest.raises(ValueError, match="rope switch"):
        tfm.init_params(key, cfg.replace(rope_layers=(True,) * 9))
    with pytest.raises(ValueError, match="key heads"):
        tfm.init_params(key, cfg.replace(kda_key_heads=3))
    with pytest.raises(ValueError, match="naive"):
        tfm.forward(seeded(0)[1], tokens_of(0, 16)[None], cfg.replace(attn_impl="full"))
    with pytest.raises(ValueError, match="gigachat3_5 does not compute"):
        gigachat3_5.dims(dict(CONFIG, gated_attention=False))
    with pytest.raises(ValueError, match="full_attention_layers"):
        gigachat3_5.dims(dict(CONFIG, full_attention_layers=[1, 6]))


# ------------------------------------------- (b) the whole-sequence forward


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_at_every_position(seed):
    cfg, params = seeded(seed)
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.kda_per_period, cfg.kda_key_heads, cfg.kda_head_dim) == (16, 8, 8, 3, 2, 16)
    tokens = tokens_of(seed, 70)
    got = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(params, tokens[None])[0]
    assert worst(got, reference(gigachat3_5, params, tokens, np.arange(70))) <= TOLERANCE


def test_the_clamp_binds_on_the_outlier_channels_and_nowhere_else():
    """What makes `no_clamp` a wrong model: under `swiglu_limit` one column in
    sixteen of a SwiGLU's gate and up matrices is drawn eight times as wide."""
    cfg, params = seeded(0)
    w = params["dense_blocks"]["mlp"]["w_up"][0].astype(jnp.float32)
    wide = jnp.std(w[:, ::16]) / jnp.std(w[:, 1::16])
    assert 7 < float(wide) < 9
    plain = tfm.init_params(jax.random.PRNGKey(0), cfg.replace(swiglu_limit=0.0))["dense_blocks"]["mlp"]["w_up"]
    assert 0.8 < float(jnp.std(plain[0][:, ::16]) / jnp.std(plain[0][:, 1::16])) < 1.25


def test_the_selecting_bias_is_drawn_at_the_programs_own_deviation_and_selects():
    """An ungrouped sigmoid router's selecting bias is drawn at the program's
    own 0.1 (the configuration names no other: what that costs in spread over
    seeds is reported, PERF.md section 6, PR 59, not cured), and it decides
    some tokens' experts."""
    cfg, params = seeded(0)
    assert 0.07 < float(jnp.std(params["kda_blocks"]["mlp"]["router_bias"])) < 0.13
    tokens = tokens_of(0, 64)
    no_bias = jax.tree_util.tree_map_with_path(lambda path, a: jnp.zeros_like(a) if "router_bias" in jax.tree_util.keystr(path) else a, params)
    chosen, plain = (jnp.sort(tfm.routing_stats(p, tokens[None], cfg)["experts"], -1) for p in (params, no_bias))
    assert 0.01 < float(jnp.mean(jnp.any(chosen != plain, axis=-1))) < 0.9
    assert "router_bias_init" not in CONFIG["assumed"] and not hasattr(cfg, "router_bias_std")


# ------------------------------------------------------ (c) both caches


def hybrid_lm(cfg, params, slots=3, pages=24):
    return PagedLM(cfg, params, num_pages=pages, page_tokens=T, max_slots=slots, max_pages_per_seq=8)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_both_caches_matches_the_reference_logits(seed):
    """A 41-token prompt prefilled in three chunks (the last one padded) into
    latent pages 3.. and state slot 2 of a pool that holds another sequence's
    leftovers, then nine tokens teacher-forced through decode steps as row 1
    (so state slot 2) beside two inactive rows: every logit the reference's."""
    cfg, params = seeded(seed)
    tokens = tokens_of(seed + 10, 50)
    want = reference(gigachat3_5, params, tokens, np.arange(50))
    lm = hybrid_lm(cfg, params)
    assert set(lm.kv) == {"s", "tail", "ckv"}
    # What a slot's or a page's last owner left must not leak. In the state slots NaN, which does not fade; in the
    # pages numbers (the plain gather expression multiplies a masked position's row by a weight of exactly 0).
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


def test_paged_lm_says_both_paths_and_counts_both_caches():
    cfg, params = seeded(3)
    tokens = [int(t) for t in tokens_of(30, 37)]
    want = reference(gigachat3_5, params, jnp.asarray(tokens), np.array([29, 30]))
    lm = hybrid_lm(cfg, params)
    prompt = PromptTokens(tokens[:30])
    prompt.slot = 1  # the engine's admission: decode row 1, so state slot 2
    first = lm.prefill(prompt, [1, 2, 3, 4], 0)
    assert int(first) == int(jnp.argmax(want[0]))
    assert set(first.counters) == {"prefill_chunks", "prefill_state", "prefill_experts", "prefill_latent"}
    assert first.counters["prefill_latent"] == {"pairs": 2 * 30 * 31 // 2, "calls": 1}  # two latent layers
    assert first.counters["prefill_experts"]["rows"] == 2 * CHUNK * 8  # two chunks through the eight routed layers
    assert np.any(np.asarray(lm.kv["s"])[:, 2] != 0) and not np.any(np.asarray(lm.kv["s"])[:, 1] != 0)
    out = lm.decode([0, tokens[30]], [-1, 30], [[], [1, 2, 3, 4]])
    assert out[1] == int(jnp.argmax(want[1]))
    assert isinstance(out, DecodeTokens) and set(out.counters) == {"decode_experts", "decode_state", "decode_kv", "decode_latent"}
    assert out.counters["decode_state"] == {"bytes": 2 * lm.state_bytes, "live_slots": 1, "steps": 1}
    assert out.counters["decode_kv"] == {"bytes": 31 * lm.page_bytes // T, "tokens": 31, "steps": 1}
    assert out.counters["decode_latent"] == {"bytes": 31 * 2 * (32 + 8) * 4, "positions": 31, "steps": 1}
    said = lm.describe()
    assert said["cache"] == {"kind": "state+kv_pages", "state_bytes": lm.state_bytes, "page_bytes": lm.page_bytes}
    assert said["decode_state"] == "xla_step" and said["decode_attention"] == "xla_gather" and lm.shares_prefix_pages is False
    assert lm.page_bytes == 2 * T * 128 * 4 and lm.state_bytes == 7 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert lm._get_decode().__name__ == "llm_decode_hybrid"
    with pytest.raises(ValueError, match="cached_tokens"):
        lm.prefill(tokens[:20], [1, 2, 3], 8)


# ------------------------------------------ (d) a routed layer's sixteen shares


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """One routed layer (the first period's latent layer's FFN) at TINY: the
    program's FFN under each of the two shares of 8 of 16 experts, and of the
    four of 4, the shared expert counted once, add up to the uncut reference's
    layer (all 16 experts held), which is also the program's with all held."""
    cfg, params = seeded(4)
    uncut = cfg.replace(n_experts_held=0, first_expert=0)
    whole = tfm.init_params(jax.random.PRNGKey(4), uncut)["blocks"]["mlp"]
    layer = jax.tree_util.tree_map(lambda a: a[0], whole)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.d_model), jnp.float32)
    m = gigachat3_5.dims(dict(CONFIG, n_routed_experts=16, assumed={"expert_rank": {"value": 0}}))
    group = {"mlp": whole}
    want = gigachat3_5._ffn(h[0], {"mlp": layer}, group, (0,), m)
    shared = gigachat3_5._swiglu(h[0], whole["shared"], (0,), m)
    assert worst(tfm._ffn(h, layer, uncut)[0], want) <= 1e-4
    for held in (8, 4):
        total = -(16 // held - 1) * shared  # every share adds the shared expert: counted once
        for rank in range(16 // held):
            share = dict(layer, **{name: layer[name][rank * held : (rank + 1) * held] for name in tfm.EXPERT_WEIGHTS})
            total = total + tfm._ffn(h, share, cfg.replace(n_experts_held=held, first_expert=rank * held))[0]
        assert worst(total, want) <= 1e-4 * max(1.0, float(jnp.max(jnp.abs(want)))), held


# ------------------------------------------------ (e) the kernels' shapes


def test_the_latent_decode_kernel_tiles_64_heads():
    """ops/latent_attention.py at the configuration's 64 heads (it had run at
    128): interpret mode against the gather expression, a live and an empty slot."""
    H, W, c = 64, 640, 512
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = (jax.random.normal(ks[0], (2, 6, 16, W)) * 0.3).astype(jnp.bfloat16)
    q = (jax.random.normal(ks[1], (2, H, W)) * 0.3).astype(jnp.bfloat16)
    tables, lengths = jnp.array([[2, 4, 0], [0, 0, 0]]), jnp.array([27, 0])
    assert latent_attention.can_tile(16, H, c, jnp.bfloat16)
    got = latent_attention.paged_latent_attention(q, pool, 1, tables, lengths, scale=0.1, v_width=c, interpret=True)
    want = latent_attention.latent_attention_gather(q, pool[1], tables, jnp.maximum(lengths, 1), scale=0.1, v_width=c)
    assert worst(got[0].astype(jnp.float32), want[0].astype(jnp.float32)) <= 0.02 and not bool(jnp.any(got[1]))


# ---------------------------------------------------- (f) the wrong models


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
        out.append((tokens, served, served_margins(gigachat3_5, params, tokens, served)))
    return out


@pytest.mark.parametrize("name", sorted(wrong_gigachat3_5.WRONG))
def test_each_wrong_model_separates_from_the_right_one_by_the_served_margins(name):
    """The float32 program's greedy tokens over 3 sequences of 70: against the
    right reference every margin is 0 to rounding; against each wrong model's
    (one line of the reference altered, and the fp8-precision control) the
    90th percentile, which a routed cell's limit names, is far over it."""
    _cfg, params = seeded(4)
    wrong = wrong_gigachat3_5.load("gigachat3_5", name)
    right = correct.error_quantiles(np.concatenate([m for _t, _s, m in served_by_the_program()]))
    margins = np.concatenate([served_margins(wrong, params, tokens, served) for tokens, served, _m in served_by_the_program()])
    wrong = correct.error_quantiles(np.where(np.isfinite(margins), margins, np.inf))
    assert right["q100"] <= 2e-3
    assert wrong["q90"] > 0.02 and wrong["q90"] > 10 * max(right["q100"], 2e-3), (right, wrong)


# ----------------------------------------------------------- (g) the engine


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
    state slot and some latent pages another left), of different lengths and
    answer lengths, at once: each gets the tokens of an engine-free greedy
    loop. Nothing enters the prefix index, and the counters of both caches add up."""
    cfg, params = seeded(5)
    prompts = [[int(t) for t in tokens_of(50 + i, n)] for i, n in enumerate((45, 18, 33, 45))]
    prompts[3] = prompts[0]  # the same prompt again: no hit
    answers = (12, 19, 7, 12)
    want = [greedy(cfg, params, p, n) for p, n in zip(prompts, answers)]
    lm = hybrid_lm(cfg, params, slots=2, pages=33)
    eng = InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=33, prefill_token_budget=64), name="t-giga")
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
    state, kv, latent = clocks["decode_state"], clocks["decode_kv"], clocks["decode_latent"]
    assert state["steps"] == kv["steps"] == latent["steps"] == clocks["decode"]["n"]
    assert state["live_slots"] == sum(answers) - 4 and state["bytes"] == state["live_slots"] * 2 * lm.state_bytes
    assert kv["tokens"] == latent["positions"] == sum(sum(range(len(p) + 1, len(p) + n)) for p, n in zip(prompts, answers))


def test_a_slot_reused_after_a_cancel_starts_clean():
    """One slot. A first request is cancelled part-way through its answer,
    leaving its state and tails in the slot and its latent rows in its pages;
    the next request on that slot is served the tokens a fresh engine serves it."""
    cfg, params = seeded(6)
    first, second = ([int(t) for t in tokens_of(60 + i, n)] for i, n in enumerate((40, 25)))

    def serve(cancel_first):
        eng = InferenceEngine(hybrid_lm(cfg, params, slots=1, pages=17), EngineConfig(page_tokens=T, pool_pages=17), name="t-giga-reuse")
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

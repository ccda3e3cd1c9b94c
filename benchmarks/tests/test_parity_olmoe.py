"""Logit parity of the program against the OLMoE architecture file's plain
reference (benchmarks/archs/olmoe.py), on seeded random float32 weights at
the architecture file's TINY widths on the CPU: `forward` on a whole
sequence, and `forward_prefill` then `forward_decode` through the paged
cache across a page boundary. Logits are compared, not argmax: with random
weights the largest logit flips on rounding. The copy of test_parity.py's
two tests that a `model_config` PR brings for its own architecture; tier-1
runs the same over all three configurations (tests/test_parity.py).

TOLERANCE: both sides compute in float32 (the reference at matmul precision
"highest"), so they differ by float32 rounding through 2 layers of width 64.
Read over 12 seeds x 3 configurations x both paths (PR 27, CPU; every norm's
scale drawn from [0.5, 1.5], `lib/correct.draw_norm_scales`), on logits up to
4.2 in size: the largest difference of the float32 program 4.2e-6 (Mistral),
3.5e-6 (DeepSeek), 2.5e-6 (OLMoE); the smallest of the control, the same
program and weights in bfloat16, 3.1e-2, 3.2e-2, 3.5e-2. 1e-4 is 24 times the
first and 1/310 of the second; each test also asserts that its control is
over 1e-3.

For OLMoE a difference could also be a router's near-tie resolved the other
way, which is no arithmetic error: so each OLMoE case first asserts that the
float32 program and the reference chose the SAME experts for every token of
every layer (`transformer.routing_stats` against `archs/olmoe.routed_experts`).
The bfloat16 control is not held to that: flipping experts is one of the
things a lower precision does.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import correct, spec
from ray_tpu.models import transformer as tfm

TOLERANCE = 1e-4
T = 8  # page tokens
CONFIGS = ["olmoe-1b-7b-0125-L2"]


def tiny(name, **changed):
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))
    config.update(spec.load_arch(config).TINY, **changed)
    return config, spec.load_arch(config)


def seeded(arch, config, seed, dtype, drawn=True):
    cfg = arch.model_config(config, dtype=dtype, remat=False)
    key = jax.random.PRNGKey(seed)
    params = correct.init_weights(tfm, cfg, key) if drawn else tfm.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (19,), 0, cfg.vocab_size, jnp.int32)
    return cfg, params, tokens


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def assert_same_experts(arch, config, cfg, params, tokens):
    if cfg.n_experts:
        got = tfm.routing_stats(params, tokens[None], cfg)["experts"]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(arch.routed_experts(params, tokens, config)))


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_logits(name, seed):
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, tokens = seeded(arch, config, seed, jnp.float32)
    assert_same_experts(arch, config, cfg, params, tokens)
    want = arch.logits_at(params, tokens, jnp.arange(tokens.shape[0]), config)
    assert worst(tfm.forward(params, tokens[None], cfg)[0], want) <= TOLERANCE
    # the control: the nearest precision below must fail
    cfg16, params16, _ = seeded(arch, config, seed, jnp.bfloat16)
    assert worst(tfm.forward(params16, tokens[None], cfg16)[0], want) > 10 * TOLERANCE


def paged_logits(cfg, params, tokens, prompt_len):
    """Prefill `prompt_len` tokens into pages 1.., then one decode step a
    token (teacher-forced) in slot 1 of 2, slot 0 inactive: the logits after
    positions prompt_len-1 .. len(tokens)-1."""
    pages = tfm.init_kv_pages(cfg, 8, T)
    table = jnp.asarray([1, 2, 3, 4], jnp.int32)
    n_prompt_pages = -(-prompt_len // T)
    padded = jnp.zeros((1, n_prompt_pages * T), jnp.int32).at[0, :prompt_len].set(tokens[:prompt_len])
    logits, pages = tfm.forward_prefill(params, padded, cfg, pages, table[:n_prompt_pages], jnp.int32(prompt_len), jnp.int32(0))
    out = [logits[0]]
    tables = jnp.stack([jnp.zeros_like(table), table])
    for pos in range(prompt_len, tokens.shape[0]):
        step, pages = tfm.forward_decode(
            params, jnp.asarray([0, tokens[pos]], jnp.int32), jnp.asarray([-1, pos], jnp.int32), cfg, pages, tables)
        out.append(step[1])
    return jnp.stack(out)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_the_paged_cache_matches_the_reference_logits(name, seed):
    """A 13-token prompt ends inside its second page; six decode steps take
    the sequence into a third page (position 16). The routed FFN runs here
    on 16 prompt rows (3 of them padding) and then on 2 rows a step, one of
    them an inactive slot: the groups' sizes change, the program does not."""
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, tokens = seeded(arch, config, seed, jnp.float32)
    assert_same_experts(arch, config, cfg, params, tokens)
    want = arch.logits_at(params, tokens, jnp.arange(12, tokens.shape[0]), config)
    assert worst(paged_logits(cfg, params, tokens, 13), want) <= TOLERANCE
    cfg16, params16, _ = seeded(arch, config, seed, jnp.bfloat16)
    assert worst(paged_logits(cfg16, params16, tokens, 13), want) > 10 * TOLERANCE


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_statistic_separates_the_program_from_its_nearest_wrong_models_once_norm_scales_are_drawn(seed):
    """What a cell's `correct` reads (`lib/correct.py`: the per-position
    relative error of the logits, its median over the positions), on the
    bfloat16 program as the cells run it: the right program reads a few
    hundredths, top-(k-1) and no q/k-norm several tenths. With every norm's
    scale at 1, as `init_params` leaves them, a model WITHOUT q/k-norm is
    under half as far off (0.07-0.15 against 0.27-0.41 at these widths, and
    an RMSNorm over 2 048 fan-in-scaled outputs is nearer the identity
    still): why both workers draw the scales."""
    config, arch = tiny(CONFIGS[0], torch_dtype="float32")

    def q50(cfg, params, tokens):
        want = arch.logits_at(params, tokens, jnp.arange(tokens.shape[0]), config)
        return correct.error_quantiles(correct.logit_relative_errors(tfm.forward(params, tokens[None], cfg)[0], want))["q50"]

    cfg, params, tokens = seeded(arch, config, seed, jnp.bfloat16)
    right = q50(cfg, params, tokens)
    assert right < 0.03
    assert q50(cfg.replace(n_experts_per_tok=cfg.n_experts_per_tok - 1), params, tokens) > 5 * right
    no_qk_norm = q50(cfg.replace(qk_norm=False), params, tokens)
    assert no_qk_norm > 5 * right
    cfg, params, tokens = seeded(arch, config, seed, jnp.bfloat16, drawn=False)
    assert q50(cfg, params, tokens) < 0.03 and q50(cfg.replace(qk_norm=False), params, tokens) < no_qk_norm / 2
    assert correct.judge({"q50": right}, {"q50": 0.045}) and not correct.judge({"q50": float("nan")}, {"q50": 0.045})
    with pytest.raises(ValueError):
        correct.judge({"q50": right}, {})

"""Logit parity of the program against an architecture file's plain
reference, on seeded random float32 weights at the file's TINY widths on the
CPU: `forward` on a whole sequence, and `forward_prefill` then
`forward_decode` through the paged cache across a page boundary. Logits are
compared, not argmax: with random weights the largest logit flips on rounding.
A `model_config` PR copies this file's two tests for its own architecture.

TOLERANCE: both sides compute in float32 (the reference at matmul precision
"highest"), so they differ by float32 rounding through 2 layers of width 64.
Read over 12 seeds x 2 configurations x both paths (PR 26, CPU), on logits
up to 4.1 in size: the largest difference of the float32 program 2.7e-6; the
smallest of the control, the same program and weights in bfloat16, 2.7e-2.
1e-4 is 37 times the first and 1/266 of the second; each test also asserts
that its control is over 1e-3.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import spec
from ray_tpu.models import transformer as tfm

TOLERANCE = 1e-4
T = 8  # page tokens


def tiny(name, **changed):
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))
    config.update(spec.load_arch(config).TINY, **changed)
    return config, spec.load_arch(config)


def seeded(arch, config, seed, dtype):
    cfg = arch.model_config(config, dtype=dtype, remat=False)
    key = jax.random.PRNGKey(seed)
    params = tfm.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (19,), 0, cfg.vocab_size, jnp.int32)
    return cfg, params, tokens


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-L4", "deepseek-llm-7b-chat-L8"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_reference_logits(name, seed):
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, tokens = seeded(arch, config, seed, jnp.float32)
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


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-L4", "deepseek-llm-7b-chat-L8"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_then_decode_through_the_paged_cache_matches_the_reference_logits(name, seed):
    """A 13-token prompt ends inside its second page; six decode steps take
    the sequence into a third page (position 16)."""
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, tokens = seeded(arch, config, seed, jnp.float32)
    want = arch.logits_at(params, tokens, jnp.arange(12, tokens.shape[0]), config)
    assert worst(paged_logits(cfg, params, tokens, 13), want) <= TOLERANCE
    cfg16, params16, _ = seeded(arch, config, seed, jnp.bfloat16)
    assert worst(paged_logits(cfg16, params16, tokens, 13), want) > 10 * TOLERANCE

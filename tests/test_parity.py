"""Logit parity of the program against each benchmark configuration's plain
reference (benchmarks/archs/<arch>.py), on seeded random float32 weights at
the architecture file's TINY widths on the CPU: `forward` on a whole
sequence, and `forward_prefill` then `forward_decode` through the paged
cache across a page boundary. Logits are compared, not argmax: with random
weights the largest logit flips on rounding. This is tier-1's copy of
benchmarks/tests/test_parity.py (ROADMAP D7), over all three configurations.

TOLERANCE: both sides compute in float32 (the reference at matmul precision
"highest"), so they differ by float32 rounding through 2 layers of width 64.
Read over 12 seeds x 3 configurations x both paths (PR 27, CPU; every norm's
scale drawn from [0.5, 1.5], see `with_drawn_norm_scales`), on logits up to
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

from benchmarks.lib import spec
from ray_tpu.models import transformer as tfm

TOLERANCE = 1e-4
T = 8  # page tokens
CONFIGS = ["mistral-7b-v0.3-L4", "deepseek-llm-7b-chat-L8", "olmoe-1b-7b-0125-L2", "trinity-mini-L5"]


def tiny(name, **changed):
    config = spec.load_config(os.path.join(spec.BENCH_DIR, "configs", name + ".json"))
    config.update(spec.load_arch(config).TINY, **changed)
    return config, spec.load_arch(config)


def seeded(arch, config, seed, dtype):
    cfg = arch.model_config(config, dtype=dtype, remat=False)
    key = jax.random.PRNGKey(seed)
    params = with_drawn_norm_scales(tfm.init_params(key, cfg), jax.random.fold_in(key, 2))
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (19,), 0, cfg.vocab_size, jnp.int32)
    return cfg, params, tokens


def with_drawn_norm_scales(params, key):
    """init_params starts every norm's scale at 1, and an RMSNorm over a
    fan-in-scaled projection with unit scale is nearly the identity: draw the
    scales from [0.5, 1.5], so that a norm left out or misplaced (q/k-norm
    over the heads instead of the whole projection) shows in the logits."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    drawn = [
        jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5).astype(leaf.dtype)
        if "norm" in jax.tree_util.keystr(path) else leaf
        for i, (path, leaf) in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, drawn)


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
    # one executable for the six steps: eager, every call compiles its layer scans again
    decode = jax.jit(lambda toks, positions, pages: tfm.forward_decode(params, toks, positions, cfg, pages, tables))
    for pos in range(prompt_len, tokens.shape[0]):
        step, pages = decode(jnp.asarray([0, tokens[pos]], jnp.int32), jnp.asarray([-1, pos], jnp.int32), pages)
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


# ------------------------------------------------- prefill: chunks, hit and miss
#
# forward_prefill walks what the cache lacks of a prompt in chunks, the
# first starting where the cache ends. At the tiny widths a chunk is set to
# 16 tokens (two pages), so that a 37-token prompt in a 64-token bucket is
# three chunks as a miss and one, [24, 40), as a hit of 24 tokens (a page
# multiple, not a chunk multiple: laid on multiples of 16 its 13 tokens
# would touch two). name: (prompt tokens, of which the cache holds).
CHUNK = 16
PREFILL_CASES = {
    "miss": (37, 0),
    "hit_whose_one_chunk_runs_past_the_bucket": (30, 24),
    "hit_whose_suffix_lies_across_a_multiple_of_the_chunk": (37, 24),
    "hit_of_one_page": (37, 8),
    "hit_whose_second_chunk_runs_past_the_bucket": (60, 40),
    "whole_pages_fully_cached": (32, 32),
    "whole_pages_fully_cached_at_a_multiple_of_the_chunk": (24, 24),
    "bucket_smaller_than_a_chunk": (5, 0),
    "hit_in_a_bucket_smaller_than_a_chunk": (13, 8),
}
OWNER_PAGES, FRESH_PAGES = 1, 9  # the owner's table starts at page 1, a hit's own pages at page 9


def prefill_hit_or_miss(cfg, params, tokens, length, cached, monkeypatch):
    """The prompt's owner prefills it whole (ONE chunk, as a bucket was
    computed before there were chunks) into pages 1..; then the case:
    the same prompt, its first `cached` tokens in the owner's pages and its
    own pages from page 9 on, in chunks of CHUNK. Returns (the case's
    logits, its pool, its table, the owner's pool)."""
    bucket = 1 << max(0, (-(-length // T) - 1).bit_length())
    padded = jnp.zeros((1, bucket * T), jnp.int32).at[0, :length].set(tokens[:length])
    owner_table = jnp.arange(OWNER_PAGES, OWNER_PAGES + bucket, dtype=jnp.int32)
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", bucket * T)
    _, owner_pool = tfm.forward_prefill(
        params, padded, cfg, tfm.init_kv_pages(cfg, 20, T), owner_table, jnp.int32(length), jnp.int32(0))
    shared = cached // T
    table = jnp.concatenate([owner_table[:shared], jnp.arange(FRESH_PAGES, FRESH_PAGES + bucket - shared, dtype=jnp.int32)])
    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", CHUNK)
    assert tfm.prefill_chunk_pages(bucket, T) * T == min(CHUNK, bucket * T)
    logits, pool = tfm.forward_prefill(params, padded, cfg, owner_pool, table, jnp.int32(length), jnp.int32(cached))
    return logits[0], pool, table, owner_pool


def long_tokens(cfg, seed):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(seed), 3), (64,), 0, cfg.vocab_size, jnp.int32)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("case", PREFILL_CASES.values(), ids=PREFILL_CASES.keys())
def test_chunked_prefill_matches_the_reference_logits_and_the_whole_prompts_pages(name, case, monkeypatch):
    """Hit or miss, the last position's logits are the reference's, and
    every page the case wrote holds what the owner's whole-prompt prefill
    wrote for those positions (the positions below the length: the rest of
    the last page is padding's)."""
    length, cached = case
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, _ = seeded(arch, config, 0, jnp.float32)
    tokens = long_tokens(cfg, 0)
    assert_same_experts(arch, config, cfg, params, tokens[:length])
    want = arch.logits_at(params, tokens[:length], jnp.asarray([length - 1]), config)[0]
    logits, pool, table, owner_pool = prefill_hit_or_miss(cfg, params, tokens, length, cached, monkeypatch)
    assert worst(logits, want) <= TOLERANCE
    for j in range(cached // T, -(-length // T)):
        live = min(T, length - j * T)
        for kv in ("k", "v"):
            assert worst(pool[kv][:, table[j], :live], owner_pool[kv][:, OWNER_PAGES + j, :live]) <= TOLERANCE, (kv, j)
    # the control: the nearest precision below must fail
    cfg16, params16, _ = seeded(arch, config, 0, jnp.bfloat16)
    assert worst(prefill_hit_or_miss(cfg16, params16, tokens, length, cached, monkeypatch)[0], want) > 10 * TOLERANCE


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("case", [(37, 24), (60, 40), (32, 32)], ids=["one_chunk", "last_chunk_past_the_bucket", "fully_cached"])
def test_a_hits_prefill_leaves_the_shared_pages_bytes_as_they_were(name, case, monkeypatch):
    """A hit's chunks start at `write_from` (a page multiple, here no chunk
    multiple) and compute no row below it; the last may run past the bucket
    (positions 64..71 of a 64-token one), where its pages go to the trash
    page. Chunked k/v are not bit-equal to the owner's: no page of the
    owner's, shared or not, may be rewritten, and where the cache holds the
    whole prompt nothing but the trash page is."""
    length, cached = case
    config, arch = tiny(name, torch_dtype="float32")
    cfg, params, _ = seeded(arch, config, 1, jnp.bfloat16)
    _, pool, table, owner_pool = prefill_hit_or_miss(cfg, params, long_tokens(cfg, 1), length, cached, monkeypatch)
    owner = slice(OWNER_PAGES, FRESH_PAGES)
    for kv in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(pool[kv][:, owner], np.float32), np.asarray(owner_pool[kv][:, owner], np.float32))
        if cached < length:
            assert float(jnp.abs(pool[kv][:, table[cached // T]].astype(jnp.float32)).max()) > 0, "the hit wrote its own pages"
        else:
            assert float(jnp.abs(pool[kv][:, FRESH_PAGES:].astype(jnp.float32)).max()) == 0, "nothing to write"


def test_the_chunk_span_on_python_ints_and_on_traced_scalars_is_the_uncached_span_in_chunks():
    """`prefill_chunk_span` has two callers, the device loop (traced scalars)
    and PagedLM's count of computed tokens (Python ints): one result. The
    anchor is where the cache ends, or the last position's page where the
    cache holds everything; the count is ceil((length - anchor) / chunk); a
    miss walks the chunks it always walked."""
    traced = jax.jit(lambda length, cached, chunk: tfm.prefill_chunk_span(length, cached, chunk, T, jnp.minimum, jnp.maximum), static_argnums=2)
    grid = [(length, cached, chunk) for chunk in (T, 2 * T, 4 * T) for length in (1, 7, 8, 9, 31, 32, 33, 60, 64)
            for cached in range(0, length + 1, T)]
    for length, cached, chunk in grid:
        anchor, count = tfm.prefill_chunk_span(length, cached, chunk, T)
        assert (anchor, count) == tuple(int(x) for x in traced(jnp.int32(length), jnp.int32(cached), chunk)), (length, cached, chunk)
        assert anchor == min(cached, (length - 1) // T * T) and count == -(-(length - anchor) // chunk)
        assert anchor <= length - 1 < anchor + count * chunk
        if cached == 0:
            assert (anchor, count) == (0, (length - 1) // chunk + 1)

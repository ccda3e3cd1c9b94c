"""Model adapters for the LLM engine.

The engine (engine.py) schedules against a tiny protocol — an object
with `prefill(prompt, pages, cached_tokens) -> token` and
`decode(last_tokens, positions, block_tables) -> tokens` plus the pool
geometry attributes — so the scheduler is testable without JAX and the
JAX path stays a thin adapter over models/transformer.py.

The protocol's arguments and results are plain lists and ints to a model
that wants no more. What else the two sides tell each other rides on them as
attributes, so a wrapper that hands them on unopened carries it through.
Model to engine: `PrefillToken.computed_tokens`, `DecodeTokens.counters`.
Engine to model: `StepTokens.step`; `PromptTokens.slot`, the decode row the
engine gave the sequence at admission and keeps for it until it finishes
(row i of every later `decode`: a model that keeps a fixed state a sequence
beside its pages keeps it by that row; None from any other caller); and on
`StepTokens` and `PromptTokens` alike `launched`, a callable the model may call once its executable has
been dispatched and before it blocks for the result. The engine uses that
instant to make the sink calls of the step before, so the streams they wake
run while the chip works (engine.py, `_launched`). A model that ignores it
loses nothing: the engine sees that no launch was announced and delivers
every step at the end of that step, as it always did. And
`StepTokens.deferred`: the engine takes a decode step's result unread
(`PendingTokens`, which resolves on its first read) from a model that can
launch without waiting, and then launches the next step before it reads this
one, a continuing row's token taken on the device from this step's output
(-1 in the host's vector). A model that returns its list is served one step
deep, as ever.

PagedLM is the real path: one jitted decode step at static shapes
([max_slots] tokens, [max_slots, max_pages_per_seq] block tables, the
whole page pool, the step before's output vector) serves every batch
composition and every caller, deferring or not; prefill compiles per
power-of-two page bucket, so compile count is O(log max_seq), not
O(distinct prompt lengths). A bucket's one executable serves hit and miss
alike: it walks what the cache lacks in chunks that start at `cached_tokens`
(transformer.forward_prefill), so a hit computes its uncached span rounded
up to chunks and nothing below it; what it did compute rides back on the
token it returns (`PrefillToken.computed_tokens`).
"""

from __future__ import annotations

import math
import threading
from typing import Any, Dict, List, Optional, Sequence

from ... import tracing as _tracing
from ...exceptions import EngineFailedError
from .kv_cache import TRASH_PAGE


class PrefillToken(int):
    """What `PagedLM.prefill` returns: the first generated token, an int to
    every caller, which also says how many positions the prefill
    executable computed for it (whole chunks from `cached_tokens` on: the
    last chunk's padding included, cached positions not). The adapter protocol has one
    return value and wrappers hand it on unopened, so this is where the
    engine's `clocks.prefill.computed_tokens` reads it; `counters`, as
    `DecodeTokens` carries them (a state model's chunks: `prefill_state`)."""

    def __new__(cls, token: int, computed_tokens: int, counters: Optional[Dict[str, Dict[str, int]]] = None):
        self = super().__new__(cls, token)
        self.computed_tokens = computed_tokens
        self.counters = counters or {}
        return self


class DecodeTokens(list):
    """What `PagedLM.decode` returns for a model with routed experts,
    attention windows or a recurrent state: the slots' next tokens, a list to
    every caller, which also carries what the step's router, windows or
    states did, as counters the engine adds up under `stats()["clocks"]` by
    name (`PrefillToken`'s way through wrappers). A model with none of them
    returns a plain list."""

    def __init__(self, tokens, counters: Dict[str, Dict[str, int]]):
        super().__init__(tokens)
        self.counters = counters


class StepTokens(list):
    """What the engine hands `decode` as `last_tokens`: the slots' last
    tokens, a list to every model, which also says which decode step of the
    engine this is (`step`: its `decode_steps` ordinal), so that a model with
    spans of its own around the executable carries the ordinal on them
    (DecodeTokens' way through wrappers, in the other direction), and carries
    `launched`: a callable of no arguments for the model to call on the
    calling thread once the step's executable has been dispatched, before it
    waits for the result (None from a caller with nothing to do then). It
    returns quickly and does not raise. A model that calls it must call it
    in every `decode` and `prefill` that launches; a call that comes back
    without it is taken as the end of that. A model that never calls it is
    served exactly as one without the attribute.

    `deferred`: the caller takes a result that is not read yet (PendingTokens)
    and reads it when it needs the tokens' values. Such a caller may put -1
    in the row of a sequence that continues from the decode step launched
    before this one: the row's token is then taken on the device, from that
    step's output. A model that cannot launch without waiting ignores the
    field, returns its list, and is never handed a -1: the caller marks a row
    only behind a step whose result came back pending."""

    def __init__(self, tokens, step: int, launched=None, deferred: bool = False):
        super().__init__(tokens)
        self.step = step
        self.launched = launched
        self.deferred = deferred


class PendingTokens:
    """What `PagedLM.decode` returns to a caller that asked for it
    (`StepTokens.deferred`): a launched step's result, still on its way. It
    is read once, at the first look at it (`resolve()`, or any read as a
    list: a length, an index, an iteration, `counters`), which waits for the
    step's execution to end; from then on it is the list (or DecodeTokens)
    that a caller who did not ask is returned at once."""

    def __init__(self, read):
        self._read, self._tokens = read, None

    def resolve(self) -> List[int]:
        if self._read is not None:
            self._tokens = self._read()  # a read that raises is made again by the next look
            self._read = None
        return self._tokens

    @property
    def counters(self) -> Dict[str, Dict[str, int]]:
        return getattr(self.resolve(), "counters", {})

    def __len__(self):
        return len(self.resolve())

    def __iter__(self):
        return iter(self.resolve())

    def __getitem__(self, i):
        return self.resolve()[i]

    def __repr__(self):
        return "PendingTokens(<in flight>)" if self._read is not None else f"PendingTokens({self._tokens!r})"


class PromptTokens(list):
    """What the engine hands `prefill` as `prompt`: the prompt's tokens, a
    list to every model, with the same `launched` as StepTokens carries and
    `slot`, the decode row the engine admitted the sequence to (set at
    admission, before the prefill; None until then)."""

    def __init__(self, tokens, launched=None, slot=None):
        super().__init__(tokens)
        self.launched = launched
        self.slot = slot


class StubModel:
    """Deterministic, JAX-free model for scheduler/chaos tests and the
    engine's disarmed-cost bench: next token = (last + 1) % vocab.
    `step_delay_s` simulates decode latency so tests can observe
    continuous batching join/leave behaviour."""

    def __init__(
        self,
        *,
        vocab: int = 256,
        max_slots: int = 4,
        max_pages_per_seq: int = 8,
        step_delay_s: float = 0.0,
    ):
        self.vocab = vocab
        self.max_slots = max_slots
        self.max_pages_per_seq = max_pages_per_seq
        self.step_delay_s = step_delay_s
        self.prefill_calls = 0
        self.decode_calls = 0

    def prefill(self, prompt: Sequence[int], pages: Sequence[int], cached_tokens: int) -> int:
        self.prefill_calls += 1
        return (sum(prompt) + 1) % self.vocab

    def decode(self, last_tokens, positions, block_tables) -> List[int]:
        self.decode_calls += 1
        if self.step_delay_s:
            import time

            time.sleep(self.step_delay_s)
        return [
            (int(t) + 1) % self.vocab if int(p) >= 0 else 0
            for t, p in zip(last_tokens, positions)
        ]


class PagedLM:
    """Paged-KV inference adapter over models/transformer.py.

    Owns the physical page pool (init_kv_pages) and the compiled
    prefill/decode steps; the engine owns the page bookkeeping and passes
    block tables in. Greedy sampling runs inside the jit (argmax) so only
    int32 tokens cross the host boundary per step.

    What a page is depends on what the model's layers keep, and
    `transformer.cache_layout(cfg)` (`self.layout`) is the one place that
    says: this class names no model family. K/V pages (`layout.kv`): a page
    holds `page_tokens` positions, a sequence's block table grows by a page
    as it fills, and full prompt pages are shared by prefix. A recurrent
    state (`layout.state`) is of a fixed size whatever the sequence's length
    and nothing of it is kept at a page's border, so such a model shares
    nothing: every prompt is computed whole (`shares_prefix_pages` is False,
    which the engine asks of a model that has it). Where a state is all the
    model keeps, a page is ONE sequence's whole state: `page_tokens` is the
    positions a sequence may reach, `max_pages_per_seq` is 1, so the
    allocator hands a sequence exactly one page for its life and `pages[0]` /
    `block_tables[i][0]` names it. Leaves indexed by slot (`layout.indexed`)
    lie in `max_slots + 1` state slots, which no allocator hands out: decode
    row i's is slot i + 1, a prefill writes the slot of the row the engine
    admitted its prompt to (`PromptTokens.slot`), and slot 0 is the trash
    slot, which a caller's bare list writes exactly as its pages are the
    trash page.
    """

    def __init__(
        self,
        cfg=None,
        params=None,
        *,
        seed: int = 0,
        num_pages: int = 128,
        page_tokens: int = 16,
        max_slots: int = 4,
        max_pages_per_seq: int = 8,
    ):
        import jax
        import jax.numpy as jnp

        from ...models import transformer as tfm
        from ...utils import compile_cache

        self._jax, self._jnp, self._tfm = jax, jnp, tfm
        self._compile_watch = compile_cache.watch()
        if cfg is None:
            cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32)
        self.cfg = cfg
        if params is None:
            params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self.vocab = cfg.vocab_size
        self.num_pages = num_pages
        self.page_tokens = page_tokens
        self.max_slots = max_slots
        self.max_pages_per_seq = max_pages_per_seq
        self.layout = layout = tfm.cache_layout(cfg)
        # What the benchmark's readers know a cache by: describe()["cache"]["kind"], and the executables' names' suffix.
        self.cache_kind, self._suffix = {
            (False, True): ("kv_pages", ""), (True, False): ("state", "_state"), (True, True): ("state+kv_pages", "_hybrid"),
        }[layout.state, layout.kv]
        if not layout.kv and max_pages_per_seq != 1:
            raise ValueError(f"a page of a model that keeps only a state is a sequence's whole state: max_pages_per_seq is 1, not {max_pages_per_seq}")
        self.kv = tfm.init_kv_pages(cfg, num_pages, page_tokens, max_slots + 1)
        slotted = [name for name, indexed in layout.indexed.items() if indexed == "slot"]
        # One page over all layers: page_tokens positions of K/V, or one sequence's whole state.
        self.page_bytes = sum(leaf.nbytes for name, leaf in self.kv.items() if name not in slotted) // num_pages
        # The slots' leaves: what one sequence keeps there over all their layers.
        self.state_bytes = sum(self.kv[name].nbytes for name in slotted) // (max_slots + 1)
        # Layers whose pages hold latent rows (transformer.KINDS["latent"]): their counters below.
        self._latent_layers = dict(layout.kinds).get("latent", 0)
        # How often a decode step moves a live row's slot: a recurrent state is read and written whole, a window's
        # ring (transformer.KINDS["window"]) is read whole and takes one row.
        self._state_passes = 1 if "window" in dict(layout.kinds) else 2
        self._decode_jit = None
        self._prefill_jits: Dict[int, Any] = {}
        # Positions of a big prefill chunk (transformer.prefill_big_chunk_tokens; 0: this model's weights ask for no more
        # rows a pass than the small chunk's) and the ONE executable that walks them, whatever the prompt's bucket.
        self._big_chunk = tfm.prefill_big_chunk_tokens(cfg, page_tokens)
        self._prefill_big_jit = None
        # The last decode step's result vector, on the device: the next step's
        # operand, from which a row marked -1 takes its token (StepTokens
        # `deferred`). Until a step has run, zeros of that vector's shape (the
        # slots' tokens, and behind them a routed model's counters:
        # forward_decode's `stats`, one, or two under a share of the experts),
        # so that every call, the first and a bare list's too, runs one executable.
        n_counts = (2 if cfg.experts_held != cfg.n_experts else 1) if cfg.n_experts else 0
        self._prev = jnp.zeros((max_slots + n_counts,), jnp.int32)
        # One lock around every launch (a jitted call and the pool it leaves
        # installed): the engine loop is the only steady-state caller, but
        # tests poke prefill directly. No result is waited for under it.
        self._mu = threading.Lock()

    @property
    def shares_prefix_pages(self) -> bool:
        """Whether a full page of one prompt may serve another (the engine asks)."""
        return not self.layout.state

    def describe(self) -> Dict[str, Any]:
        """Which process and devices serve this model, what its cache is
        (`cache`: "kv_pages", a page `page_tokens` positions of K/V, or
        "state", a page one sequence's whole recurrent state; the bytes of a
        page over all layers either way; or "state+kv_pages", both:
        `page_bytes` of a page of K/V (or of latent rows: whatever its
        page-indexed layers keep) and `state_bytes` of what one sequence
        keeps in its state slot), which expression the decode and prefill
        executables run over the pages (`decode_attention`: "paged_kernel" or
        "xla_gather", transformer.paged_attention_path; "latent_kernel" or
        "xla_gather" over latent pages; "retention_kernel" or "xla_step" over
        a state, whose prefill chunk says so apart: `prefill_attention`,
        "retention_kernel" or "xla_chunk") and over the state slots (`decode_state`:
        "kda_kernel" or "xla_step") or the window layers' rings (`decode_window`:
        "xla_ring"; `state_bytes` is then one sequence's rings): each kind's own
        answer, `KINDS`; a stack of state or ring layers beside K/V or latent
        pages reports BOTH, `decode_state` or `decode_window` and
        `decode_attention`), and
        what compiling cost so far (LLMServer.engine_stats() carries it out)."""
        import os

        devs = self._jax.devices()
        paths = {**self._tfm.decode_paths(self.cfg, self.page_tokens), **self._tfm.prefill_paths(self.cfg, self.page_tokens)}
        cache = {"kind": self.cache_kind, **({"state_bytes": self.state_bytes} if self.state_bytes else {}), "page_bytes": self.page_bytes}
        return {
            "pid": os.getpid(),
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "cache": cache,
            **paths,
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs
            ],
            "compile": self._compile_watch.snapshot(),
        }

    # ------------------------------------------------------------- compile

    def _donate(self, argnums):
        # Buffer donation keeps the page pool from doubling per step on
        # TPU; the CPU backend does not implement donation and would warn
        # on every call.
        if self._jax.default_backend() == "cpu":
            return ()
        return argnums

    def _get_decode(self):
        if self._decode_jit is None:
            cfg, tfm = self.cfg, self._tfm

            def step(params, tokens, positions, kv, block_tables, prev):
                # A row that continues from the step before (-1 from the host) takes the token that step left here.
                tokens = self._jnp.where(tokens >= 0, tokens, prev[: tokens.shape[0]])
                logits, kv, *stats = tfm.forward_decode(
                    params, tokens, positions, cfg, kv, block_tables, stats=bool(cfg.n_experts)
                )
                out = self._jnp.argmax(logits, axis=-1).astype(self._jnp.int32)
                if stats:  # a routed model: the experts the step touched ride behind the tokens, one transfer
                    out = self._jnp.concatenate([out, *(count[None] for count in stats[0].values())])
                return out, kv

            # The executable's name in a device trace (line `XLA Modules`:
            # jit_llm_decode), where every jitted closure called `step` reads alike.
            # Each cache layout's executables under names of their own (llm_decode_state, llm_decode_hybrid).
            step.__name__ = "llm_decode" + self._suffix
            self._decode_jit = self._jax.jit(step, donate_argnums=self._donate((3,)))
        return self._decode_jit

    def _get_prefill(self, n_pages_bucket: int):
        fn = self._prefill_jits.get(n_pages_bucket)
        if fn is None:
            cfg, tfm = self.cfg, self._tfm

            def step(params, tokens, kv, block_table, length, write_from, *slot):
                logits, kv = tfm.forward_prefill(
                    params, tokens, cfg, kv, block_table, length, write_from, *slot
                )
                return self._jnp.argmax(logits[0], axis=-1).astype(self._jnp.int32), kv

            # jit_llm_prefill_p<pages>, a bucket a name (jit_llm_prefill_state_p1, jit_llm_prefill_hybrid_p<pages>)
            step.__name__ = f"llm_prefill{self._suffix}_p{n_pages_bucket}"
            fn = self._jax.jit(step, donate_argnums=self._donate((2,)))
            self._prefill_jits[n_pages_bucket] = fn
        return fn

    def _get_prefill_big(self):
        if self._prefill_big_jit is None:
            cfg, tfm = self.cfg, self._tfm

            def step(params, tokens, kv, block_table, length, write_from, chunks, *slot):
                _, kv = tfm.forward_prefill(params, tokens, cfg, kv, block_table, length, write_from, *slot, big_chunks=chunks)
                return kv

            # jit_llm_prefill_big (jit_llm_prefill_hybrid_big): one for every bucket, over the longest block table
            step.__name__ = f"llm_prefill{self._suffix}_big"
            self._prefill_big_jit = self._jax.jit(step, donate_argnums=self._donate((2,)))
        return self._prefill_big_jit

    def _bucket_pages(self, n_pages: int) -> int:
        return min(self.max_pages_per_seq, 1 << max(0, math.ceil(math.log2(n_pages))))

    # --------------------------------------------------------------- steps

    def _launch(self, call, span: str, attrs=None, chain: bool = False):
        """Dispatches one jitted step `call(kv) -> (out, new_kv)` and installs
        the new pool, which the next launch takes whether or not this one's
        result has been read: the device runs one stream in launch order.
        `<span>.dispatch` is the jitted call returning. The copy of `out` to
        the host is asked for here, so that it travels when this execution
        ends and is not queued behind a newer one's. Returns (out, the pool
        given in), for `_read`. `chain`: `out` is the next decode step's `prev`."""
        with self._mu:
            kv = self.kv
            if kv is None:
                raise EngineFailedError("KV page pool was lost in an earlier failed step")
            try:
                with _tracing.span(span + ".dispatch", attrs, device=True):
                    out, new_kv = call(kv)
            except Exception as e:
                self._pool_lost(kv, e)
                raise
            self.kv = new_kv
            if chain:
                self._prev = out
        out.copy_to_host_async()
        return out, kv

    def _read(self, out, kv, span: str, attrs=None):
        """Waits for a launched step's tokens on the host: `<span>.wait`, the
        transfer of `out` (device annotations both, so an idle gap of the chip
        can be put down to one of them or to the caller's `.prep`). Dispatch
        is asynchronous: a device-side failure surfaces here, not at the
        call. Takes no lock: a step may be read from the hook of a newer
        launch (engine.py)."""
        import numpy as np

        try:
            with _tracing.span(span + ".wait", attrs, device=True):
                return np.asarray(out)
        except Exception as e:
            self._pool_lost(kv, e)
            raise

    def _pool_lost(self, kv, e: BaseException) -> None:
        """If a step raised after the pool `kv` was donated into it, the pool
        buffer is deleted and nothing can be served any more: that is an
        EngineFailedError, not a per-request error."""
        if any(leaf.is_deleted() for leaf in self._jax.tree_util.tree_leaves(kv)):
            self.kv = None
            raise EngineFailedError(
                "jitted step failed after the KV page pool was donated "
                f"to it; the pool is gone ({type(e).__name__}: {e})"
            ) from e

    def _run_step(self, call, span: str, attrs=None, launched=None):
        """Runs one jitted step `call(kv) -> (tokens, new_kv)` and waits for
        its tokens on the host. Between the launch and the wait the caller's
        `launched`, if it gave one, is told that the chip has work and this
        thread is about to block (StepTokens)."""
        out, kv = self._launch(call, span, attrs)
        if launched is not None:
            launched()
        return self._read(out, kv, span, attrs)

    def prefill(self, prompt: Sequence[int], pages: Sequence[int], cached_tokens: int) -> PrefillToken:
        """The prompt's first generated token. Positions below
        `cached_tokens` are read from `pages` (the radix cache matched
        them); the rest is computed and written, in chunks from there on."""
        import numpy as np

        T = self.page_tokens
        n_pages = max(1, -(-len(prompt) // T))
        bucket = self._bucket_pages(n_pages)
        S = bucket * T
        if self.layout.state and cached_tokens:
            raise ValueError("a model with a recurrent state keeps none at a page's border, so no prefix is shared: cached_tokens is 0")
        # Leaves in state slots: the slot of the decode row the engine admitted this prompt to; the trash slot for a bare list.
        row = getattr(prompt, "slot", None)
        slot = (np.int32(TRASH_PAGE if row is None else row + 1),) if "slot" in self.layout.indexed.values() else ()
        chunk, granule = self._tfm.prefill_chunk_tokens(self.cfg, bucket, T)
        # A long span's head in big chunks: all of the span's big chunks but the last, which holds the last position and,
        # whole or not, is walked in small ones behind them by the bucket's executable, from where the big ones ended.
        big, small_from = self._big_chunk, int(cached_tokens)
        start, n_big = self._tfm.prefill_chunk_span(len(prompt), small_from, big or chunk, granule)  # where the cache ends
        n_big = n_big - 1 if big else 0
        if n_big:
            small_from = start + n_big * big
        _, chunks = self._tfm.prefill_chunk_span(len(prompt), small_from, chunk, granule)
        computed = n_big * big + chunks * chunk
        attrs = {"bucket_tokens": S, "computed_tokens": computed, "big_chunks": n_big, "small_chunks": chunks}
        with _tracing.span("llm.prefill.prep", attrs, device=True):
            toks = np.zeros((1, S), dtype=np.int32)
            toks[0, : len(prompt)] = np.asarray(prompt, dtype=np.int32)
            bt = np.full((bucket,), TRASH_PAGE, dtype=np.int32)
            bt[: len(pages)] = np.asarray(pages, dtype=np.int32)
            fn = self._get_prefill(bucket)
            if n_big:  # the same prompt and table at the longest bucket's shapes: the big chunks' one executable
                longest = self._bucket_pages(self.max_pages_per_seq)
                big_toks, big_bt = np.zeros((1, longest * T), dtype=np.int32), np.full((longest,), TRASH_PAGE, dtype=np.int32)
                big_toks[0, :S], big_bt[:bucket] = toks[0], bt
                big_fn = self._get_prefill_big()

        def call(kv):
            if n_big:  # launched first; the bucket's call queues behind it on the pool it hands on
                kv = big_fn(self.params, big_toks, kv, big_bt, np.int32(len(prompt)), np.int32(cached_tokens), np.int32(n_big), *slot)
            return fn(self.params, toks, kv, bt, np.int32(len(prompt)), np.int32(small_from), *slot)

        tok = self._run_step(call, "llm.prefill", attrs, getattr(prompt, "launched", None))  # the engine's PromptTokens; a bare list from anyone else
        counters = {"prefill_chunks": {"big": n_big, "small": chunks, "big_rows": n_big * big, "rows": computed}}
        chunks += n_big
        if self.layout.state:
            # The chunks that started from the state their predecessor left (all but a prompt's first).
            counters["prefill_state"] = {"chunks": chunks, "carried_in": chunks - 1}
        if self.cfg.n_experts:
            # A routed model: the rows its routed layers' experts were handed, and those of them sorted to their own experts.
            routed_layers = self.cfg.n_layers - self.cfg.n_dense_layers
            grouped = sum(n * rows for n, rows in ((n_big, big), (chunks - n_big, chunk)) if self._tfm.experts_grouped_at(rows))
            counters["prefill_experts"] = {"rows": computed * routed_layers, "grouped_rows": grouped * routed_layers, "chunks": chunks}
        if self._latent_layers:
            # The (query, key) pairs of the call's computed rows below the length, a latent layer each (every one
            # attended absorbed: transformer._latent_chunk is the one serving form).
            n, first = len(prompt), min(start, len(prompt))
            pairs = self._latent_layers * (n * (n + 1) - first * (first + 1)) // 2
            counters["prefill_latent"] = {"pairs": pairs, "calls": 1}
        return PrefillToken(tok, attrs["computed_tokens"], counters)

    def decode(self, last_tokens, positions, block_tables) -> List[int]:
        """The slots' next tokens. One executable whatever the caller: it
        takes the host's tokens and, in a row the host marks -1, the token the
        decode step before left on the device (`self._prev`). A caller's bare
        list, and a StepTokens that does not ask, is served its list: dispatch,
        the `launched` hook, the wait. A StepTokens that asks (`deferred`) is
        returned a PendingTokens behind the hook, and the wait
        (`llm.decode.wait`, carrying the `step` of the step READ) is made
        where the caller first reads it."""
        import numpy as np

        B, P = self.max_slots, self.max_pages_per_seq
        with _tracing.span("llm.decode.prep", device=True):
            toks = np.zeros((B,), dtype=np.int32)
            pos = np.full((B,), -1, dtype=np.int32)
            bts = np.full((B, P), TRASH_PAGE, dtype=np.int32)
            toks[: len(last_tokens)] = np.asarray(last_tokens, dtype=np.int32)
            pos[: len(positions)] = np.asarray(positions, dtype=np.int32)
            for i, row in enumerate(block_tables):
                bts[i, : len(row)] = np.asarray(row, dtype=np.int32)
            fn = self._get_decode()
        step = getattr(last_tokens, "step", None)  # the engine's StepTokens; a bare list from anyone else
        attrs = None if step is None else {"step": step}
        out, kv = self._launch(lambda kv: fn(self.params, toks, pos, kv, bts, self._prev), "llm.decode", attrs, chain=True)
        result = PendingTokens(lambda: self._step_tokens(self._read(out, kv, "llm.decode", attrs), pos))
        launched = getattr(last_tokens, "launched", None)
        if launched is not None:
            launched()
        return result if getattr(last_tokens, "deferred", False) else result.resolve()

    def _step_tokens(self, out, pos) -> List[int]:
        """A decode step's result vector as the protocol's list, with what the
        step's router, windows or states did (`pos`: the positions it ran at)."""
        import numpy as np

        B = self.max_slots
        tokens, cfg, counters = [int(t) for t in out[:B]], self.cfg, {}
        if cfg.n_experts:
            # Every row of the step is routed, the inactive slots' too.
            routed_layers = cfg.n_layers - cfg.n_dense_layers
            picks = routed_layers * B * cfg.n_experts_per_tok
            counters["decode_experts"] = {
                "touched": int(out[B]), "held": routed_layers * cfg.experts_held, "steps": 1,
                # of the rows' choices, those that fell on experts held here: all of them unless this is a share
                "picks": picks, "held_picks": int(out[B + 1]) if len(out) > B + 1 else picks,
            }
        if any(cfg.windows):
            live = pos[pos >= 0].astype(np.int64) + 1  # each live row's K/V length
            reach = np.asarray([w or self._tfm.NO_WINDOW for w in cfg.windows], np.int64)
            counters["decode_window"] = {
                "kv_read": int(np.minimum(live[None, :], reach[:, None]).sum()),
                "kv_live": int(cfg.n_layers * live.sum()),
            }
        if self.layout.state:
            # Every live row's state of every layer is read once and written once (its slot's, or its one page); a ring is read.
            live = int((pos >= 0).sum())
            counters["decode_state"] = {"bytes": self._state_passes * live * (self.state_bytes or self.page_bytes), "live_slots": live, "steps": 1}
        if self.layout.state and self.layout.kv:
            # The K/V the live rows read beside their states: every position up to their own, a page's bytes / page_tokens each.
            kv_tokens = int((pos[pos >= 0].astype(np.int64) + 1).sum())
            counters["decode_kv"] = {"bytes": kv_tokens * self.page_bytes // self.page_tokens, "tokens": kv_tokens, "steps": 1}
        if self._latent_layers:
            # The latent rows the live rows read: every position up to their own, in every latent layer, unpadded.
            positions = int((pos[pos >= 0].astype(np.int64) + 1).sum())
            counters["decode_latent"] = {
                "bytes": positions * self._latent_layers * self._tfm.latent_position_bytes(cfg), "positions": positions, "steps": 1,
            }
        return DecodeTokens(tokens, counters) if counters else tokens


def tiny_paged_lm(**kw) -> PagedLM:
    """Builder for deployments/tests: the CI-sized transformer on the
    paged decode path (picklable by reference for serve deploy blobs)."""
    return PagedLM(**kw)


def stub_model(**kw) -> StubModel:
    return StubModel(**kw)

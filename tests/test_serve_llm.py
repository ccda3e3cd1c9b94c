"""LLM inference engine tests: paged KV allocator, continuous-batching
scheduler, cancellation/backpressure semantics, the channel feed path,
and the serve-facing deployment (serve/llm/*).

Scheduler tests run on StubModel (JAX-free, deterministic: prefill =
(sum(prompt)+1) % vocab, decode = last+1) so they exercise pure
scheduling logic fast; decode-vs-forward numerics live in
test_models.py::test_paged_decode_matches_full_forward.
"""

import collections
import os
import random
import threading
import time

import pytest

from ray_tpu.exceptions import (
    ActorDiedError,
    BackpressureError,
    BatchItemError,
    KVPoolExhaustedError,
    RayTpuError,
)
from ray_tpu.serve.llm import (
    EngineConfig,
    InferenceEngine,
    LLMClient,
    PagedKVAllocator,
    StubModel,
)
from ray_tpu.utils import internal_metrics as imet


def _wait_for(predicate, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        last = predicate()
        if last:
            return last
        time.sleep(interval)
    return last


@pytest.fixture
def rt():
    import ray_tpu as rtpu
    from ray_tpu import serve

    rtpu.shutdown()
    rtpu.init(local_mode=True, num_cpus=8)
    yield rtpu
    serve.shutdown()
    rtpu.shutdown()


# ------------------------------------------------------------- allocator


def test_allocator_basic_alloc_release():
    a = PagedKVAllocator(num_pages=8, page_tokens=4)
    assert a.total_pages == 7  # page 0 is the trash page
    assert a.pages_for(1) == 1 and a.pages_for(4) == 1 and a.pages_for(5) == 2
    sp = a.allocate(list(range(10)))  # 3 pages
    assert sp.num_pages == 3
    assert 0 not in sp.pages  # trash page never handed out
    assert a.used_pages() == 3
    a.release(sp)
    assert a.used_pages() == 0
    assert a.free_pages() == 7
    a.release(sp)  # idempotent (cancel path races the finish path)
    assert a.free_pages() == 7


def test_allocator_exhaustion_typed_and_atomic():
    a = PagedKVAllocator(num_pages=4, page_tokens=4)  # 3 usable pages
    sp = a.allocate(list(range(8)))  # 2 pages
    with pytest.raises(KVPoolExhaustedError) as ei:
        a.allocate(list(range(100, 108)))  # needs 2, only 1 free
    assert isinstance(ei.value, BackpressureError)
    assert ei.value.needed_pages == 2 and ei.value.free_pages == 1
    # Failed allocation reserved nothing.
    assert a.used_pages() == 2
    ok = a.allocate(list(range(200, 204)))  # 1 page still fits
    a.release(ok)
    a.release(sp)


def test_allocator_prefix_reuse_and_eviction():
    a = PagedKVAllocator(num_pages=10, page_tokens=4)
    system = list(range(8))  # two full pages of shared prefix
    s1 = a.allocate(system + [50, 51])
    a.commit(s1, system + [50, 51])
    shared = s1.pages[:2]

    # Live sharing: a second prompt with the same prefix maps onto the
    # same physical pages and only pays for its private tail.
    s2 = a.allocate(system + [60])
    assert s2.pages[:2] == shared
    assert s2.cached_tokens == 8
    assert a.prefix_hits == 2
    a.release(s1)
    assert a.used_pages() == 3  # shared pages still referenced by s2
    a.release(s2)

    # Released-but-indexed pages revive from the eviction LRU for free.
    s3 = a.allocate(system + [70])
    assert s3.pages[:2] == shared
    a.release(s3)

    # Allocation pressure evicts cold cached pages instead of shedding.
    big = a.allocate(list(range(100, 136)))  # 9 pages = whole pool
    assert big.num_pages == 9
    a.release(big)


def test_allocator_commit_concurrent_twin_keeps_private_pages():
    a = PagedKVAllocator(num_pages=8, page_tokens=4)
    p = list(range(4))
    s1 = a.allocate(p)
    s2 = a.allocate(p)  # before s1 commits: no index entry yet, fresh page
    assert s1.pages != s2.pages
    a.commit(s1, p)
    a.commit(s2, p)  # loses the race; its page stays private
    a.release(s1)
    a.release(s2)
    s3 = a.allocate(p)
    assert s3.pages == s1.pages  # the committed winner is the shared copy
    a.release(s3)


class _NestedTupleIndex:
    """The allocator as it stood before the trie, kept here as a plain
    reference model: page i of a prompt is keyed by the nested tuple
    (key of page i-1, tokens of page i), () for the root. Independent of
    ray_tpu.serve.llm.kv_cache: the equivalence test holds the allocator
    to what this returns, call by call."""

    def __init__(self, num_pages, page_tokens):
        self.T, self.total = page_tokens, num_pages - 1
        self.free = list(range(num_pages - 1, 0, -1))
        self.ref, self.index, self.page_key = {}, {}, {}
        self.evictable = collections.OrderedDict()
        self.hits = self.misses = self.evictions = 0

    def _keys(self, tokens):
        key = ()
        for i in range(len(tokens) // self.T):
            key = (key, tuple(tokens[i * self.T:(i + 1) * self.T]))
            yield i, key

    def _take(self):
        if self.free:
            return self.free.pop()
        page, _ = self.evictable.popitem(last=False)
        del self.index[self.page_key.pop(page)]
        self.evictions += 1
        return page

    def allocate(self, tokens):
        """(pages, cached_tokens), or ("exhausted", needed, free, total) with nothing reserved."""
        matched = []
        for _, key in self._keys(tokens):
            if key not in self.index:
                break
            matched.append(self.index[key])
        fresh = max(1, -(-len(tokens) // self.T)) - len(matched)
        free = len(self.free) + len(self.evictable) - sum(p in self.evictable for p in matched)
        if fresh > free:
            return ("exhausted", fresh, free, self.total)
        for p in matched:
            self.evictable.pop(p, None)
            self.ref[p] = self.ref.get(p, 0) + 1
        pages = matched + [self._take() for _ in range(fresh)]
        for p in pages[len(matched):]:
            self.ref[p] = 1
        self.hits, self.misses = self.hits + len(matched), self.misses + fresh
        return pages, len(matched) * self.T

    def extend(self, pages):
        if not self.free and not self.evictable:
            return ("exhausted", 1, 0, self.total)
        pages.append(self._take())
        self.ref[pages[-1]] = 1
        return pages[-1]

    def commit(self, pages, tokens):
        for i, key in self._keys(tokens):
            cur = self.index.get(key)
            if cur is None and pages[i] not in self.page_key:
                self.index[key], self.page_key[pages[i]] = pages[i], key
            elif cur != pages[i]:
                break

    def release(self, pages):
        for p in pages:
            self.ref[p] -= 1
            if not self.ref[p]:
                del self.ref[p]
                if p in self.page_key:
                    self.evictable[p] = None
                else:
                    self.free.append(p)

    def stats(self):
        return {"total_pages": self.total, "used_pages": len(self.ref), "free_pages": len(self.free),
                "evictable_pages": len(self.evictable), "indexed_pages": len(self.page_key),
                "prefix_hits": self.hits, "prefix_misses": self.misses}


def _exhausted(call):
    try:
        return call()
    except KVPoolExhaustedError as e:
        return ("exhausted", e.needed_pages, e.free_pages, e.total_pages)


@pytest.mark.parametrize("num_pages", [12, 24])
@pytest.mark.parametrize("page_tokens", [2, 4])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_allocator_returns_what_the_nested_tuple_index_returned(seed, page_tokens, num_pages):
    rng = random.Random(seed * 1000 + page_tokens * 100 + num_pages)
    a, ref = PagedKVAllocator(num_pages=num_pages, page_tokens=page_tokens), _NestedTupleIndex(num_pages, page_tokens)
    # a few shared documents over a small alphabet, -1 and -2 (equal hashes) in it
    docs = [[rng.choice([-2, -1, 0, 1]) for _ in range(page_tokens * rng.randint(2, 7))] for _ in range(4)]
    live = []  # (SeqPages, the reference's page list, tokens)
    outcomes = collections.Counter()
    for _ in range(400):
        op = rng.random()
        if op < 0.4 or not live:
            doc = rng.choice(docs)
            tokens = doc[:rng.randint(1, len(doc))] + [rng.randint(2, 5) for _ in range(rng.randint(0, 2 * page_tokens))]
            got, want = _exhausted(lambda: a.allocate(tokens)), ref.allocate(tokens)
            if want[0] == "exhausted":
                assert got == want
                outcomes["exhausted"] += 1
            else:
                assert (got.pages, got.cached_tokens) == want
                live.append((got, want[0], tokens))
                outcomes["hit" if want[1] else "miss"] += 1
        elif op < 0.65:
            seq, pages, tokens = rng.choice(live)
            a.commit(seq, tokens)
            ref.commit(pages, tokens)
        elif op < 0.75:
            seq, pages, _ = rng.choice(live)
            assert _exhausted(lambda: a.extend(seq)) == ref.extend(pages)
        else:
            seq, pages, _ = live.pop(rng.randrange(len(live)))
            a.release(seq)
            ref.release(pages)
        for seq, pages, _ in live:
            assert seq.pages == pages
        want = ref.stats()
        got = a.stats()
        assert {k: got[k] for k in want} == want
        assert a._free == ref.free
        assert list(a._evictable) == list(ref.evictable)  # the eviction order
        assert got["index_nodes"] >= got["indexed_pages"]
    assert ref.evictions and outcomes["hit"] and outcomes["miss"] and outcomes["exhausted"], (ref.evictions, outcomes)


def test_allocator_chain_whose_root_was_evicted_matches_its_children_once_the_root_is_recommitted():
    a = PagedKVAllocator(num_pages=7, page_tokens=2)  # 6 usable pages
    doc = [1, 2, 3, 4, 5, 6]
    s1 = a.allocate(doc)
    a.commit(s1, doc)
    a.release(s1)  # parked root first: the LRU evicts the chain's root before its leaves
    stranger = a.allocate([9] * 8)  # 3 free pages + the coldest of the LRU
    assert stranger.pages[-1] == s1.pages[0]
    assert a.stats()["indexed_pages"] == 2 and a.stats()["index_nodes"] == 3  # the root's node lives by its child
    a.release(stranger)
    s2 = a.allocate(doc)  # the walk stops at the link that holds no page
    assert s2.cached_tokens == 0 and not set(s2.pages) & set(s1.pages[1:])
    a.commit(s2, doc)  # gives the root a page; the old children win, ours stay private
    assert a.stats()["indexed_pages"] == 3 and a.stats()["index_nodes"] == 3
    s3 = a.allocate(doc + [7])
    assert s3.pages[:3] == [s2.pages[0]] + s1.pages[1:] and s3.cached_tokens == 6
    for s in (s2, s3):
        a.release(s)


def test_allocator_prompts_whose_tokens_differ_only_by_equal_hashes_share_no_page():
    assert hash(-1) == hash(-2) and hash((-1, 7)) == hash((-2, 7))
    a = PagedKVAllocator(num_pages=16, page_tokens=2)
    p1, p2 = [-1, 7, 3, 4, 5, 6], [-2, 7, 3, 4, 5, 6]
    s1 = a.allocate(p1)
    a.commit(s1, p1)
    s2 = a.allocate(p2)
    assert s2.cached_tokens == 0 and not set(s1.pages) & set(s2.pages)
    a.commit(s2, p2)
    # equal pages under parents that differ are two contents: the chain is the key
    s3, s4 = a.allocate(p1), a.allocate(p2)
    assert (s3.pages, s3.cached_tokens) == (s1.pages, 6) and (s4.pages, s4.cached_tokens) == (s2.pages, 6)
    assert a.stats()["index_nodes"] == 6


def test_allocator_index_prunes_to_nothing_when_no_page_is_left_in_it():
    a = PagedKVAllocator(num_pages=9, page_tokens=2)
    prompts = [[1, 2, 3, 4, 5], [1, 2, 3, 4, 6, 7], [1, 2, 8, 9], [5, 5]]
    for p in prompts:
        s = a.allocate(p)
        a.commit(s, p)
        a.release(s)
    assert a.stats()["index_nodes"] == a.stats()["indexed_pages"] == 5  # (1,2) (3,4) (6,7) (8,9) (5,5)
    stranger = a.allocate(list(range(100, 116)))  # the whole pool, committed by nobody
    assert a.stats()["index_nodes"] == a.stats()["indexed_pages"] == 0 and not a._links
    a.release(stranger)
    assert a.free_pages() == 8


class _CountedToken(int):
    """A token that counts how often it is hashed."""

    hashed = 0

    def __hash__(self):
        _CountedToken.hashed += 1
        return int.__hash__(self)


def test_allocator_hashes_a_prompts_tokens_a_bounded_number_of_times():
    T, n = 4, 64
    prompt = [_CountedToken(t % 7) for t in range(T * n + 1)]  # 64 full pages and a tail
    a, ref = PagedKVAllocator(num_pages=4 * n, page_tokens=T), _NestedTupleIndex(4 * n, T)
    _CountedToken.hashed = 0
    s1 = a.allocate(prompt)
    a.commit(s1, prompt)
    first = _CountedToken.hashed
    s2 = a.allocate(prompt)  # a hit of 64 pages
    a.commit(s2, prompt)
    total = _CountedToken.hashed
    assert s2.cached_tokens == T * n
    assert total - first <= 2 * len(prompt) and total <= 4 * len(prompt), (first, total)
    # the nested-tuple key hashes the whole chain under a page at every lookup
    _CountedToken.hashed = 0
    for _ in range(2):
        ref.commit(ref.allocate(prompt)[0], prompt)
    assert _CountedToken.hashed >= 16 * total, (_CountedToken.hashed, total)


def test_allocator_counts_its_index_walks():
    a = PagedKVAllocator(num_pages=16, page_tokens=4)
    assert {"index_s", "index_calls", "index_nodes"} <= set(a.stats())
    assert (a.stats()["index_s"], a.stats()["index_calls"], a.stats()["index_nodes"]) == (0.0, 0, 0)
    short = a.allocate([1, 2, 3])  # no full page: nothing to walk
    a.commit(short, [1, 2, 3])
    assert a.stats()["index_calls"] == 0 and a.stats()["index_nodes"] == 0
    s = a.allocate(list(range(9)))
    after_allocate = a.stats()
    assert after_allocate["index_calls"] == 1 and after_allocate["index_s"] > 0
    a.commit(s, list(range(9)))
    st = a.stats()
    assert st["index_calls"] == 1 and st["index_s"] > after_allocate["index_s"] and st["index_nodes"] == 2
    unshared = PagedKVAllocator(num_pages=16, page_tokens=4, share_prefixes=False)
    s = unshared.allocate(list(range(9)))
    unshared.commit(s, list(range(9)))
    st = unshared.stats()
    assert (st["index_s"], st["index_calls"], st["index_nodes"]) == (0.0, 0, 0)


def test_engine_stats_carry_the_index_counters_and_counter_mean_reads_them():
    from benchmarks.readers import counter_mean

    eng = InferenceEngine(StubModel(max_slots=2, max_pages_per_seq=8), EngineConfig(page_tokens=4, pool_pages=32))
    try:
        marks = [{"engine": eng.stats()}]
        for _ in range(3):
            _collect(eng, list(range(10)), 2)
        marks.append({"engine": eng.stats()})
    finally:
        eng.close()
    kv = marks[-1]["engine"]["kv"]
    assert kv["index_calls"] == 3 and kv["index_s"] > 0 and kv["index_nodes"] == 2
    args = {"sum": "kv.index_s", "count": "kv.index_calls", "scale": 1000}
    assert counter_mean.read({"marks": marks}, args) == pytest.approx(1000 * kv["index_s"] / 3)
    # a program without the counter (a parent commit): nothing, and no raise
    without = [{"engine": {"kv": {k: v for k, v in m["engine"]["kv"].items() if not k.startswith("index_")}}} for m in marks]
    assert counter_mean.read({"marks": without}, args) is None


# ---------------------------------------------------------------- engine


def _collect(engine, prompt, max_new):
    return list(engine.generate(prompt, max_new))


def _stub_tokens(prompt, n, vocab=256):
    first = (sum(prompt) + 1) % vocab
    return [(first + i) % vocab for i in range(n)]


def test_engine_stream_completes_and_frees_pages():
    eng = InferenceEngine(
        StubModel(), EngineConfig(page_tokens=4, pool_pages=16), name="t-basic"
    )
    try:
        out = _collect(eng, [1, 2, 3], 6)
        assert out == _stub_tokens([1, 2, 3], 6)
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        # The satellite contract: pool occupancy is observable via the
        # raytpu_kv_pages_used gauge, not just engine internals.
        g = imet.KV_PAGES_USED.labels(deployment="t-basic")
        assert g._value == 0.0
        assert imet.KV_PAGES_TOTAL.labels(deployment="t-basic")._value == 15.0
    finally:
        eng.close()


def test_engine_continuous_join_leave():
    """Token-level scheduling: a short request submitted mid-flight joins
    the running batch and finishes while the long one is still decoding."""
    eng = InferenceEngine(
        StubModel(max_slots=2, step_delay_s=0.02),
        EngineConfig(page_tokens=4, pool_pages=32),
        name="t-join",
    )
    try:
        events = []

        def sink_for(tag):
            def sink(ev, val):
                events.append((tag, ev, val))

            return sink

        eng.submit([1, 2], 25, sink=sink_for("long"))
        _wait_for(lambda: any(e[0] == "long" and e[1] == "tok" for e in events))
        eng.submit([3], 3, sink=sink_for("short"))
        assert _wait_for(
            lambda: ("short", "done", "stop") in events, timeout=20.0
        ), events
        done_idx = events.index(("short", "done", "stop"))
        # The long request decoded before AND after the short one's whole
        # lifetime — they shared decode steps, not a request-level queue.
        long_toks = [i for i, e in enumerate(events) if e[0] == "long" and e[1] == "tok"]
        assert any(i < done_idx for i in long_toks)
        assert ("long", "done", "stop") not in events[: done_idx + 1]
        _wait_for(lambda: ("long", "done", "stop") in events, timeout=30.0)
        assert [v for t, e, v in events if t == "short" and e == "tok"] == _stub_tokens([3], 3)
    finally:
        eng.close()


def test_engine_cancellation_frees_pages_within_one_step():
    eng = InferenceEngine(
        StubModel(step_delay_s=0.02),
        EngineConfig(page_tokens=4, pool_pages=16),
        name="t-cancel",
    )
    try:
        it = eng.generate([1, 2, 3, 4, 5], 25)  # long-ish stream
        next(it)
        next(it)
        assert eng.alloc.used_pages() > 0
        it.close()  # client disconnect: generator finalizer cancels
        # Pages and the batch slot free within ~one decode step.
        assert _wait_for(lambda: eng.alloc.used_pages() == 0, timeout=5.0)
        assert _wait_for(lambda: eng.stats()["running"] == 0, timeout=5.0)
        assert imet.KV_PAGES_USED.labels(deployment="t-cancel")._value == 0.0
    finally:
        eng.close()


def test_engine_shed_typed_backpressure():
    eng = InferenceEngine(
        StubModel(step_delay_s=0.05),
        EngineConfig(page_tokens=4, pool_pages=4),  # 3 usable pages
        name="t-shed",
    )
    try:
        it = eng.generate([1] * 8, 2)  # holds 2 of 3 pages
        with pytest.raises(KVPoolExhaustedError):
            eng.submit([2] * 8, 2, sink=lambda ev, v: None)  # needs 2 pages
        assert eng.shed_total == 1
        assert eng.stats()["shed_total"] == 1
        list(it)  # drain; pages return
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
    finally:
        eng.close()


def test_engine_queue_full_sheds():
    eng = InferenceEngine(
        StubModel(),
        EngineConfig(page_tokens=4, pool_pages=16, max_queue=0),
        name="t-q",
    )
    try:
        with pytest.raises(BackpressureError):
            eng.submit([1], 1, sink=lambda ev, v: None)
        assert eng.shed_total == 1
        assert eng.alloc.used_pages() == 0  # shed before reservation
    finally:
        eng.close()


def test_engine_validation_errors():
    eng = InferenceEngine(
        StubModel(max_pages_per_seq=2),
        EngineConfig(page_tokens=4, pool_pages=16),
        name="t-val",
    )
    try:
        with pytest.raises(ValueError):
            eng.submit([], 4, sink=lambda ev, v: None)
        with pytest.raises(ValueError):  # 8 positions max for 2 pages of 4
            eng.submit([1, 2, 3, 4], 8, sink=lambda ev, v: None)
    finally:
        eng.close()


def test_engine_eos_stops_stream():
    # Stub emits consecutive ints; make the 3rd token the eos.
    prompt = [5]
    toks = _stub_tokens(prompt, 8)
    eng = InferenceEngine(
        StubModel(),
        EngineConfig(page_tokens=4, pool_pages=16, eos_token=toks[2]),
        name="t-eos",
    )
    try:
        assert _collect(eng, prompt, 8) == toks[:3]  # eos token included, then stop
    finally:
        eng.close()


def test_engine_chaos_decode_fault_fail_fast_then_recovers():
    """The chaos drill (engine half): an injected decode fault fails the
    in-flight batch with a TYPED error, frees its pages, and the loop
    keeps serving — no wedge, no leak."""
    from ray_tpu import chaos

    eng = InferenceEngine(
        StubModel(step_delay_s=0.01),
        EngineConfig(page_tokens=4, pool_pages=16),
        name="t-chaos",
    )
    try:
        chaos.configure([{"point": "serve.decode", "action": "raise", "times": 1}])
        with pytest.raises(RayTpuError):
            _collect(eng, [1, 2, 3], 10)
        assert _wait_for(lambda: eng.alloc.used_pages() == 0, timeout=5.0)
        # Next request (chaos rule exhausted) succeeds on the same loop.
        assert _collect(eng, [1, 2, 3], 4) == _stub_tokens([1, 2, 3], 4)
    finally:
        chaos.disable()
        eng.close()


def test_engine_close_fails_inflight_typed():
    eng = InferenceEngine(
        StubModel(step_delay_s=0.05),
        EngineConfig(page_tokens=4, pool_pages=16),
        name="t-close",
    )
    it = eng.generate([1, 2], 25)
    next(it)
    eng.close()
    with pytest.raises(RayTpuError):
        list(it)
    assert eng.alloc.used_pages() == 0


# ------------------------------------------------- serve deployment (e2e)


def _deploy_stub(serve, name="llm", **model_kw):
    from ray_tpu.serve.llm import llm_deployment
    from ray_tpu.serve.llm.model import stub_model

    app = llm_deployment(
        stub_model,
        name=name,
        model_kwargs=model_kw,
        engine_config=EngineConfig(page_tokens=4, pool_pages=32),
    )
    return serve.run(app, name=name, http_port=None)


def _replica_for(rt, name):
    from ray_tpu.serve.controller import get_or_create_controller

    controller = get_or_create_controller()
    _, replicas = rt.get(controller.get_replicas.remote(name))
    assert replicas
    return replicas[0]


def _engine_stats(rt, replica):
    return rt.get(replica.handle_request.remote("engine_stats", (), {}))


def test_llm_deployment_streaming_e2e(rt):
    from ray_tpu import serve

    handle = _deploy_stub(serve, name="llm-stream")
    gen = handle.options(stream=True).remote([1, 2, 3], 5)
    assert list(gen) == _stub_tokens([1, 2, 3], 5)
    replica = _replica_for(rt, "llm-stream")
    stats = _wait_for(
        lambda: (s := _engine_stats(rt, replica))["kv"]["used_pages"] == 0 and s
    )
    assert stats["tokens_emitted"] >= 5
    serve.shutdown()


def test_handle_stream_close_cancels_and_frees_pages(rt):
    """Serve-handle path cancellation: a client calling close() on the
    streaming response generator (or dropping it) must interrupt the
    in-flight request — KV pages and batch slot free within one decode
    step, and the engine must NOT decode the remaining tokens."""
    from ray_tpu import serve

    handle = _deploy_stub(serve, name="llm-hclose", step_delay_s=0.02)
    replica = _replica_for(rt, "llm-hclose")

    gen = handle.options(stream=True).remote([1, 2, 3], 25)
    got = [next(gen), next(gen)]
    assert got == _stub_tokens([1, 2, 3], 25)[:2]
    gen.close()

    assert _wait_for(
        lambda: (s := _engine_stats(rt, replica))["running"] == 0
        and s["kv"]["used_pages"] == 0,
        timeout=10.0,
    )
    # Proves interruption, not just completion: at 20ms/step the full 25
    # tokens take ~0.5s; the cancel lands after ~2-3 steps.
    stats = _engine_stats(rt, replica)
    assert stats["tokens_emitted"] < 25, stats

    # closing again is idempotent; the deployment keeps serving.
    gen.close()
    assert list(handle.options(stream=True).remote([9], 3)) == _stub_tokens([9], 3)
    serve.shutdown()


def test_llm_feed_client_roundtrip_and_cancel(rt):
    from ray_tpu import serve

    handle = _deploy_stub(serve, name="llm-feed", step_delay_s=0.01)
    del handle
    replica = _replica_for(rt, "llm-feed")
    client = LLMClient("llm-feed")
    try:
        # Round trip: same tokens the handle path would produce.
        assert list(client.generate([4, 5], 4)) == _stub_tokens([4, 5], 4)

        # Mid-stream cancel: dropping the iterator sends a cancel and the
        # replica frees the pages + slot within a decode step.
        it = client.generate([6, 7, 8], 25)
        next(it)
        it.close()
        assert _wait_for(
            lambda: _engine_stats(rt, replica)["kv"]["used_pages"] == 0, timeout=10.0
        )
        assert _engine_stats(rt, replica)["running"] == 0

        # The feed stays usable after a cancel.
        assert list(client.generate([9], 3)) == _stub_tokens([9], 3)
    finally:
        client.close()
    serve.shutdown()


def test_feed_client_death_frees_pages(rt):
    """Chaos drill, client half: a client that VANISHES mid-stream (no
    polite detach) must not leak replica-side pages — the response
    channel's closure cancels its outstanding sequences."""
    from ray_tpu import serve

    _deploy_stub(serve, name="llm-cdie", step_delay_s=0.02)
    replica = _replica_for(rt, "llm-cdie")
    client = LLMClient("llm-cdie")
    it = client.generate([1, 2, 3], 25)
    next(it)
    assert _engine_stats(rt, replica)["kv"]["used_pages"] > 0
    # Simulate client death: tear the response channel down abruptly.
    client.resp_reader.close()
    client.req_writer.close()
    assert _wait_for(
        lambda: _engine_stats(rt, replica)["kv"]["used_pages"] == 0, timeout=15.0
    )
    assert _engine_stats(rt, replica)["running"] == 0
    serve.shutdown()


def test_feed_replica_death_fails_fast(rt):
    """Chaos drill, replica half: when the replica side goes away
    mid-stream the client gets a TYPED ActorDiedError promptly (never a
    hang), and later generate() calls fail fast too."""
    from ray_tpu import serve

    _deploy_stub(serve, name="llm-rdie", step_delay_s=0.02)
    replica = _replica_for(rt, "llm-rdie")
    client = LLMClient("llm-rdie")
    it = client.generate([1, 2], 25)
    next(it)
    # Replica death as the wire sees it: engine + feed channels torn down.
    rt.get(replica.handle_request.remote("shutdown_engine", (), {}))
    with pytest.raises((ActorDiedError, RayTpuError)):
        deadline = time.monotonic() + 15.0
        for _ in it:
            assert time.monotonic() < deadline, "stream wedged after replica death"
    with pytest.raises(ActorDiedError):
        for _ in client.generate([3], 2):
            pass
    serve.shutdown()


def test_llm_deployment_concurrent_clients(rt):
    from ray_tpu import serve

    handle = _deploy_stub(serve, name="llm-many")
    results = {}

    def call(i):
        results[i] = list(handle.options(stream=True).remote([i], 4))

    threads = [threading.Thread(target=call, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for i in range(6):
        assert results[i] == _stub_tokens([i], 4), i
    serve.shutdown()


# ----------------------------------------------- batching error isolation


def test_serve_batch_per_item_error_isolation(rt):
    """One bad request in a batch fails ONLY its own caller (typed), the
    rest of the batch completes (serve/batching.py _distribute)."""
    from ray_tpu import serve

    @serve.deployment(max_ongoing_requests=16)
    class Half:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.5)
        def __call__(self, items):
            return [
                ValueError(f"odd input {i}") if i % 2 else i * 10 for i in items
            ]

    handle = serve.run(Half.bind(), name="peritem")
    resps = [handle.remote(i) for i in range(4)]
    assert resps[0].result(timeout=30) == 0
    assert resps[2].result(timeout=30) == 20
    for odd in (1, 3):
        with pytest.raises(BatchItemError) as ei:
            resps[odd].result(timeout=30)
        assert "odd input" in str(ei.value)
    serve.shutdown()


def test_serve_batch_handler_raise_still_fails_batch(rt):
    from ray_tpu import serve

    @serve.deployment(max_ongoing_requests=16)
    class Boom:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.3)
        def __call__(self, items):
            raise RuntimeError("whole batch down")

    handle = serve.run(Boom.bind(), name="boom")
    resps = [handle.remote(i) for i in range(3)]
    for r in resps:
        with pytest.raises(Exception, match="whole batch down"):
            r.result(timeout=30)
    serve.shutdown()


# ------------------------------------------- stage clocks and spans (PR 24)


class _LaunchingStub(StubModel):
    """A stub that announces its launches the way PagedLM does: it calls the
    `launched` its argument carries, then waits for its 'result'. `watch`, if
    given, is called as watch(kind, step, when) around every hook
    ("before" / "after"), on the engine's thread."""

    watch = None

    def _announce(self, carrier, kind, step=None):
        if self.watch is not None:
            self.watch(kind, step, "before")
        carrier.launched()
        if self.watch is not None:
            self.watch(kind, step, "after")

    def prefill(self, prompt, pages, cached_tokens):
        self._announce(prompt, "prefill")
        return super().prefill(prompt, pages, cached_tokens)

    def decode(self, last_tokens, positions, block_tables):
        from ray_tpu import tracing

        attrs = {"step": last_tokens.step}
        with tracing.span("llm.decode.dispatch", attrs):
            pass
        self._announce(last_tokens, "decode", last_tokens.step)
        with tracing.span("llm.decode.wait", dict(attrs)):
            return super().decode(list(last_tokens), positions, block_tables)



def _clock_identity(clk):
    assert clk["loop"]["s"] >= clk["loop"]["idle_s"] + clk["prefill"]["s"] + clk["decode"]["s"] - 1e-9
    # the stages tile the loop's life: an equality, not a bound
    stages = clk["loop"]["idle_s"] + sum(clk[k]["s"] for k in ("admit", "prefill", "batch", "decode", "emit"))
    assert stages == pytest.approx(clk["loop"]["s"], rel=0.01, abs=1e-6)


def _flat(clk):
    return {f"{k}.{f}": v for k, d in clk.items() for f, v in d.items()}


@pytest.mark.parametrize("kind", ["silent", "announces", "defers"])
@pytest.mark.parametrize("n_requests,step_delay_s", [(1, 0.0), (3, 0.003), (6, 0.0)])
def test_engine_stage_clocks(n_requests, step_delay_s, kind):
    """stats()["clocks"]: monotone across calls, queue_wait.n = requests
    admitted, and the loop's wall time is idle + admit + prefill + batch +
    decode + emit, whether the deliveries are made at the end of their step,
    from the next launch's hook, or there with the step's read and decide
    (a model that defers); deliver.n counts every sink call."""
    model_cls = {"silent": StubModel, "announces": _LaunchingStub, "defers": _DeferringStub}[kind]
    announces = kind != "silent"
    eng = InferenceEngine(
        model_cls(max_slots=2, step_delay_s=step_delay_s),
        EngineConfig(page_tokens=4, pool_pages=64),
        name=f"t-clocks-{n_requests}-{kind}",
    )
    try:
        before = eng.stats()["clocks"]
        _clock_identity(before)
        assert before["queue_wait"]["n"] == 0 and before["decode"]["n"] == 0
        outs = []
        threads = [
            threading.Thread(target=lambda i=i: outs.append(_collect(eng, [i + 1, 2], 4)))
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()
        mid = eng.stats()["clocks"]  # taken while the loop may be inside a stage
        for t in threads:
            t.join(timeout=30)
        assert len(outs) == n_requests
        after = eng.stats()["clocks"]
        later = eng.stats()["clocks"]
    finally:
        eng.close()
    for a, b in ((before, mid), (mid, after), (after, later)):
        _clock_identity(b)
        fa, fb = _flat(a), _flat(b)
        assert all(fb[k] >= fa[k] for k in fa), (fa, fb)
    assert after["queue_wait"]["n"] == after["first_token"]["n"] == after["prefill"]["n"] == n_requests
    assert after["prefill"]["tokens"] == 2 * n_requests
    # a model that does not say what it computed computed the uncached tokens
    assert after["prefill"]["computed_tokens"] == 2 * n_requests
    assert after["decode"]["n"] == eng.decode_steps >= 3
    assert after["first_token"]["s"] >= after["queue_wait"]["s"] >= 0.0
    if step_delay_s and kind != "defers":  # a model that defers: the host's other stages run under the step, and take from its wait
        assert after["decode"]["s"] >= after["decode"]["n"] * step_delay_s
    assert (after["decode"]["chained"] > 0) == (kind == "defers")
    # 4 tokens and a done a request, each one sink call
    assert after["deliver"]["n"] == 5 * n_requests
    if announces:  # all but the first tokens, and what found nothing left to launch
        assert 2 * n_requests <= after["deliver"]["under_step"] <= 4 * n_requests
    else:
        assert after["deliver"]["under_step"] == 0
    final = eng.stats()["clocks"]  # the loop has ended: its wall time stands still
    assert final["loop"]["s"] == eng.stats()["clocks"]["loop"]["s"]


@pytest.mark.parametrize("suffix", [3, 9, 16, 22], ids=[
    "under_a_chunk", "across_a_multiple_of_the_chunk", "exactly_a_chunk", "into_a_second_chunk"])
def test_a_prefix_hit_through_the_engine_computes_less_and_serves_the_same_tokens(suffix, monkeypatch):
    """Two requests that share a 24-token prefix, through InferenceEngine +
    PagedLM with 16-token chunks: the second's prefill computes its uncached
    span in chunks that start at the 24th token, ceil(suffix / 16) of them
    (`clocks.prefill.computed_tokens`, and `computed_tokens` on its
    `llm.prefill` span), whichever multiples of 16 the suffix lies across,
    and both stream the tokens the same requests get from an engine that
    has seen neither (cold)."""
    import jax.numpy as jnp

    from ray_tpu import tracing
    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.model import PagedLM

    monkeypatch.setattr(tfm, "PREFILL_CHUNK_TOKENS", 16)
    T = 8
    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, remat=False)
    first = [(7 * i + 3) % cfg.vocab_size for i in range(29)]
    second = first[:24] + [(5 * i + 1) % cfg.vocab_size for i in range(suffix)]

    def engine(name):
        lm = PagedLM(cfg, seed=0, num_pages=48, page_tokens=T, max_slots=2, max_pages_per_seq=8)
        return InferenceEngine(lm, EngineConfig(page_tokens=T, pool_pages=48), name=name)

    cold = []
    for i, prompt in enumerate((first, second)):
        eng = engine(f"t-cold-{suffix}-{i}")
        try:
            cold.append(_collect(eng, prompt, 5))
        finally:
            eng.close()

    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    eng = engine(f"t-hit-{suffix}")
    try:
        assert _collect(eng, first, 5) == cold[0]
        one = eng.stats()["clocks"]["prefill"]
        assert _collect(eng, second, 5) == cold[1]
        two = eng.stats()["clocks"]["prefill"]
        kv = eng.stats()["kv"]
    finally:
        eng.close()
        tracing.disable()
    # the first is a miss: its bucket (32 tokens) in two chunks
    assert one == dict(one, n=1, tokens=29, computed_tokens=32)
    assert kv["prefix_hits"] == 3  # three whole pages of the second were the first's
    computed = two["computed_tokens"] - one["computed_tokens"]
    assert computed == -(-suffix // 16) * 16 < len(second) == two["tokens"] - one["tokens"]
    spans = [s["attrs"] for s in exp.spans if s["name"] == "llm.prefill"]
    assert [(a["prompt_tokens"], a["cached_tokens"], a["computed_tokens"]) for a in spans] == [
        (29, 0, 32), (len(second), 24, computed)]


@pytest.mark.parametrize("n_requests,max_new", [(1, 3), (2, 9), (5, 6)])
def test_engine_counts_live_pages_against_the_table(n_requests, max_new):
    """clocks["decode.kv_pages"]: `live` is the pages that the positions of
    every completed decode step cover (what a paged-attention step reads),
    `table` is slots x pages a sequence for each of those steps (what a
    step that gathers the block tables reads)."""
    T, slots, per_seq = 4, 2, 8
    seen = []

    class Recording(StubModel):
        def decode(self, last_tokens, positions, block_tables):
            seen.append([int(p) for p in positions])
            return super().decode(last_tokens, positions, block_tables)

    eng = InferenceEngine(
        Recording(max_slots=slots, max_pages_per_seq=per_seq),
        EngineConfig(page_tokens=T, pool_pages=64),
        name=f"t-kv-pages-{n_requests}",
    )
    try:
        assert eng.stats()["clocks"]["decode.kv_pages"] == {"live": 0, "table": 0}
        threads = [
            threading.Thread(target=lambda i=i: _collect(eng, [1] * (3 + 2 * i), max_new))
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        clk = eng.stats()["clocks"]
    finally:
        eng.close()
    pages = clk["decode.kv_pages"]
    assert len(seen) == clk["decode"]["n"] >= 1
    assert pages["table"] == len(seen) * slots * per_seq
    assert pages["live"] == sum(-(-(p + 1) // T) for step in seen for p in step if p >= 0)
    assert 0 < pages["live"] <= pages["table"]


def test_engine_thread_spans_carry_the_request_context():
    """llm.queue / llm.prefill / llm.first_token are recorded on the engine
    thread under the context submit() saw; llm.decode and llm.emit nest in
    llm.step."""
    from ray_tpu import tracing

    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    eng = InferenceEngine(StubModel(), EngineConfig(page_tokens=4, pool_pages=16), name="t-spans")
    try:
        with tracing.span("request") as req:
            gen = eng.generate([1, 2, 3], 3)
        assert list(gen) == _stub_tokens([1, 2, 3], 3)
    finally:
        eng.close()
        tracing.disable()
    by = {}
    for s in exp.spans:
        by.setdefault(s["name"], []).append(s)
    for name in ("llm.queue", "llm.prefill", "llm.first_token"):
        (s,) = by[name]
        assert s["trace_id"] == req["trace_id"] and s["parent_id"] == req["span_id"], name
        assert s["attrs"]["rid"] == 1
    assert by["llm.queue"][0]["attrs"] == {"rid": 1, "prompt_tokens": 3, "cached_tokens": 0, "waiting_ahead": 0}
    assert by["llm.queue"][0]["t1_ns"] <= by["llm.first_token"][0]["t0_ns"]
    steps = {s["span_id"]: s for s in by["llm.step"]}
    assert len(steps) == len(by["llm.decode"]) == len(by["llm.emit"]) == 2
    assert all(s["parent_id"] in steps for s in by["llm.decode"] + by["llm.emit"])
    assert by["llm.step"][0]["attrs"] == {"admitted": 1, "live": 1}
    assert by["llm.decode"][0]["attrs"] == {"live": 1, "kv_tokens": 4, "step": 1, "after_prefill": 1}
    assert by["llm.decode"][1]["attrs"] == {"live": 1, "kv_tokens": 5, "step": 2, "after_prefill": 0}
    assert all(s["parent_id"] in steps for s in by["llm.batch"])
    assert [s["attrs"] for s in by["llm.batch"]] == [{"live": 1}] * 2
    # the admit that found the request, beside the ones that found nothing
    assert {"waiting": 0, "admitted": 1, "live": 1} in [s["attrs"] for s in by["llm.admit"]]


class _SpannedStub(StubModel):
    """A stub that puts PagedLM's decode spans around its step, to show what
    reaches a model through a wrapper that hands `last_tokens` on unopened."""

    def decode(self, last_tokens, positions, block_tables):
        from ray_tpu import tracing

        attrs = {"step": last_tokens.step}
        with tracing.span("llm.decode.dispatch", attrs), tracing.span("llm.decode.wait", dict(attrs)):
            return super().decode(list(last_tokens), positions, block_tables)


def _spans_of_an_engines_life(n_requests, step_delay_s, model_cls=_SpannedStub):
    """An engine from start to stop with tracing on: one wait with nothing to
    do, `n_requests` at once, close. (decode_steps, its llm.* spans)."""
    from ray_tpu import tracing

    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    eng = InferenceEngine(
        model_cls(max_slots=2, step_delay_s=step_delay_s),
        EngineConfig(page_tokens=4, pool_pages=64),
        name=f"t-tiles-{n_requests}-{model_cls.__name__}",
    )
    try:
        time.sleep(0.02)  # the loop finds nothing and waits: an llm.idle before any request
        outs = []
        threads = [
            threading.Thread(target=lambda i=i: outs.append(_collect(eng, [i + 1, 2], 4)))
            for i in range(n_requests)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(outs) == n_requests
    finally:
        eng.close()
        tracing.disable()
    return eng.decode_steps, [s for s in exp.spans if s["name"].startswith("llm.")]


class _DeferringStub(_LaunchingStub):
    """A stub that launches without waiting, as PagedLM does. `decode`
    'dispatches': the step's tokens are computed at once and are ready
    `step_delay_s` after the step before them is (one stream, in launch
    order); its output stays for the rows the next step marks -1; it announces
    the launch and returns a PendingTokens whose read (`llm.decode.wait`,
    with the step READ) waits until the step is ready. `launches` keeps
    (step, tokens as handed, positions) of every launch; `fail_read`, if set
    to (step, exception), makes that step's read raise."""

    fail_read = None

    def __init__(self, **kw):
        super().__init__(**kw)
        self._prev = [0] * self.max_slots
        self._ready_at = 0.0
        self.launches = []

    def decode(self, last_tokens, positions, block_tables):
        from ray_tpu import tracing
        from ray_tpu.serve.llm.model import PendingTokens

        step = last_tokens.step
        attrs = {"step": step}
        with tracing.span("llm.decode.prep"):
            handed = [int(t) for t in last_tokens]
        with tracing.span("llm.decode.dispatch", attrs):
            self.decode_calls += 1
            toks = [t if t >= 0 else prev for t, prev in zip(handed, self._prev)]
            out = [(t + 1) % self.vocab if int(p) >= 0 else 0 for t, p in zip(toks, positions)]
            self._prev = out
            ready_at = self._ready_at = max(self._ready_at, time.monotonic()) + self.step_delay_s
            self.launches.append((step, handed, [int(p) for p in positions]))

        def read():
            with tracing.span("llm.decode.wait", dict(attrs)):
                time.sleep(max(0.0, ready_at - time.monotonic()))
                if self.fail_read is not None and self.fail_read[0] == step:
                    raise self.fail_read[1]
                return out

        result = PendingTokens(read)
        self._announce(last_tokens, "decode", step)
        return result if getattr(last_tokens, "deferred", False) else result.resolve()


def _inside(inner, outer):
    return outer["t0_ns"] <= inner["t0_ns"] and inner["t1_ns"] <= outer["t1_ns"]


def _assert_two_deep(spans):
    """The spans of an engine over a model that defers: launch ordinals
    consecutive from 1 and the same on `llm.decode`, `.dispatch` and `.wait`;
    a step's `.prep` and `.dispatch` start inside its `llm.decode`; every step
    launched is read, once, in launch order, behind its own dispatch; never
    more than two steps in flight (step k - 2 is read before step k is
    dispatched). Returns the steps dispatched while the step before them was
    unread: what `clocks.decode.chained` counts."""
    by = {}
    for s in sorted(spans, key=lambda s: s["t0_ns"]):
        by.setdefault(s["name"], []).append(s)
    decodes, dispatches, waits = by["llm.decode"], by["llm.decode.dispatch"], by["llm.decode.wait"]
    n = len(decodes)
    for group in (decodes, dispatches, waits):
        assert [s["attrs"]["step"] for s in group] == list(range(1, n + 1))
    assert len(by["llm.decode.prep"]) == n
    chained = 0
    for k, (call, prep, dispatch, wait) in enumerate(zip(decodes, by["llm.decode.prep"], dispatches, waits)):
        assert call["t0_ns"] <= prep["t0_ns"] <= prep["t1_ns"] <= dispatch["t0_ns"] <= call["t1_ns"]
        assert dispatch["t1_ns"] <= wait["t0_ns"]  # read behind its own launch
        if k >= 1:
            assert waits[k - 1]["t1_ns"] <= wait["t0_ns"]
            chained += dispatch["t1_ns"] <= waits[k - 1]["t0_ns"]
        if k >= 2:
            assert waits[k - 2]["t1_ns"] <= dispatch["t0_ns"]  # two in flight at most
    # a wait carries the step it READ: where a step was launched behind an unread one, the wait inside its
    # `llm.decode` is the older step's, and its own lies under a later launch (or under `llm.step`: nothing left to launch)
    for call in decodes:
        inside = [w["attrs"]["step"] for w in waits if _inside(w, call)]
        assert inside in ([], [call["attrs"]["step"] - 1]), (call["attrs"], inside)
    return chained


@pytest.mark.parametrize("model_cls", [_SpannedStub, _LaunchingStub, _DeferringStub], ids=["silent", "announces", "defers"])
@pytest.mark.parametrize("n_requests,step_delay_s", [(1, 0.0), (3, 0.003), (6, 0.0)])
def test_engine_loop_spans_tile_the_thread_from_start_to_stop(n_requests, step_delay_s, model_cls):
    """Every instant of the engine thread, from the loop's first span to its
    last, lies under exactly one of llm.idle / llm.admit / llm.step; the
    stages of a step nest in it; decode steps carry consecutive ordinals, the
    same on the model's own spans. A step's deliveries (llm.emit) close the
    step of a model that announces nothing, and lie between the dispatch and
    the wait of the next launch of one that announces it. A model that
    defers has its steps read, decided and delivered under the launch behind
    them, and at once where nothing is left to launch.

    The structure is judged on every life. The holes between two top-level
    spans (a clock read and a lock) are judged on the quietest of three lives
    and by their upper quartile: a loaded machine takes the processor from
    the thread at a few borders of a life, which says nothing of the loop; a
    stage left outside the spans would show at a third of them in every life."""
    upper_quartiles = []
    for _attempt in range(3):
        decode_steps, spans = _spans_of_an_engines_life(n_requests, step_delay_s, model_cls)
        holes = _assert_the_loops_structure(decode_steps, spans, n_requests, model_cls)
        upper_quartiles.append(sorted(holes)[(3 * len(holes)) // 4])
        if upper_quartiles[-1] < 1_000_000:
            break
    assert min(upper_quartiles) < 1_000_000, upper_quartiles


def _assert_the_loops_structure(decode_steps, spans, n_requests, model_cls):
    """One life's spans against the loop's rules; returns the holes between consecutive top-level spans (ns)."""
    top = sorted(
        (s for s in spans if s["name"] in ("llm.idle", "llm.admit", "llm.step")), key=lambda s: s["t0_ns"]
    )
    holes = [b["t0_ns"] - a["t1_ns"] for a, b in zip(top, top[1:])]
    assert min(holes) >= 0  # none overlap
    (tid,) = {s["tid"] for s in spans if s["name"] == "llm.step"}
    assert {s["tid"] for s in top} == {tid}
    assert {s["name"] for s in top} == {"llm.idle", "llm.admit", "llm.step"}
    assert top[0]["name"] == "llm.admit" and top[0]["attrs"] == {"waiting": 0, "admitted": 0, "live": 0}
    assert top[-1]["name"] == "llm.admit"  # the one that saw the stop
    for a, b in zip(top, top[1:]):
        # an admit is followed by the wait or the step it decided on, and by nothing else
        assert (a["name"] == "llm.admit") != (b["name"] == "llm.admit")
        if a["name"] == "llm.admit":
            assert b["name"] == ("llm.step" if a["attrs"]["live"] else "llm.idle")
            if b["name"] == "llm.step":
                assert b["attrs"] == {"admitted": a["attrs"]["admitted"], "live": a["attrs"]["live"]}
    assert sum(s["attrs"]["admitted"] for s in top if s["name"] == "llm.admit") == n_requests

    steps = [s for s in top if s["name"] == "llm.step"]
    announces, defers = model_cls is not _SpannedStub, model_cls is _DeferringStub
    inner = ("llm.prefill", "llm.batch", "llm.decode") + (() if defers else ("llm.decide",)) + (() if announces else ("llm.emit",))
    for name in inner:
        for s in (s for s in spans if s["name"] == name):
            (outer,) = [o for o in steps if _inside(s, o)]
            if name != "llm.prefill":  # a prefill's parent is its request
                assert s["parent_id"] == outer["span_id"]
    launching = []  # the iterations that launched a decode step
    for o in steps:
        got = [s["name"] for s in sorted(spans, key=lambda s: s["t0_ns"])
               if s["name"] in inner and o["t0_ns"] <= s["t0_ns"] < o["t1_ns"]]
        if defers and got[-1] != "llm.decode":
            # every live row's last step is in flight: nothing to launch, the step is read at once under `llm.step`
            assert got == ["llm.prefill"] * o["attrs"]["admitted"] + ["llm.batch"]
            tail = [s for s in spans if s["name"] in ("llm.decode.wait", "llm.decide", "llm.emit") and s["parent_id"] == o["span_id"]]
            assert [s["name"] for s in sorted(tail, key=lambda s: s["t0_ns"])] in ([], ["llm.decode.wait", "llm.decide", "llm.emit"])
        else:
            assert got == ["llm.prefill"] * o["attrs"]["admitted"] + list(inner[1:])
            launching.append(o)
    emits = [s for s in spans if s["name"] == "llm.emit"]
    launches = {s["span_id"]: s for s in spans if s["name"] in ("llm.prefill", "llm.decode")}
    part = {(s["name"], s["attrs"]["step"]): s for s in spans if s["name"] in ("llm.decode.dispatch", "llm.decode.wait")}
    if defers:
        # one a step read; under the launch behind the step (`under_step` 1: after that launch's dispatch), or at once
        assert len(emits) == decode_steps == len([s for s in spans if s["name"] == "llm.decide"])
        for s in emits:
            over = launches.get(s["parent_id"])
            assert s["attrs"]["under_step"] == int(over is not None)
            if over is not None and over["name"] == "llm.decode":
                k = over["attrs"]["step"]
                assert part["llm.decode.dispatch", k]["t1_ns"] <= part["llm.decode.wait", k - 1]["t0_ns"]
                assert part["llm.decode.wait", k - 1]["t1_ns"] <= s["t0_ns"] and _inside(s, over)
        assert _assert_two_deep(spans) >= 0
    elif announces:
        # each under the prefill or the decode whose launch made it; a decode's after its dispatch, before its wait
        assert [s["attrs"]["under_step"] for s in emits] == [1] * len(emits)
        assert 0 < len(emits) <= decode_steps and sum(s["attrs"]["tokens"] for s in emits) <= 3 * n_requests
        for s in emits:
            over = launches[s["parent_id"]]
            assert _inside(s, over)
            if over["name"] == "llm.decode":
                k = over["attrs"]["step"]
                assert part["llm.decode.dispatch", k]["t1_ns"] <= s["t0_ns"] and s["t1_ns"] <= part["llm.decode.wait", k]["t0_ns"]
    else:
        assert [s["attrs"]["under_step"] for s in emits] == [0] * len(emits)
        assert len(emits) == decode_steps

    decodes = sorted((s for s in spans if s["name"] == "llm.decode"), key=lambda s: s["t0_ns"])
    assert [s["attrs"]["step"] for s in decodes] == list(range(1, decode_steps + 1))
    assert [s["attrs"]["after_prefill"] for s in decodes] == [int(o["attrs"]["admitted"] > 0) for o in launching]
    for name in ("llm.decode.dispatch", "llm.decode.wait"):
        got = sorted((s for s in spans if s["name"] == name), key=lambda s: s["t0_ns"])
        assert [s["attrs"]["step"] for s in got] == [s["attrs"]["step"] for s in decodes]
    return holes


# ------------------------------- a step decided at once, delivered late (PR 43)


class _Streams:
    """Sinks that keep what each stream saw, in order, and who was running."""

    def __init__(self):
        self.events = {}
        self.threads = set()

    def sink(self, tag):
        got = self.events.setdefault(tag, [])

        def sink(ev, val):
            self.threads.add(threading.current_thread().name)
            got.append((ev, val))

        return sink

    def tokens(self, tag):
        return [v for ev, v in self.events[tag] if ev == "tok"]

    def counts(self):
        return {tag: sum(1 for ev, _v in got if ev == "tok") for tag, got in self.events.items()}

    def ended(self, tag):
        return bool(self.events[tag]) and self.events[tag][-1][0] != "tok"


def _watched_engine(model, name, **cfg):
    """(engine, streams, seen): `seen` gets (kind, step, when, tokens a stream so far) at every hook of `model`."""
    streams, seen = _Streams(), []
    model.watch = lambda kind, step, when: seen.append((kind, step, when, streams.counts()))
    eng = InferenceEngine(model, EngineConfig(page_tokens=4, pool_pages=64, **cfg), name=name)
    return eng, streams, seen


def _assert_stream_shape(events, prompt, n_tokens, last):
    """`n_tokens` right tokens in order, then exactly one closing event."""
    assert [ev for ev, _v in events] == ["tok"] * n_tokens + [last[0]], events
    assert [v for ev, v in events[:-1]] == _stub_tokens(prompt, n_tokens)
    if last[0] == "done":
        assert events[-1] == last
    else:
        assert isinstance(events[-1][1], last[1]), events[-1]


def test_a_steps_tokens_reach_their_streams_under_the_next_launch():
    """A model that announces its launches: the tokens of step N are at their
    streams when the hook of step N+1 returns, and not before it is called:
    they were held for the launch, not delivered in front of the dispatch. A
    prefill's first token is there before the decode after it is dispatched."""
    eng, streams, seen = _watched_engine(_LaunchingStub(max_slots=4, step_delay_s=0.002), "t-under")
    try:
        prompts = {tag: [tag + 1, 2] for tag in range(3)}
        for tag, prompt in prompts.items():
            eng.submit(prompt, 6, sink=streams.sink(tag))
        assert _wait_for(lambda: all(streams.ended(tag) for tag in prompts))
        clk = eng.stats()["clocks"]
    finally:
        eng.close()
    for tag, prompt in prompts.items():
        _assert_stream_shape(streams.events[tag], prompt, 6, ("done", "stop"))
    assert streams.threads == {"llm-engine-t-under"}
    decodes = [(step, when, counts) for kind, step, when, counts in seen if kind == "decode"]
    assert [step for step, when, _c in decodes if when == "before"] == list(range(1, eng.decode_steps + 1))
    first_token_at = {}  # tag -> the first decode step that ran with it
    for step, when, counts in decodes:
        for tag, n in counts.items():
            if n == 0:
                continue  # submitted, not yet prefilled
            k = first_token_at.setdefault(tag, step)
            if step - k >= 5:
                continue  # done: its last token went out with its `done`
            # the first token and one of every step before this one; step - 1's only once the hook has run
            assert n == 1 + (step - k) - (1 if when == "before" and step > k else 0), (tag, step, when, counts)
    assert clk["deliver"]["n"] == 3 * 7 == sum(len(e) for e in streams.events.values())
    # every stream: 4 of its 5 decode tokens under a later launch, the first token and the last with `done` not
    assert clk["deliver"]["under_step"] == 3 * 4


def test_a_model_that_announces_nothing_is_delivered_to_at_the_end_of_each_step():
    """StubModel calls no hook: at the entry of decode step N + 1 every stream
    holds the tokens of all N steps before it, as it always did."""
    streams, seen = _Streams(), []

    class Watching(StubModel):
        def decode(self, last_tokens, positions, block_tables):
            assert callable(last_tokens.launched)  # offered, and ignored
            seen.append((last_tokens.step, streams.counts()))
            return super().decode(last_tokens, positions, block_tables)

    eng = InferenceEngine(Watching(max_slots=4), EngineConfig(page_tokens=4, pool_pages=64), name="t-silent")
    try:
        for tag in range(3):
            eng.submit([tag + 1, 2], 5, sink=streams.sink(tag))
        assert _wait_for(lambda: all(streams.ended(tag) for tag in range(3)))
        clk = eng.stats()["clocks"]
    finally:
        eng.close()
    start = {}
    for step, counts in seen:
        for tag, n in counts.items():
            if n:
                assert n == 1 + step - start.setdefault(tag, step), (tag, step, counts)
    assert clk["deliver"] == {"n": 3 * 6, "under_step": 0}


def test_a_model_that_stops_announcing_is_not_waited_for():
    """The hook fires for a while and then no more (a wrapper that stopped
    handing it on): the first step that comes back without it has its
    predecessor's tokens delivered on return, and from then on every step is
    delivered at its end."""
    streams, seen = _Streams(), []

    class Fading(_LaunchingStub):
        def decode(self, last_tokens, positions, block_tables):
            seen.append((last_tokens.step, streams.counts()[0]))
            if last_tokens.step <= 3:
                return super().decode(last_tokens, positions, block_tables)
            return StubModel.decode(self, last_tokens, positions, block_tables)

    eng = InferenceEngine(Fading(), EngineConfig(page_tokens=4, pool_pages=64), name="t-fading")
    try:
        eng.submit([1, 2], 9, sink=streams.sink(0))
        assert _wait_for(lambda: streams.ended(0))
        clk = eng.stats()["clocks"]
    finally:
        eng.close()
    _assert_stream_shape(streams.events[0], [1, 2], 9, ("done", "stop"))
    # tokens at the stream at the entry of steps 1..8: held one step back while announced (1-4), then level
    assert seen == [(1, 1), (2, 1), (3, 2), (4, 3), (5, 5), (6, 6), (7, 7), (8, 8)]
    assert clk["deliver"] == {"n": 10, "under_step": 2}


@pytest.mark.parametrize("case", ["length", "eos", "cancel_mid_step", "decode_raises_before_launch",
                                  "decode_raises_after_launch", "prefill_raises", "pool_lost", "close"])
def test_a_stream_sees_its_tokens_then_one_closing_event(case):
    """Whatever ends a stream of a model that announces its launches, the
    stream sees its tokens in order, all that were decided, and then one
    closing event; nothing is left queued and every page comes back."""
    from ray_tpu.exceptions import EngineFailedError

    gate, at_gate = threading.Event(), threading.Event()

    class Model(_LaunchingStub):
        def prefill(self, prompt, pages, cached_tokens):
            if case == "prefill_raises" and list(prompt) == [9, 9]:
                raise ValueError("this prompt cannot be prefilled")
            return super().prefill(prompt, pages, cached_tokens)

        def decode(self, last_tokens, positions, block_tables):
            step = last_tokens.step
            if step == 3 and case == "decode_raises_before_launch":
                raise ValueError("before the launch: step 2's tokens are still queued")
            if step == 3 and case == "pool_lost":
                raise EngineFailedError("the pool is gone")
            out = super().decode(last_tokens, positions, block_tables)
            if step == 3 and case == "decode_raises_after_launch":
                raise ValueError("after the launch: step 2's tokens are out")
            if step == 3 and case in ("cancel_mid_step", "close"):
                at_gate.set()  # step 3 in flight, its launch announced
                assert gate.wait(10)
            return out

    prompt = [1, 2]
    toks = _stub_tokens(prompt, 8)
    eos = toks[3] if case == "eos" else None
    eng, streams, _seen = _watched_engine(Model(max_slots=4), f"t-ends-{case}", eos_token=eos)
    try:
        rid = eng.submit(prompt, 6, sink=streams.sink("a"))
        if case == "prefill_raises":
            assert _wait_for(lambda: streams.counts()["a"] >= 2)
            eng.submit([9, 9], 3, sink=streams.sink("b"))
            assert _wait_for(lambda: streams.ended("b"))
            _assert_stream_shape(streams.events["b"], [9, 9], 0, ("error", RayTpuError))
        if case in ("cancel_mid_step", "close"):
            assert at_gate.wait(10)
            assert streams.counts()["a"] == 3  # the first token, steps 1 and 2: step 3 is in flight
            if case == "cancel_mid_step":
                eng.cancel(rid)
                gate.set()
            else:
                closer = threading.Thread(target=eng.close)
                closer.start()
                assert _wait_for(lambda: eng._stop)
                gate.set()
                closer.join(10)
        assert _wait_for(lambda: streams.ended("a"))
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        clk = eng.stats()["clocks"]
        assert not eng._pending
    finally:
        eng.close()
    want = {
        "length": (6, ("done", "stop")),
        "eos": (4, ("done", "stop")),
        # cancelled while step 3 ran: its token is not the stream's any more
        "cancel_mid_step": (3, ("done", "cancelled")),
        "decode_raises_before_launch": (3, ("error", RayTpuError)),
        "decode_raises_after_launch": (3, ("error", RayTpuError)),
        "prefill_raises": (6, ("done", "stop")),
        "pool_lost": (3, ("error", EngineFailedError)),
        # step 3 completed before the loop saw the stop: its token is decided, and delivered before the error
        "close": (4, ("error", RayTpuError)),
    }[case]
    _assert_stream_shape(streams.events["a"], prompt, *want)
    assert clk["deliver"]["n"] == sum(len(e) for e in streams.events.values())
    assert (eng.failed is not None) == (case == "pool_lost")


def test_a_sink_that_raises_under_the_next_launch_cancels_its_sequence():
    """A consumer that is gone shows when its token is delivered, which for a
    model that announces its launches is a step later: the sequence is
    cancelled then and its pages come back; the others are served on."""
    streams = _Streams()
    good = streams.sink("good")

    def gone(ev, val):
        if ev == "tok" and val == _stub_tokens([3, 4], 3)[2]:
            raise ConnectionError("consumer went away")

    eng = InferenceEngine(_LaunchingStub(max_slots=2), EngineConfig(page_tokens=4, pool_pages=64), name="t-gone")
    try:
        eng.submit([1, 2], 12, sink=good)
        eng.submit([3, 4], 30, sink=gone)
        assert _wait_for(lambda: streams.ended("good"))
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        assert eng.decode_steps < 20  # the second did not run its 30
    finally:
        eng.close()
    _assert_stream_shape(streams.events["good"], [1, 2], 12, ("done", "stop"))


def test_paged_lm_announces_its_launches_between_dispatch_and_wait():
    """PagedLM calls the `launched` of the prompt and of the step's tokens
    once each, after its jitted call has returned and before it reads the
    result; through the engine, which takes its results unread, a step is
    read, decided and delivered from the hook of the launch behind it: every
    token but the first and last of a stream, and the tokens are what they were."""
    from ray_tpu import tracing
    from ray_tpu.serve.llm.model import PagedLM, PromptTokens, StepTokens

    lm = PagedLM(num_pages=32, page_tokens=16, max_slots=2, max_pages_per_seq=4)
    calls = []
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        first = lm.prefill(PromptTokens([5, 6, 7], lambda: calls.append("prefill")), [1], 0)
        out = lm.decode(StepTokens([int(first), 0], 1, lambda: calls.append("decode")), [3, -1], [[1], []])
        assert type(out) is list and [s["name"] for s in exp.spans][-3:] == ["llm.decode.prep", "llm.decode.dispatch", "llm.decode.wait"]
        plain = lm.prefill([5, 6, 7], [2], 0)  # a bare list: nothing to call
        assert int(plain) == int(first) and calls == ["prefill", "decode"]
        eng = InferenceEngine(lm, EngineConfig(page_tokens=16, pool_pages=32), name="t-paged-hook")
        try:
            a, b = _collect(eng, [5, 6, 7], 5), _collect(eng, [5, 6, 7], 5)
            clk = eng.stats()["clocks"]
        finally:
            eng.close()
    finally:
        tracing.disable()
    assert a == b and a[0] == int(first) and a[1] == out[0]
    assert clk["deliver"] == {"n": 12, "under_step": 6}  # a stream: tokens 2-4 of 5; the first, the last and `done` at once
    assert clk["decode"]["n"] == 8 and clk["decode"]["chained"] == 6  # a stream's first step follows its prefill
    _clock_identity(clk)
    by = {}
    for s in exp.spans:
        by.setdefault(s["name"], []).append(s)
    waits = sorted(by["llm.decode.wait"] + by["llm.prefill.wait"], key=lambda s: s["t0_ns"])
    dispatches = sorted(by["llm.decode.dispatch"] + by["llm.prefill.dispatch"], key=lambda s: s["t0_ns"])
    under = [e for e in by["llm.emit"] if e["attrs"]["under_step"]]
    assert len(under) == 6 and sum(e["attrs"]["tokens"] for e in under) == 6
    for e in under:
        # launch k, then the wait for step k - 1, its decide, and this emit, before anything else is launched
        d = max((d for d in dispatches if d["t1_ns"] <= e["t0_ns"]), key=lambda d: d["t1_ns"])
        w = max((w for w in waits if w["t1_ns"] <= e["t0_ns"]), key=lambda w: w["t1_ns"])
        assert d["name"] == "llm.decode.dispatch" and w["name"] == "llm.decode.wait" and d["t1_ns"] <= w["t0_ns"]
        assert w["attrs"]["step"] == d["attrs"]["step"] - 1


# --------------------------------------- two decode steps in flight (PR 46)


class _CannotDefer:
    """An adapter around a model that hands the step's tokens on without the
    request to defer: the model waits for every step, as before PR 46."""

    def __init__(self, inner):
        self.inner = inner
        self.max_slots, self.max_pages_per_seq = inner.max_slots, inner.max_pages_per_seq

    def prefill(self, prompt, pages, cached_tokens):
        return self.inner.prefill(prompt, pages, cached_tokens)

    def decode(self, last_tokens, positions, block_tables):
        from ray_tpu.serve.llm.model import StepTokens

        assert min(last_tokens) >= 0  # never handed a marker: nothing of its came back pending
        return self.inner.decode(StepTokens(last_tokens, last_tokens.step, last_tokens.launched), positions, block_tables)


def _scripted_mix(model, name):
    """Seven requests over three slots: lengths from 1 to 9 tokens, submitted
    as earlier ones make progress, two cancelled from their own sinks at a
    set count of tokens. {tag: its stream's events}, the engine's stats."""
    streams = _Streams()
    eng = InferenceEngine(model, EngineConfig(page_tokens=4, pool_pages=64, max_queue=16), name=name)
    rids = {}
    plan = [(0, [3, 1, 4], 9, None), (1, [1, 5], 6, None), (2, [9, 2, 6, 5], 7, 3), (3, [3, 5], 1, None),
            (4, [8, 9, 7, 9, 3], 8, 2), (5, [2, 3], 5, None), (6, [8, 4, 6], 4, None)]

    def sink(tag, cancel_at):
        inner = streams.sink(tag)

        def call(ev, val):
            inner(ev, val)
            if cancel_at is not None and streams.counts()[tag] == cancel_at and ev == "tok":
                eng.cancel(rids[tag])

        return call

    try:
        for tag, prompt, max_new, cancel_at in plan:
            # the next one once the streams before it hold `tag` tokens in all: joins and leaves at many batch shapes
            assert _wait_for(lambda: sum(streams.counts().values()) >= 2 * tag)
            rids[tag] = eng.submit(prompt, max_new, sink=sink(tag, cancel_at))
        assert _wait_for(lambda: all(streams.ended(tag) for tag, *_ in plan))
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        stats = eng.stats()
    finally:
        eng.close()
    return plan, streams.events, stats


def test_a_paged_lm_serves_the_same_tokens_two_deep_as_one_deep():
    """(a) A tiny PagedLM under a scripted mix of submits, cancels and
    finishes: every request is served, token for token, what the same engine
    serves it when the adapter is wrapped so that it cannot defer. A request
    cancelled from its own sink at n tokens holds those n and then what was
    decided before the cancel was seen: one a prefix of the other."""
    from ray_tpu.serve.llm.model import PagedLM

    got = {}
    for kind in ("two_deep", "one_deep"):
        lm = PagedLM(num_pages=64, page_tokens=4, max_slots=3, max_pages_per_seq=8)
        lm.decode([], [], [])  # the benchmark's warm-up: the executable every later call runs
        compiles = lm.describe()["compile"]["compiles"]
        plan, events, stats = _scripted_mix(lm if kind == "two_deep" else _CannotDefer(lm), f"t-mix-{kind}")
        assert lm._decode_jit._cache_size() == 1 and lm.describe()["compile"]["compiles"] - compiles == len(lm._prefill_jits)
        got[kind] = events
        assert (stats["clocks"]["decode"]["chained"] > 0) == (kind == "two_deep")
        _clock_identity(stats["clocks"])
        assert stats["clocks"]["deliver"]["n"] == sum(len(e) for e in events.values())
    for tag, _prompt, max_new, cancel_at in plan:
        two, one = ([v for ev, v in got[kind][tag] if ev == "tok"] for kind in ("two_deep", "one_deep"))
        if cancel_at is None:
            assert two == one and len(two) == max_new and got["two_deep"][tag][-1] == ("done", "stop")
        else:
            short, long = sorted((two, one), key=len)
            assert cancel_at <= len(short) <= len(long) < max_new and long[: len(short)] == short
            assert got["two_deep"][tag][-1] == got["one_deep"][tag][-1] == ("done", "cancelled")


def test_a_paged_lm_engine_keeps_at_most_two_steps_in_flight():
    """(b), (g) Through PagedLM's own spans: never more than two steps in
    flight, launch ordinals consecutive and equal on `llm.decode` /
    `.dispatch` / `.wait`, a `.wait` carries the step it READ, `.prep` and
    `.dispatch` start inside their step's `llm.decode`;
    `clocks.decode.chained` counts the steps dispatched over an unread one,
    and the stage clocks add up to `loop.s`."""
    from ray_tpu import tracing
    from ray_tpu.serve.llm.model import PagedLM

    lm = PagedLM(num_pages=64, page_tokens=4, max_slots=3, max_pages_per_seq=8)
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        _plan, _events, stats = _scripted_mix(lm, "t-two-deep")
    finally:
        tracing.disable()
    spans = [s for s in exp.spans if s["name"].startswith("llm.")]
    chained = _assert_two_deep(spans)
    clk = stats["clocks"]
    assert 0 < chained == clk["decode"]["chained"] < clk["decode"]["n"] == stats["decode_steps"]
    _clock_identity(clk)
    # a step behind a prefill is not chained (the prefill's hook read the step before), every other one behind a live step is
    decodes = [s for s in spans if s["name"] == "llm.decode"]
    assert sum(1 for s in decodes if s["attrs"]["after_prefill"]) <= len(decodes) - chained


def _deferring_engine(name, max_slots=2, step_delay_s=0.002, **cfg):
    model = _DeferringStub(max_slots=max_slots, step_delay_s=step_delay_s)
    eng, streams, seen = _watched_engine(model, name, **cfg)
    return model, eng, streams, seen


def test_a_row_ended_by_eos_computes_one_dead_step_and_nothing_of_it_shows():
    """(c) Done-ness by `eos_token` is known when the step is read, one
    launch late: the row is in exactly one more launch, whose token reaches
    no sink, `tokens_emitted` or `n_out`."""
    prompt = [1, 2]
    toks = _stub_tokens(prompt, 9)
    model, eng, streams, _seen = _deferring_engine("t-dead-step", eos_token=toks[4])
    try:
        eng.submit(prompt, 9, sink=streams.sink("a"))
        seq = next(iter(eng._by_rid.values()))
        assert _wait_for(lambda: streams.ended("a")) and _wait_for(lambda: eng.alloc.used_pages() == 0)
        # the dead step is read (and dropped) before a later request's first step is launched
        eng.submit([20], 3, sink=streams.sink("b"))
        assert _wait_for(lambda: streams.ended("b"))
        stats = eng.stats()
    finally:
        eng.close()
    _assert_stream_shape(streams.events["a"], prompt, 5, ("done", "stop"))
    _assert_stream_shape(streams.events["b"], [20], 3, ("done", "stop"))
    first = [positions[0] for _step, _handed, positions in model.launches[:5]]
    # tokens 2..5 are steps 1..4; step 5 is the dead one, a position further; then the row is gone
    assert first == [2, 3, 4, 5, 6] and model.launches[5][2][0] == 1 and len(model.launches) == 5 + 2
    assert seq.n_out == 5 and seq.finished and stats["tokens_emitted"] == 5 + 3
    assert stats["decode_steps"] == 7 and stats["clocks"]["deliver"]["n"] == 6 + 4


def test_a_cancel_and_a_reused_slot_under_a_step_in_flight():
    """(d) A sequence is cancelled, and its slot given to a new admission,
    while a step that holds the old row is in flight and unread: the old
    row's token is dropped, and the new row's first step takes the token of
    its prefill from the host, not the old row's from the device."""
    gate, at_gate = threading.Event(), threading.Event()

    class Gated(_DeferringStub):
        def decode(self, last_tokens, positions, block_tables):
            out = super().decode(last_tokens, positions, block_tables)
            if last_tokens.step == 3:
                at_gate.set()  # step 3 launched, its result unread; step 2 read and delivered from its hook
                assert gate.wait(10)
            return out

    model = Gated(max_slots=1, step_delay_s=0.001)
    eng, streams, _seen = _watched_engine(model, "t-reuse")
    try:
        rid = eng.submit([1, 2], 9, sink=streams.sink("old"))
        assert at_gate.wait(10)
        assert streams.counts()["old"] == 3  # the first token, steps 1 and 2
        eng.cancel(rid)
        eng.submit([5, 6, 7], 4, sink=streams.sink("new"))
        gate.set()
        assert _wait_for(lambda: streams.ended("new") and streams.ended("old"))
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        stats = eng.stats()
    finally:
        eng.close()
    _assert_stream_shape(streams.events["old"], [1, 2], 3, ("done", "cancelled"))
    _assert_stream_shape(streams.events["new"], [5, 6, 7], 4, ("done", "stop"))
    step, handed, positions = model.launches[3]  # the new row's first step: slot 0 again
    assert (step, handed, positions) == (4, [_stub_tokens([5, 6, 7], 1)[0]], [3])
    assert stats["decode_steps"] == 3 + 3 and stats["tokens_emitted"] == 3 + 4


@pytest.mark.parametrize("lost", [False, True], ids=["read_raises", "pool_lost"])
def test_a_step_that_fails_at_its_deferred_read(lost):
    """(e) The read of step 3 raises, under the launch of step 4: step 3's
    batch fails fast, step 4 (run on step 3's pool and tokens) is dropped
    unread, and the engine serves on; if the pool went with it
    (EngineFailedError) the engine stops and every request gets that."""
    from ray_tpu.exceptions import EngineFailedError

    err = EngineFailedError("the pool is gone") if lost else ValueError("device-side failure, seen at the transfer")
    model, eng, streams, _seen = _deferring_engine("t-read-fails-" + str(int(lost)))
    model.fail_read = (3, err)
    try:
        eng.submit([1, 2], 9, sink=streams.sink("a"))
        eng.submit([3, 4], 9, sink=streams.sink("b"))
        assert _wait_for(lambda: streams.ended("a") and streams.ended("b"))
        assert _wait_for(lambda: eng.alloc.used_pages() == 0)
        assert not eng._pending and eng._flight is None
        if lost:
            assert _wait_for(lambda: not eng._thread.is_alive()) and eng.failed is err
            with pytest.raises(EngineFailedError):
                eng.submit([7], 2, sink=lambda ev, val: None)
        else:
            assert _collect(eng, [5, 6], 4) == _stub_tokens([5, 6], 4) and eng.failed is None
        stats = eng.stats()
    finally:
        eng.close()
    launched = [step for step, _handed, _positions in model.launches]
    for tag, prompt in (("a", [1, 2]), ("b", [3, 4])):
        # the first token and steps 1 and 2 (both rows were in them: admitted in one iteration), then the error
        _assert_stream_shape(streams.events[tag], prompt, 3, ("error", EngineFailedError if lost else RayTpuError))
    assert launched[:4] == [1, 2, 3, 4] and stats["decode_steps"] == 2 + (0 if lost else 3)
    if not lost:  # step 3 failed, step 4 was dropped: neither completed; the later request's three steps did
        assert launched == [1, 2, 3, 4, 5, 6, 7]


def test_a_launch_that_raises_finds_the_step_in_flight_read_first():
    """Step 3 raises before its launch while step 2 is in flight and unread
    (no hook fires): step 2 is read and its token delivered, then the batch
    of step 3 gets the error, and the engine serves on."""

    class Raises(_DeferringStub):
        def decode(self, last_tokens, positions, block_tables):
            if last_tokens.step == 3 and not self.launches[-1][0] == 3:
                self.launches.append((3, [], []))
                raise ValueError("before the launch")
            return super().decode(last_tokens, positions, block_tables)

    eng, streams, _seen = _watched_engine(Raises(max_slots=2, step_delay_s=0.002), "t-raise-over-flight")
    try:
        eng.submit([1, 2], 9, sink=streams.sink("a"))
        assert _wait_for(lambda: streams.ended("a")) and _wait_for(lambda: eng.alloc.used_pages() == 0)
        assert eng._flight is None and not eng._pending
        assert _collect(eng, [5, 6], 4) == _stub_tokens([5, 6], 4)
        stats = eng.stats()
    finally:
        eng.close()
    # the first token, step 1 (read under step 2's launch) and step 2 (read when step 3's launch had raised), then the error
    _assert_stream_shape(streams.events["a"], [1, 2], 3, ("error", RayTpuError))
    assert stats["decode_steps"] == 2 + 3 and stats["clocks"]["deliver"]["n"] == 4 + 5


def test_a_paged_lm_read_that_fails_after_the_pool_was_donated_is_an_engine_failure():
    """(e) PagedLM's rule at a deferred read: an exception there with a leaf
    of the pool given into the step deleted is an EngineFailedError, and the
    pool is gone for every later call; with the pool intact it is the
    exception itself."""
    from ray_tpu.exceptions import EngineFailedError
    from ray_tpu.serve.llm.model import PagedLM

    class Unreadable:
        def __array__(self, *a, **kw):
            raise RuntimeError("INTERNAL: injected at the transfer")

    lm = PagedLM(num_pages=8, page_tokens=4, max_slots=2, max_pages_per_seq=2)
    kv = dict(lm.kv)
    with pytest.raises(RuntimeError, match="injected"):
        lm._read(Unreadable(), kv, "llm.decode")
    assert lm.kv is not None
    import jax.numpy as jnp

    gone = dict(kv, k=jnp.zeros((2,)))
    gone["k"].delete()  # what donation does to the argument buffer
    with pytest.raises(EngineFailedError, match="donated"):
        lm._read(Unreadable(), gone, "llm.decode")
    assert lm.kv is None
    with pytest.raises(EngineFailedError):
        lm.decode([0], [0], [[1]])


def test_a_row_that_reaches_max_new_at_the_step_in_flight_is_absent_from_the_next_launch():
    """(f) Done-ness by `max_new` follows from counts: the row whose last
    token is on its way is not launched again, so a lone request of n tokens
    costs n - 1 steps and a longer neighbour goes on alone, marked -1."""
    model, eng, streams, _seen = _deferring_engine("t-max-new")
    try:
        eng.submit([1, 2], 3, sink=streams.sink("short"))
        eng.submit([3, 4], 6, sink=streams.sink("long"))
        assert _wait_for(lambda: streams.ended("short") and streams.ended("long"))
        stats = eng.stats()
    finally:
        eng.close()
    _assert_stream_shape(streams.events["short"], [1, 2], 3, ("done", "stop"))
    _assert_stream_shape(streams.events["long"], [3, 4], 6, ("done", "stop"))
    first = {tag: _stub_tokens(prompt, 1)[0] for tag, prompt in (("short", [1, 2]), ("long", [3, 4]))}
    assert model.launches == [
        (1, [first["short"], first["long"]], [2, 2]),  # behind their prefills: the host's tokens
        (2, [-1, -1], [3, 3]),  # the short row's last step
        (3, [0, -1], [-1, 4]), (4, [0, -1], [-1, 5]), (5, [0, -1], [-1, 6]),
    ]
    assert stats["decode_steps"] == 5 and stats["clocks"]["decode"]["chained"] == 4 and stats["tokens_emitted"] == 9


@pytest.mark.parametrize("model_cls", [_LaunchingStub, _DeferringStub], ids=["announces", "defers"])
def test_a_first_token_does_not_wait_for_the_other_prefills_of_its_iteration(model_cls):
    """Requests that arrive together are admitted together; each one's first
    token is at its stream before the next one's prefill starts, not behind
    the last one's (clients in lock step would each wait for all their
    neighbours' prompts)."""
    gate, at_gate = threading.Event(), threading.Event()

    class Gated(model_cls):
        def decode(self, last_tokens, positions, block_tables):
            out = super().decode(last_tokens, positions, block_tables)
            if last_tokens.step == 2:
                at_gate.set()
                assert gate.wait(10)
            return out

    eng, streams, seen = _watched_engine(Gated(max_slots=4), f"t-first-token-{model_cls.__name__}")
    try:
        eng.submit([1, 2], 8, sink=streams.sink("a"))
        assert at_gate.wait(10)
        for tag in ("b", "c", "d"):  # while the loop is held inside step 2: one `llm.admit` finds all three
            eng.submit([ord(tag), 2], 3, sink=streams.sink(tag))
        gate.set()
        assert _wait_for(lambda: all(streams.ended(tag) for tag in "abcd"))
        clk = eng.stats()["clocks"]
    finally:
        eng.close()
    prefills = [counts for kind, _step, when, counts in seen if kind == "prefill" and when == "before"][1:]
    assert [(c["b"], c["c"], c["d"]) for c in prefills] == [(0, 0, 0), (1, 0, 0), (1, 1, 0)]
    for tag in "bcd":
        _assert_stream_shape(streams.events[tag], [ord(tag), 2], 3, ("done", "stop"))
    _clock_identity(clk)


def test_a_stub_model_is_offered_deferral_and_served_as_ever():
    """(h) StubModel returns a plain list: one step in flight, never a
    marker, `llm.decide` and `llm.emit` close the step under `llm.step`, and
    no step counts as chained."""
    from ray_tpu import tracing

    seen = []

    class Watching(StubModel):
        def decode(self, last_tokens, positions, block_tables):
            seen.append((last_tokens.step, last_tokens.deferred, min(last_tokens), eng.decode_steps))
            return super().decode(last_tokens, positions, block_tables)

    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    eng = InferenceEngine(Watching(max_slots=2), EngineConfig(page_tokens=4, pool_pages=64), name="t-stub-as-ever")
    try:
        assert _collect(eng, [1, 2], 5) == _stub_tokens([1, 2], 5)
        stats = eng.stats()
    finally:
        eng.close()
        tracing.disable()
    # offered every time, no marker ever, and every step before this one decided when it is launched
    assert seen == [(k, True, seen[k - 1][2], k - 1) for k in range(1, 5)] and all(low >= 0 for _k, _d, low, _n in seen)
    assert stats["clocks"]["decode"] == dict(stats["clocks"]["decode"], n=4, chained=0)
    steps = {s["span_id"] for s in exp.spans if s["name"] == "llm.step"}
    for name in ("llm.decode", "llm.decide", "llm.emit"):
        got = [s for s in exp.spans if s["name"] == name]
        assert len(got) == 4 and all(s["parent_id"] in steps for s in got), name


@pytest.fixture(scope="module")
def traced_stream(stream_next_counts):
    """One streamed request through serve.run(llm_deployment(stub_model)) on
    the cluster runtime with RAY_TPU_TRACING=1: every process's spans, read
    back with collect() after serve.shutdown() killed the replica."""
    import tempfile

    import ray_tpu as rtpu
    from ray_tpu import serve, tracing

    mp = pytest.MonkeyPatch()
    with tempfile.TemporaryDirectory() as d:
        mp.setenv("RAY_TPU_TRACING", "1")
        mp.setenv("RAY_TPU_TRACE_DIR", d)
        rtpu.shutdown()
        rtpu.init(num_cpus=4, num_workers=2)
        tracing.enable()
        try:
            # 20 ms a decode step: the client is waiting when each token lands.
            handle = _deploy_stub(serve, name="llm-traced", step_delay_s=0.02)
            before = stream_next_counts()
            tokens = list(handle.options(stream=True).remote([1, 2, 3], 5))
            woken = {k: v - before[k] for k, v in stream_next_counts().items()}
        finally:
            serve.shutdown()
            rtpu.shutdown()
            tracing.disable()
            mp.undo()
        yield {"tokens": tokens, "spans": tracing.collect(d), "driver_pid": os.getpid(),
               "woken": woken}


def _named(spans, prefix):
    return [s for s in spans if s["name"].split(" ")[0] == prefix]


def test_streamed_request_is_one_trace_from_handle_to_engine(traced_stream):
    spans = traced_stream["spans"]
    assert traced_stream["tokens"] == _stub_tokens([1, 2, 3], 5)
    (request,) = [s for s in _named(spans, "serve.request") if s["attrs"]["stream"]]
    trace = [s for s in spans if s["trace_id"] == request["trace_id"]]
    ids = {s["span_id"] for s in trace}
    names = {s["name"].split(" ")[0] for s in trace}
    assert {"serve.request", "serve.stream.next", "serve.replica", "llm.queue", "llm.prefill",
            "llm.first_token", "core.stream_next", "core.stream_item"} <= names, names
    # Every parent resolves inside the trace; the request is its only root.
    assert [s["name"] for s in trace if s["parent_id"] not in ids] == [request["name"]]
    replica = next(s for s in trace if s["name"].startswith("serve.replica"))
    assert replica["pid"] != request["pid"]
    for name in ("llm.queue", "llm.prefill", "llm.first_token"):
        (s,) = _named(trace, name)
        assert s["parent_id"] == replica["span_id"] and s["pid"] == replica["pid"]
    nexts = _named(trace, "serve.stream.next")
    assert [s["attrs"]["index"] for s in nexts] == list(range(6))  # 5 tokens + the end
    assert all(s["parent_id"] == request["span_id"] for s in nexts)


def test_core_stream_hops_join_on_task_and_index(traced_stream):
    spans = traced_stream["spans"]
    hops = {}
    for name in ("core.stream_item", "core.stream_ack", "core.stream_next"):
        for s in _named(spans, name):
            if s["attrs"].get("found") != "header":
                hops.setdefault((s["attrs"]["task"], s["attrs"]["index"]), {})[name] = s
    stream = {k: v for k, v in hops.items() if "core.stream_next" in v}
    assert len({task for task, _ in stream}) == 1
    assert sorted(i for _, i in stream) == list(range(5))
    for hop in stream.values():
        item, ack, nxt = hop["core.stream_item"], hop["core.stream_ack"], hop["core.stream_next"]
        assert item["t0_ns"] <= item["t1_ns"] and item["t0_ns"] <= ack["t1_ns"] <= nxt["t1_ns"]
        assert item["attrs"]["route"] == "inline" and item["attrs"]["reported"] == "direct"
        assert ack["attrs"]["inline"] is True and nxt["attrs"]["found"] == "memstore"
        # An inline item arrives as an ack on the direct connection: the raylet
        # is never asked, and the call returns when the ack lands.
        assert nxt["attrs"]["remote_checks"] == 0
        assert nxt["t1_ns"] - ack["t1_ns"] < 50e6
        if nxt["attrs"]["waits"]:
            # The consumer was waiting: the ack woke it.
            assert nxt["attrs"]["woken"] == "ack" and ack["attrs"]["notified"] is True
        else:
            # The ack landed before the call looked: nobody to notify.
            assert nxt["attrs"]["woken"] == "none" and ack["attrs"]["notified"] is False
        assert ack["pid"] == nxt["pid"] == traced_stream["driver_pid"] != item["pid"]
    assert sum(1 for hop in stream.values() if hop["core.stream_ack"]["attrs"]["notified"]) >= 3


def test_stream_next_counter_matches_the_spans(traced_stream):
    """raytpu_stream_next_total{woken} is always on and counts what the
    core.stream_next spans say: one a call, the end of the stream included."""
    nexts = _named(traced_stream["spans"], "core.stream_next")
    assert len(nexts) == 6  # 5 tokens + the header
    tally = {}
    for s in nexts:
        tally[s["attrs"]["woken"]] = tally.get(s["attrs"]["woken"], 0) + 1
    woken = traced_stream["woken"]
    assert set(woken) == {"none", "ack", "raylet", "poll", "timeout"}
    assert {k: v for k, v in woken.items() if v} == tally
    assert woken["raylet"] == woken["poll"] == woken["timeout"] == 0 and woken["ack"] >= 3


def test_replica_spans_survive_serve_shutdown(traced_stream):
    """The replica is SIGKILLed by serve.shutdown(): its buffer (engine
    thread included) is flushed in prepare_shutdown."""
    spans = traced_stream["spans"]
    replica_pid = next(s["pid"] for s in spans if s["name"].startswith("serve.replica"))
    engine = [s for s in spans if s["name"] in ("llm.step", "llm.decode", "llm.emit")]
    assert len(engine) == 3 * 4 and {s["pid"] for s in engine} == {replica_pid}
    assert {s["name"] for s in _named(spans, "serve.run")} == {"serve.run"}


# ----------------------------------------- the slot on the admitted prompt


class _SlotWatchingStub(StubModel):
    """Notes the `slot` each prompt carried into `prefill` and the rows that
    were live in each `decode`: what a model with a fixed state a sequence
    (PagedLM over a KDA stack) keeps that state by."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.prefilled, self.live_rows = [], []

    def prefill(self, prompt, pages, cached_tokens):
        self.prefilled.append((getattr(prompt, "slot", "absent"), tuple(pages)))
        return super().prefill(prompt, pages, cached_tokens)

    def decode(self, last_tokens, positions, block_tables):
        self.live_rows.append({i: tuple(block_tables[i]) for i, p in enumerate(positions) if p >= 0})
        return super().decode(last_tokens, positions, block_tables)


@pytest.mark.parametrize("n_requests", [2, 5], ids=["as_many_as_slots", "more_than_twice_the_slots"])
def test_the_engine_writes_the_decode_row_onto_the_prompt_it_admits(n_requests):
    """Engine to model: `PromptTokens.slot` is the row the sequence decodes in
    from its prefill to its end. Every prefill carries one; the row whose
    block table holds a prefill's pages is that slot in every later step; a
    waiting request gets the slot of the one that left."""
    model = _SlotWatchingStub(max_slots=2, step_delay_s=0.002)
    eng = InferenceEngine(model, EngineConfig(page_tokens=4, pool_pages=64), name="t-slot")
    try:
        threads = [threading.Thread(target=_collect, args=(eng, [i + 1, i + 2, i + 3], 6 + i)) for i in range(n_requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        eng.close()
    assert len(model.prefilled) == n_requests and all(slot in (0, 1) for slot, _pages in model.prefilled)
    for slot, pages in model.prefilled:
        rows = [step for step in model.live_rows if any(table[: len(pages)] == pages for table in step.values())]
        assert rows and all(step.get(slot, ())[: len(pages)] == pages for step in rows), (slot, pages)
    assert {slot for slot, _ in model.prefilled} == {0, 1}


def test_a_bare_list_has_no_slot_and_the_prompt_type_carries_one():
    from ray_tpu.serve.llm.model import PromptTokens

    assert PromptTokens([1, 2]).slot is None and PromptTokens([1], slot=3).slot == 3 and list(PromptTokens((1, 2))) == [1, 2]
    model = _SlotWatchingStub()
    model.prefill([1, 2, 3], [1], 0)
    assert model.prefilled == [("absent", (1,))]

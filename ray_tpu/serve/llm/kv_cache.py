"""Paged KV-cache allocator with prefix reuse.

The physical cache (device arrays, models/transformer.py init_kv_pages)
is a pool of fixed-size pages; this module owns the BOOKKEEPING: which
pages are free, which sequence holds which pages (its block table), and
which full pages hold content that future prompts can share.

Prefix reuse is an exact radix index (vLLM's automatic prefix caching,
SGLang's RadixAttention), kept as a trie over FULL pages of prompts: a
node is one link of a content chain, named by a small integer, and
`(parent node's number, tokens-in-page)` -> node is interned in one dict
(the root is 0; numbers come from a counter and are never reused). Two
prompts that share a system prefix walk the same nodes and resolve to
the same physical pages, so the shared prefix costs one physical copy,
and a page's lookup hashes that page's tokens and nothing of the pages
under it: a prompt's walk is linear in its length. A node holds at most
one page (the indexed copy of that content). It lives while it holds a
page or has a child and is pruned, upwards, when it has neither: a
chain whose root was evicted keeps its children addressable for the
prompt that recommits the root, and a chain with no page left in it
prunes to nothing. No hash or digest stands in for a comparison: the
dict compares the page's tokens on every hit (Python's hash of ints is
not randomised, hash(-1) == hash(-2), and a collision taken for a match
would serve one request another's K/V).

Pages are refcounted; when the last holder releases an indexed page it
parks on an eviction LRU with its content intact — a later identical
prefix revives it for free, while allocation pressure evicts from the
LRU's cold end before declaring the pool exhausted.

Sizing knobs (read by the engine, documented in README):
  RAY_TPU_KV_PAGE_TOKENS  tokens per page        (default 16)
  RAY_TPU_KV_POOL_PAGES   pages in the pool      (default 128)
"""

from __future__ import annotations

import collections
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ...exceptions import KVPoolExhaustedError
from ...utils import lock_order

# Page index 0 is the model's trash page (masked writes land there); the
# allocator never hands it out.
TRASH_PAGE = 0

_Link = Tuple[int, Tuple[int, ...]]  # (parent node's number, tokens-in-page)


class _Node:
    """One link of a content chain in the prefix trie."""

    __slots__ = ("id", "link", "parent", "page", "children")

    def __init__(self, id: int, link: Optional[_Link], parent: "Optional[_Node]"):
        self.id = id
        self.link = link  # its key in the allocator's dict; None for the root
        self.parent = parent
        self.page: Optional[int] = None  # the indexed copy of this content, if any
        self.children = 0


@dataclass
class SeqPages:
    """One sequence's slice of the pool: its block table plus how much of
    the prompt arrived via the prefix cache (prefill may skip re-writing
    those positions — the bytes are already on device)."""

    pages: List[int]
    cached_tokens: int  # prompt positions covered by shared prefix pages
    released: bool = field(default=False, repr=False)
    # Where allocate's walk of the index ended: the node of the last matched
    # page (None: the root). The sequence holds the matched pages, so their
    # nodes are alive and commit resumes from here.
    node: Optional[_Node] = field(default=None, repr=False, compare=False)

    @property
    def num_pages(self) -> int:
        return len(self.pages)


class PagedKVAllocator:
    """Free-list page allocator + refcounts + exact prefix trie.

    Thread-safe: the engine loop extends/releases while submitters
    allocate. `metrics` is an optional dict of pre-bound instrument
    handles ({"hits", "misses", "used", "total"}) so the allocator stays
    importable without pulling a deployment label in here.
    `share_prefixes` False: no page is indexed or matched (a model whose page
    is one sequence's recurrent state, not positions of K/V: serve/llm/model.py).
    """

    def __init__(self, num_pages: int, page_tokens: int, metrics: Optional[dict] = None, share_prefixes: bool = True):
        if num_pages < 2:
            raise ValueError(f"pool needs >= 2 pages (1 is the trash page), got {num_pages}")
        if page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
        self.page_tokens = page_tokens
        self.num_pages = num_pages
        self.share_prefixes = share_prefixes
        self._lock = lock_order.tracked_lock("serve.llm.kv")
        self._free: List[int] = list(range(num_pages - 1, TRASH_PAGE, -1))
        self._ref: Dict[int, int] = {}
        # prefix trie: (parent's number, tokens-in-page) -> node, and the
        # node of each indexed page for eviction
        self._root = _Node(0, None, None)
        self._links: Dict[_Link, _Node] = {}
        self._node_ids = itertools.count(1)
        self._page_node: Dict[int, _Node] = {}
        # zero-ref indexed pages, oldest-released first (eviction order)
        self._evictable: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        self._metrics = metrics or {}
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.index_s = 0.0  # spent under the lock in allocate's and commit's walks
        self.index_calls = 0  # allocate calls that walked
        g = self._metrics.get("total")
        if g is not None:
            g.set(self.total_pages)

    # ---------------------------------------------------------- capacity

    @property
    def total_pages(self) -> int:
        return self.num_pages - 1  # trash page excluded

    def used_pages(self) -> int:
        with self._lock:
            return len(self._ref)

    def free_pages(self) -> int:
        """Pages allocatable right now (free list + evictable LRU)."""
        with self._lock:
            return len(self._free) + len(self._evictable)

    def pages_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_tokens))

    # --------------------------------------------------------- allocation

    def _take_page_locked(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        if self._evictable:
            page, _ = self._evictable.popitem(last=False)  # coldest first
            node = self._page_node.pop(page)
            node.page = None
            while node.page is None and not node.children and node.parent is not None:
                del self._links[node.link]
                node = node.parent
                node.children -= 1
            return page
        return None

    def _return_page_locked(self, page: int) -> None:
        if page in self._page_node:
            # Content stays addressable: park on the LRU, revive on match.
            self._evictable[page] = None
        else:
            self._free.append(page)

    def allocate(self, tokens) -> SeqPages:
        """Reserves pages covering `tokens`, reusing indexed full pages.

        Raises KVPoolExhaustedError (typed, a BackpressureError) when the
        pool — after evicting every cold cached page — still cannot hold
        the prompt. Nothing is reserved on failure.
        """
        tokens = tuple(tokens)
        need = self.pages_for(len(tokens))
        T = self.page_tokens
        with self._lock:
            # Walk the trie over FULL pages of the prompt, to the first link
            # that is absent or holds no page.
            matched: List[int] = []
            node = self._root
            n_full = len(tokens) // T if self.share_prefixes else 0
            if n_full:
                t0 = time.perf_counter()
                links = self._links
                for i in range(n_full):
                    child = links.get((node.id, tokens[i * T:(i + 1) * T]))
                    if child is None or child.page is None:
                        break
                    matched.append(child.page)
                    node = child
                self.index_s += time.perf_counter() - t0
                self.index_calls += 1
            fresh_needed = need - len(matched)
            free_now = len(self._free) + len(self._evictable)
            # Matched evictable pages are revived, not consumed from the
            # allocatable count — but a matched page sitting on the LRU
            # both "frees" and "is used", so count conservatively: fresh
            # pages must come from pages NOT in the match set.
            revivable = sum(1 for p in matched if p in self._evictable)
            if fresh_needed > free_now - revivable:
                raise KVPoolExhaustedError(
                    needed_pages=fresh_needed,
                    free_pages=free_now - revivable,
                    total_pages=self.total_pages,
                )
            for page in matched:
                if page in self._evictable:
                    del self._evictable[page]
                self._ref[page] = self._ref.get(page, 0) + 1
            fresh: List[int] = []
            for _ in range(fresh_needed):
                page = self._take_page_locked()
                assert page is not None  # guaranteed by the check above
                self._ref[page] = 1
                fresh.append(page)
            self.prefix_hits += len(matched)
            self.prefix_misses += fresh_needed
            self._observe_locked(hits=len(matched), misses=fresh_needed)
            return SeqPages(pages=matched + fresh, cached_tokens=len(matched) * T, node=node)

    def extend(self, seq: SeqPages) -> int:
        """Appends one decode-growth page to `seq`'s block table."""
        with self._lock:
            page = self._take_page_locked()
            if page is None:
                raise KVPoolExhaustedError(
                    needed_pages=1, free_pages=0, total_pages=self.total_pages
                )
            self._ref[page] = 1
            seq.pages.append(page)
            self._observe_locked()
            return page

    def commit(self, seq: SeqPages, tokens) -> None:
        """Indexes `seq`'s full prompt pages so later prompts can share
        them. Called after prefill (the pages now hold real k/v), with the
        tokens `allocate` was given: the walk resumes behind the pages that
        call matched, which `seq` has held since."""
        if not self.share_prefixes:
            return
        tokens = tuple(tokens)
        T = self.page_tokens
        with self._lock:
            t0 = time.perf_counter()
            links = self._links
            if seq.released or seq.node is None:
                first, node = 0, self._root  # its pages may be gone: the whole walk
            else:
                first, node = seq.cached_tokens // T, seq.node
            for i in range(first, len(tokens) // T):
                link = (node.id, tokens[i * T:(i + 1) * T])
                page = seq.pages[i]
                child = links.get(link)
                cur = None if child is None else child.page
                if cur is None and page not in self._page_node:
                    if child is None:
                        child = links[link] = _Node(next(self._node_ids), link, node)
                        node.children += 1
                    child.page = page
                    self._page_node[page] = child
                elif cur != page:
                    # A concurrent twin committed the same content first;
                    # ours stays private and frees normally.
                    break
                node = child
            self.index_s += time.perf_counter() - t0

    def release(self, seq: SeqPages) -> None:
        """Drops `seq`'s references. Idempotent — the cancel path and the
        normal finish path may race to release the same sequence."""
        with self._lock:
            if seq.released:
                return
            seq.released = True
            for page in seq.pages:
                n = self._ref.get(page, 0) - 1
                if n > 0:
                    self._ref[page] = n
                else:
                    self._ref.pop(page, None)
                    self._return_page_locked(page)
            self._observe_locked()

    # ----------------------------------------------------------- metrics

    def _observe_locked(self, hits: int = 0, misses: int = 0) -> None:
        g = self._metrics.get("used")
        if g is not None:
            g.set(len(self._ref))
        if hits:
            c = self._metrics.get("hits")
            if c is not None:
                c.inc(hits)
        if misses:
            c = self._metrics.get("misses")
            if c is not None:
                c.inc(misses)

    def stats(self) -> dict:
        with self._lock:
            return {
                "total_pages": self.total_pages,
                "used_pages": len(self._ref),
                "free_pages": len(self._free),
                "evictable_pages": len(self._evictable),
                "indexed_pages": len(self._page_node),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "index_s": self.index_s,
                "index_calls": self.index_calls,
                "index_nodes": len(self._links),
            }

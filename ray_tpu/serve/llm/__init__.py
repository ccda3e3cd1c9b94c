"""serve.llm — production LLM inference engine on Serve.

Continuous batching + paged KV cache + prefix reuse:

- kv_cache:  free-list page allocator, refcounted pages, exact prefix
             trie (shared system prompts cost one physical copy);
- engine:    resident continuous-batching loop (token-level join/leave,
             prefill admission against a token budget, typed
             reject-with-backpressure shedding);
- model:     paged prefill/decode adapters over models/transformer.py
             (one compiled decode step for every batch composition);
- feed:      persistent cgraph-channel request path (no per-call actor
             task submission);
- deployment: LLMServer / llm_deployment — the serve-facing surface.
"""

from .deployment import LLMServer, llm_deployment
from .engine import EngineConfig, InferenceEngine
from .feed import FeedServer, LLMClient
from .kv_cache import PagedKVAllocator, SeqPages
from .model import PagedLM, StubModel, stub_model, tiny_paged_lm

__all__ = [
    "EngineConfig",
    "FeedServer",
    "InferenceEngine",
    "LLMClient",
    "LLMServer",
    "PagedKVAllocator",
    "PagedLM",
    "SeqPages",
    "StubModel",
    "llm_deployment",
    "stub_model",
    "tiny_paged_lm",
]

"""Where XLA's persistent compilation cache lives.

Every process that can own a chip (worker start-up, the in-process
runtime) calls `configure()` before its first compile. The directory must
not move: a cache at a temp name, pid or timestamp never hits.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): the same path for the driver, every
# worker the raylet spawns and every child the zygote forks.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure() -> str:
    """Returns the cache directory in effect. With JAX_COMPILATION_CACHE_DIR
    set, jax reads it itself and nothing is set in code; otherwise jax is
    pointed at DEFAULT_DIR. Never imports jax (most workers never use it)
    and never initializes a backend."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    # jax reads the variable when it is imported; processes spawned from
    # here inherit it and take the early return above.
    os.environ[ENV_VAR] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:  # already imported: its config was read too early
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CompileWatch:
    """Counts this process's XLA compiles and persistent-cache traffic from
    jax's own monitoring events. jax's listener registry is process-wide
    and so is this: take it from `watch()` before the first compile and
    read differences of `snapshot()`. Read by chip_smoke.py (compile seconds
    apart from step time; compiles inside a window; cache hits when warm)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0  # backend compile, or retrieval on a cache hit
        self.cache_hits = 0
        self.cache_writes = 0  # entries written: compiles over jax's thresholds
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self) -> Dict[str, Any]:
        import jax

        return {
            "cache_dir": jax.config.jax_compilation_cache_dir,
            "compiles": self.compiles,
            "compile_s": round(self.compile_s, 3),
            "cache_hits": self.cache_hits,
            "cache_writes": self.cache_writes,
        }


_watch: Optional[CompileWatch] = None


def watch() -> CompileWatch:
    """The process's one CompileWatch (registered on first use)."""
    global _watch
    if _watch is None:
        _watch = CompileWatch()
    return _watch

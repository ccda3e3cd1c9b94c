"""The benchmark's model_builder for llm_deployment: runs in the replica that
owns the chip. It builds the program's PagedLM at the configuration's widths
(weights from one jitted init of the seed, on the device, in bf16) and wraps
its `prefill` / `decode` with the benchmark's spans; the engine above it and
the paged forward below it are the program's, untouched.

The driver steers it through one extra method, `bench(cmd, **kw)`, which the
builder attaches to the replica's LLMServer class in the replica process (the
program has no control surface of its own yet; PERF.md lists it for the
tracing issue): warm-up of every prefill bucket, window marks (CompileWatch
and engine counters), start/stop of the device trace, the plain reference
over a sample of the window's finished requests once the load has stopped,
and the spans at the end.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time
import zlib
from typing import Any, Dict, List

from . import spec
from .worker_train import cache_everything, device_facts, find_xplane, memory_peak_bytes, seeded_key


def prompt_crc(prompt) -> int:
    import numpy as np

    return zlib.crc32(np.asarray(prompt, dtype=np.int32).tobytes())


class BenchModel:
    """The engine's model-adapter protocol over a PagedLM, with spans."""

    def __init__(self, conf: Dict[str, Any]):
        import jax

        from ray_tpu.models import transformer as tfm
        from ray_tpu.serve.llm.model import PagedLM
        from ray_tpu.utils import compile_cache

        from . import correct

        t0 = time.monotonic()
        self._jax = jax
        self.conf = conf
        self.watch = compile_cache.watch()
        cache_everything()
        self.device = device_facts(jax.devices(), conf["allow_cpu"])
        t1 = time.monotonic()
        self.arch = spec.load_arch(conf["model"])
        cfg = self.arch.model_config(conf["model"])
        eng = {k: v["value"] for k, v in conf["model"]["assumed"].items()}
        params = jax.jit(lambda k: correct.init_weights(tfm, cfg, k))(seeded_key(conf["seed"]))
        self.lm = PagedLM(
            cfg, params, num_pages=eng["pool_pages"], page_tokens=eng["page_tokens"],
            max_slots=eng["max_slots"], max_pages_per_seq=eng["max_pages_per_seq"],
        )
        jax.block_until_ready(params)
        self.vocab = self.lm.vocab
        self.max_slots = self.lm.max_slots
        self.max_pages_per_seq = self.lm.max_pages_per_seq
        self.page_tokens = self.lm.page_tokens
        self.spans: List[list] = []
        self.peak_bytes = None  # read by the check before it runs the reference
        self.tracing = False
        self._logdir = conf["out_prefix"] + "-trace"
        self.setup_parts_s = {"replica_start_to_devices": t1 - t0, "init_weights_and_pool": time.monotonic() - t1}

    # ------------------------------------------------ the adapter protocol

    def prefill(self, prompt, pages, cached_tokens: int) -> int:
        T = self.page_tokens
        args = {
            "prompt_tokens": len(prompt),
            "bucket_tokens": self.lm._bucket_pages(max(1, -(-len(prompt) // T))) * T,
            "cached_tokens": int(cached_tokens),
        }
        ctx = self._jax.profiler.TraceAnnotation("bench.prefill", **args) if self.tracing else contextlib.nullcontext()
        t0 = time.monotonic()
        with ctx:
            tok = self.lm.prefill(prompt, pages, cached_tokens)
        self.spans.append(["bench.prefill", t0, time.monotonic(), dict(args, crc=prompt_crc(prompt))])
        return tok

    def decode(self, last_tokens, positions, block_tables):
        live = [int(p) for p in positions if int(p) >= 0]
        args = {"live": len(live), "kv_tokens": sum(p + 1 for p in live)}
        ctx = self._jax.profiler.TraceAnnotation("bench.decode", **args) if self.tracing else contextlib.nullcontext()
        t0 = time.monotonic()
        with ctx:
            out = self.lm.decode(last_tokens, positions, block_tables)
        self.spans.append(["bench.decode", t0, time.monotonic(), args])
        return out

    def describe(self) -> Dict[str, Any]:
        return self.lm.describe()

    # ------------------------------------------------------- the control

    def control(self, cmd: str, engine=None, **kw) -> Dict[str, Any]:
        return getattr(self, "_cmd_" + cmd)(engine=engine, **kw)

    def _cmd_warmup(self, engine, min_prompt_tokens: int, max_prompt_tokens: int) -> Dict[str, Any]:
        """Runs every executable the traffic can use once: decode, and the
        prefill of every bucket that a prompt length in the FILE's range maps
        to under the program's own bucket rule. All writes go to the trash page."""
        from ray_tpu.serve.llm.kv_cache import TRASH_PAGE

        t0, T = time.monotonic(), self.page_tokens
        pages = range(max(1, -(-min_prompt_tokens // T)), max(1, -(-max_prompt_tokens // T)) + 1)
        buckets = sorted({self.lm._bucket_pages(p) for p in pages})
        for b in buckets:
            self.lm.prefill([1] * (b * T), [TRASH_PAGE] * b, 0)
        self.lm.decode([], [], [])
        return {"buckets": buckets, "warmup_s": time.monotonic() - t0, "setup_parts_s": self.setup_parts_s,
                "compile": self.watch.snapshot(), "device": self.device}

    def _cmd_check(self, engine, requests, pad_tokens: int, pad_served: int, control: bool) -> Dict[str, Any]:
        """After the load has stopped and before `finish`, so that neither
        setup_s nor the window sees it: the reference once over each sampled
        request's prompt with the tokens it was served. Waits for the engine
        to reap what was cancelled, and reads the peak before the reference
        adds to it."""
        from . import correct

        t0 = time.monotonic()
        while time.monotonic() - t0 < 30.0:
            st = engine.stats()
            if not st["running"] and not st["waiting"]:
                break
            time.sleep(0.02)
        self.peak_bytes = memory_peak_bytes(self._jax.devices())
        rows = [correct.served_margins(self.arch, self.lm.params, r["prompt"], r["served"], self.conf["model"], pad_tokens, pad_served, control)
                for r in requests]
        return {"rows": rows, "seconds": time.monotonic() - t0}

    def _cmd_mark(self, engine) -> Dict[str, Any]:
        return {"t": time.monotonic(), "compile": self.watch.snapshot(), "engine": engine.stats()}

    def _cmd_trace_start(self, engine) -> Dict[str, Any]:
        shutil.rmtree(self._logdir, ignore_errors=True)
        self._jax.profiler.start_trace(self._logdir)
        self.tracing = True
        return {"t": time.monotonic()}

    def _cmd_trace_stop(self, engine) -> Dict[str, Any]:
        self.tracing = False
        self._jax.profiler.stop_trace()
        return {"t": time.monotonic(), "trace_path": find_xplane(self._logdir)}

    def _cmd_finish(self, engine) -> Dict[str, Any]:
        return {
            "pid": os.getpid(),
            "device": self.device,
            "spans": self.spans,
            "memory_peak_bytes": self.peak_bytes or memory_peak_bytes(self._jax.devices()),
            "engine": engine.stats(),
            "compile": self.watch.snapshot(),
            "arch_file": os.path.relpath(self.arch.__file__, spec.ROOT),
        }


def _bench(self, cmd: str, **kw):
    """LLMServer.bench: handle.options(method_name="bench").remote(cmd, ...)."""
    return self.model.control(cmd, engine=self.engine, **kw)


def build(bench: Dict[str, Any]) -> BenchModel:
    from ray_tpu.serve.llm.deployment import LLMServer

    LLMServer.bench = _bench
    return BenchModel(bench)

"""Records benchmarks/recorded/tiny_v5e_llm.xplane.pb.gz (run once, on the chip):
a small trace WITH the program's own `llm.*` host events in it, for the tests
of benchmarks/readers/trace_program_spans.py (tiny_v5e.xplane.pb.gz predates
those spans and has none). One process, no runtime: an InferenceEngine over a
2-layer PagedLM serves three requests while the profiler runs; the engine's
model adapter adds the benchmark's `bench.prefill` / `bench.decode`
annotations the way lib/worker_serve.py's BenchModel does, because
lib/trace.py takes its window from them.

    chiprun -- python3 benchmarks/tools/record_program_trace.py   # -> chiprun_out/recorded/
"""

from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm.model import PagedLM

    out = os.path.join(ROOT, "chiprun_out", "recorded")
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=512, max_seq_len=512,
    )
    lm = PagedLM(cfg, None, seed=0, num_pages=64, page_tokens=16, max_slots=4, max_pages_per_seq=8)

    class Annotated:
        """bench.* annotations around the program's adapter, as BenchModel's."""

        vocab, max_slots, max_pages_per_seq = lm.vocab, lm.max_slots, lm.max_pages_per_seq

        def prefill(self, prompt, pages, cached_tokens):
            with jax.profiler.TraceAnnotation("bench.prefill", prompt_tokens=len(prompt), cached_tokens=int(cached_tokens)):
                return lm.prefill(prompt, pages, cached_tokens)

        def decode(self, last_tokens, positions, block_tables):
            live = [int(p) for p in positions if int(p) >= 0]
            with jax.profiler.TraceAnnotation("bench.decode", live=len(live), kv_tokens=sum(p + 1 for p in live)):
                return lm.decode(last_tokens, positions, block_tables)

    eng = InferenceEngine(Annotated(), EngineConfig(page_tokens=16, pool_pages=64), name="recorded")
    list(eng.generate(list(range(1, 41)), 3))  # compiles the 64-token prefill bucket and decode
    list(eng.generate(list(range(1, 101)), 2))  # and the 128-token bucket

    logdir = os.path.join(out, "tb_llm")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    outs = []
    threads = [
        threading.Thread(target=lambda p=p, n=n: outs.append(list(eng.generate(p, n))))
        for p, n in ((list(range(1, 41)), 6), (list(range(5, 105)), 4), (list(range(1, 41)) + [7, 8, 9], 5))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, "tiny_v5e_llm.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(dst, "wb", 9) as g:
        g.write(f.read())
    print("tokens", sorted(len(o) for o in outs), "xplane bytes", os.path.getsize(path), "gz", os.path.getsize(dst), flush=True)

    from benchmarks.lib.trace import Trace
    from benchmarks.readers import trace_program_spans as tps

    import collections

    tr, spans = Trace(dst), tps.program_spans(dst)
    print("bench spans", collections.Counter(s["name"] for s in tr.spans), flush=True)
    print("llm events", collections.Counter(s["name"] for s in spans), flush=True)
    print("window_s", tr.window_s(), "busy_s", tr.busy_s(), "skew_s", tr.skew_s, flush=True)
    print("idle by span", tps.idle_by_innermost_span(tr, spans), flush=True)
    args = {"stat": "median_sum_ms", "within": "llm.decode", "spans": ["llm.decode.prep", "llm.decode.dispatch"]}
    print(args["stat"], tps.read({"worker": {"trace_path": dst}}, args), flush=True)
    first = [s for s in spans if s["name"] in ("llm.step", "llm.decode", "llm.prefill")][:6]
    print("first events", [(s["name"], s["args"]) for s in first], flush=True)
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

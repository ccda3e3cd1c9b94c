"""Records the small trace kept under benchmarks/recorded/ (run once, on the chip).

One process, no runtime: a few train steps and a few paged prefill/decode
steps of a model small enough that the trace stays small, but with
head_dim 128 and a sequence the Mosaic flash kernels tile, so the trace
holds the same kinds of event as a real run: the three flash custom
calls, XLA fusions, and the benchmark's own host spans
(`jax.profiler.TraceAnnotation`, with their arguments as event stats).
benchmarks/tests checks benchmarks/lib/trace.py against the file.

    chiprun -- python3 benchmarks/tools/record_trace.py   # -> chiprun_out/recorded/
"""

from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.model import PagedLM

    out = os.path.join(ROOT, "chiprun_out", "recorded")
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=512,
        max_seq_len=512, remat_policy="hot",
    )
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    init_state, step = tfm.build_train_step(cfg, optax.adamw(1e-4), mesh)
    params, opt = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 512), 0, cfg.vocab_size, jnp.int32)
    for _ in range(2):
        params, opt, loss = step(params, opt, tokens)
        jax.block_until_ready(loss)

    lm = PagedLM(cfg, None, seed=0, num_pages=64, page_tokens=16, max_slots=4, max_pages_per_seq=8)
    prompt = list(range(1, 101))
    pages = list(range(1, 8))
    lm.prefill(prompt, pages, 0)
    lm.decode([5], [100], [pages])

    logdir = os.path.join(out, "tb")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.train_step", step=i, tokens=1024):
            params, opt, loss = step(params, opt, tokens)
            jax.block_until_ready(loss)
    with jax.profiler.TraceAnnotation("bench.prefill", prompt_tokens=100, bucket_tokens=128, cached_tokens=0):
        tok = lm.prefill(prompt, pages, 0)
    pos = 100
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench.decode", live=1, kv_tokens=pos + 1):
            tok = lm.decode([tok], [pos], [pages])[0]
        pos += 1
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, "tiny_v5e.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(dst, "wb", 9) as g:
        g.write(f.read())
    print("xplane bytes", os.path.getsize(path), "gz", os.path.getsize(dst), flush=True)

    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", repr(plane.name), "lines", len(list(plane.lines)))
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print("  LINE", repr(line.name), "events", len(evs), "first_start_ns", evs[0].start_ns,
                  "dur_ns", evs[0].duration_ns)
            print("     ", top)
            if "bench" in " ".join(names) or line.name in ("XLA Ops", "XLA Modules", "Steps"):
                for e in evs[:6]:
                    print("      EV", e.name, e.start_ns, e.duration_ns, list(e.stats)[:8])
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Roofline share of the paged decode kernel under attention windows: the K/V
the traced decode steps' live rows can see, each layer clipped to its window
(the architecture file's `decode_attention_bytes`, from each step's span), /
peak bytes/s / the device time of the kernel's calls. A kernel that walks the
pages below a window reads that share of its roofline less."""

from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None or not hasattr(args["cell"].arch, "decode_attention_bytes"):
        return None
    cell = args["cell"]
    bw = device_peaks(evidence)["hbm_bytes_per_s"]
    least = sum(
        cell.arch.decode_attention_bytes(cell.config, int(s["args"]["live"]), int(s["args"]["kv_tokens"])) / bw
        for s in tr.spans if s["name"] == args["span"] and s["args"].get("live", 0) > 0
    )
    traced = tr.op_seconds(args["pattern"])
    return None if not traced else 100.0 * least / traced

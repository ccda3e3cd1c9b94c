"""Roofline share of the decode step's state update under power retention:
the state the traced decode steps' live rows must move, every layer's, read
once and written once (the architecture file's `decode_state_bytes`, from each
step's span), / peak bytes/s / the device time of the kernel's calls. The
bytes are the least any layout holds, so a share past 100 % cannot come from
the count. None where the trace holds no such op (a program that updates its
states in plain XLA ops, or has none)."""

from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None or not hasattr(args["cell"].arch, "decode_state_bytes"):
        return None
    cell = args["cell"]
    bw = device_peaks(evidence)["hbm_bytes_per_s"]
    least = sum(
        cell.arch.decode_state_bytes(cell.config, int(s["args"]["live"])) / bw
        for s in tr.spans if s["name"] == args["span"] and s["args"].get("live", 0) > 0
    )
    traced = tr.op_seconds(args["pattern"])
    return None if not traced else 100.0 * least / traced

"""Bytes the traced decode steps must read (the cell's architecture file:
for a dense block the weights once a step + the live K/V of the batch) /
peak bytes/s / the device time inside those steps' spans. Decode at these
batch sizes is bound by bytes."""

from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    cell = args["cell"]
    bw = device_peaks(evidence)["hbm_bytes_per_s"]
    least = traced = 0.0
    for span_args, device_s in tr.span_device_seconds(args["span"]):
        if span_args.get("live", 0) > 0:
            least += cell.arch.decode_step_min_bytes(cell.config, int(span_args["live"]), int(span_args["kv_tokens"])) / bw
            traced += device_s
    return None if not traced else 100.0 * least / traced

"""Bytes the traced decode steps must read (weights once a step + the live
K/V of the batch, benchmarks/lib/flops.py) / peak bytes/s / the device time
inside those steps' spans. Decode at these batch sizes is bound by bytes."""

from ..lib import flops, spec
from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    m = spec.model_dims(args["cell"].config)
    bw = device_peaks(evidence)["hbm_bytes_per_s"]
    least = traced = 0.0
    for span_args, device_s in tr.span_device_seconds(args["span"]):
        if span_args.get("live", 0) > 0:
            least += flops.decode_step_min_bytes(m, int(span_args["kv_tokens"])) / bw
            traced += device_s
    return None if not traced else 100.0 * least / traced

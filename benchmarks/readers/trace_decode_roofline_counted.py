"""`trace_decode_roofline` for a routed model: bytes the traced decode steps
must read / peak bytes/s / the device time inside those steps' spans, with the
experts counted by the program (`clocks.decode_experts`, the window's mean a
step) and not by a uniform router's expectation, which random weights fall
short of: the architecture file's `decode_step_bytes`. None where the program
has no such counter."""

from ._common import device_peaks, trace_of
from ._counted import experts_touched_a_step


def read(evidence, args):
    tr, touched = trace_of(evidence), experts_touched_a_step(evidence)
    if tr is None or touched is None:
        return None
    cell = args["cell"]
    bw = device_peaks(evidence)["hbm_bytes_per_s"]
    least = traced = 0.0
    for span_args, device_s in tr.span_device_seconds(args["span"]):
        if span_args.get("live", 0) > 0:
            least += cell.arch.decode_step_bytes(cell.config, int(span_args["live"]), int(span_args["kv_tokens"]), touched) / bw
            traced += device_s
    return None if not traced else 100.0 * least / traced

"""Roofline share of the Mosaic flash kernels at the cell's shapes: the least
time the chip could take for the traced calls (the larger of FLOPs / peak
FLOP/s and bytes / peak bytes/s, benchmarks/lib/flops.py) / their traced
time. args: kinds = {kind: regex on the op's HLO text}. The kernels carry no
name yet, so a kind is told by the custom call's result signature."""

import re

from ..lib import flops, spec
from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    cell = args["cell"]
    m = spec.model_dims(cell.config)
    batch, seq = int(cell.traffic["batch_per_chip"]), int(cell.traffic["seq_len"])
    need_f, need_b = flops.flash_kernel_flops(m, batch, seq), flops.flash_kernel_bytes(m, batch, seq)
    peaks = device_peaks(evidence)
    least = traced = 0.0
    for hlo, seconds in tr.op_calls(args["pattern"]):
        kind = next((k for k, rx in args["kinds"].items() if re.search(rx, hlo)), None)
        if kind is None:
            continue
        least += max(need_f[kind] / peaks["bf16_flops_per_s"], need_b[kind] / peaks["hbm_bytes_per_s"])
        traced += seconds
    return None if not traced else 100.0 * least / traced

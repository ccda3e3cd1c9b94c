"""Roofline share of a train step's kernels at the cell's shapes: the least
time the chip could take for the traced calls (the larger of FLOPs / peak
FLOP/s and bytes / peak bytes/s, from the table of kernels in the cell's
architecture file) / their traced time. args: kinds = {kind: regex on the
op's HLO text}, each a kind of that table. The kernels carry no name yet, so
a kind is told by the custom call's result signature."""

import re

from ._common import device_peaks, trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    cell = args["cell"]
    need = cell.arch.kernels(cell.config, int(cell.traffic["batch_per_chip"]), int(cell.traffic["seq_len"]))
    peaks = device_peaks(evidence)
    least = traced = 0.0
    for hlo, seconds in tr.op_calls(args["pattern"]):
        kind = next((k for k, rx in args["kinds"].items() if re.search(rx, hlo)), None)
        if kind is None:
            continue
        need_flops, need_bytes = need[kind]
        least += max(need_flops / peaks["bf16_flops_per_s"], need_bytes / peaks["hbm_bytes_per_s"])
        traced += seconds
    return None if not traced else 100.0 * least / traced

"""Tokens/s x the model's FLOPs per token (the cell's architecture file:
forward + backward, no recomputation) / (chips x the chip's peak), in percent."""

from . import train_throughput
from ._common import device_peaks


def read(evidence, args):
    rate = train_throughput.read(evidence, args)
    peaks = device_peaks(evidence)
    if rate is None or peaks is None:
        return None
    cell = args["cell"]
    per_token = cell.arch.train_flops_per_token(cell.config, int(cell.traffic["seq_len"]))
    return 100.0 * rate * per_token / peaks["bf16_flops_per_s"]

"""Tokens/s x the model's FLOPs per token (benchmarks/lib/flops.py: forward +
backward, no recomputation) / (chips x the chip's peak), in percent."""

from ..lib import flops, spec
from . import train_throughput
from ._common import device_peaks


def read(evidence, args):
    rate = train_throughput.read(evidence, args)
    peaks = device_peaks(evidence)
    if rate is None or peaks is None:
        return None
    cell = args["cell"]
    per_token = flops.train_flops_per_token(spec.model_dims(cell.config), int(cell.traffic["seq_len"]))
    return 100.0 * rate * per_token / peaks["bf16_flops_per_s"]

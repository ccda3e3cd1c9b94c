"""100 x XLA's `model_flops` of the ops under the given scopes / (their device
seconds x the chip's bf16 peak): how near the MXU's peak the scopes' own ops
ran, whatever else the step does. Custom calls carry no XLA count and are
left out of both sums (their rooflines are `flash_attn_roofline*`'s). It
divides the FLOPs XLA counted for an op by the time of that very op, so it
cannot pass 100 unless XLA's count is wrong. args: `scopes`, as
`trace_scope_share` takes them. None where no such op ran."""

from ..lib import xplane_meta as xm
from ._common import device_peaks


def read(evidence, args):
    table = xm.table_of(evidence)
    peaks = device_peaks(evidence)
    if table is None or peaks is None or not table.scoped():
        return None
    ops = [op for op in table.sync if not op.custom_call and table.scope_of(op) in args["scopes"]]
    seconds = sum(op.seconds for op in ops)  # over every chip's ops: each chip did its own FLOPs
    if not seconds:
        return None
    return 100.0 * sum(op.model_flops for op in ops) / (seconds * peaks["bf16_flops_per_s"])

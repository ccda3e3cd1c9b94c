"""The expert products of a routed model's decode steps in a device trace,
told by their operands: an op that reads a routed group's expert stack
(`[layers, E, d, f]` or `[layers, E, f, d]`) beside a decode step's
`[E, slots, f]` rows (a prefill chunk has the chunk's rows there and is left
out). The shapes and the counts are the architecture file's
(`decode_expert_products`, from the configuration and its slots); an op's
matrices are the stack operands its HLO text names, whatever the compiler
fused around them. args.stat:
  "time_share_pct"     100 x those ops' device time / device busy time
  "needed_roofline"    100 x the least time for what the steps NEED, the
                       matrices of the experts the program counted as
                       touched (`clocks.decode_experts`), / their traced time
  "streamed_roofline"  the same for what the steps STREAM: every expert's
None without a trace, the counter or such an op."""

import re

from ._common import device_peaks, trace_of
from ._counted import experts_touched_a_step


def shape_rx(shapes):
    return re.compile("|".join(r"\w+\[" + ",".join(str(n) for n in shape) + r"\]" for shape in shapes))


def read(evidence, args):
    tr, touched, cell = trace_of(evidence), experts_touched_a_step(evidence), args["cell"]
    if tr is None or touched is None or not hasattr(cell.arch, "decode_expert_products"):
        return None
    need = cell.arch.decode_expert_products(cell.config, touched)
    stacks, rows = shape_rx(need["stacks"]), shape_rx([need["rows"]])
    calls = [(len(stacks.findall(hlo)), s) for hlo, s in tr.op_calls(stacks.pattern) if rows.search(hlo)]
    traced = sum(s for _n, s in calls)
    if not traced:
        return None
    if args["stat"] == "time_share_pct":
        return 100.0 * traced / tr.busy_s()
    peaks = device_peaks(evidence)
    flops, nbytes = need[{"needed_roofline": "needed", "streamed_roofline": "streamed"}[args["stat"]]]
    least = sum(
        max(n * flops / peaks["bf16_flops_per_s"], (n * nbytes + need["rows_in_bytes"]) / peaks["hbm_bytes_per_s"]) for n, _s in calls
    )
    return 100.0 * least / traced

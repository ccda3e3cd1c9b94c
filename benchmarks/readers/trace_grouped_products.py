"""The GROUPED expert products of a routed model's decode steps in a device
trace: the calls of the kernels `ops/grouped_matmul.py` names
(`grouped_swiglu`, `grouped_matmul`) whose result has a decode step's rows
(slots x choices; a prefill chunk's call has the chunk's rows there and is
left out). A grouped product reads no expert that no row chose, so what a
call must move is the matrices of the experts the step TOUCHED: the
program's own count (`clocks.decode_experts`, the mean over the window's
steps, a routed layer's share of it a call), through the architecture file's
`decode_grouped_products`. args.stat:
  "time_share_pct"  100 x those calls' device time / device busy time
  "roofline"        100 x the least time for those matrices (and the held
                    picks' FLOPs, whichever bound is the larger) / the calls'
                    traced time
None without a trace, the counter, the function (an architecture whose decode
step takes the every-expert product: `trace_expert_products.py` reads that)
or such a call."""

import re

from ._common import device_peaks, trace_of
from ._counted import experts_touched_a_step


def read(evidence, args):
    tr, touched, cell = trace_of(evidence), experts_touched_a_step(evidence), args["cell"]
    products = getattr(cell.arch, "decode_grouped_products", None)
    if tr is None or touched is None or products is None:
        return None
    need = products(cell.config, touched)  # {kernel: {"result": [rows, cols], "flops": .., "bytes": ..}} of ONE call
    told = {k: re.compile(rf"%{k}[.\d]* = \w+\[{n['result'][0]},{n['result'][1]}\]") for k, n in need.items()}
    calls = [(k, s) for hlo, s in tr.op_calls("|".join(need)) for k, rx in told.items() if rx.match(hlo)]
    traced = sum(s for _k, s in calls)
    if not traced:
        return None
    if args["stat"] == "time_share_pct":
        return 100.0 * traced / tr.busy_s()
    peaks = device_peaks(evidence)
    least = sum(max(need[k]["flops"] / peaks["bf16_flops_per_s"], need[k]["bytes"] / peaks["hbm_bytes_per_s"]) for k, _s in calls)
    return 100.0 * least / traced

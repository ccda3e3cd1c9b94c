"""Roofline share of a latent-attention kernel whose work follows each call's
own size: for every traced span `args.span` (a `bench.decode` step with its
`live` and `kv_tokens`; a `bench.prefill` call with its `prompt_tokens` and
`cached_tokens`) the architecture file's `args.work` gives (FLOPs, HBM bytes)
of the LEAST work that computes the call's latent attention over all layers;
a call's least time is `max(FLOPs / peak FLOP/s, bytes / peak bytes/s)` (at
128 heads a decode step sits on the ridge, so neither bound may hide the
other), and the share is 100 x their sum / the device time of the ops whose
HLO text matches `args.pattern`. The count does not follow the form the
program chose (absorbed or expanded): a program that does more work than the
least reads a lower share. None without a trace, without the function (an
architecture with no such layer) or without such an op (a program that
attends in plain XLA ops, or a parent commit)."""

from ._common import device_peaks, trace_of


def read(evidence, args):
    tr, cell = trace_of(evidence), args["cell"]
    work = getattr(cell.arch, args["work"], None)
    if tr is None or work is None:
        return None
    peaks = device_peaks(evidence)
    least = 0.0
    for s in tr.spans:
        if s["name"] == args["span"]:
            flops, nbytes = work(cell.config, **{k: int(v) for k, v in s["args"].items()})
            least += max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    traced = tr.op_seconds(args["pattern"])
    return None if not traced else 100.0 * least / traced

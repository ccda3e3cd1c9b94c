"""Share of the pooled inter-token gaps longer than `factor` x the run's
median gap: the gaps that waited for somebody's prefill. It says on which
side of the mode boundary a gap percentile lies."""

from ..lib import stats


def read(evidence, args):
    tl = evidence.get("timeline")
    if tl is None:
        return None
    return stats.stalled_share_pct(stats.gaps_ms(tl, *evidence["window"]), float(args.get("factor", 1.5)))

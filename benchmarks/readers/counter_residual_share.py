"""100 x (total - excluded - accounted) / (total - excluded), each a sum of
deltas of program counters between the marks at the window's two ends: the
share of a clock's time that none of the `accounted` stages explains, e.g.
the engine loop's own host time = loop.s less idle, over loop.s less idle,
with prefill.s and decode.s accounted. Dotted paths into the engine's
stats(). None where a counter is absent or the denominator is not positive."""

from .counter_mean import deltas


def read(evidence, args):
    paths = [args["total"]] + list(args.get("excluded", [])) + list(args["accounted"])
    d = deltas(evidence, paths)
    if d is None:
        return None
    n_exc = len(args.get("excluded", []))
    base = d[0] - sum(d[1 : 1 + n_exc])
    if base <= 0:
        return None
    return 100.0 * (base - sum(d[1 + n_exc :])) / base

"""100 x delta(numerator) / delta(sum of denominator counters) between the
marks at the window's two ends. Counters are paths into the engine's stats():
dotted (kv.prefix_hits), or a list of keys where a key holds a dot itself
(["clocks", "decode.kv_pages", "live"]). None where the program has no such
counter (a parent commit without it) or the denominator counted nothing."""

from .counter_mean import deltas


def read(evidence, args):
    d = deltas(evidence, [args["numerator"]] + list(args["denominator"]))
    if d is None:
        return None
    den = sum(d[1:])
    return None if den <= 0 else 100.0 * d[0] / den

"""100 x delta(numerator) / delta(sum of denominator counters) between the
marks at the window's two ends. Counters are dotted paths into the engine's
stats() (e.g. kv.prefix_hits)."""


def _get(d, path):
    for part in path.split("."):
        d = d[part]
    return d


def read(evidence, args):
    marks = evidence.get("marks")
    if not marks:
        return None
    a, b = marks[0]["engine"], marks[-1]["engine"]
    num = _get(b, args["numerator"]) - _get(a, args["numerator"])
    den = sum(_get(b, p) - _get(a, p) for p in args["denominator"])
    return None if den <= 0 else 100.0 * num / den

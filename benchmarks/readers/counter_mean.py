"""scale x delta(sum) / delta(count) between the marks at the window's two
ends: the mean of a quantity the program accumulates as {n, s} (seconds per
event -> scale 1000 gives ms). `sum` and `count` are paths into the engine's
stats(): dotted (clocks.queue_wait.s), or a list of keys where a key holds a
dot itself (["clocks", "decode.kv_pages", "live"]). None where the program has no
such counter (a parent commit without it) or counted nothing in the window."""


def lookup(d, path):
    """The value at a path (dotted, or a list of keys), or None where any part of it is absent."""
    for part in path if isinstance(path, (list, tuple)) else path.split("."):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d


def deltas(evidence, paths):
    """Last mark - first mark for each path; None if a path is absent in either."""
    marks = evidence.get("marks")
    if not marks:
        return None
    a, b = marks[0].get("engine") or {}, marks[-1].get("engine") or {}
    out = []
    for p in paths:
        va, vb = lookup(a, p), lookup(b, p)
        if va is None or vb is None:
            return None
        out.append(vb - va)
    return out


def read(evidence, args):
    d = deltas(evidence, [args["sum"], args["count"]])
    if d is None or d[1] <= 0:
        return None
    return float(args.get("scale", 1.0)) * d[0] / d[1]

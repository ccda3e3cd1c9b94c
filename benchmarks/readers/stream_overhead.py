"""Median over the window's requests of (first token at the client - end of
that request's prefill span in the replica), matched by the prompt's
checksum. time.monotonic() is one clock for both processes of a host. What
is left of TTFT when queueing and the prefill are taken out: the engine's
emit, the replica's stream, the object store and the handle."""

from ..lib.stats import percentile


def read(evidence, args):
    tl = evidence.get("timeline")
    if tl is None:
        return None
    ends = {}
    for s in evidence.get("spans", []):
        if s[0] == args["span"]:
            ends.setdefault(s[3]["crc"], []).append(s[2])
    deltas = []
    for r in tl:
        if r["counted"] and r["token_times"] and r["crc"] in ends:
            first = r["token_times"][0]
            before = [e for e in ends[r["crc"]] if e <= first]
            if before:
                deltas.append((first - max(before)) * 1e3)
    return percentile(deltas, 50)

"""The host-clock decode step as a PERIOD: the median start-to-start distance
of consecutive `args.span` calls (bench.decode) of the measured window with no
`args.without` call (bench.prefill) between them, ms. It is what a stream
waits between two tokens of a plain decode step, whatever the call itself
does: a call that launches step N+1 and returns step N's tokens lasts as long
as the host waited, which says nothing, while two launches still lie one
executed step apart. The period less `decode_device_step_p50_ms*` is what
the chip waits a step for the host, the launch and the result's way back.

A pair counts where the later call ENDED inside the window (span_stat's rule).
None where the window holds no such pair."""

from ..lib.stats import percentile


def read(evidence, args):
    w0, w1 = evidence["window"]
    calls = sorted((s for s in evidence.get("spans", []) if s[0] in (args["span"], args["without"])), key=lambda s: s[1])
    periods = [
        (b[1] - a[1]) * 1e3 for a, b in zip(calls, calls[1:])
        if a[0] == b[0] == args["span"] and w0 <= b[2] <= w1
    ]
    return percentile(periods, 50) if periods else None

"""Tokens of the steps completed in the window / seconds / chips. A step
ends when `block_until_ready` returns in the worker; the window ends with
the step that crosses `--seconds`, and all of its time counts."""

from ._common import window_spans


def read(evidence, args):
    steps = window_spans(evidence, args["span"])
    if not steps:
        return None
    w0, w1 = evidence["window"]
    tokens = sum(s[3]["tokens"] for s in steps)
    return tokens / (w1 - w0) / evidence["worker"]["chips"]

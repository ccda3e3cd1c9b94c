"""What the routed-model readers share: the experts a decode step touched,
from the program's own counter."""

from .counter_mean import deltas


def experts_touched_a_step(evidence):
    """Mean over the window's decode steps of the distinct experts a step's
    rows chose, summed over the routed layers (`clocks.decode_experts`); None
    where the program keeps no such counter or counted no step."""
    d = deltas(evidence, ["clocks.decode_experts.touched", "clocks.decode_experts.steps"])
    return None if d is None or d[1] <= 0 else d[0] / d[1]

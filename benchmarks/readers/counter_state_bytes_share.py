"""100 x the recurrent states' bytes / all the bytes the window's decode steps
had to move: the live state slots the program counted a step
(`clocks.decode_state`: live_slots, steps) through the architecture file's
`decode_state_bytes`, over that and every weight once a step
(`decode_step_min_bytes` of no live row). None where the program keeps no such
counter (a model whose cache is K/V pages; a parent commit) or counted no step."""

from .counter_mean import deltas


def read(evidence, args):
    d = deltas(evidence, ["clocks.decode_state.live_slots", "clocks.decode_state.steps"])
    if d is None or d[1] <= 0:
        return None
    cell = args["cell"]
    state = cell.arch.decode_state_bytes(cell.config, d[0])
    weights = d[1] * cell.arch.decode_step_min_bytes(cell.config, 0, 0)
    return 100.0 * state / (state + weights)

"""100 x one cache's bytes / all the bytes the window's decode steps had to
move, for a model that keeps a recurrent state AND K/V pages a sequence: the
live state slots and the K/V positions the program counted
(`clocks.decode_state`: live_slots, steps; `clocks.decode_kv`: tokens) through
the architecture file's `decode_state_bytes` and `decode_kv_bytes`, over both
and every weight once a step (`decode_step_min_bytes` of no live row). The
two shares and the weights' add up to 100 % of `decode_step_min_bytes` summed
over the steps. args.cache: "state" | "kv". None where the program keeps no
such counters (a model with one kind of cache; a parent commit) or counted no
step."""

from .counter_mean import deltas


def read(evidence, args):
    d = deltas(evidence, ["clocks.decode_state.live_slots", "clocks.decode_kv.tokens", "clocks.decode_state.steps"])
    arch, config = args["cell"].arch, args["cell"].config
    if d is None or d[2] <= 0 or not hasattr(arch, "decode_kv_bytes"):
        return None
    part = {"state": arch.decode_state_bytes(config, d[0]), "kv": arch.decode_kv_bytes(config, d[1])}
    return 100.0 * part[args["cache"]] / (sum(part.values()) + d[2] * arch.decode_step_min_bytes(config, 0, 0))

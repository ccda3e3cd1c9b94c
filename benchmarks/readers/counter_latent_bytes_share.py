"""100 x the latent rows' bytes / all the bytes the window's decode steps had
to move, for a model whose pages hold latent rows: the bytes the program
counted (`clocks.decode_latent`: bytes, steps; a cached position's `[c_kv |
k_r]` in every latent layer, unpadded) over them and every weight once a step
(the architecture file's `decode_step_min_bytes` of no live row). None where
the program keeps no such counter (another kind of cache; a parent commit) or
counted no step."""

from .counter_mean import deltas


def read(evidence, args):
    d = deltas(evidence, ["clocks.decode_latent.bytes", "clocks.decode_latent.steps"])
    if d is None or d[1] <= 0:
        return None
    arch, config = args["cell"].arch, args["cell"].config
    return 100.0 * d[0] / (d[0] + d[1] * arch.decode_step_min_bytes(config, 0, 0))

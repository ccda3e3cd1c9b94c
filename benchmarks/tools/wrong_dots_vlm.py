"""The wrong models that `dotsvlm1-serve-longdoc-batch`'s `correct` has to
refuse: `tools/wrong_reference.py`'s machinery (a copy of `archs/dots_vlm.py`
with ONE line of its reference altered, a configuration and a cell of its own,
new files only, under `.chipcheck/wrong/`) with this architecture's lines. That
tool's `WRONG` table is the accepted benchmark's and is not edited: this file
adds its lines to the table of the module it imports, in this process alone.

    chiprun -- python3 benchmarks/tools/wrong_dots_vlm.py --workload dotsvlm1-serve-longdoc-batch \\
        --wrong no_rope_key,top_7,fp8_weights --seed 2147483900 [--seconds 30]

`--seconds` is 30 unless given: the cell's answers (128-256 tokens at ~20 ms)
outlast a 10 s window, which would compare nothing. Lines go to stdout and
chiprun_out/wrong_reference.jsonl. Never part of a check. The tests
(`tests/test_mla.py`, `benchmarks/tests/test_dots_vlm_cell.py`) use `source`,
`load` and `add_cells` at TINY widths.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.tools import wrong_reference  # noqa: E402
from benchmarks.tools.wrong_reference import FP8, add_cells, load, source  # noqa: E402,F401 - what the tests use

WRONG = {
    # k_r left out of the scores: a head attends by its latent part alone, and no position is told from another
    "no_rope_key": ('        scores = (jnp.einsum("qhn,khn->hqk", qn, k_nope) + jnp.einsum("qhr,kr->hqk", qr, k_r)) * m["scale"]\n',
                    '        scores = jnp.einsum("qhn,khn->hqk", qn, k_nope) * m["scale"]\n'),
    # m(40, 1)^2 = 1.8739 left out of the softmax's scale
    "no_mscale": ("    softmax_scale = (nope + rope) ** -0.5 * (_mscale(yarn[0], yarn[5]) ** 2 if yarn else 1.0)  # [P] the temperature under YaRN\n",
                  "    softmax_scale = (nope + rope) ** -0.5\n"),
    # YaRN's ramp left out: plain rope at theta
    "plain_rope": ("    return f / factor * ramp + f * (1.0 - ramp)  # [P]\n", "    return f\n"),
    "no_latent_norm": ('        c_kv = _rms_norm(kv[:, :c], a["kv_a_norm"]["scale"], m["eps"])  # [P] the latent\'s own norm\n', "        c_kv = kv[:, :c]\n"),
    # groups ignored: the plain top-k of all the router's experts
    "no_groups": ('    ranked = jnp.where(jnp.repeat(kept, m["E"] // m["G"], axis=-1), ranked, -jnp.inf)  # [P] no expert of another group\n',
                  "    ranked = ranked + 0.0 * jnp.sum(kept)\n"),
    "top_7": ('    top_e = jax.lax.top_k(ranked, m["k"])[1]\n', '    top_e = jax.lax.top_k(ranked, m["k"] - 1)[1]\n'),
    "no_shared_expert": ('    return _experts(hn, _router_weights(hn, w["mlp"], m), mlp, layer, m) + _swiglu(hn, mlp["shared"], (layer,))\n',
                         '    return _experts(hn, _router_weights(hn, w["mlp"], m), mlp, layer, m)\n'),
    # the held experts weighed as the next rank's: the router's columns 16-31 for rank 0 of 16 (in the reference's sum
    # alone: `dims` also tells the program which experts it holds)
    "next_ranks_experts": ('        return acc + jax.lax.dynamic_index_in_dim(weights, m["first"] + e, axis=1) * _swiglu(hn, mlp, (layer, e))\n',
                           '        return acc + jax.lax.dynamic_index_in_dim(weights, (m["first"] + m["held"]) % m["E"] + e, axis=1) * _swiglu(hn, mlp, (layer, e))\n'),
    "fp8_weights": FP8,
}
wrong_reference.WRONG["dots_vlm"] = WRONG


def main() -> int:
    if "--seconds" not in sys.argv:
        sys.argv += ["--seconds", "30"]
    return wrong_reference.main()


if __name__ == "__main__":
    sys.exit(main())

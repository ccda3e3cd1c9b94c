"""The wrong models that `gigachat35-serve-longanswer-batch`'s `correct` has to
refuse: `tools/wrong_reference.py`'s machinery (a copy of `archs/gigachat3_5.py`
with ONE line of its reference altered, a configuration and a cell of its own,
new files only, under `.chipcheck/wrong/`) with this architecture's lines. That
tool's `WRONG` table is the accepted benchmark's and is not edited: this file
adds its lines to the table of the module it imports, in this process alone
(as `tools/wrong_dots_vlm.py` does).

    chiprun -- python3 benchmarks/tools/wrong_gigachat3_5.py --workload gigachat35-serve-longanswer-batch \\
        --wrong no_decay,top_7,fp8_weights --seed 2147484000 [--seconds 30]

`--seconds` is 30 unless given: the cell's answers (512-1536 tokens at ~25 ms)
outlast a 10 s window, which would compare nothing. Lines go to stdout and
chiprun_out/wrong_reference.jsonl. Never part of a check. The tests
(`tests/test_gigachat3_5.py`, `benchmarks/tests/test_gigachat3_5_cell.py`) use
`source`, `load` and `add_cells` at TINY widths.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.tools import wrong_reference  # noqa: E402
from benchmarks.tools.wrong_reference import FP8, add_cells, load, source  # noqa: E402,F401 - what the tests use

WRONG = {
    # the decay left out: a state that forgets nothing
    "no_decay": ('    g = -jnp.exp(_f32(a["a_log"]))[None, :] * jax.nn.softplus(hn @ _f32(a["w_a"]) + _f32(a["dt_bias"]))  # [A] one decay a value head\n',
                 '    g = jnp.zeros((s, m["hv"]), F32) * jnp.sum(hn)\n'),
    # beta doubled: Kimi Delta Attention's (0, 2) in the gated delta rule's (0, 1)
    "beta_doubled": ('    beta = jax.nn.sigmoid(hn @ _f32(a["w_b"]))  # [A] in (0, 1)\n', '    beta = 2.0 * jax.nn.sigmoid(hn @ _f32(a["w_b"]))\n'),
    # value head j on key head j % 32 (the key heads tiled) where it reads key head j // 2 (repeated)
    "key_head_j_mod": ('    q, k = (jnp.repeat(t, m["hv"] // m["hk"], axis=1) for t in (q, k))  # [A] value head j reads key head j // 2\n',
                       '    q, k = (jnp.tile(t, (1, m["hv"] // m["hk"], 1)) for t in (q, k))\n'),
    "no_latent_gate": ('    o = o * jax.nn.sigmoid(hn @ _f32(a["wg"]))  # [A] gated_attention: elementwise, on the layer\'s normed input\n', "    o = o + 0.0 * jnp.sum(hn)\n"),
    "no_clamp": ("        act = jax.nn.silu(jnp.minimum(hn @ gate, limit)) * jnp.clip(hn @ up, -limit, limit)  # [A] swiglu_limit\n",
                 "        act = jax.nn.silu(hn @ gate) * (hn @ up) + 0.0 * limit\n"),
    # the norm's scale as 1 + w (a zero-centred plain scale) where it is c * sigmoid(w)
    "norm_as_1_plus_w": ('    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + m["eps"]) * (m["c_norm"] * jax.nn.sigmoid(_f32(w)))\n',
                         '    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + m["eps"]) * (1.0 + _f32(w))\n'),
    "top_7": ('    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # [A] the bias selects; it never weighs\n',
              '    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"] - 1)[1]\n'),
    "fp8_weights": FP8,
}
wrong_reference.WRONG["gigachat3_5"] = WRONG


def main() -> int:
    if "--seconds" not in sys.argv:
        sys.argv += ["--seconds", "30"]
    return wrong_reference.main()


if __name__ == "__main__":
    sys.exit(main())

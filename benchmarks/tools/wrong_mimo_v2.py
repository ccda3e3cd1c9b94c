"""The wrong models that `mimov25-serve-longctx-batch`'s `correct` has to
refuse: `tools/wrong_reference.py`'s machinery (a copy of `archs/mimo_v2.py`
with ONE line of its reference altered, a configuration and a cell of its own,
new files only, under `.chipcheck/wrong/`) with this architecture's lines. That
tool's `WRONG` table is the accepted benchmark's and is not edited: this file
adds its lines to the table of the module it imports, in this process alone
(as `tools/wrong_gigachat3_5.py` does).

    chiprun -- python3 benchmarks/tools/wrong_mimo_v2.py --workload mimov25-serve-longctx-batch \\
        --wrong no_sink,top_7,fp8_weights --seed 2147484000 [--seconds 30]

`--seconds` is 30 unless given: the cell's answers (512-1024 tokens at ~15 ms)
outlast a 10 s window, which would compare nothing. Lines go to stdout and
chiprun_out/wrong_reference.jsonl. Never part of a check. The tests
(`tests/test_mimo_v2.py`, `benchmarks/tests/test_mimo_v2_cell.py`) use
`source`, `load` and `add_cells` at TINY widths.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.tools import wrong_reference  # noqa: E402
from benchmarks.tools.wrong_reference import FP8, add_cells, load, source  # noqa: E402,F401 - what the tests use

WRONG = {
    # the sink left out: a window layer's plain softmax
    "no_sink": ("        if sink is None:\n", "        if True:\n"),
    # the window one position longer: a query sees itself and the 128 before it
    "window_plus_1": ('        o = _attention(q, kg, vg, q0, m["window"] if window else 0, sink_g)\n', '        o = _attention(q, kg, vg, q0, m["window"] + 1 if window else 0, sink_g)\n'),
    # a window layer's query heads grouped as if it had the global layers' 4 K/V heads: head i on K/V head i // 16 of its 8
    "window_heads_as_global": ("        kg, vg = (jax.lax.dynamic_index_in_dim(t, g, axis=1, keepdims=False) for t in (k, v))\n",
                               '        kg, vg = (jax.lax.dynamic_index_in_dim(t, g * m["kv"] // kvh, axis=1, keepdims=False) for t in (k, v))\n'),
    "thetas_swapped": ('    return (m["kv_w"], m["theta_w"]) if window else (m["kv"], m["theta"])\n', '    return (m["kv_w"], m["theta"]) if window else (m["kv"], m["theta_w"])\n'),
    # rope over all 192 dims of a head where it is over the first 64
    "rope_all_dims": ("    half = rot // 2\n", "    rot = x.shape[-1]\n    half = rot // 2\n"),
    "no_value_scale": ('        return k, (hn @ wv).reshape(block, kvh, m["v"]) * m["value_scale"]  # [M] v scaled behind its projection\n',
                       '        return k, (hn @ wv).reshape(block, kvh, m["v"])\n'),
    "top_7": ('    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # the bias selects; it never weighs\n',
              '    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"] - 1)[1]\n'),
    "fp8_weights": FP8,
}
wrong_reference.WRONG["mimo_v2"] = WRONG


def main() -> int:
    if "--seconds" not in sys.argv:
        sys.argv += ["--seconds", "30"]
    return wrong_reference.main()


if __name__ == "__main__":
    sys.exit(main())

"""Shrinks a cell to the tiny widths of benchmarks/tests/tiny.json for the
CPU rehearsal and the tests. Used by benchmarks/rehearse.py and
benchmarks/tests only; run.py has no way to reach it."""

from __future__ import annotations

import os

from .spec import Cell, load_json


def _merge(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


def shrink(cell: Cell) -> Cell:
    tiny = load_json(os.path.join(cell.bench_dir, "tests", "tiny.json"))
    _merge(cell.config, tiny["config"])
    if cell.config["num_key_value_heads"] > cell.config["num_attention_heads"]:
        cell.config["num_key_value_heads"] = cell.config["num_attention_heads"]
    _merge(cell.traffic, tiny["traffic"][cell.traffic["runner"]])
    cell.allow_cpu = True
    return cell

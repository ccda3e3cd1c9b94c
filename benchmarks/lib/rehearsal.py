"""Shrinks a cell for the CPU rehearsal and the tests: its architecture
file's TINY widths, and the engine sizes and traffic of
benchmarks/tests/tiny.json. Used by benchmarks/rehearse.py and
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
    _merge(cell.config, cell.arch.TINY)
    cell.traffic["correctness"].pop("served_margin_tolerance", None)  # read on the chip at published widths: tiny.json's stands alone
    _merge(cell.traffic, tiny["traffic"][cell.traffic["runner"]])
    cell.allow_cpu = True
    return cell

"""A traced run's evidence on disk, beside its trace, so that a list of
per-layer names can be read again from THAT run without the chip:

    benchmarks/out/<cell>-<seed>-evidence.json   what the runner returned (window, spans, timeline, marks, worker
                                                 facts), and the cell as it ran (seed, seconds, config, traffic)
    benchmarks/out/<cell>-<seed>-trace/          the profiler's directory (the .xplane.pb, gzipped or not)

`benchmarks/tools/same_readings.py` reads it under two roots' names. JSON
keeps every float digit for digit (`repr` round-trips); a tuple comes back
a list, which no reader tells apart. No jax backend is opened here.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict

from .spec import Cell

SUFFIX = "-evidence.json"
AS_RUN = ("seed", "seconds", "trace", "allow_cpu", "config", "traffic")


def _plain(x):
    return x.item() if hasattr(x, "item") else str(x)


def write(cell: Cell, evidence: Dict[str, Any]) -> str:
    kept = {k: v for k, v in evidence.items() if k != "cell" and not k.startswith("_")}
    if "spans" in kept and kept["spans"] is evidence["worker"].get("spans"):
        del kept["spans"]  # the runners hand the worker's list up under a second key: once on disk
    path = cell.out_prefix + SUFFIX
    with open(path, "w") as f:
        json.dump({"cell": dict({k: getattr(cell, k) for k in AS_RUN}, name=cell.name), "evidence": kept}, f, default=_plain)
    return path


def load(out_prefix: str, find_cell) -> Dict[str, Any]:
    """The evidence of the run that wrote `out_prefix`, for the readers of the
    root whose `spec.find_cell` is given: the cell is that root's, as the run
    had it (a rehearsal shrinks its config and traffic), and the trace is the
    one beside the file wherever the run wrote it."""
    with open(out_prefix + SUFFIX) as f:
        said = json.load(f)
    cell = find_cell(said["cell"]["name"])
    for k in AS_RUN:
        setattr(cell, k, said["cell"][k])
    evidence = dict(said["evidence"], cell=cell)
    if "spans" in evidence["worker"]:
        evidence.setdefault("spans", evidence["worker"]["spans"])
    traces = sorted(glob.glob(os.path.join(out_prefix + "-trace", "plugins", "profile", "*", "*.xplane.pb*")))
    if evidence["worker"].get("trace_path"):
        evidence["worker"]["trace_path"] = traces[0] if traces else None
    return evidence

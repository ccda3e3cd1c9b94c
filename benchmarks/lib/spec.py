"""Finds every piece of a cell by the names in BENCHMARK.json.

    workloads[i].config   -> benchmarks/configs/<config>.json      (names its architecture)
    config["arch"]        -> benchmarks/archs/<arch>.py           (mapping, plain reference, counts)
    workloads[i].traffic  -> benchmarks/traffic/<traffic>.json   (names its runner)
    traffic["runner"]     -> benchmarks/runners/<runner>.py       (run(cell) -> evidence)
    per_layer[j].name     -> benchmarks/metrics/<name>.json       (names its reader)
    metric["reader"]      -> benchmarks/readers/<reader>.py       (read(evidence, args) -> value | None)

Nothing here opens a jax backend: the process that runs a cell's driver
side must never hold the chip (it belongs to the trainer's worker or the
serve replica).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# Keys of a configuration file that are the harness's own, and published
# keys that carry no shape. Every other key must be read by the
# configuration's architecture file (its PUBLISHED_KEYS).
HARNESS_KEYS = frozenset({"source", "arch", "reduced_from", "stands_for", "fewer_layers_mean", "assumed"})
SHAPELESS_KEYS = frozenset({"architectures", "model_type", "torch_dtype", "bos_token_id", "eos_token_id", "pad_token_id"})


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark_json(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with the files its names lead to."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json entries reported by this cell
    per_layer: List[Dict[str, Any]]
    bench_dir: str = BENCH_DIR
    # set by run.py for one run
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    t_process_start: float = 0.0
    allow_cpu: bool = False  # tests and rehearsals only; run.py never sets it

    @property
    def arch(self):
        return load_arch(self.config)

    @property
    def out_prefix(self) -> str:
        return os.path.join(self.bench_dir, "out", f"{self.name}-{self.seed}")


def _in_cell(metric: Dict[str, Any], cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def find_cell(name: str, root: str = ROOT) -> Cell:
    spec = benchmark_json(root)
    bench_dir = os.path.join(root, spec["paths"][0])
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_config(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        traffic_name=w["traffic"],
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in spec["per_layer"] if _in_cell(m, name)],
        bench_dir=bench_dir,
    )


def load_arch(config: Dict[str, Any]):
    """The architecture file a configuration names: the only place that
    gives a model key a meaning."""
    return importlib.import_module(f"benchmarks.archs.{config['arch']}")


def load_config(path: str) -> Dict[str, Any]:
    """A configuration file, refused unless its architecture maps it whole:
    a published key that nothing reads would be a different model under
    this model's name."""
    config = load_json(path)
    if "arch" not in config:
        raise ValueError(f'{path}: names no architecture file ("arch": a module of benchmarks/archs/)')
    arch = load_arch(config)
    unread = sorted(set(config) - arch.PUBLISHED_KEYS - HARNESS_KEYS - SHAPELESS_KEYS)
    if unread:
        raise ValueError(
            f"{path}: {', '.join(map(repr, unread))} is read neither by its architecture file "
            f"benchmarks/archs/{config['arch']}.py (PUBLISHED_KEYS) nor by the harness: "
            "a configuration is mapped whole or does not run"
        )
    return config


def load_runner(cell: Cell):
    return importlib.import_module(f"benchmarks.runners.{cell.traffic['runner']}")


def metric_file(cell: Cell, metric_name: str) -> Dict[str, Any]:
    return load_json(os.path.join(cell.bench_dir, "metrics", metric_name + ".json"))


def read_metric(cell: Cell, metric_name: str, evidence: Dict[str, Any]) -> Optional[float]:
    """One per-layer (or end-to-end) metric through its own file and reader.
    A reader that finds nothing to read returns None and the metric is
    left out of the line."""
    mf = metric_file(cell, metric_name)
    reader = importlib.import_module(f"benchmarks.readers.{mf['reader']}")
    value = reader.read(evidence, dict(mf.get("args", {}), cell=cell))
    return None if value is None else float(value)

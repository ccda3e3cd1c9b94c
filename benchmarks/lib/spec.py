"""Finds every piece of a cell by the names in BENCHMARK.json.

    workloads[i].config   -> benchmarks/configs/<config>.json
    workloads[i].traffic  -> benchmarks/traffic/<traffic>.json   (names its runner)
    traffic["runner"]     -> benchmarks/runners/<runner>.py       (run(cell) -> evidence)
    per_layer[j].name     -> benchmarks/metrics/<name>.json       (names its reader)
    metric["reader"]      -> benchmarks/readers/<reader>.py       (read(evidence, args) -> value | None)

Nothing here imports jax: the process that runs a cell's driver side must
never open a backend (the chip belongs to the trainer's worker or the
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
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
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


# ------------------------------------------------------------ model config


def model_dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark's own arithmetic (FLOPs, bytes, reference)
    needs, under short names, from the published keys of a config file."""
    return {
        "d": int(config["hidden_size"]),
        "f": int(config["intermediate_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]),
        "L": int(config["num_hidden_layers"]),
        "V": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "bytes_per_param": {"bfloat16": 2, "float32": 4}[config.get("torch_dtype", "bfloat16")],
    }


def transformer_config(config: Dict[str, Any], **overrides):
    """The program's TransformerConfig for a config file (imports jax;
    call it only in the process that owns the chip)."""
    import jax.numpy as jnp

    from ray_tpu.models import transformer as tfm

    m = model_dims(config)
    if m["hd"] * m["h"] != m["d"]:
        raise ValueError("TransformerConfig derives head_dim as d_model // n_heads")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only the gated-silu MLP is mapped")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"],
        d_ff=m["f"], max_seq_len=int(config["max_position_embeddings"]), rope_theta=m["theta"],
        norm_eps=m["eps"], tie_embeddings=m["tied"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        attn_impl=assumed.get("attn_impl", "full"),
    )
    if "remat_policy" in assumed:
        kw["remat_policy"] = assumed["remat_policy"]
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)

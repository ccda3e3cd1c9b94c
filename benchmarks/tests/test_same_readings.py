"""tools/same_readings.py on a CPU rehearsal's evidence: one traced run of a cell, read under two roots' lists of
per-layer names. The roots are temporary copies of the benchmark: the change's as it stands, and parents made from
it by splitting a merged quantity into a suffixed copy again (what PR 58 undid), by a copy whose args differ, and by
an entry the change has dropped. A CPU run's device metrics are silent on both sides and say nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec

from test_run import ENV, add_files, copy_of_the_benchmark, rehearse_one  # rootdir-less: pytest puts this directory on the path

CELL = "dsllm7b-serve-docqa-batch"
TOOL = os.path.join(spec.BENCH_DIR, "tools", "same_readings.py")


def a_root(tmp_path_factory, name, edit=None):
    root = str(tmp_path_factory.mktemp(name))
    copy_of_the_benchmark(root)
    bench, files = spec.benchmark_json(), {}
    if edit is not None:
        edit(bench, files)
    add_files(root, bench, files)
    return root


def split_again(suffix, args=None):
    """`decode_batch_mean` as a parent before PR 58 held it: the cell under a copy of the entry with a file of its own."""
    def edit(bench, files):
        entry = next(m for m in bench["per_layer"] if m["name"] == "decode_batch_mean")
        entry["workloads"].remove(CELL)
        bench["per_layer"].append(dict(entry, name="decode_batch_mean" + suffix, workloads=[CELL]))
        mf = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", "decode_batch_mean.json"))
        files[f"metrics/decode_batch_mean{suffix}.json"] = dict(mf, cells=[CELL], args=dict(mf["args"], **(args or {})))
    return edit


def dropped(bench, files):
    bench["per_layer"].append({"name": "prefill_cached_tokens_mean", "unit": "tokens", "better": "higher", "source": "program_span",
                               "layer": "paged forward", "moves": "serve_tok_s", "workloads": [CELL]})
    files["metrics/prefill_cached_tokens_mean.json"] = {"layer": "paged forward", "moves": "serve_tok_s", "reader": "span_stat",
                                                         "args": {"span": "bench.prefill", "stat": "mean_arg", "arg": "cached_tokens"}}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    change = a_root(tmp_path_factory, "change")
    line = rehearse_one(change, CELL, 1)
    return change, os.path.join(change, "benchmarks", "out", f"{CELL}-3000000019"), line


def same_readings(out, parent, change, renamed, tmp_path):
    table = os.path.join(str(tmp_path), "renamed.json")
    with open(table, "w") as f:
        json.dump(renamed, f)
    p = subprocess.run([sys.executable, TOOL, out, parent, change, "--renamed", table], cwd=spec.ROOT, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode in (0, 1), p.stderr[-3000:]
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1])


def test_the_evidence_on_disk_reads_what_the_run_itself_read(traced):
    change, out, line = traced
    p = subprocess.run([sys.executable, TOOL, "--read", out, change], cwd=change, env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    again = {name: said[0] for name, said in json.loads(p.stdout.strip().splitlines()[-1]).items() if said[0] is not None}
    assert again == {name: m["value"] for name, m in line["metrics"].items()} and len(again) >= 10  # digit for digit


def test_a_quantity_under_its_old_suffixed_name_reads_the_same(traced, tmp_path_factory, tmp_path):
    change, out, line = traced
    parent = a_root(tmp_path_factory, "parent", split_again(".old"))
    rc, said = same_readings(out, parent, change, {"decode_batch_mean.old": "decode_batch_mean"}, tmp_path)
    assert rc == 0 and said["differ"] == [] and said["lost"] == [] and said["added"] == {}
    assert said["renamed"] == ["decode_batch_mean.old"] and said["parent_names"] == said["change_names"] == len(spec.find_cell(CELL).per_layer)
    assert said["same"] == len(line["metrics"]) and said["same"] + len(said["silent_on_both"]) == said["parent_names"]
    assert "decode_device_roofline" in said["silent_on_both"]  # no device number from a CPU: silent under both lists, which shows nothing
    # without the table's row the old name has no counterpart: lost, and the change's own name is reported as added
    rc, said = same_readings(out, parent, change, {}, tmp_path)
    assert rc == 1 and said["lost"] == [{"parent": "decode_batch_mean.old", "change": "decode_batch_mean.old"}]
    assert said["added"] == {"decode_batch_mean": line["metrics"]["decode_batch_mean"]["value"]}


def test_a_copy_that_read_something_else_is_told_apart(traced, tmp_path_factory, tmp_path):
    change, out, line = traced
    parent = a_root(tmp_path_factory, "parent", split_again(".old", {"arg": "kv_tokens"}))
    rc, said = same_readings(out, parent, change, {"decode_batch_mean.old": "decode_batch_mean"}, tmp_path)
    assert rc == 1 and said["lost"] == [] and [d["parent"] for d in said["differ"]] == ["decode_batch_mean.old"]
    was, now = said["differ"][0]["was"][0], said["differ"][0]["now"][0]
    assert now == line["metrics"]["decode_batch_mean"]["value"] and was != now


def test_a_quantity_the_change_dropped_is_lost(traced, tmp_path_factory, tmp_path):
    change, out, _line = traced
    rc, said = same_readings(out, a_root(tmp_path_factory, "parent", dropped), change, {}, tmp_path)
    assert rc == 1 and said["differ"] == [] and said["lost"] == [{"parent": "prefill_cached_tokens_mean", "change": "prefill_cached_tokens_mean"}]


def test_the_table_of_pr_58_names_entries_that_stand():
    renamed = spec.load_json(os.path.join(spec.BENCH_DIR, "tools", "renamed_pr58.json"))
    names = {m["name"] for m in spec.benchmark_json()["per_layer"]}
    assert len(renamed) == 59 and not set(renamed) & names and set(renamed.values()) <= names

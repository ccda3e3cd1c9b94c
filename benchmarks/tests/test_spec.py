"""BENCHMARK.json against its contract, and the files its names lead to."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from benchmarks.lib import spec, traffic

BENCH = spec.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert BENCH["paths"] == ["benchmarks"] and BENCH["command"][-1].startswith("benchmarks/")
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert len({(w["config"], w["traffic"], w["chips"]) for w in BENCH["workloads"]}) == len(CELLS)


@pytest.mark.parametrize("entry", METRICS, ids=lambda m: m["name"])
def test_metric_entry(entry):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if entry in BENCH["end_to_end"] else {"layer", "moves"}
    assert set(entry) <= allowed
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher") and entry["source"] in SOURCES
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    if entry["name"].endswith("_roofline") or "_roofline." in entry["name"]:
        assert entry["unit"] == "%"
    for w in entry.get("workloads", []):
        assert w in CELLS


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_and_its_moves_is_reported_in_each_of_its_cells(entry):
    cell = spec.find_cell(CELLS[0])
    mf = spec.metric_file(cell, entry["name"])
    assert mf["layer"] == entry["layer"] and mf["moves"] == entry["moves"]
    assert set(mf) <= {"layer", "moves", "reader", "args", "what"}  # its cells are the entry's `workloads`, and stand nowhere else
    importlib.import_module(f"benchmarks.readers.{mf['reader']}").read  # the reader exists
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == entry["moves"])
    for c in entry.get("workloads", CELLS):
        assert c in moved.get("workloads", CELLS), f"{entry['name']} moves {moved['name']}, which {c} does not report"


def test_one_entry_a_quantity_and_a_metrics_cells_in_one_place():
    """PR 58. A quantity is split only where what it `moves`, its reader or its args differ: two entries that read
    the same thing for the same end-to-end metric are one entry with both cells. No metric file names cells (the test
    above holds a file to its keys), so a PR that adds a cell appends its name to `workloads` lists and copies no entry;
    and no file is left without an entry."""
    assert len(BENCH["per_layer"]) <= 128
    cell = spec.find_cell(CELLS[0])
    seen = {}
    for entry in BENCH["per_layer"]:
        mf = spec.metric_file(cell, entry["name"])
        key = (mf["reader"], json.dumps(mf.get("args", {}), sort_keys=True), mf["moves"])
        assert key not in seen, f"{entry['name']} and {seen[key]} read the same quantity for {mf['moves']}: one entry, both cells"
        seen[key] = entry["name"]
    files = {f[: -len(".json")] for f in os.listdir(os.path.join(spec.BENCH_DIR, "metrics"))}
    assert files == {m["name"] for m in METRICS}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist_and_load(name):
    cell = spec.find_cell(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert NAME.match(name) and NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    spec.load_runner(cell).run  # the runner exists
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.end_to_end:
        importlib.import_module(f"benchmarks.readers.{spec.metric_file(cell, m['name'])['reader']}").read


# Every key its architecture reads, as PUBLISHED (a reduced key too), for the configurations this test knows.
PUBLISHED = {
    "mistral-7b-v0.3-L4": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32, num_key_value_heads=8, head_dim=128,
        num_hidden_layers=32, vocab_size=32768, max_position_embeddings=32768, rope_theta=1e6, rms_norm_eps=1e-5,
        hidden_act="silu", sliding_window=None, tie_word_embeddings=False, torch_dtype="bfloat16"),
    "deepseek-llm-7b-chat-L8": dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32, num_key_value_heads=32, head_dim=128,
        num_hidden_layers=30, vocab_size=102400, max_position_embeddings=4096, rope_theta=1e4, rms_norm_eps=1e-6,
        hidden_act="silu", tie_word_embeddings=False, torch_dtype="bfloat16"),
}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_keeps_its_published_values_and_states_its_cuts(conf):
    """`reduced` may name any key whose published value `reduced_from`
    records; every other key the architecture reads is as published. A
    configuration this test does not know is held to the same form."""
    cfg = spec.load_config(os.path.join(spec.ROOT, conf["file"]))
    arch = spec.load_arch(cfg)
    assert conf["reduced"] == list(cfg["reduced_from"]) and set(conf["reduced"]) <= arch.PUBLISHED_KEYS
    assert cfg["source"] == conf["source"]
    assert re.match(r"^https://huggingface\.co/[^/]+/[^/]+/blob/main/config\.json$", conf["source"])
    for k, v in cfg["assumed"].items():
        assert v.get("why"), f"assumed.{k} has no reason"
    assert cfg["stands_for"]
    if "num_hidden_layers" in cfg["reduced_from"]:
        assert cfg["fewer_layers_mean"]
    published = PUBLISHED.get(conf["name"])
    if published is not None:
        assert set(cfg) & arch.PUBLISHED_KEYS == set(published)
        for k, v in published.items():
            if k in cfg["reduced_from"]:
                assert cfg["reduced_from"][k] == v and cfg[k] != v, k
            else:
                assert cfg[k] == v, k


OLMOE = {  # catalog `OLMoE-1B-7B-0125-Instruct`: intermediate_size is ONE expert's width
    "source": "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json", "arch": "dense_decoder",
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe", "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}


def test_a_configuration_with_a_key_nothing_maps_is_refused(tmp_path):
    """A routed-expert model under the dense architecture would run as a
    dense 2048 x 1024 model and agree with a reference that is the same wrong
    model. It does not run: finding the cell fails, before a runner, a
    runtime or a backend exists, and says which key and which file."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmarks", "configs"))
    shutil.copytree(os.path.join(spec.BENCH_DIR, "traffic"), os.path.join(root, "benchmarks", "traffic"))
    bench = dict(BENCH, configs=[{"name": "olmoe", "source": OLMOE["source"], "file": "benchmarks/configs/olmoe.json",
                                  "reduced": [], "why": "test"}],
                 workloads=[dict(BENCH["workloads"][0], config="olmoe")])

    def write(config):
        for path, data in ((os.path.join(root, "BENCHMARK.json"), bench), (os.path.join(root, "benchmarks", "configs", "olmoe.json"), config)):
            with open(path, "w") as f:
                json.dump(data, f)

    write(OLMOE)
    with pytest.raises(ValueError) as refused:
        spec.find_cell(bench["workloads"][0]["name"], root)
    said = str(refused.value)
    assert "'num_experts'" in said and "'num_experts_per_tok'" in said and "'norm_topk_prob'" in said
    assert "benchmarks/archs/dense_decoder.py" in said and "olmoe.json" in said
    probe = ("import sys; from benchmarks.lib import driver, spec\n"
             "try: spec.find_cell(sys.argv[1], sys.argv[2])\n"
             "except ValueError as e: print('refused', driver.backend_initialized(), e)")
    alone = subprocess.run([sys.executable, "-c", probe, bench["workloads"][0]["name"], root], cwd=spec.ROOT,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=120)
    assert alone.stdout.startswith("refused False ") and "'num_experts'" in alone.stdout, alone.stderr[-2000:]
    write({k: v for k, v in OLMOE.items() if k not in ("num_experts", "num_experts_per_tok", "norm_topk_prob")})
    cell = spec.find_cell(bench["workloads"][0]["name"], root)
    assert cell.arch.matmul_params(cell.config) == 472_121_344  # the dense model those keys describe: not OLMoE's 6.9 B


def test_the_counts_did_not_move():
    """The architecture file's counts, as benchmarks/lib/flops.py gave them
    before they moved (PR 26): pure functions of the configuration files."""
    mistral = spec.find_cell("mistral7b-train-seq4k-1chip")
    arch = mistral.arch
    assert os.path.relpath(arch.__file__, spec.ROOT) == "benchmarks/archs/dense_decoder.py"
    assert arch.matmul_params(mistral.config) == 1_006_632_960
    assert arch.train_flops_per_token(mistral.config, 4096) == 6_442_450_944
    assert arch.kernels(mistral.config, 3, 4096) == {
        "fwd": (412_316_860_416, 253_231_104), "dq": (618_475_290_624, 355_467_264), "dkv": (824_633_720_832, 305_135_616)}
    deepseek = spec.find_cell("dsllm7b-serve-chat-steady")
    assert deepseek.arch is arch
    assert arch.matmul_params(deepseek.config) == 2_038_431_744
    assert arch.weight_bytes_per_decode_step(deepseek.config) == 4_076_863_488
    assert arch.kv_bytes_per_token(deepseek.config) == 131_072
    assert arch.decode_step_min_bytes(deepseek.config, 10, 1000) == arch.decode_step_min_bytes(deepseek.config, 1, 1000) == 4_207_935_488


@pytest.mark.parametrize("name", [c for c in CELLS if "serve" in c])
def test_warmed_prefill_buckets_cover_the_files_length_range(name):
    """Warm-up covers what the FILE can produce, by the program's own bucket
    rule; the benchmark's copy of that rule (rehearsal, AOT) agrees with it."""
    from ray_tpu.serve.llm.model import PagedLM

    cell = spec.find_cell(name)
    assumed = {k: v["value"] for k, v in cell.config["assumed"].items()}
    T, P = assumed["page_tokens"], assumed["max_pages_per_seq"]
    lm = types.SimpleNamespace(max_pages_per_seq=P)
    lo, hi = traffic.prompt_length_range(cell.traffic)
    warmed = set(traffic.prefill_buckets(cell.traffic, T, P))
    for n_tokens in range(lo, hi + 1):
        pages = max(1, -(-n_tokens // T))
        assert PagedLM._bucket_pages(lm, pages) == traffic.bucket_pages(pages, P)
        assert traffic.bucket_pages(pages, P) in warmed
    drawn = [r.prompt_tokens for r in traffic.generate(cell.traffic, 120)]
    assert lo <= min(drawn) and max(drawn) <= hi
    assert max(r.prompt_tokens + r.max_new_tokens for r in traffic.generate(cell.traffic, 120)) <= P * T
    cor = cell.traffic["correctness"]
    assert cor["sample_requests"] >= 2 and set(cor["served_margin_tolerance"]) <= {"q50", "q90", "q99", "q100"}


@pytest.mark.parametrize("name", [c for c in CELLS if "serve" in c])
def test_generator_is_deterministic_and_seed_changes_only_content(name):
    cell = spec.find_cell(name)
    a, b = traffic.generate(cell.traffic, 30), traffic.generate(cell.traffic, 30)
    assert a == b and len(a) > 10
    longer = traffic.generate(cell.traffic, 60)
    if a[0].due_s is not None:
        assert longer[: len(a)] == a  # a longer horizon extends the schedule, it does not reshuffle it
    vocab = cell.arch.vocab_size(cell.config)
    p1, p1b = traffic.prompt_tokens(a[3], 7, vocab), traffic.prompt_tokens(a[3], 7, vocab)
    p2 = traffic.prompt_tokens(a[3], 3000000019, vocab)
    assert p1 == p1b and p1 != p2 and len(p1) == len(p2) == a[3].prompt_tokens
    assert 0 < min(p1) and max(p1) < vocab


def test_followup_turn_shares_its_sessions_prefix():
    cell = spec.find_cell("dsllm7b-serve-chat-steady")
    reqs = traffic.generate(cell.traffic, 60)
    follow = next(r for r in reqs if len(r.segments) > 2)
    parent = next(r for r in reqs if r.idx < follow.idx and r.segments == follow.segments[: len(r.segments)])
    vocab = cell.arch.vocab_size(cell.config)
    a, b = traffic.prompt_tokens(parent, 5, vocab), traffic.prompt_tokens(follow, 5, vocab)
    assert b[: len(a)] == a and len(b) > len(a) + follow.segments[-1][1]  # parent + stand-in answer + new turn
    share = sum(1 for r in reqs if len(r.segments) > 2) / len(reqs)
    assert 0.3 < share < 0.75


def test_staggered_clients_stand_spread_over_a_sessions_turns():
    """agent-turns: after any number of requests a client, an eighth of the 64 clients stand at each
    of a session's 8 turns (in step, all 64 would open a session, and miss its document, at once);
    a file without the key draws what it drew before the key was read."""
    cell = spec.find_cell("trinitymini-serve-agent-turns")
    assert cell.traffic["stagger_first_session"] is True
    by_client = {}
    for r in traffic.generate(cell.traffic, 54):
        by_client.setdefault(r.client, []).append(r)
    assert len(by_client) == 64
    for k in range(12):  # the k-th request of every client: how many of them open a session
        opens = sum(1 for reqs in by_client.values() if len(reqs[k].segments) == 3)  # shared + document + the turn
        assert (opens == 64) if k == 0 else (6 <= opens <= 10), (k, opens)
    plain = dict(cell.traffic)
    del plain["stagger_first_session"]
    in_step = {}
    for r in traffic.generate(plain, 54):
        in_step.setdefault(r.client, []).append(r)
    assert [sum(1 for reqs in in_step.values() if len(reqs[k].segments) == 3) for k in (0, 1, 7)] == [64, 0, 0]


def test_docqa_questions_share_their_document():
    cell = spec.find_cell("dsllm7b-serve-docqa-batch")
    reqs = [r for r in traffic.generate(cell.traffic, 30) if r.client == 0][:8]
    assert [r.segments[0] for r in reqs[:4]] == [reqs[0].segments[0]] * 4
    assert reqs[4].segments[0] != reqs[0].segments[0]
    assert all(r.max_new_tokens == 32 for r in reqs)

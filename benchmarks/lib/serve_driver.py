"""The driver side of a serving cell: serve.run(llm_deployment(...)), then
load through the streaming DeploymentHandle from this process, which never
opens a jax backend. Shared by the open-loop and the closed-loop runner.

Order of a run: deploy -> warm every executable (in the replica) -> start
the load -> lead-in (served, not counted) -> window -> [traced runs: a
traced segment, the load still running] -> wait for the window's first
tokens -> cancel what is in flight -> the reference over a sample of the
requests the window finished (in the replica) -> collect spans -> shut down.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import correct, driver, stats, traffic as traffic_lib
from .spec import Cell
from .worker_serve import build, prompt_crc

APP = "bench_llm"


class Ticker(threading.Thread):
    """Wakes every 50 ms and remembers its largest gaps: a stall of this
    process (or of the whole host) shows here, next to the request timeline."""

    def __init__(self, period_s: float = 0.05, keep: int = 8):
        super().__init__(name="bench-ticker", daemon=True)
        self.period_s, self.keep = period_s, keep
        self.gaps: List[List[float]] = []  # [gap_s, at]
        self._halt = threading.Event()

    def run(self) -> None:
        last = time.monotonic()
        while not self._halt.wait(self.period_s):
            now = time.monotonic()
            self.gaps.append([now - last, now])
            if len(self.gaps) > 4 * self.keep:
                self.gaps = sorted(self.gaps, reverse=True)[: self.keep]
            last = now

    def stop(self) -> List[List[float]]:
        self._halt.set()
        self.join(timeout=2)
        return sorted(self.gaps, reverse=True)[: self.keep]


class Load:
    """Sends requests and records, per request, when it was due and sent and
    when each token reached this process."""

    def __init__(self, stream, requests: List[traffic_lib.Request], seed: int, vocab: int):
        self.stream = stream
        self.requests = requests
        self.prompts = [traffic_lib.prompt_tokens(r, seed, vocab) for r in requests]
        self.records: List[Dict[str, Any]] = []
        self.threads: List[threading.Thread] = []
        self.stop = threading.Event()
        self._lock = threading.Lock()

    def _record(self, i: int, due: Optional[float]) -> Dict[str, Any]:
        r = self.requests[i]
        rec = {
            "idx": r.idx, "client": r.client, "due": due, "sent": None, "token_times": [], "tokens": [],
            "prompt_tokens": r.prompt_tokens, "max_new_tokens": r.max_new_tokens, "crc": prompt_crc(self.prompts[i]),
            "error": None, "done": None, "counted": False, "_gen": None,
        }
        with self._lock:
            self.records.append(rec)
        return rec

    def _consume(self, i: int, rec: Dict[str, Any]) -> None:
        rec["sent"] = time.monotonic()
        if rec["due"] is None:
            rec["due"] = rec["sent"]  # closed loop: a request is due when its client sends it
        try:
            gen = self.stream.remote(self.prompts[i], self.requests[i].max_new_tokens)
            rec["_gen"] = gen
            for tok in gen:
                rec["token_times"].append(time.monotonic())
                rec["tokens"].append(int(tok))
        except Exception as e:  # noqa: BLE001 - recorded: a failed request misses every latency
            if not self.stop.is_set():
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            rec["done"] = time.monotonic()

    def start_open(self, t_start: float) -> None:
        def schedule():
            for i, r in enumerate(self.requests):
                due = t_start + r.due_s
                delay = due - time.monotonic()
                if delay > 0 and self.stop.wait(delay):
                    return
                if self.stop.is_set():
                    return
                t = threading.Thread(target=self._consume, args=(i, self._record(i, due)), daemon=True)
                self.threads.append(t)
                t.start()

        t = threading.Thread(target=schedule, name="bench-schedule", daemon=True)
        self.threads.append(t)
        t.start()

    def start_closed(self) -> None:
        by_client: Dict[int, List[int]] = {}
        for i, r in enumerate(self.requests):
            by_client.setdefault(r.client, []).append(i)

        def client(indices):
            for i in indices:
                if self.stop.is_set():
                    return
                self._consume(i, self._record(i, None))

        for c, indices in sorted(by_client.items()):
            t = threading.Thread(target=client, args=(indices,), name=f"bench-client-{c}", daemon=True)
            self.threads.append(t)
            t.start()

    def finish(self, w0: float, w1: float, grace_s: float) -> None:
        """Marks the window's requests, gives those still without a first
        token `grace_s` to get it, then cancels everything in flight."""
        with self._lock:
            for rec in self.records:
                rec["counted"] = rec["due"] is not None and w0 <= rec["due"] < w1
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline and any(
            r["counted"] and not r["token_times"] and r["done"] is None for r in self.records
        ):
            time.sleep(0.01)
        self.stop.set()
        for rec in list(self.records):
            gen = rec.pop("_gen", None)
            if gen is not None and rec["done"] is None:
                gen.close()
        for t in self.threads:
            t.join(timeout=10)


def _engine_config(assumed: Dict[str, Any]):
    from ray_tpu.serve.llm import EngineConfig

    return EngineConfig(
        page_tokens=assumed["page_tokens"], pool_pages=assumed["pool_pages"],
        prefill_token_budget=assumed["prefill_token_budget"], max_queue=assumed["max_queue"],
    )


def run_cell(cell: Cell, start: Callable[[Load, float], None], horizon_s: float) -> Dict[str, Any]:
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    tr = cell.traffic
    assumed = {k: v["value"] for k, v in cell.config["assumed"].items()}
    vocab = cell.arch.vocab_size(cell.config)
    with driver.runtime(cell) as chips:
        app = llm_deployment(
            build, name=APP, model_kwargs={"bench": driver.worker_config(cell)},
            engine_config=_engine_config(assumed), max_ongoing_requests=assumed["max_ongoing_requests"],
            ray_actor_options={"num_tpus": 1} if chips else None,
        )
        handle = serve.run(app, name=APP, http_port=None)
        try:
            stream = handle.options(stream=True)
            ctl = handle.options(method_name="bench")

            def call(cmd: str, **kw):
                return ctl.remote(cmd, **kw).result(timeout=900)

            lo, hi = traffic_lib.prompt_length_range(tr)
            warm = call("warmup", min_prompt_tokens=lo, max_prompt_tokens=hi)

            cor = tr["correctness"]
            load = Load(stream, traffic_lib.generate(tr, horizon_s), cell.seed, vocab)
            ticker = Ticker()
            ticker.start()
            t_start = time.monotonic() + 0.05
            w0 = t_start + float(tr["lead_in_s"])
            w1 = w0 + cell.seconds
            start(load, t_start)
            time.sleep(max(0.0, w0 - time.monotonic()))
            marks = [call("mark")]
            time.sleep(max(0.0, w1 - time.monotonic()))
            marks.append(call("mark"))
            trace_path = None
            if cell.trace:
                call("trace_start")
                time.sleep(float(tr["trace_seconds"]))
                trace_path = call("trace_stop")["trace_path"]
            load.finish(w0, w1, float(tr["grace_s"]))
            ticker_gaps = ticker.stop()
            # What the window served, against the reference: a sample of its
            # finished requests drawn from the seed, the longest among them.
            timeline = sorted(load.records, key=lambda r: r["idx"])
            prompt_of = {r.idx: p for r, p in zip(load.requests, load.prompts)}
            sample = correct.window_sample(timeline, cell.seed, int(cor["sample_requests"]))
            pad_served = traffic_lib.max_answer_tokens(tr)
            checked = call("check", requests=[{"prompt": prompt_of[r["idx"]], "served": r["tokens"]} for r in sample],
                           pad_tokens=-(-(hi + pad_served) // 512) * 512, pad_served=pad_served, control=bool(cor.get("control")))
            worker = call("finish")
        finally:
            serve.shutdown()
        driver.wait_pid_gone(worker["pid"])

    worker["trace_path"] = trace_path
    worker["warmup"] = warm
    write_timeline(cell, timeline, w0, w1, ticker_gaps)
    attempted, failed = stats.attempted_failed(timeline)
    shed = marks[1]["engine"]["shed_total"] - marks[0]["engine"]["shed_total"]
    tol = cor["served_margin_tolerance"]
    served_sample = {
        "requests": [{"idx": r["idx"], "prompt_tokens": r["prompt_tokens"], "served_tokens": len(r["tokens"]), "margin_max": max(row["margins"])}
                     for r, row in zip(sample, checked["rows"])],
        "margins": correct.error_quantiles([x for row in checked["rows"] for x in row["margins"]]) if sample else None,
        "limits": tol, "seconds": checked["seconds"],
    }
    if cor.get("control"):  # benchmarks/tools/control.py only
        served_sample["control"] = correct.error_quantiles([x for row in checked["rows"] for x in row["control"]])
    checks = {
        "the_window_finished_requests": bool(sample),
        "served_tokens_within_reference_margin": bool(sample) and correct.judge(served_sample["margins"], tol),
        "no_request_failed": failed == 0 and shed == 0,
        "engine_not_failed": worker["engine"]["failed"] is None,
    }
    gaps = stats.gaps_ms(timeline, w0, w1)
    counted = [r for r in timeline if r["counted"] and r["token_times"]]
    half = w0 + (w1 - w0) / 2
    load_facts = {
        "gap_ms_percentiles": {str(q): stats.percentile(gaps, q) for q in (5, 25, 50, 75, 90, 95, 99)},
        "gaps": len(gaps),
        "ttft_p50_ms_first_half": stats.percentile([(r["token_times"][0] - r["due"]) * 1e3 for r in counted if r["due"] < half], 50),
        "ttft_p50_ms_second_half": stats.percentile([(r["token_times"][0] - r["due"]) * 1e3 for r in counted if r["due"] >= half], 50),
        "ttft_max_ms": max([(r["token_times"][0] - r["due"]) * 1e3 for r in counted], default=None),
        "engine_running_waiting_at_window_ends": [[m["engine"]["running"], m["engine"]["waiting"]] for m in marks],
        "shed_in_window": shed,
        "requests_sent": len(timeline),
    }
    return {
        "load_facts": load_facts,
        "cell": cell, "worker": worker, "window": [w0, w1], "spans": worker["spans"], "timeline": timeline,
        "marks": marks, "ticker_gaps": ticker_gaps, "attempted": attempted, "failed": failed + shed,
        "correct": all(checks.values()), "checks": checks, "served_sample": served_sample,
        "compared": correct.compared_quantiles("served_margin", [served_sample["margins"]], tol) if sample else {},
    }


def write_timeline(cell: Cell, timeline, w0: float, w1: float, ticker_gaps) -> str:
    """benchmarks/out/<cell>-<seed>-timeline.jsonl: what explains a stalled run."""
    path = cell.out_prefix + "-timeline.jsonl"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(json.dumps({"window": [w0, w1], "ticker_largest_gaps_s_at": ticker_gaps}) + "\n")
        for r in timeline:
            tt = r["token_times"]
            f.write(json.dumps({
                "idx": r["idx"], "client": r["client"], "counted": r["counted"], "due": r["due"], "sent": r["sent"],
                "first_token": tt[0] if tt else None, "last_token": tt[-1] if tt else None, "tokens": len(tt),
                "prompt_tokens": r["prompt_tokens"], "max_new_tokens": r["max_new_tokens"], "error": r["error"],
                "done": r["done"],
            }) + "\n")
    return path

"""chip_smoke.py on the CPU: the legs' code at tfm.tiny width through the
cluster runtime, the driver staying off jax, the compile-cache placement,
and the refusal to run without a TPU. The chip run itself is the builder's
(`chiprun -- python3 chip_smoke.py`); nothing here is a device number."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, env_drop=(), env_add=None, timeout=600):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(env_add or {})
    argv = code_or_argv if isinstance(code_or_argv, list) else ["-c", textwrap.dedent(code_or_argv)]
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout
    )


_LEGS_DRIVER = """
    import json
    import chip_smoke
    import ray_tpu as rt
    from ray_tpu.models import transformer as tfm

    out = {}
    rt.init(num_cpus=4, num_tpus=1, num_workers=2)
    try:
        train = chip_smoke.train_leg(
            {"cfg": tfm.tiny(max_seq_len=64), "batch_per_chip": 2, "seq": 64,
             "steps": 2, "lr": 1e-2, "seed": 0}
        )
        out["backend_after_fit"] = chip_smoke.driver_backend_initialized()
        serve = chip_smoke.serve_leg(
            {"cfg": tfm.tiny(max_seq_len=256), "num_pages": 128, "page_tokens": 4,
             "max_slots": 4, "max_pages_per_seq": 32, "seed": 0,
             "requests": {"long": (40, 12), "long_shared": (40, 8), "short_a": (6, 4),
                          "short_b": (9, 6), "longest": (70, 8)},
             "shared_prefix_tokens": 24}
        )
        out["backend_after_serve"] = chip_smoke.driver_backend_initialized()
        # The node claims a chip (num_tpus=1) but the worker's jax is on the
        # CPU: with require_tpu the leg must refuse before it computes.
        try:
            chip_smoke.train_leg(
                {"cfg": tfm.tiny(max_seq_len=64), "batch_per_chip": 2, "seq": 64,
                 "steps": 1, "lr": 1e-2, "seed": 0},
                num_tpus=1, require_tpu=True,
            )
            out["refusal"] = None
        except Exception as e:
            out["refusal"] = repr(e)
    finally:
        rt.shutdown()
    out["train"], out["serve"] = train, serve
    print("RESULT " + json.dumps(out, default=str))
"""


def test_legs_run_under_cluster_runtime_and_driver_stays_off_jax():
    """Both legs, cluster mode, from a subprocess driver: the driver holds
    no backend after fit() and after serve.run — the worker sizes its own
    mesh (pins the removal of JaxTrainer's driver-side device query)."""
    proc = _run(_LEGS_DRIVER)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["backend_after_fit"] is False
    assert out["backend_after_serve"] is False
    train, serve = out["train"], out["serve"]
    assert all(train["checks"].values()) and all(serve["checks"].values())
    # 8 virtual devices: the same ZeRO path the four-chip host takes.
    assert train["mesh"] == {"data": 8} and train["zero_axis"] == "data"
    assert train["owner_pid"] != serve["owner_pid"] != os.getpid()
    assert train["compiles_after_first_step"] == 0
    assert serve["engine"]["kv"]["prefix_hits"] >= 1
    assert "needs a TPU" in (out["refusal"] or "") and "cpu" in out["refusal"]


def test_compile_cache_placement():
    """Env set: returned untouched, nothing set in code. Unset: one fixed
    path inside the checkout, the same in every process."""
    placed = _run(
        "import os, sys; from ray_tpu.utils import compile_cache as c; "
        "print(c.configure(), os.environ[c.ENV_VAR], 'jax' in sys.modules)",
        env_add={"JAX_COMPILATION_CACHE_DIR": "/some/dir"},
    )
    assert placed.stdout.split() == ["/some/dir", "/some/dir", "False"], placed.stderr[-2000:]

    # Two processes, jax imported before configure() (a zygote-forked
    # worker) and after it (a spawned one): the same fixed directory.
    fixed = os.path.join(REPO, ".jax_cache")
    for order in ("import jax; d = c.configure()", "d = c.configure(); import jax"):
        proc = _run(
            f"from ray_tpu.utils import compile_cache as c; {order}; "
            "print(d, jax.config.jax_compilation_cache_dir)",
            env_drop=("JAX_COMPILATION_CACHE_DIR",),
        )
        assert proc.stdout.split() == [fixed, fixed], proc.stderr[-2000:]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = _run(["chip_smoke.py"], env_add={"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode not in (0, None)
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_last_line_is_the_contract_object_and_nothing_more():
    """What the driver parses: exactly ok + device{platform, kind, count};
    the facts go on the summary line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.contract_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4, "extra": "dropped"}
    )
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }
    assert "\n" not in line


def test_lost_kv_pool_stops_the_engine_with_a_typed_error():
    """A jitted step that raises after its pool was donated leaves a deleted
    buffer: PagedLM must say so with EngineFailedError and the engine must
    stop, failing in-flight and later requests with it — not answer every
    later request from the deleted buffer while looking alive."""
    import time

    from ray_tpu.exceptions import EngineFailedError
    from ray_tpu.serve.llm import EngineConfig, InferenceEngine, PagedLM

    model = PagedLM(num_pages=16, page_tokens=4, max_slots=2, max_pages_per_seq=4)
    eng = InferenceEngine(model, EngineConfig(page_tokens=4, pool_pages=16), name="t-lost")
    try:
        assert len(list(eng.generate([1, 2, 3], 3))) == 3  # healthy first

        real = model._get_decode()

        def donated_then_failed(params, toks, pos, kv, bts, prev):
            kv["k"].delete()  # what donation does to the argument buffer
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")

        model._decode_jit = donated_then_failed
        with pytest.raises(EngineFailedError, match="donated"):
            list(eng.generate([4, 5, 6], 4))
        model._decode_jit = real  # even a working step cannot bring it back
        deadline = time.monotonic() + 10
        while eng._thread.is_alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not eng._thread.is_alive()
        assert isinstance(eng.failed, EngineFailedError)
        assert "EngineFailedError" in eng.stats()["failed"]
        with pytest.raises(EngineFailedError):
            eng.submit([7], 2, sink=lambda ev, val: None)
        with pytest.raises(EngineFailedError):
            model.decode([0], [0], [[1]])
        assert eng.alloc.used_pages() == 0
    finally:
        eng.close()

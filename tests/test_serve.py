"""The serving entry point driven in-process, the compile-cache helper, and
chip_smoke's refusal to report from a CPU run."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, serve

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def keep_cache_dir():
    """enable_compile_cache() changes process-wide JAX config: restore it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()


def _records(out: str):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_serve_main_in_process(capsys, keep_cache_dir):
    rc = serve.main(["--n", "3", "--max-output", "5", "--max-len", "128",
                     "--slots", "2", "--attn-impl", "pallas"])
    out = capsys.readouterr()
    assert rc == 0
    recs = _records(out.out)
    assert sorted(r["request_id"] for r in recs) == [0, 1, 2]
    for r in recs:
        assert r["status"] == "finished"
        assert len(r["tokens"]) == r["n_tokens"] > 0
    assert "platform=cpu" in out.err and "attn=pallas" in out.err


def test_serve_main_fails_when_a_request_does_not_finish(
        tmp_path, capsys, keep_cache_dir):
    trace = tmp_path / "trace.jsonl"
    trace.write_text("\n".join(json.dumps(r) for r in [
        {"request_id": 0, "prompt": "a", "prompt_tokens": [11, 12, 13],
         "arrival_time": 0.0, "max_tokens": 4},
        {"request_id": 1, "prompt": "b", "prompt_tokens": [21, 22],
         "arrival_time": 0.0, "max_tokens": 4, "deadline": 1e-9},
    ]))
    rc = serve.main(["--trace", str(trace), "--policy", "fcfs",
                     "--max-output", "4", "--max-len", "64"])
    out = capsys.readouterr()
    assert rc == 1
    status = {r["request_id"]: r["status"] for r in _records(out.out)}
    assert status == {0: "finished", 1: "expired"}
    assert "did not finish" in out.err


def test_serve_needs_one_device_per_worker(keep_cache_dir):
    with pytest.raises(SystemExit, match="devices"):
        serve.main(["--workers", str(len(jax.devices()) + 1)])


@pytest.mark.parametrize("env_dir", [None, "/some/cache/dir"])
def test_compile_cache_dir(monkeypatch, keep_cache_dir, env_dir):
    """The variable wins and the code then sets no directory of its own;
    without it the cache sits at a fixed <repo>/.jax_cache."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env_dir is None:
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_refuses_a_cpu_backend():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        smoke.require_tpu(jax.devices())
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        smoke.main([])

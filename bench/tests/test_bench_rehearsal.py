"""The harness end to end on the CPU: smoke-size cells through the real
front end and engine, Pallas kernels in interpret mode; and the command,
run for real, refusing to run without an accelerator."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench.harness import runner, spec
from bench.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def run(kind, trace=False, seed=2**33 + 1):
    return runner.run_cell(tiny.cell(kind), seed, 3.0, trace,
                           time.perf_counter(), lambda m: None)


@pytest.fixture(autouse=True)
def interpret(monkeypatch):
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_last_line_has_the_contract_keys(kind):
    out = run(kind)
    assert KEYS <= set(out) and list(out)[-1] == "checks"
    json.dumps(out)
    cell = tiny.cell(kind)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        v = out["metrics"][m["name"]]
        assert v["unit"] == m["unit"] and v["value"] > 0
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    assert out["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])


def test_traced_run_reads_per_layer_metrics(monkeypatch):
    """With --trace 1 the per-layer readers run; the device trace a CPU
    cannot give is the small trace in ``data/``."""
    from bench.harness import tracing
    rec = spec.load_json(spec.BENCH_DIR / "tests" / "data" /
                         "trace_small.json")
    monkeypatch.setattr(runner, "load_trace", lambda d, n: rec)
    monkeypatch.setattr(runner, "TRACE_AT_S", 0.2)
    monkeypatch.setattr(runner, "TRACE_S", 0.5)
    monkeypatch.setitem(runner.PEAKS, "cpu", {"bf16_flops": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(tracing.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(tracing.jax.profiler, "stop_trace", lambda: None)
    out = run("groups", trace=True)
    names = {m["name"] for m in tiny.cell("groups").per_layer}
    assert set(out["metrics"]) <= names
    for key in ("prefix_hit_share.batch", "decode_batch_mean",
                "compiles_in_window.batch", "idle_share.batch",
                "decode_step_ms.batch", "mfu.batch"):
        assert key in out["metrics"], key
    assert out["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b-agent-dsa",
         "--seed", str(2**33), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(spec.ROOT, env)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _command(tmp_path, env)
    assert p.returncode != 0
    assert "correct" not in p.stdout

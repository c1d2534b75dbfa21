"""``chip_smoke.py``'s serving path at smoke size on the CPU.

The script itself refuses to run without a TPU; its body is a function of
the config, so this drives the same requests and the same checks — every
request ``ok``, no request fault, no restart, radix hit + COW fork, and
the first-token agreement with the gather path — on the yi-6b smoke
config in bf16.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp

from repro.configs import get_smoke_config

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_serving_checks_pass_at_smoke_size():
    smoke = _chip_smoke()
    cfg = get_smoke_config("yi_6b")
    params = smoke.build_params(cfg, seed=0, dtype=jnp.bfloat16)
    lines = []
    failures = smoke.serve_and_check(
        cfg, params, long_len=100, short_len=20, shared_len=12, max_new=6,
        prefill_chunk=32, max_len=256, num_blocks=64, max_batch=4,
        block_size=16, log=lines.append)
    assert failures == [], (failures, lines)
    assert any(line.startswith("first token of a") for line in lines)


def test_chip_smoke_kernel_checks_pass_in_interpret_mode(monkeypatch):
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    smoke = _chip_smoke()
    lines = []
    failures = smoke.check_kernels(
        gqa={"gqa": (4, 2, 32, 0, 0.0), "windowed": (4, 2, 32, 40, 30.0)},
        mla=(4, 32, 16), indexer=(2, 16), prefill_len=8, verify_len=3,
        bs=8, mb=12, lens=(0, 7, 8, 50, 95), log=lines.append)
    assert failures == [], (failures, lines)
    assert len(lines) == 9, lines


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert '"ok"' not in p.stdout

"""Smoke-size cells for CPU tests: the published configurations cut to
two layers and small widths, and small traffic mixes of both loops."""
from __future__ import annotations

import copy

from bench.harness import spec, traffic

SMOKE = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=256,
             vocab_size=512)
PROGRAM = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
               head_dim=32, d_ff=256, vocab_size=512)
DSA = {"index_heads": 2, "index_head_dim": 16, "top_k": 64}


def config(name: str) -> dict:
    cfg = spec.load_json(spec.BENCH_DIR / "configs" / f"{name}.json")
    cfg.update(SMOKE)
    cfg["program"] = copy.deepcopy(cfg["program"])
    cfg["program"]["set"].update(PROGRAM)
    if cfg["dsa"] is not None:
        cfg["dsa"] = dict(DSA)
        cfg["program"]["set"]["dsa"] = dict(DSA)
    return cfg


def sessions_mix() -> dict:
    mix = spec.load_json(spec.BENCH_DIR / "traffic" / "agent-dsa.json")
    mix.update(system_prompt_tokens=64, pool_size=2, gap_pool_size=32)
    mix["distributions"] = {
        "task_tokens": {"median": 40, "sigma": 0.3, "min": 20, "max": 60},
        "turns": {"min": 2, "max": 3},
        "tool_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
        "answer_tokens": {"median": 6, "sigma": 0.3, "min": 3, "max": 10},
        "tool_time_s": [0.05, 0.1]}
    mix["pool"] = traffic.draw_pool(mix)
    return mix


def groups_mix() -> dict:
    mix = spec.load_json(spec.BENCH_DIR / "traffic" / "rollout-grpo.json")
    mix.update(group_size=4, pool_size=3, response_pool_size=8)
    mix["distributions"] = {
        "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 20, "max": 70},
        "response_tokens": {"median": 4, "sigma": 0.3, "min": 3, "max": 6}}
    mix["pool"] = traffic.draw_pool(mix)
    return mix


# Smoke-size limits, between the program's and the fp8 control's readings
# on five seeds on the CPU.  groups: widest gap, program at most 0.016,
# control at least 0.12.  sessions: DSA picks 64 keys, and bf16 indexer
# scores flip its choice near the boundary, so the widest gaps of program
# (up to 0.25) and control (0.21-0.29) overlap; their means do not
# (program at most 0.016, control at least 0.058).
LIMITS = {"groups": {"logprob_gap": 0.06},
          "sessions": {"logprob_gap_mean": 0.03}}


def cell_settings(loop: str) -> dict:
    load = {"sessions_per_s": 4.0} if loop == "sessions" \
        else {"outstanding": 8}
    return {"engine": {"max_batch": 4, "block_size": 16, "prefill_chunk": 32,
                       "max_len": 512, "kv_pool_bytes": 4e6},
            "load": load, "lead_in_s": 0.5,
            "check": {"sample": 4, "limits": dict(LIMITS[loop])}}


def cell(kind: str) -> spec.Cell:
    """'sessions' (yi6b-dsa shape) or 'groups' (minitron4b shape)."""
    if kind == "sessions":
        cfg, mix = config("yi6b-dsa"), sessions_mix()
    else:
        cfg, mix = config("minitron4b"), groups_mix()
    name = "yi6b-agent-dsa" if kind == "sessions" \
        else "minitron4b-rollout-grpo"
    real = spec.resolve(name)
    return spec.Cell(name=name, chips=1, config=cfg, traffic=mix,
                     cell=cell_settings(kind),
                     end_to_end=real.end_to_end, per_layer=real.per_layer,
                     reference=real.reference)

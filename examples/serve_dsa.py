"""Serving example: batched requests against a DSA model + the search-agent
context-management stack (GLM-5 §4.2.4).

  PYTHONPATH=src python examples/serve_dsa.py
"""
import functools

import jax
import numpy as np

from repro.agents import (Hierarchical, KeepRecentK, make_env, run_episode,
                          scripted_agent)
from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving import ContinuousEngine, Request


def main():
    cfg = get_smoke_config("yi_6b")     # GQA + DSA retrofit
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    # continuous batching: paged KV cache + iteration-level scheduling;
    # DSA sparse decode runs through the block-table gather
    engine = ContinuousEngine(cfg, params, max_batch=2, block_size=16,
                              num_blocks=32, max_len=256)

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, size=n).astype(
        np.int32), max_new=m) for n, m in
        ((16, 8), (24, 4), (32, 12), (9, 6))]
    engine.serve(reqs)
    for i, r in enumerate(reqs):
        print(f"req{i}: prompt_len={len(r.prompt)} -> {r.out.tolist()}")
    s = engine.stats
    print(f"scheduler: {s['decode_steps']} decode steps for "
          f"{s['decode_tokens']} tokens across {s['prefills']} requests "
          f"in {s['steps']} engine steps")

    # context management on the synthetic multi-hop search env
    print("\ncontext management (hierarchical vs keep-recent, one episode):")
    agent = functools.partial(scripted_agent, r_tokens=1500)
    for strat in (KeepRecentK(5), Hierarchical(5, 40_000)):
        r = np.random.default_rng(7)
        env = make_env(r, hops=80, obs_tokens=5000, degrade_start=60_000)
        ok, stats = run_episode(env, agent, strat, budget_tokens=8_000_000,
                                max_rounds=400)
        print(f"  {strat.name:14s} solved={ok} rounds={stats['rounds']} "
              f"restarts={stats['restarts']} "
              f"tokens={stats['spent']/1e6:.1f}M")


if __name__ == "__main__":
    main()

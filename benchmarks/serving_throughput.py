"""Continuous-batching vs static-batching serving throughput (PR-1 tentpole).

Serves the SAME mixed-length workload (>=4x spread in both prompt length
and max_new — the shape of agentic traffic, GLM-5 §3.6) through

  * the static ``ServingEngine`` (left-pad to batch max, lock-step decode
    until the longest ``max_new`` finishes), and
  * the paged ``ContinuousEngine`` (block-table KV, iteration-level
    admission/eviction),

and reports end-to-end generated tokens/sec after a warm-up pass that
absorbs XLA compilation.  Acceptance bar: continuous >= 1.3x static.

  PYTHONPATH=src python -m benchmarks.serving_throughput
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving import ContinuousEngine, Request, ServingEngine


def _workload(cfg, n_requests: int, seed: int) -> List[Request]:
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(8, 97))        # 12x spread in prompt length
        max_new = int(rng.integers(4, 49))     # 12x spread in decode length
        reqs.append(Request(
            prompt=rng.integers(3, cfg.vocab_size, size=plen).astype(
                np.int32), max_new=max_new))
    return reqs


def _clone(reqs: List[Request]) -> List[Request]:
    return [Request(prompt=r.prompt, max_new=r.max_new,
                    temperature=r.temperature) for r in reqs]


def run(fast: bool = False, **kw):
    cfg = get_smoke_config("yi_6b").replace(dsa=None, vocab_size=256)
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    n_requests = 8 if fast else 16
    max_batch = 4
    max_len = 160                                # >= max plen + max_new
    reqs = _workload(cfg, n_requests, seed=7)
    total_tokens = sum(r.max_new for r in reqs)

    def time_static():
        eng = ServingEngine(cfg, params, max_batch=max_batch,
                            max_len=max_len)
        eng.serve(_clone(reqs))                  # warm-up: compile
        t0 = time.time()
        eng.serve(_clone(reqs))
        return time.time() - t0

    def time_continuous():
        # prefix cache OFF: the warm-up pass serves the identical workload,
        # so a warm radix cache would skip the timed run's prefills and
        # inflate the batching speedup this suite is meant to isolate
        # (prefix reuse is measured by benchmarks/prefix_cache.py)
        eng = ContinuousEngine(cfg, params, max_batch=max_batch,
                               block_size=16, num_blocks=64,
                               max_len=max_len, prefix_cache=False)
        eng.serve(_clone(reqs))                  # warm-up: compile
        eng.stats = {k: 0 for k in eng.stats}    # count timed run only
        eng.registry.reset_histograms("engine")  # drop warm-up latencies
        t0 = time.time()
        eng.serve(_clone(reqs))
        return time.time() - t0, eng

    st = time_static()
    ct, eng = time_continuous()
    stats = eng.stats
    tps_static = total_tokens / st
    tps_cont = total_tokens / ct
    speedup = tps_cont / tps_static
    # live per-request latency percentiles, measured by the engine's own
    # clock stamps through the metrics registry (NOT the pd_sim model)
    lat = eng.latency_summary()
    ttft, tpot = lat["ttft_ms"], lat["tpot_ms"]
    return [{
        "name": "serving_throughput/static",
        "us_per_call": st * 1e6,
        "derived": f"{tps_static:.1f} tok/s over {total_tokens} tokens",
    }, {
        "name": "serving_throughput/continuous",
        "us_per_call": ct * 1e6,
        "derived": (f"{tps_cont:.1f} tok/s, speedup={speedup:.2f}x "
                    f"(bar: >=1.3x), decode_steps={stats['decode_steps']}, "
                    f"prefills={stats['prefills']}"),
    }, {
        "name": "serving_throughput/latency",
        "us_per_call": ttft["mean"] * 1e3,
        "derived": (f"live TTFT p50/p95/p99 = {ttft['p50']:.1f}/"
                    f"{ttft['p95']:.1f}/{ttft['p99']:.1f} ms; "
                    f"TPOT p50/p95/p99 = {tpot['p50']:.2f}/"
                    f"{tpot['p95']:.2f}/{tpot['p99']:.2f} ms "
                    f"(n={int(ttft['count'])} requests)"),
        "registry": eng.registry.snapshot(),
    }]


if __name__ == "__main__":
    for row in run():
        print(f"{row['name']},{row['us_per_call']:.0f},{row['derived']}")

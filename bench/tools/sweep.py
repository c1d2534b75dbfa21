#!/usr/bin/env python3
"""Find the knee of an open-loop cell by a sweep of offered rates.

    python bench/tools/sweep.py --workload <cell> --rates 0.1,0.2,0.3 \
        [--seconds 30] [--seed N]

One process, one engine: for each rate (sessions per second) the cell's
traffic runs its lead-in and a window of ``--seconds``; prints one JSON
line per rate with the offered and completed request rates, TTFT and
TPOT percentiles, and the requests due in the window still unfinished
when it closed (a growing backlog).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=2**31 + 12345)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import numpy as np
    from bench.harness import model, runner, spec
    from bench.harness.readings import pct

    cell = spec.resolve(args.workload)
    if jax.devices()[0].platform == "cpu":
        print("sweep: no accelerator", file=sys.stderr)
        return 2
    cfg, mix, cc = cell.config, cell.traffic, cell.cell
    w = model.make_weights(cfg, args.seed)
    eng, fe = runner.build(cfg, cc, args.seed, w, False)
    try:
        t = time.perf_counter()
        runner.offer(fe, mix, cc, args.seed, cfg["vocab_size"], 0.0,
                     warmup=True)
        print(f"sweep: warm-up {time.perf_counter() - t:.1f} s, "
              f"{eng.stats['compiles']} engine compiles", file=sys.stderr,
              flush=True)
        for rate in [float(r) for r in args.rates.split(",")]:
            fe.call(eng.reset_cache)
            c = dict(cc, load={"sessions_per_s": rate})
            win = runner.offer(fe, mix, c, args.seed, cfg["vocab_size"],
                               args.seconds)
            due = [r for r in win.recs if win.t0 <= r.due < win.t1]
            done = [r for r in due if r.req is not None]
            late = [r for r in due if r.req is None
                    or r.req.t_finish > win.t1]
            ttft = [(r.req.t_first - r.due) * 1e3 for r in done]
            tpot = [(r.req.t_finish - r.req.t_first) / (len(r.req.out) - 1)
                    * 1e3 for r in done if len(r.req.out) > 1]
            print(json.dumps({
                "rate": rate, "due": len(due),
                "offered_per_s": len(due) / args.seconds,
                "finished_in_window": sum(1 for r in done
                                          if r.req.t_finish <= win.t1),
                "open_at_close": len(late), "failed": len(due) - len(done),
                "ttft_p50_ms": pct(ttft, 50), "ttft_p95_ms": pct(ttft, 95),
                "tpot_p95_ms": pct(tpot, 95),
                "tokens_per_s": float(np.sum([len(r.req.out) for r in done])
                                      / args.seconds),
                "late_max_ms": win.late_max_s * 1e3,
                "compiles": (win.stats1.get("compiles", 0)
                             - win.stats0.get("compiles", 0)),
                "evictions": eng.prefix.stats["evictions"]}), flush=True)
    finally:
        fe.close(timeout=120)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds S]

One process serves the cell's traffic for each seed through one engine
(weights swapped between seeds, the compiled programs kept), exactly as a
run does, and keeps the requests the check samples.  Then, with the
program's state freed, the plain reference reads each seed's served
tokens; for the control seeds the fp8 control reads the same positions.
Prints one JSON line per seed: the program's reading (``logprob_gap``),
the control's (``control.logprob_gap``) and, for a control seed, the
verdict the control gets at the cell's limits in the program's place
(``control_correct``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import check, model, runner, spec

    cell = spec.resolve(args.workload)
    if jax.devices()[0].platform == "cpu":
        print("calibrate: no accelerator", file=sys.stderr)
        return 2
    cfg, mix, cc = cell.config, cell.traffic, cell.cell
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    log = lambda m: print(f"[calibrate] {m}", file=sys.stderr,  # noqa: E731
                          flush=True)
    served = {}
    w = model.make_weights(cfg, seeds[0])
    eng, fe = runner.build(cfg, cc, seeds[0], w, False)
    try:
        runner.offer(fe, mix, cc, seeds[0], cfg["vocab_size"], 0.0,
                     warmup=True)
        for i, seed in enumerate(seeds):
            if i:
                fe.call(lambda: setattr(eng, "params", None))
                del w
                gc.collect()
                w = model.make_weights(cfg, seed)
                params = model.program_params(cfg, w, eng.cfg)
                fe.call(lambda p=params: setattr(eng, "params", p))
                del params
            fe.call(eng.reset_cache)
            # the sampler's stream follows the seed, as in a run
            fe.call(lambda s=seed: setattr(eng, "_rng",
                                           np.random.default_rng(s)))
            t = time.perf_counter()
            win = runner.offer(fe, mix, cc, seed, cfg["vocab_size"],
                               args.seconds)
            served[seed] = runner.sample_seqs(mix, win,
                                              cc["check"]["sample"], seed)
            fails = sum(1 for r in win.recs if r.error)
            log(f"seed {seed}: served in {time.perf_counter() - t:.1f} s, "
                f"{len(win.recs)} requests, {fails} failed, compiles "
                f"{win.stats1.get('compiles', 0) - win.stats0.get('compiles', 0)}")
    finally:
        fe.close(timeout=120)
    eng.pool = eng.params = None
    del fe, eng, w
    gc.collect()
    ref = spec.load_module(cell.reference)
    for seed in seeds:
        w = model.make_weights(cfg, seed)
        t = time.perf_counter()
        r = check.readings(ref, w, cfg, served[seed], control=seed in controls)
        limits = cc["check"]["limits"]
        r["correct"] = check.verdict(r, limits, 0)[0]
        if seed in controls:
            r["control_correct"] = check.control_verdict(r, limits)[0]
        r.update(seed=seed, seconds=time.perf_counter() - t,
                 lengths=[len(s.tokens) for s in served[seed]])
        print(json.dumps(r), flush=True)
        del w
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

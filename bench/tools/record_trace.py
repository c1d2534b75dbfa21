#!/usr/bin/env python3
"""Run one traced cell and keep its normalized device trace.

    python bench/tools/record_trace.py --workload <cell> --seed N \
        --seconds S --out trace.json [--small small.json]

``--small`` also writes the first 100 ms of the traced window, with the
numbers ``bench/tests/test_bench_reduce.py`` checks, for the tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def small(tr: dict, ns: float) -> dict:
    from bench.harness import reduce
    lo = tr["window"][0]
    hi = min(tr["window"][1], lo + ns)
    keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]  # noqa
    out = {"window": [lo, hi],
           "devices": [{"ops": keep(d["ops"]), "modules": keep(d["modules"])}
                       for d in tr["devices"]],
           "host": keep(tr["host"])}
    secs, n = reduce.module_calls(out, lambda m: "_decode_fn" in m)
    out["expect"] = {"busy_s": reduce.busy_s(out), "decode_calls": n,
                     "decode_s": secs}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--small")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import runner, spec
    out = runner.run_cell(spec.resolve(args.workload), args.seed,
                          args.seconds, True, T_START,
                          lambda m: print(m, file=sys.stderr, flush=True),
                          trace_out=args.out)
    print(json.dumps(out), flush=True)
    if args.small:
        with open(args.out) as f:
            tr = json.load(f)
        with open(args.small, "w") as f:
            json.dump(small(tr, 100e6), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process draws the weights from ``--seed`` on the device, builds the
serving stack (``AsyncFrontend`` over ``ContinuousEngine``) to the cell's
configuration, replays every request shape of the traffic pool once,
serves a session mix's shared system prompt once, offers a lead-in of
the cell's traffic, then measures ``--seconds``.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of part of the window.  Once the
window has closed and the program's state is freed, a plain reference
recomputes a sample of the served requests, which decides ``correct``.

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, optionally ``breakdown``, and last
``checks``: each compared number beside its limit); the last stderr lines
repeat the checks.  Without an accelerator, or with fewer chips than the
cell asks for, it exits nonzero before printing a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # the persistent compilation cache lives at a fixed path inside the
    # checkout: only the first run of a cell here compiles.  The TPU
    # runtime's logs would go to a fixed path under /tmp: none are kept.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import runner, spec
    from bench.harness.peaks import peaks

    cell = spec.resolve(args.workload)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 2
    peaks(devs[0].device_kind)          # an unknown chip is an error
    impls = runner.paged_impls()
    if impls != ("pallas", "pallas"):
        log(f"paged attention resolved to {impls}, not the Pallas kernels")
        return 2
    log(f"{cell.name}: {devs[0].device_kind} x{len(devs)}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}")
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, log)
    for name, value, limit in out["checks"]:
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

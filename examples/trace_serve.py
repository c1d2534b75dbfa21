"""Trace-enabled serving smoke: run a short front-end serve, export the
Chrome trace, and validate it.

  PYTHONPATH=src REPRO_TRACE=1 python examples/trace_serve.py [OUT.json]

Submits a small concurrent workload (with a mid-flight weight push, so
the drain-barrier span machinery fires) through an ``AsyncFrontend``,
exports the engine's trace-event buffer to ``OUT.json`` (default
``trace_serve.json``), schema-checks it with ``validate_trace_file``,
and prints the live TTFT/TPOT percentiles from the metrics registry.
Exits non-zero if the exported trace fails validation — CI runs this as
the observability smoke.

Open the output at https://ui.perfetto.dev (or chrome://tracing): one
row per thread, ``engine.step`` spans on the serve thread holding the
``engine.admit`` / ``engine.prefill`` / ``engine.decode`` /
``engine.sample`` / ``engine.retire`` spans, with request lifecycle
instants between them.
"""
import sys

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.obs.trace import Tracer, validate_trace_file
from repro.serving import AsyncFrontend, ContinuousEngine


def main() -> int:
    out_path = sys.argv[1] if len(sys.argv) > 1 else "trace_serve.json"
    cfg = get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)

    fe = AsyncFrontend(ContinuousEngine(
        cfg, params, tracer=Tracer(enabled=True),
        max_batch=4, block_size=16, num_blocks=96, max_len=128))
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(3, cfg.vocab_size, size=32)
    prompts = [np.concatenate([
        sys_prompt, rng.integers(3, cfg.vocab_size, size=int(
            rng.integers(4, 17)))]).astype(np.int32) for _ in range(8)]

    handles = [fe.submit(p, max_new=8) for p in prompts[:4]]
    [fe.result(h) for h in handles]
    # a push between cohorts: the second wave refreshes stale cache paths
    # and the trace shows push.requested -> push.applied with drain time
    fe.push_weights(params, 1)
    handles = [fe.submit(p, max_new=8) for p in prompts[4:]]
    [fe.result(h) for h in handles]

    lat = fe.latency_summary()
    snap = fe.registry.snapshot()
    fe.export_trace(out_path)
    fe.close()

    problems = validate_trace_file(out_path)
    ttft, tpot = lat["ttft_ms"], lat["tpot_ms"]
    print(f"served {int(ttft['count'])} requests, "
          f"{snap['counters']['engine.steps']} engine steps, "
          f"{snap['counters']['engine.compiles']} jit compiles, "
          f"{snap['counters']['engine.weight_pushes']} weight pushes")
    print(f"TTFT p50/p95/p99 = {ttft['p50']:.1f}/{ttft['p95']:.1f}/"
          f"{ttft['p99']:.1f} ms; TPOT p50/p95/p99 = {tpot['p50']:.2f}/"
          f"{tpot['p95']:.2f}/{tpot['p99']:.2f} ms")
    if problems:
        print(f"INVALID trace at {out_path}:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"trace OK: {out_path} "
          f"(open at https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

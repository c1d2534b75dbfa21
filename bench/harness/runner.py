"""One run of one cell: build, warm up, measure, check.

``run_cell`` is everything ``bench/run.py`` does after it has found an
accelerator; tests drive it on the CPU at smoke sizes.
"""
from __future__ import annotations

import gc
import json
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.harness import check, drive, model, readings, reduce, spec
from bench.harness.peaks import PEAKS
from bench.harness.tracing import Instrument, Profiler, load_trace

clock = time.perf_counter
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
WARMUP_S = 900.0        # a first run in a checkout may take 1200 s
DRAIN_S = 60.0          # requests due in the window get this long to finish
TRACE_AT_S = 0.0        # a traced run traces from this second of the window
TRACE_S = 12.0          # ... for this long


@dataclass
class Run:
    """What a metric reader reads."""
    cfg: dict
    mix: dict
    cell: dict
    peaks: Optional[dict]
    setup_s: float
    window: drive.Window
    compiles_in_window: int
    compiled_spans: List[int]
    trace: Optional[dict] = None
    prefill_calls: list = field(default_factory=list)
    decode_calls: list = field(default_factory=list)
    admitted: Dict[int, float] = field(default_factory=dict)

    def due_in_window(self) -> List[drive.Rec]:
        w = self.window
        return [r for r in w.recs if w.t0 <= r.due < w.t1]

    def delta(self, key: str) -> int:
        return self.window.stats1.get(key, 0) - self.window.stats0.get(key, 0)


def build(cfg: dict, cc: dict, seed: int, w, trace: bool):
    """The program under test: engine + front end, built to ``cfg``."""
    from repro.obs.trace import Tracer
    from repro.serving import AsyncFrontend, ContinuousEngine
    pc = model.program_config(cfg)
    params = model.program_params(cfg, w, pc)
    e = cc["engine"]
    nb = int(e["kv_pool_bytes"] // (e["block_size"] * model.kv_token_bytes(cfg)))
    eng = ContinuousEngine(
        pc, params, max_batch=e["max_batch"], block_size=e["block_size"],
        num_blocks=nb, max_len=e["max_len"], seed=seed, prefix_cache=True,
        prefill_chunk=e["prefill_chunk"],
        capture_logprobs=True, true_logprobs=True, spec_steps=0,
        tracer=Tracer(enabled=trace, limit=10 ** 7), max_waiting=0,
        spill=False)
    return eng, AsyncFrontend(eng, max_restarts=0)


def paged_impls() -> tuple:
    from repro.kernels.paged_attention.ops import (resolve_impl,
                                                   resolve_prefill_impl)
    return resolve_impl(None), resolve_prefill_impl(None)


def offer(fe, mix: dict, cc: dict, seed: int, vocab: int, seconds: float,
          hooks=None, warmup: bool = False) -> drive.Window:
    """Offer the cell's load (or its warm-up) through the front end.
    Warm-up may compile every program of a cold cache: it gets
    ``WARMUP_S`` to finish where the window's drain gets ``DRAIN_S``."""
    kw = dict(seed=seed, vocab=vocab, lead_in=cc["lead_in_s"],
              seconds=seconds, hooks=hooks, warmup=warmup,
              drain_s=WARMUP_S if warmup else DRAIN_S)
    if mix["loop"] == "sessions":
        return drive.drive_sessions(
            fe, mix, rate=cc["load"]["sessions_per_s"],
            warm_system=cc["engine"]["prefill_chunk"], **kw)
    return drive.drive_groups(fe, mix, outstanding=cc["load"]["outstanding"],
                              **kw)


def sample_seqs(mix: dict, win: drive.Window, n: int, seed: int
                ) -> List[check.Seq]:
    """The served requests the reference checks: the longest and ``n - 1``
    more drawn from the seed.  A session is checked whole: its last
    finished turn's prompt holds every earlier turn and reply.  A rollout
    sample is a finished request or one still decoding at the close."""
    rng = np.random.default_rng([seed, 2])
    units: Dict[int, List[drive.Rec]] = {}
    for i, r in enumerate(win.recs):
        if r.req is not None and r.error is None:
            key = r.owner if mix["loop"] == "sessions" else i
            units.setdefault(key, []).append(r)
    seqs = []
    for rs in units.values():
        rs.sort(key=lambda r: r.turn)
        last = rs[-1]
        seqs.append(check.Seq(
            tokens=np.concatenate([last.prompt, last.req.out]),
            pos=np.concatenate([len(r.prompt) + np.arange(len(r.req.out))
                                for r in rs]),
            lp=np.concatenate([r.req.out_logprobs for r in rs])))
    for prompt, out, lp in win.partial:
        seqs.append(check.Seq(tokens=np.concatenate([prompt, out]),
                              pos=len(prompt) + np.arange(len(out)), lp=lp))
    if not seqs:        # nothing served: no reading, so not correct
        return []
    seqs.sort(key=lambda s: -len(s.tokens))
    return seqs[:1] + [seqs[1:][i] for i in
                       rng.permutation(len(seqs) - 1)[:n - 1]]


def attempted_failed(run: Run) -> tuple:
    w = run.window
    if run.mix["loop"] == "sessions":
        recs = run.due_in_window()
    else:
        recs = [r for r in w.recs
                if (r.req is not None and w.t0 <= r.req.t_finish < w.t1)
                or (r.error is not None and r.due < w.t1)]
    return len(recs), sum(1 for r in recs if r.req is None or r.error)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None],
             trace_out: Optional[str] = None) -> dict:
    """One run; returns the result line's object.  ``trace_out`` keeps
    the normalized device trace of a traced run as JSON there."""
    cfg, mix, cc = cell.config, cell.traffic, cell.cell
    dev = jax.devices()[0]
    # bench/run.py refuses a chip missing from the table; CPU tests have none
    pk = PEAKS.get(dev.device_kind)
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(clock())
        if ev == BACKEND_COMPILE else None)
    cache = {CACHE_HIT: 0, CACHE_MISS: 0}
    jax.monitoring.register_event_listener(
        lambda ev, **kw: cache.__setitem__(ev, cache[ev] + 1)
        if ev in cache else None)

    t = clock()
    w = model.make_weights(cfg, seed)
    jax.block_until_ready(w)
    log(f"weights: {time.perf_counter() - t:.2f} s")
    eng, fe = build(cfg, cc, seed, w, trace)
    inst = Instrument(eng, fe, annotate=trace)
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    prof = Profiler(inst, logdir) if trace else None
    vocab = cfg["vocab_size"]
    try:
        t = clock()
        offer(fe, mix, cc, seed, vocab, 0.0, warmup=True)
        log(f"warm-up: {clock() - t:.2f} s, {eng.stats['compiles']} engine "
            f"compiles, {len(compiles)} backend compiles, persistent cache "
            f"{cache[CACHE_HIT]} hits / {cache[CACHE_MISS]} misses")
        fe.call(eng.reset_cache)
        hooks = []
        if trace:
            hooks = [(TRACE_AT_S, 1, prof.start),
                     (TRACE_AT_S + TRACE_S, 2, prof.stop)]
        win = offer(fe, mix, cc, seed, vocab, seconds, hooks=hooks)
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        fe.close(timeout=120)
        if prof is not None:
            prof.join()
    if fe.crashed is not None:
        raise RuntimeError(f"serve loop crashed: {fe.crashed!r}")
    run = Run(cfg=cfg, mix=mix, cell=cc, peaks=pk,
              setup_s=win.t0 - t_start, window=win,
              compiles_in_window=(win.stats1.get("compiles", 0)
                                  - win.stats0.get("compiles", 0))
              + sum(1 for c in compiles if win.t0 <= c < win.t1),
              compiled_spans=[s for t_, s in inst.compiled_spans
                              if win.t0 <= t_ < win.t1])
    if trace:
        run.trace = load_trace(logdir, cell.chips)
        shutil.rmtree(logdir, ignore_errors=True)
        ops = run.trace["devices"][0]["ops"]
        log(f"trace: window {run.trace['window']}, {len(ops)} device ops "
            f"from {min(o[1] for o in ops)} to "
            f"{max(o[1] + o[2] for o in ops)} ns, "
            f"{len(run.trace['host'])} host spans")
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(run.trace, f)
        run.prefill_calls = inst.prefill_calls
        run.decode_calls = inst.decode_calls
        t0 = eng.tracer._t0
        run.admitted = {ev["args"]["req"]: t0 + ev["ts"] / 1e6
                        for ev in eng.tracer.export()["traceEvents"]
                        if ev.get("name") == "req.admitted"}
    attempted, failed = attempted_failed(run)
    log(f"window: {attempted} attempted, {failed} failed; load generator at most "
        f"{win.late_max_s * 1e3:.1f} ms late; compiles in window "
        f"{run.compiles_in_window} (prefill spans {run.compiled_spans})")
    log("window deltas: " + json.dumps(
        {k: run.delta(k) for k in ("steps", "chunk_steps", "decode_steps",
                                   "decode_tokens", "prefill_tokens",
                                   "cached_tokens", "cow_forks")})
        + "; prefix cache " + json.dumps(
            dict(eng.prefix.stats) if eng.prefix is not None else {}))
    if mix["loop"] == "sessions":
        log("ttft_ms of turns due in the window, sorted: "
            + json.dumps(sorted(round(v) for v in readings.ttfts_ms(run))))

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        v = spec.load_module(spec.metric_file(m["name"])).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": mem}
    result = {"metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduce.busy_s(run.trace)
        device["window_s"] = reduce.window_s(run.trace)
        result["breakdown"] = {"device_ops": reduce.top_ops(run.trace),
                               "idle_gaps": reduce.idle_by_host(run.trace)}

    # the program's state goes before the reference runs
    seqs = sample_seqs(mix, win, cc["check"]["sample"], seed)
    eng.pool = eng.params = None
    del fe, eng, inst, prof, run
    gc.collect()
    t = clock()
    ref = spec.load_module(cell.reference)
    reads = check.readings(ref, w, cfg, seqs)
    log(f"reference: {clock() - t:.2f} s over {len(seqs)} sequences, "
        f"{reads['lp_tokens']} served tokens")
    correct, compared = check.verdict(reads, cc["check"]["limits"], failed)
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    out.update(result)
    out["checks"] = compared
    return out

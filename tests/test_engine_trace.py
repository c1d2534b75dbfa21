"""The engine's own spans, per-token stamps and sampling counter.

  * ``Tracer.span``: matched ``B``/``E`` pairs when enabled, nothing (the
    shared ``NO_SPAN``) when disabled;
  * a real ``jax.profiler`` trace of a tiny serve shows the engine spans on
    a ``/host:`` plane, nested in ``engine.step``, ``req`` on the prefill
    spans; the export's ``clock_offset_ns`` puts a Chrome event on its
    xplane twin;
  * ``Request.t_tokens``: one stamp per emitted token, ``t_tokens[0] ==
    t_first``, never decreasing, shared by a speculative round's tokens;
  * ``stats["sample_ns"]``: host time inside ``_sample`` only, which hands
    the device sampler's draws out to the slots;
  * the benchmark's readers of these (``host_sample_ms``, ``itl_p99_ms``)
    read a traced smoke run, and read nothing where the program lacks the
    counter and the stamps.
"""
import dataclasses
import functools
import glob
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import MTPConfig
from repro.models import get_model
from repro.obs.trace import NO_SPAN, Tracer, validate_chrome_trace
from repro.serving import AsyncFrontend, ContinuousEngine, Request

_KW = dict(max_batch=2, block_size=8, num_blocks=32, max_len=64)
_SPANS = ("engine.admit", "engine.prefill", "engine.decode",
          "engine.sample", "engine.retire")


def _cfg(family):
    if family == "hybrid":
        return get_smoke_config("zamba2_2p7b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=256, ssm_state=8, dsa=None)
    return get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None,
        mtp=MTPConfig(num_predict=3, share_params=True))


@functools.lru_cache(maxsize=None)
def _params(family):
    cfg = _cfg(family)
    return cfg, get_model(cfg).init(jax.random.key(0), cfg)[0]


def _requests(cfg, temperature=0.0):
    rng = np.random.default_rng(3)
    return [Request(prompt=rng.integers(3, cfg.vocab_size, size=k)
                    .astype(np.int32), max_new=m, temperature=temperature)
            for k, m in zip((11, 5, 17, 7), (6, 9, 3, 7))]


# ---------------------------------------------------------------------------
# Tracer.span
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("enabled", [True, False])
def test_span_writes_matched_pairs_only_when_enabled(enabled):
    tr = Tracer(enabled=enabled)
    with tr.span("outer", k=1) as outer:
        with tr.span("inner"):
            pass
        outer.set(n=2)
    obj = tr.export()
    if not enabled:
        assert obj["traceEvents"] == [] and tr.span("x") is NO_SPAN
        return
    evs = obj["traceEvents"]
    assert [(e["name"], e["ph"]) for e in evs] == [
        ("outer", "B"), ("inner", "B"), ("inner", "E"), ("outer", "E")]
    assert evs[0]["args"] == {"k": 1} and evs[-1]["args"] == {"n": 2}
    assert validate_chrome_trace(obj) == []
    assert isinstance(obj["otherData"]["clock_offset_ns"], int)


# ---------------------------------------------------------------------------
# a real profile of a tiny serve
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A chunked-prefill serve through the front end, profiled: (host
    events of the xplane as (name, start ns on the profiler's clock,
    duration ns, args), the tracer's Chrome export)."""
    cfg, params = _params("gqa")
    fe = AsyncFrontend(ContinuousEngine(
        cfg, params, tracer=Tracer(enabled=True), prefill_chunk=8, **_KW))
    for r in _requests(cfg):                     # compile outside the trace
        fe.result(fe.submit(r.prompt, max_new=r.max_new))
    logdir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(logdir)
    handles = [fe.submit(r.prompt, max_new=r.max_new, temperature=1.0)
               for r in _requests(cfg)]
    for h in handles:
        fe.result(h)
    fe.flush()
    jax.profiler.stop_trace()
    obj = fe.export_trace()
    fe.close()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0])
    t0 = dict(next(p for p in pd.planes if p.name == "Task Environment")
              .stats)["profile_start_time"]
    host = [(e.name, t0 + e.start_ns, e.duration_ns, dict(e.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    return host, obj


def test_profile_holds_engine_spans_nested_in_step(profiled):
    host, obj = profiled
    assert validate_chrome_trace(obj) == []
    names = {h[0] for h in host}
    assert {"engine.step", "frontend.harvest", *_SPANS} <= names
    steps = [(s, s + d) for n, s, d, _ in host if n == "engine.step"]
    for name, s, d, args in host:
        if name in _SPANS:
            assert any(a <= s and s + d <= b for a, b in steps), name
        if name == "engine.prefill":
            assert {"req", "start", "span"} <= set(args)
        if name in ("engine.decode", "engine.sample"):
            assert "rows" in args
    # the lifecycle instants are mirrored, carrying the request id
    assert any(n == "req.admitted" and "req" in a for n, _, _, a in host)


def test_export_clock_offset_lines_up_with_xplane(profiled):
    host, obj = profiled
    off = obj["otherData"]["clock_offset_ns"]
    chrome = {(e["args"]["req"], e["args"]["start"]): e["ts"] * 1e3 + off
              for e in obj["traceEvents"]
              if e["name"] == "engine.prefill" and e["ph"] == "B"}
    pairs = [(chrome[(a["req"], a["start"])], s) for n, s, _, a in host
             if n == "engine.prefill"]
    assert len(pairs) >= 4
    for c, x in pairs:
        assert abs(c - x) < 200e3, (c - x)             # within 200 us


# ---------------------------------------------------------------------------
# per-token stamps and the sampling counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["whole", "chunked", "spec", "hybrid"])
def test_t_tokens_one_stamp_per_token(path):
    cfg, params = _params("hybrid" if path == "hybrid" else "gqa")
    if path == "spec":
        # zero weights: every logit ties, trunk and MTP head both pick
        # token 0, so every draft is accepted
        params = jax.tree.map(jnp.zeros_like, params)
    eng = ContinuousEngine(
        cfg, params, prefill_chunk=8 if path in ("chunked", "hybrid")
        else None, spec_steps=3 if path == "spec" else 0, **_KW)
    reqs = _requests(cfg)
    eng.serve(reqs)
    shared = 0
    for r in reqs:
        t = r.t_tokens
        assert t.dtype == np.float64 and len(t) == len(r.out)
        assert t[0] == r.t_first
        assert np.all(np.diff(t) >= 0)
        shared += len(t) - len(np.unique(t))
    # a round's accepted drafts share its stamp with its bonus token (the
    # bonus is dropped where the drafts reached max_new)
    st = eng.stats
    if path == "spec":
        assert 0 < shared <= st["accepted_tokens"] - st["spec_rounds"]
    else:
        assert shared == 0


def test_sample_ns_counts_only_inside_sample():
    """Every token reaches its slot through one ``_sample`` call, which
    hands out what the device sampler drew (a row as long as the
    vocabulary, not logits); ``sample_ns`` counts the time inside it and
    ``device_sampled_rows`` the rows drawn on the device."""
    cfg, params = _params("gqa")
    eng = ContinuousEngine(cfg, params, capture_logprobs=True,
                           true_logprobs=True, **_KW)
    calls, inner = [], eng._sample

    def wrapped(row, temperature):              # host time outside the body
        time.sleep(0.002)
        assert not isinstance(row, np.ndarray) and len(row) == cfg.vocab_size
        calls.append(1)
        return inner(row, temperature)
    eng._sample = wrapped
    reqs = _requests(cfg, temperature=1.0)
    eng.serve(reqs)
    first = eng.stats["sample_ns"]
    assert len(calls) == sum(r.max_new for r in reqs)
    assert eng.stats["device_sampled_rows"] == len(calls)
    assert 0 < first < len(calls) * 2e6
    eng.serve(_requests(cfg, temperature=1.0))
    assert eng.stats["sample_ns"] > first        # grows with sampled rows
    assert eng.stats["device_sampled_rows"] == len(calls)
    eng.stats = {k: 0 for k in eng.stats}        # the reset idiom
    assert eng.stats["sample_ns"] == 0
    assert eng.stats["device_sampled_rows"] == 0


# ---------------------------------------------------------------------------
# the benchmark's readers, on the tiny traced rehearsal
# ---------------------------------------------------------------------------

_READ = {"sessions": ("host_sample_ms.serve", "itl_p99_ms"),
         "groups": ("host_sample_ms.batch",)}


def _traced_run(kind, monkeypatch):
    """A smoke-size traced run of a cell: its result line and its Run."""
    from bench.harness import runner, spec, tracing
    from bench.tests import tiny
    small = spec.load_json(spec.BENCH_DIR / "tests" / "data" /
                           "trace_small.json")
    monkeypatch.setattr(runner, "load_trace", lambda d, n: small)
    monkeypatch.setattr(runner, "TRACE_AT_S", 0.2)
    monkeypatch.setattr(runner, "TRACE_S", 0.5)
    monkeypatch.setitem(runner.PEAKS, "cpu", {"bf16_flops": 197e12,
                                              "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(tracing.jax.profiler, "start_trace",
                        lambda *a, **k: None)
    monkeypatch.setattr(tracing.jax.profiler, "stop_trace", lambda: None)
    runs, judge = [], runner.attempted_failed
    monkeypatch.setattr(runner, "attempted_failed",
                        lambda run: runs.append(run) or judge(run))
    out = runner.run_cell(tiny.cell(kind), 2**33 + 5, 3.0, True,
                          time.perf_counter(), lambda m: None)
    return out, runs[0]


def _as_parent(run):
    """The run as a program without the counter and the stamps reads."""
    w = run.window
    strip = {k: v for k, v in w.stats1.items() if k != "sample_ns"}
    recs = [dataclasses.replace(r, req=None if r.req is None else
                                types.SimpleNamespace(**{
                                    k: v for k, v in vars(r.req).items()
                                    if k != "t_tokens"}))
            for r in w.recs]
    win = dataclasses.replace(
        w, recs=recs, stats1=strip,
        stats0={k: v for k, v in w.stats0.items() if k != "sample_ns"})
    return dataclasses.replace(run, window=win)


@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_readers_read_the_program_and_skip_the_parent(kind, monkeypatch):
    from bench.harness import spec
    out, run = _traced_run(kind, monkeypatch)
    parent = _as_parent(run)
    for name in _READ[kind]:
        assert out["metrics"][name]["value"] > 0, name
        reader = spec.load_module(spec.metric_file(name))
        assert reader.read(run) == out["metrics"][name]["value"]
        assert reader.read(parent) is None, name

"""The comparison catches a fault planted under the timed path, and its
fp8 control, put in the program's place, comes out not correct (smoke
size, CPU)."""
import time

import pytest

from bench.harness import check, model, runner, spec
from bench.tests import tiny
from repro.serving.scheduler import ContinuousEngine


@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_altered_token_is_not_correct(kind, monkeypatch):
    """A token altered where it is produced: the sampler's choice is
    replaced by the next id, its recorded logprob left as it was."""
    orig = ContinuousEngine._sample

    def altered(self, row, temperature):
        tok, lp = orig(self, row, temperature)
        return (tok + 1) % len(row), lp
    monkeypatch.setattr(ContinuousEngine, "_sample", altered)
    out = runner.run_cell(tiny.cell(kind), 2**33 + 3, 1.5, False,
                          time.perf_counter(), lambda m: None)
    assert out["correct"] is False
    assert out["failed"] == 0
    assert any(v > lim for n, v, lim in out["checks"]
               if n in tiny.LIMITS[kind])


_DECODE = ContinuousEngine._decode_fn


def _stale_kv(self, params, tok, pool, tables, lengths):
    """A step that returns its state unchanged: no decoded token's keys
    and values are ever written."""
    logits, _ = _DECODE(self, params, tok, pool, tables, lengths)
    return logits, pool


def _half_batch(self, params, tok, pool, tables, lengths):
    """Half of the batch left out: the second half of the rows get the
    first half's logits in place of their own."""
    logits, pool = _DECODE(self, params, tok, pool, tables, lengths)
    h = logits.shape[0] // 2
    return logits.at[h:2 * h].set(logits[:h]), pool


@pytest.mark.parametrize("fault", [_stale_kv, _half_batch],
                         ids=["stale_kv", "half_batch"])
@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_broken_decode_step_is_not_correct(kind, fault, monkeypatch):
    """The decode program, broken underneath the timed path, makes the
    run not correct."""
    monkeypatch.setattr(ContinuousEngine, "_decode_fn", fault)
    out = runner.run_cell(tiny.cell(kind), 2**33 + 5, 1.5, False,
                          time.perf_counter(), lambda m: None)
    assert out["correct"] is False
    assert out["failed"] == 0
    assert any(v > lim for n, v, lim in out["checks"]
               if n in tiny.LIMITS[kind])


def _served(kind, seed, dtype=None):
    """Serve a smoke cell's traffic once; return the cell, weights, the
    sequences the check samples and the engine's stats."""
    cell = tiny.cell(kind)
    cfg = cell.config
    w = model.make_weights(cfg, seed, **({"dtype": dtype} if dtype else {}))
    eng, fe = runner.build(cfg, cell.cell, seed, w, False)
    try:
        runner.offer(fe, cell.traffic, cell.cell, seed, cfg["vocab_size"],
                     0.0, warmup=True)
        win = runner.offer(fe, cell.traffic, cell.cell, seed,
                           cfg["vocab_size"], 1.5)
    finally:
        fe.close(timeout=60)
    seqs = runner.sample_seqs(cell.traffic, win, 3, seed)
    return cell, w, seqs, dict(eng.stats)


@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_fp8_control_is_not_correct(kind):
    """The control through the verdict a run gets: the program passes the
    cell's limit and the fp8 reference in its place fails it."""
    cell, w, seqs, _ = _served(kind, 2**32 + 9)
    r = check.readings(spec.load_module(cell.reference), w, cell.config,
                       seqs, control=True)
    assert r["lp_tokens"] > 0
    limits = cell.cell["check"]["limits"]
    assert check.verdict(r, limits, 0)[0] is True, r
    assert check.control_verdict(r, limits)[0] is False, r


@pytest.mark.parametrize("kind", ["sessions", "groups"])
def test_reference_is_the_served_model_in_float32(kind):
    """With float32 weights the engine and the reference compute the same
    function to rounding: every served token's logprob agrees, through
    chunked prefill, radix aliasing, COW forks and (sessions) DSA
    selection past top-k."""
    import jax.numpy as jnp
    cell, w, seqs, stats = _served(kind, 2**33 + 1, jnp.float32)
    assert stats["cached_tokens"] > 0
    if kind == "sessions":
        top_k = cell.config["dsa"]["top_k"]
        assert max(len(s.tokens) for s in seqs) > 2 * top_k
    r = check.readings(spec.load_module(cell.reference), w, cell.config,
                       seqs)
    assert r["lp_tokens"] > 0
    assert r["logprob_gap"] < 1e-4


def test_requests_in_flight_at_the_close_are_checked():
    """Rollout responses outlast the window: the tokens that requests
    still decoding at the close have served are what the check reads."""
    cell = tiny.cell("groups")
    cell.traffic["pool"]["responses"] = [400] * 8
    cfg = cell.config
    w = model.make_weights(cfg, 11)
    eng, fe = runner.build(cfg, cell.cell, 11, w, False)
    try:
        runner.offer(fe, cell.traffic, cell.cell, 11, cfg["vocab_size"],
                     0.0, warmup=True)
        win = runner.offer(fe, cell.traffic, cell.cell, 11,
                           cfg["vocab_size"], 1.0)
    finally:
        fe.close(timeout=60)
    assert not any(r.req is not None for r in win.recs)
    seqs = runner.sample_seqs(cell.traffic, win, 3, 11)
    assert len(seqs) == 3 and min(len(s.lp) for s in seqs) > 0
    r = check.readings(spec.load_module(cell.reference), w, cfg, seqs)
    assert check.verdict(r, cell.cell["check"]["limits"], 0)[0] is True, r

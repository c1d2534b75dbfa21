"""Live disaggregated prefill/decode + fault-tolerant KV-block migration.

Covers the PR-9 robustness surface:

  * ``pd_sim`` oracle invariants: percentile ordering, determinism,
    zero-queueing ideal (slowdown == 1 with no contention), the
    colocated-vs-disaggregated interference DIRECTION (the shape the
    live benchmark's TPOT bars must agree with), and the rho
    monotonicity behind it;
  * fault-spec grammar hardening: malformed clauses raise a ``ValueError``
    NAMING the bad clause (unknown point, bad range, bad probability,
    bad param), with the ``points=`` extension hook for custom sites;
  * ``MigrationChannel``: byte-identical decode after handoff, version
    stamps preserved, refcount-correct extract/install, typed
    ``MigrationFailed`` on every failure path (no prefix, injected xfer
    fault, retry exhaustion, version skew, destination pool pressure) —
    with both pools conserved after each;
  * ``DisaggServer`` end to end: routing split, oracle parity, xfer-fault
    fallback (zero lost), prefill crash -> degraded colocated ->
    respawn -> fail-back, and the ``bind_dp_router`` health wiring;
  * ``DPRouter`` rank health: drop reroutes immediately, restore
    re-adds, rebalance ignores dead ranks;
  * property test (hypothesis): refcount conservation /
    no-double-free / free-list integrity across BOTH pools under random
    prefill/migrate/fault/skew/pin interleavings.
"""
from __future__ import annotations

import functools
import re
import time

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.async_rl.router import DPRouter
from repro.configs import get_smoke_config
from repro.faults import FaultInjector
from repro.models import get_model
from repro.serving import (ContinuousEngine, DisaggServer, MigrationChannel,
                           MigrationFailed, Request, bind_dp_router)
from repro.serving.disagg import PREFILL
from repro.serving.pd_sim import ServingConfig, Workload, simulate

_KW = dict(max_batch=4, block_size=8, num_blocks=64, max_len=128)
_PD = 32                                  # pd threshold for server tests


@functools.lru_cache(maxsize=None)
def _cfg_params():
    cfg = get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None)
    return cfg, get_model(cfg).init(jax.random.key(0), cfg)[0]


def _engine(**kw):
    cfg, params = _cfg_params()
    return ContinuousEngine(cfg, params, faults=FaultInjector(""),
                            **dict(_KW, **kw))


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return rng.integers(3, 256, size=n).tolist()


def _prefill(eng, tokens):
    """Drive one prompt through the engine's normal serve path (prefill
    + radix insert on finish); the single greedy token is discarded."""
    r = Request(prompt=np.asarray(tokens, np.int32), max_new=1)
    eng.serve([r])
    assert r.error is None, r.error


def _pool_conserved(eng):
    kv = eng.kv
    assert kv.free_blocks + kv.used_blocks == kv.num_blocks
    nodes = list(eng.prefix._iter_nodes())
    assert all(kv.refcount(n.block) >= 1 for n in nodes)
    assert kv.used_blocks == len({n.block for n in nodes})


def _wait(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# pd_sim: the analytical oracle the live server is validated against
# ---------------------------------------------------------------------------

_SIM_W = Workload(n_rollouts=64, turns=4, prefill_tokens_per_turn=131072,
                  decode_tokens_mean=256, decode_tokens_tail=2048,
                  tail_frac=0.15)


def test_sim_percentile_ordering_and_determinism():
    m = simulate(_SIM_W, ServingConfig(pd_disaggregated=False), seed=3)
    assert m["p50_s"] <= m["p95_s"] <= m["p99_s"] <= m["max_s"]
    assert 0 < m["mean_s"] <= m["max_s"]
    assert m["mean_slowdown"] >= 1.0       # finish never beats the ideal
    again = simulate(_SIM_W, ServingConfig(pd_disaggregated=False), seed=3)
    assert m == again                      # same (workload, config, seed)


def test_sim_zero_contention_is_ideal():
    # one rollout on a disaggregated fleet: no queueing, no interference
    # -> every turn finishes exactly at its zero-queueing ideal
    w = Workload(n_rollouts=1, turns=3)
    m = simulate(w, ServingConfig(pd_disaggregated=True), seed=5)
    assert m["mean_slowdown"] == pytest.approx(1.0)
    assert m["p99_slowdown"] == pytest.approx(1.0)
    # the same single rollout COLOCATED still pays the rho interference
    mc = simulate(w, ServingConfig(pd_disaggregated=False), seed=5)
    assert mc["mean_slowdown"] > 1.0


def test_sim_interference_direction_matches_live_contract():
    """The direction the live benchmark enforces on real engines
    (disagg p95 TPOT <= colocated) must be the sim's prediction on the
    SAME long-prefill workload shape — and it must come from prefill
    interference (rho), not an artifact: heavier prefills widen the gap."""
    co = simulate(_SIM_W, ServingConfig(pd_disaggregated=False), seed=0)
    pd = simulate(_SIM_W, ServingConfig(pd_disaggregated=True,
                                        prefill_frac=0.34), seed=0)
    assert pd["p99_slowdown"] <= co["p99_slowdown"]
    assert pd["p99_s"] <= co["p99_s"]
    # rho monotonicity, isolated from queueing (single rollout): heavier
    # prefills steal MORE decode capacity in the colocated topology
    import dataclasses
    one = dataclasses.replace(_SIM_W, n_rollouts=1)
    light = dataclasses.replace(one, prefill_tokens_per_turn=1024)
    co_one = simulate(one, ServingConfig(pd_disaggregated=False), seed=0)
    co_light = simulate(light, ServingConfig(pd_disaggregated=False), seed=0)
    assert co_light["mean_slowdown"] < co_one["mean_slowdown"]


# ---------------------------------------------------------------------------
# fault-spec grammar hardening (satellite: reject bad clauses loudly)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    "bogus@1",                 # unknown point name
    "@1",                      # empty point
    "xfer@3..1",               # inverted range
    "xfer@-1",                 # negative index
    "xfer@1..x",               # non-integer range end
    "xfer~1.5",                # probability out of [0, 1]
    "xfer~nope",               # non-float probability
    "xfer@1@2",                # doubled @
    "slow@0=abc",              # non-float param
])
def test_fault_grammar_rejects_bad_clause_naming_it(spec):
    bad = spec.split(",")[-1]
    with pytest.raises(ValueError, match=re.escape(repr(bad))):
        FaultInjector(spec)
    # a bad clause poisons the whole spec even next to valid ones
    with pytest.raises(ValueError, match=re.escape(repr(bad))):
        FaultInjector("alloc@1," + spec)


def test_fault_grammar_accepts_new_points_and_extension():
    inj = FaultInjector("xfer@1,route~0.5,xfer@3..4=0.01", seed=1)
    assert inj.armed("xfer") and inj.armed("route")
    assert inj.param("xfer", 0.0) == pytest.approx(0.01)
    # custom sites opt in through points= instead of editing the library
    custom = FaultInjector("warp@0", points=frozenset({"warp"}))
    assert custom.fires("warp")
    with pytest.raises(ValueError, match="alloc"):
        FaultInjector("alloc@0", points=frozenset({"warp"}))


# ---------------------------------------------------------------------------
# MigrationChannel: handoff correctness + every failure path, pools conserved
# ---------------------------------------------------------------------------

def test_migrate_handoff_byte_parity_version_and_reuse():
    src, dst, oracle = _engine(), _engine(), _engine()
    tokens = _prompt(2, 37)
    _prefill(src, tokens)
    ch = MigrationChannel(src, dst, faults=FaultInjector(""))
    blocks = ch.migrate(tokens)
    assert len(blocks) == (37 + 7) // 8
    assert set(blocks) == ch.recent_migrated_blocks()
    # version stamp preserved: migrated blocks are FRESH in dst's tree
    assert all(dst.kv.block_version(b) == src.kv.version for b in blocks)
    _pool_conserved(src)
    _pool_conserved(dst)
    # decode on dst must reuse the migrated prefix AND match the oracle
    r = Request(prompt=np.asarray(tokens, np.int32), max_new=8)
    ro = Request(prompt=np.asarray(tokens, np.int32), max_new=8)
    dst.serve([r])
    oracle.serve([ro])
    np.testing.assert_array_equal(r.out, ro.out)
    assert dst.stats["cached_tokens"] > 0
    assert ch.registry.summary("disagg.migrate_ms")["count"] == 1
    assert ch.registry.counter("disagg.migrated_blocks") == len(blocks)


def test_migrate_failure_paths_typed_and_conserved():
    src, dst = _engine(), _engine()
    tokens = _prompt(3, 24)
    _prefill(src, tokens)

    # (1) no cached prefix: typed failure, nothing allocated anywhere
    ch = MigrationChannel(src, dst, max_retries=0, faults=FaultInjector(""))
    with pytest.raises(MigrationFailed, match="no cached prefix"):
        ch.migrate(_prompt(99, 16))
    _pool_conserved(src)
    _pool_conserved(dst)

    # (2) injected xfer fault on attempt 0, retry succeeds
    ch = MigrationChannel(src, dst, max_retries=2, backoff_s=0.0,
                          faults=FaultInjector("xfer@0"))
    blocks = ch.migrate(tokens)
    assert blocks
    assert ch.registry.counter("disagg.migration_retries") == 1
    assert ch.registry.counter("disagg.migrations") == 1
    _pool_conserved(src)
    _pool_conserved(dst)

    # (3) retry budget exhausted: typed failure, counted, conserved
    ch = MigrationChannel(src, dst, max_retries=1, backoff_s=0.0,
                          faults=FaultInjector("xfer@0..9"))
    with pytest.raises(MigrationFailed, match="2 attempts"):
        ch.migrate(tokens)
    assert ch.registry.counter("disagg.migration_failures") == 1
    _pool_conserved(src)
    _pool_conserved(dst)

    # (4) stalled transfer (=x param) trips the per-attempt timeout path
    ch = MigrationChannel(src, dst, max_retries=0, timeout_s=0.001,
                          backoff_s=0.0,
                          faults=FaultInjector("xfer@0=0.02"))
    with pytest.raises(MigrationFailed):
        ch.migrate(tokens)
    _pool_conserved(src)
    _pool_conserved(dst)


def test_migrate_whole_attempt_timeout_covers_stalled_install():
    """Regression: the per-attempt timeout used to be checked only
    BETWEEN extract and install, so a destination install that wedged
    never tripped it — migrate() reported success however long the
    install stalled.  The timeout now bounds the WHOLE attempt: the
    ``xfer=x`` stall lands on the install half and must still fail."""
    src, dst = _engine(), _engine()
    tokens = _prompt(11, 24)
    _prefill(src, tokens)
    # warm the extract gather so it fits WELL inside the budget (the
    # old between-halves check passes); the transfer then wedges INSIDE
    # install for 0.6s against a 0.25s whole-attempt budget
    MigrationChannel(src, dst, max_retries=0,
                     faults=FaultInjector("")).migrate(tokens)
    ch = MigrationChannel(src, dst, max_retries=0, timeout_s=0.25,
                          backoff_s=0.0,
                          faults=FaultInjector("xfer@0=0.6"))
    with pytest.raises(MigrationFailed, match="stalled install"):
        ch.migrate(tokens)
    assert ch.registry.counter("disagg.migration_failures") == 1
    # the install itself landed before the deadline check fired, and
    # its blocks are owned by the destination TREE — nothing leaks, and
    # a fresh attempt dedupes through insert()
    _pool_conserved(src)
    _pool_conserved(dst)
    ch2 = MigrationChannel(src, dst, max_retries=0,
                           faults=FaultInjector(""))
    assert ch2.migrate(tokens)
    _pool_conserved(src)
    _pool_conserved(dst)


def test_migrate_version_skew_refused_both_directions():
    src, dst = _engine(), _engine()
    tokens = _prompt(5, 40)
    _prefill(src, tokens)
    # decode tier took a weight push the prefill tier has not seen
    dst.push_weights(dst.params, 1)
    ch = MigrationChannel(src, dst, max_retries=1, backoff_s=0.0,
                          faults=FaultInjector(""))
    used_before = dst.kv.used_blocks
    with pytest.raises(MigrationFailed, match="version skew"):
        ch.migrate(tokens)
    assert dst.kv.used_blocks == used_before    # nothing landed
    _pool_conserved(src)
    _pool_conserved(dst)
    # converge the tiers -> the SAME migration now lands (extract was
    # net-zero on src, so the prefix is still there to re-extract)
    src.push_weights(src.params, 1)
    _prefill(src, tokens)                       # re-derive fresh KV
    blocks = ch.migrate(tokens)
    assert all(dst.kv.block_version(b) == 1 for b in blocks)
    _pool_conserved(src)
    _pool_conserved(dst)


def test_migrate_destination_pool_pressure():
    src, dst = _engine(), _engine(num_blocks=8)
    tokens = _prompt(7, 48)                     # needs 6 landing blocks
    _prefill(src, tokens)
    pins = dst.kv.alloc(6)                      # squeeze the free list
    ch = MigrationChannel(src, dst, max_retries=0, faults=FaultInjector(""))
    with pytest.raises(MigrationFailed, match="cannot land"):
        ch.migrate(tokens)
    assert dst.kv.free_blocks + dst.kv.used_blocks == dst.kv.num_blocks
    dst.kv.release(pins)
    assert ch.migrate(tokens)                   # pressure cleared -> lands
    _pool_conserved(src)
    _pool_conserved(dst)


def test_migrate_requires_compatible_engines():
    src = _engine()
    with pytest.raises(ValueError, match="block_size"):
        MigrationChannel(src, _engine(block_size=16, num_blocks=32,
                                      max_len=128))
    with pytest.raises(ValueError, match="prefix_cache"):
        MigrationChannel(src, _engine(prefix_cache=False))


# ---------------------------------------------------------------------------
# DisaggServer end to end (live threads)
# ---------------------------------------------------------------------------

def _mixed(seed):
    return [_prompt(seed, 44), _prompt(seed + 1, 10),
            _prompt(seed + 2, 52), _prompt(seed + 3, 8)]


def _oracle_outs(prompts, max_new):
    eng = _engine()
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new=max_new)
            for p in prompts]
    eng.serve(reqs)
    return [list(r.out) for r in reqs]


def test_disagg_routing_split_and_oracle_parity():
    cfg, params = _cfg_params()
    prompts = _mixed(11)
    oracle = _oracle_outs(prompts, 4)
    srv = DisaggServer(cfg, params, decode_kw=dict(_KW), pd_threshold=_PD,
                       heartbeat_timeout_s=30.0,
                       faults=FaultInjector(""),
                       prefill_faults=FaultInjector(""))
    try:
        hs = [srv.submit(p, max_new=4) for p in prompts]
        outs = [list(srv.result(h, timeout=120).out) for h in hs]
        assert outs == oracle               # byte parity on every path
        assert srv.stats["pd_routes"] == 2       # the two long prompts
        assert srv.stats["colocated_routes"] == 2
        assert srv.stats["migrations"] >= 1
        assert srv.stats["migrated_tokens"] > 0
        # migration observability: latency + bytes histograms populated
        assert srv.registry.summary("disagg.migrate_ms")["count"] >= 1
        assert srv.registry.summary("disagg.migrate_bytes")["count"] >= 1
    finally:
        srv.close()


def test_disagg_migration_faults_fall_back_zero_lost():
    cfg, params = _cfg_params()
    prompts = [_prompt(21, 44), _prompt(22, 52)]
    oracle = _oracle_outs(prompts, 4)
    srv = DisaggServer(cfg, params, decode_kw=dict(_KW), pd_threshold=_PD,
                       migrate_retries=0, heartbeat_timeout_s=30.0,
                       faults=FaultInjector("xfer"),   # every attempt fails
                       prefill_faults=FaultInjector(""))
    try:
        hs = [srv.submit(p, max_new=4) for p in prompts]
        outs = [list(srv.result(h, timeout=120).out) for h in hs]
        assert outs == oracle               # fallback is slower, not wrong
        assert srv.stats["colocated_fallbacks"] == 2
        assert srv.stats["migration_failures"] == 2
        assert srv.stats["migrations"] == 0
    finally:
        srv.close()


def test_disagg_route_fault_hedges_to_colocated():
    cfg, params = _cfg_params()
    p = _prompt(31, 44)
    srv = DisaggServer(cfg, params, decode_kw=dict(_KW), pd_threshold=_PD,
                       heartbeat_timeout_s=30.0,
                       faults=FaultInjector("route"),  # hedge every route
                       prefill_faults=FaultInjector(""))
    try:
        out = list(srv.result(srv.submit(p, max_new=4), timeout=120).out)
        assert out == _oracle_outs([p], 4)[0]
        assert srv.stats["route_faults"] == 1
        assert srv.stats["pd_routes"] == 0
        assert srv.stats["colocated_routes"] == 1
    finally:
        srv.close()


def test_disagg_prefill_crash_degrades_respawns_fails_back():
    cfg, params = _cfg_params()
    prompts = [_prompt(41 + i, 44 + 8 * (i % 3)) for i in range(4)]
    oracle = _oracle_outs(prompts, 4)
    router = DPRouter(n_ranks=2)
    srv = DisaggServer(cfg, params, decode_kw=dict(_KW), pd_threshold=_PD,
                       respawn_delay_s=0.02, heartbeat_timeout_s=0.5,
                       faults=FaultInjector(""),
                       prefill_faults=FaultInjector("crash@0"))
    bind_dp_router(srv, router, {PREFILL: 0})
    try:
        hs = [srv.submit(p, max_new=4) for p in prompts]
        outs = [list(srv.result(h, timeout=120).out) for h in hs]
        assert outs == oracle               # zero lost through the outage
        _wait(lambda: srv.stats["prefill_respawns"] >= 1
              and not srv.degraded, 30, "respawn + fail-back")
        assert srv.stats["tier_down_events"] >= 1
        assert srv.stats["failbacks"] >= 1
        assert srv.stats["colocated_fallbacks"] >= 1
        assert srv.prefill_healthy
        # the DP hash ring saw the same transitions (satellite wiring)
        assert router.stats["dropped_ranks"] >= 1
        assert router.stats["restored_ranks"] >= 1
        assert router.healthy_ranks() == [0, 1]
        assert not srv.callback_errors
        # post-fail-back: the split actually works again (migration runs)
        mig0 = srv.stats["migrations"]
        p = _prompt(51, 48)
        out = list(srv.result(srv.submit(p, max_new=4), timeout=120).out)
        assert out == _oracle_outs([p], 4)[0]
        assert srv.stats["migrations"] > mig0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# DPRouter rank health (satellite: crashed ranks leave the hash ring)
# ---------------------------------------------------------------------------

def test_dp_router_drop_reroutes_and_restore_readds():
    r = DPRouter(n_ranks=3, vnodes=16)
    pinned = {f"ro{i}": r.route(f"ro{i}") for i in range(30)}
    victim = max(set(pinned.values()),
                 key=lambda k: sum(v == k for v in pinned.values()))
    orphans = [rid for rid, rk in pinned.items() if rk == victim]
    r.drop_rank(victim)
    r.drop_rank(victim)                       # idempotent
    assert r.stats["dropped_ranks"] == 1
    assert r.stats["repinned_rollouts"] == len(orphans)
    assert victim not in r.healthy_ranks()
    # the dead rank's keyspace reroutes IMMEDIATELY — old pins included
    for rid in list(pinned) + [f"new{i}" for i in range(20)]:
        assert r.route(rid) != victim
    assert r.load[victim] == 0
    r.restore_rank(victim)
    assert r.healthy_ranks() == [0, 1, 2]
    assert any(r.route(f"post{i}") == victim for i in range(64))


def test_dp_router_all_dead_raises_and_rebalance_skips_dead():
    r = DPRouter(n_ranks=2, vnodes=8, rebalance_threshold=0.1)
    r.drop_rank(0)
    # rebalance target can only be the surviving rank
    assert all(r.route(f"x{i}") == 1 for i in range(16))
    r.drop_rank(1)
    with pytest.raises(RuntimeError, match="no healthy ranks"):
        r.route("anything")
    r.restore_rank(1)
    assert r.route("back") == 1


# ---------------------------------------------------------------------------
# property test: refcount conservation across BOTH pools under interleavings
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _prop_pair():
    """One long-lived engine pair + channel for every example (fresh
    engines would recompile per-instance jits each time); the invariant
    — both pools conserved, no leak, no double-free — holds at any point
    of any op sequence, so state carries across examples."""
    src = _engine(num_blocks=24)
    dst = _engine(num_blocks=24)
    ch = MigrationChannel(src, dst, max_retries=1, backoff_s=0.0,
                          faults=FaultInjector("xfer~0.3", seed=3))
    return src, dst, ch, {"vs": 0, "vd": 0, "prompts": []}


_PROP_OPS = st.lists(st.tuples(st.sampled_from(
    ["prefill", "migrate", "migrate_unknown", "skew_src", "skew_dst",
     "converge", "pin", "unpin"]),
    st.integers(min_value=0, max_value=7)), min_size=1, max_size=12)


@settings(max_examples=8, deadline=None)
@given(_PROP_OPS)
def test_property_migration_pool_integrity(ops):
    from repro.serving.paged import CacheFull
    src, dst, ch, state = _prop_pair()
    pins = []
    for op, arg in ops:
        if op == "prefill":
            tokens = _prompt(arg, 16 + 8 * (arg % 3))
            try:
                _prefill(src, tokens)
                state["prompts"].append(tokens)
            except AssertionError:
                pass                       # shed under pool pressure: fine
        elif op == "migrate" and state["prompts"]:
            try:
                ch.migrate(state["prompts"][arg % len(state["prompts"])])
            except MigrationFailed:
                pass                       # injected fault / skew / pressure
        elif op == "migrate_unknown":
            with pytest.raises(MigrationFailed):
                ch.migrate([200 + arg] * 12)
        elif op == "skew_src":
            state["vs"] += 1
            src.push_weights(src.params, state["vs"])
            state["prompts"].clear()       # stale KV: never matched again
        elif op == "skew_dst":
            state["vd"] += 1
            dst.push_weights(dst.params, state["vd"])
        elif op == "converge":
            v = max(state["vs"], state["vd"])
            if state["vs"] < v:
                src.push_weights(src.params, v)
                state["vs"] = v
                state["prompts"].clear()
            if state["vd"] < v:
                dst.push_weights(dst.params, v)
                state["vd"] = v
        elif op == "pin":
            try:
                pins.append(dst.kv.alloc(1 + arg % 4))
            except CacheFull:
                pass
        elif op == "unpin" and pins:
            dst.kv.release(pins.pop(arg % len(pins)))
    for p in pins:
        dst.kv.release(p)
    # the contract: no interleaving of migrations, injected faults,
    # version skew, and pool pressure leaks a block or frees one twice
    _pool_conserved(src)
    _pool_conserved(dst)

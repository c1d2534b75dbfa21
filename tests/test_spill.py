"""Tiered KV cache (PR-10): host-RAM prefix spill — demote, don't forget.

Covers the spill-tier acceptance criteria:
  * demote/restore BYTE parity at the pool level (fake engine, no
    model) and at the engine level per family (GQA, DSA, MLA): greedy
    outputs with the tier enabled byte-identical to spill-off, with
    restored-prefix hits > 0 and prefill tokens saved;
  * refcount conservation across random demote -> restore -> evict ->
    weight-push interleavings (hypothesis property over the allocator +
    radix tree + tier triple);
  * weight-version contract across the tier boundary: entries stale at
    LOOKUP are dropped (``spill.dropped_stale``) and never restored;
    blocks stale at EVICT time are never demoted at all;
  * capacity bound: past ``capacity_blocks`` the OLDEST spilled entry
    drops (``spill.dropped_capacity``); partial tails are never spilled;
  * restore composing with COW mid-block forks and ``AgentSession``
    pins (a pinned conversation survives the tier churning around it);
  * engine wiring: ``spill=``/``REPRO_SPILL_ENABLE`` resolution,
    ``respawn()`` keeping the tier, ``reset_cache()`` clearing it;
  * satellite bugfixes: the partial-overlap scan counting
    ``version_refused`` (it silently filtered stale children while the
    full-block walk counted), and ``retain()`` rejecting duplicate
    blocks atomically (``release``/``free`` already did).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving import (AgentSession, CacheFull, ContinuousEngine,
                           HostSpillTier, PagedKVCache, PrefixCache, Request)


class _FakeEngine:
    """The spill tier's engine contract without a model: a refcounted
    allocator plus a layer-major pool pytree filled with random bytes
    (leaf shape ``(L * (num_blocks + 1), block_size, feat)``)."""

    def __init__(self, num_blocks=8, block_size=4, L=2, feat=3, seed=0):
        self.kv = PagedKVCache(num_blocks, block_size)
        rng = np.random.default_rng(seed)
        shape = (L * (num_blocks + 1), block_size, feat)
        self.pool = {"k": jnp.asarray(rng.normal(size=shape), jnp.float32),
                     "v": jnp.asarray(rng.normal(size=shape), jnp.float32)}
        self._L, self._stride = L, num_blocks + 1

    def rows(self, leaf_name, block):
        leaf = self.pool[leaf_name]
        idx = np.arange(self._L) * self._stride + block
        return np.asarray(leaf[idx])


def _setup(num_blocks=8, block_size=4, capacity=None, **kw):
    eng = _FakeEngine(num_blocks=num_blocks, block_size=block_size, **kw)
    prefix = PrefixCache(eng.kv)
    tier = HostSpillTier(eng, capacity_blocks=capacity)
    tier.attach(prefix)
    return eng, prefix, tier


def _conserved(eng, prefix):
    kv = eng.kv
    assert kv.free_blocks + kv.used_blocks == kv.num_blocks
    nodes = list(prefix._iter_nodes())
    assert all(kv.refcount(n.block) >= 1 for n in nodes)


# ---------------------------------------------------------------------------
# pool-level demote/restore (no model)
# ---------------------------------------------------------------------------

def test_demote_restore_byte_parity_and_conservation():
    eng, prefix, tier = _setup()
    bs = 4
    toks = list(range(10, 10 + 2 * bs))
    blocks = eng.kv.alloc(2)
    expected = {(n, b): eng.rows(n, b) for n in eng.pool for b in blocks}
    prefix.insert(toks, blocks)
    # allocation pressure unwinds the cold chain tail-first -> demoted
    assert prefix.evict(2) == 2
    assert tier.spilled_blocks == 2 and eng.kv.used_blocks == 0
    assert eng.kv.registry.counter("spill.demotions") == 2
    # the spilled-prefix hit: landing blocks allocated, ONE scatter,
    # the walk continues through the grafted chain like a warm hit
    m, mb = prefix.match(toks)
    assert m == 2 * bs and len(mb) == 2
    assert eng.kv.registry.counter("spill.restores") == 1
    assert eng.kv.registry.counter("spill.restored_blocks") == 2
    assert tier.spilled_blocks == 0               # entries consumed
    for name in eng.pool:
        for orig, b in zip(blocks, mb):
            np.testing.assert_array_equal(eng.rows(name, b),
                                          expected[(name, orig)])
    # restored blocks carry the WRITER version and are matchable again
    assert all(eng.kv.block_version(b) == 0 for b in mb)
    eng.kv.release(mb)
    _conserved(eng, prefix)
    m2, mb2 = prefix.match(toks)                  # now a plain warm hit
    assert m2 == 2 * bs
    assert eng.kv.registry.counter("spill.restores") == 1
    eng.kv.release(mb2)
    _conserved(eng, prefix)


def test_stale_spilled_entries_dropped_never_restored():
    eng, prefix, tier = _setup()
    toks = list(range(20, 28))
    prefix.insert(toks, eng.kv.alloc(2))
    prefix.evict(2)
    assert tier.spilled_blocks == 2
    eng.kv.set_version(1)                         # a weight push lands
    m, mb = prefix.match(toks)
    assert m == 0 and mb == []                    # miss, not stale KV
    assert eng.kv.registry.counter("spill.dropped_stale") >= 1
    assert eng.kv.registry.counter("spill.restores") == 0
    _conserved(eng, prefix)


def test_stale_blocks_never_demoted():
    eng, prefix, tier = _setup()
    prefix.insert(list(range(30, 34)), eng.kv.alloc(1))
    eng.kv.set_version(1)                         # block is now stale
    assert prefix.evict(1) == 1
    assert tier.spilled_blocks == 0               # forgotten, not spilled
    assert prefix.stats["stale_evictions"] == 1
    assert eng.kv.registry.counter("spill.demotions") == 0


def test_partial_tail_leaves_never_demoted():
    eng, prefix, tier = _setup()
    prefix.insert([40, 41, 42], eng.kv.alloc(1))  # 3 tokens < block_size
    assert prefix.evict(1) == 1
    assert tier.spilled_blocks == 0
    assert eng.kv.registry.counter("spill.demotions") == 0


def test_capacity_bound_drops_oldest_entry():
    eng, prefix, tier = _setup(num_blocks=12, capacity=2)
    paths = []
    for f in range(3):                            # three 1-block prefixes
        toks = [100 * (f + 1) + j for j in range(4)]
        paths.append(tuple(toks))
        prefix.insert(toks, eng.kv.alloc(1))
    assert prefix.evict(3) == 3                   # LRU: oldest demotes first
    assert eng.kv.registry.counter("spill.demotions") == 3
    assert eng.kv.registry.counter("spill.dropped_capacity") == 1
    assert tier.spilled_blocks == 2
    assert not tier.has(paths[0])                 # the oldest fell off
    assert tier.has(paths[1]) and tier.has(paths[2])
    assert eng.kv.registry.gauge("spill.blocks") == 2


def test_redemote_refreshes_in_place():
    eng, prefix, tier = _setup(capacity=4)
    toks = list(range(50, 54))
    prefix.insert(toks, eng.kv.alloc(1))
    prefix.evict(1)
    m, mb = prefix.match(toks)                    # restore consumes entry
    eng.kv.release(mb)
    prefix.evict(1)                               # demote the same path again
    assert tier.spilled_blocks == 1
    assert eng.kv.registry.counter("spill.demotions") == 2
    _conserved(eng, prefix)


# ---------------------------------------------------------------------------
# property: conservation across demote/restore/evict/push interleavings
# ---------------------------------------------------------------------------

_SPILL_OPS = st.lists(st.tuples(st.sampled_from(
    ["insert", "match", "evict", "push", "pin", "unpin", "clear_spill"]),
    st.integers(min_value=0, max_value=11)), min_size=1, max_size=20)


@settings(max_examples=15, deadline=None)
@given(_SPILL_OPS)
def test_property_conservation_under_spill_interleavings(ops):
    eng, prefix, tier = _setup(num_blocks=12, capacity=6)
    kv, bs = eng.kv, 4
    version = 0
    pins = []

    def toks(f, n):
        # four token families; chains within a family share prefixes,
        # so inserts/matches exercise dedupe, graft, and chain restore
        return [50 * (f + 1) + j for j in range(n * bs)]

    for op, arg in ops:
        f, n = arg % 4, 1 + arg % 3
        if op == "insert":
            try:
                blocks = kv.alloc(n)
            except CacheFull:
                continue
            prefix.insert(toks(f, n), blocks)
        elif op == "match":
            m, mb = prefix.match(toks(f, n))
            assert m == len(mb) * bs              # full blocks only
            if mb:
                kv.release(mb)
        elif op == "evict":
            prefix.evict(1 + arg % 3)
        elif op == "push":
            version += 1
            kv.set_version(version)
        elif op == "pin":                         # a reader holds on
            m, mb = prefix.match(toks(f, n))
            if mb:
                pins.append(mb)
        elif op == "unpin" and pins:
            kv.release(pins.pop(arg % len(pins)))
        elif op == "clear_spill":
            tier.clear()
        _conserved(eng, prefix)
        if tier.capacity_blocks is not None:
            assert tier.spilled_blocks <= tier.capacity_blocks
    for mb in pins:
        kv.release(mb)
    _conserved(eng, prefix)
    # with readers gone the tree holds exactly one ref per node
    nodes = list(prefix._iter_nodes())
    assert kv.used_blocks == len({nd.block for nd in nodes})
    prefix.clear()
    assert kv.free_blocks == kv.num_blocks


# ---------------------------------------------------------------------------
# satellite bugfixes
# ---------------------------------------------------------------------------

def test_partial_overlap_stale_child_counts_version_refused():
    """Regression: the partial-overlap scan silently FILTERED stale
    children while the full-block walk counted each refusal — the
    telemetry undercounted exactly the mid-block-divergence case."""
    kv = PagedKVCache(num_blocks=8, block_size=8)
    prefix = PrefixCache(kv)
    # (a) a partial tail child at the root goes stale
    prefix.insert([10, 11, 12, 13, 14], kv.alloc(1))
    kv.set_version(1)
    m, mb = prefix.match([10, 11, 12, 13, 14])
    assert m == 0 and mb == []
    assert prefix.stats["version_refused"] == 1
    # (b) a stale FULL child reached via partial overlap (the prompt
    # diverges mid-block, so the full-block walk never sees it)
    prefix.insert(list(range(20, 28)), kv.alloc(1))
    kv.set_version(2)
    m, mb = prefix.match([20, 21, 22, 99, 99, 99, 99, 99])
    assert m == 0 and mb == []
    assert prefix.stats["version_refused"] == 2


def test_retain_rejects_duplicates_atomically():
    """Regression: ``retain`` silently accepted duplicate blocks while
    ``release``/``free`` reject them — a buggy caller could create
    references in one call that release() then refused to drop."""
    kv = PagedKVCache(num_blocks=4, block_size=4)
    a = kv.alloc(2)
    with pytest.raises(ValueError, match="duplicate"):
        kv.retain([a[0], a[0]])
    assert [kv.refcount(b) for b in a] == [1, 1]  # nothing half-applied
    with pytest.raises(ValueError, match="duplicate"):
        kv.retain([a[0], a[1], a[0]])
    assert [kv.refcount(b) for b in a] == [1, 1]
    kv.retain(a)                                  # valid aliasing still works
    assert [kv.refcount(b) for b in a] == [2, 2]


# ---------------------------------------------------------------------------
# engine-level: byte parity per family, COW composition, sessions, wiring
# ---------------------------------------------------------------------------

def _family_cfg(name):
    if name in ("gqa", "dsa"):
        from repro.configs.base import DSAConfig
        return get_smoke_config("yi_6b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=256,
            dsa=DSAConfig(index_heads=2, index_head_dim=16, top_k=32,
                          block_size=16) if name == "dsa" else None)
    return get_smoke_config("glm5_744b").replace(            # mla
        d_model=128, num_heads=2, num_kv_heads=2, d_ff=256,
        vocab_size=256, num_experts=0, num_shared_experts=0,
        first_k_dense=1, mtp=None)


@functools.lru_cache(maxsize=None)
def _family_params(name):
    cfg = _family_cfg(name)
    return cfg, get_model(cfg).init(jax.random.key(0), cfg)[0]


_KW = dict(max_batch=2, block_size=8, num_blocks=24, max_len=96)


def _spill_workload(cfg):
    """A pool-overflowing trace: a shared prefix, filler pressure that
    evicts it, the shared prefix again (the restore hit), and a
    mid-block divergence of it (COW fork off a restored block)."""
    rng = np.random.default_rng(7)
    shared = rng.integers(3, cfg.vocab_size, size=40).astype(np.int32)
    fillers = [rng.integers(3, cfg.vocab_size, size=48).astype(np.int32)
               for _ in range(4)]
    div = np.concatenate([shared[:20],
                          rng.integers(3, cfg.vocab_size,
                                       size=12).astype(np.int32)])
    return [shared, *fillers, shared, div]


def _run_trace(cfg, params, prompts, **kw):
    eng = ContinuousEngine(cfg, params, **dict(_KW, **kw))
    outs = []
    for p in prompts:
        r = Request(prompt=p, max_new=6)
        eng.serve([r])
        assert r.error is None, r.error
        outs.append(np.asarray(r.out))
    return eng, outs


@pytest.mark.parametrize("family", ["gqa", "dsa", "mla"])
def test_engine_spill_byte_parity(family):
    cfg, params = _family_params(family)
    prompts = _spill_workload(cfg)
    off_eng, off = _run_trace(cfg, params, prompts, spill=False)
    on_eng, on = _run_trace(cfg, params, prompts, spill=True,
                            spill_blocks=64)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)       # byte-exact greedy
    reg = on_eng.registry
    assert reg.counter("spill.demotions") > 0
    assert reg.counter("spill.restores") > 0      # restored-prefix hits
    # the restore is the point: tokens the off engine re-prefilled
    assert on_eng.stats["prefill_tokens"] < off_eng.stats["prefill_tokens"]
    # the COW-fork prompt diverges INSIDE a restored block and still
    # matched a cached prefix (composition, not just the warm path)
    assert on_eng.stats["cow_forks"] >= 1
    kv = on_eng.kv
    assert kv.free_blocks + kv.used_blocks == kv.num_blocks


def test_restore_composes_with_session_pins():
    cfg, params = _family_params("gqa")
    rng = np.random.default_rng(9)
    msgs = [rng.integers(3, cfg.vocab_size, size=10).astype(np.int32)
            for _ in range(2)]
    fillers = [rng.integers(3, cfg.vocab_size, size=48).astype(np.int32)
               for _ in range(4)]
    shared = rng.integers(3, cfg.vocab_size, size=40).astype(np.int32)

    def run(spill):
        eng = ContinuousEngine(cfg, params, spill=spill, spill_blocks=64,
                               **_KW)
        sess = AgentSession(eng)
        outs = [np.asarray(sess.send(msgs[0], max_new=4))]
        pinned = sess.pinned_blocks
        assert pinned > 0
        eng.serve([Request(prompt=shared, max_new=4)])     # cache it
        for f in fillers:                  # churn: evict/demote the rest
            eng.serve([Request(prompt=f, max_new=4)])
        assert sess.pinned_blocks == pinned        # pins never spill away
        # a restore allocates landing blocks UNDER the pins
        eng.serve([Request(prompt=shared, max_new=4)])
        outs.append(np.asarray(sess.send(msgs[1], max_new=4)))
        sess.close()
        kv = eng.kv
        assert kv.free_blocks + kv.used_blocks == kv.num_blocks
        return outs, eng

    off, _ = run(False)
    on, eng = run(True)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    assert eng.registry.counter("spill.restores") > 0


def test_engine_spill_wiring_flag_respawn_reset(monkeypatch):
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spill=True, **_KW)
    assert eng.spill_tier is not None
    assert eng._init_kw["spill"] is True
    # respawn (supervisor crash recovery) reproduces the tier
    eng2 = eng.respawn()
    assert eng2.spill_tier is not None
    # reset_cache drops the spilled entries too (benchmark hygiene)
    b = eng.kv.alloc(1)
    assert eng.spill_tier.demote((1, 2, 3), b[0], 0)
    assert eng.spilled_blocks == 1
    eng.reset_cache()
    assert eng.spilled_blocks == 0
    eng.kv.release(b)
    # cache-off engines never get a tier, even with spill requested
    off = ContinuousEngine(cfg, params, spill=True, prefix_cache=False,
                           **_KW)
    assert off.spill_tier is None
    # the env default wires the tier when spill= is not passed
    monkeypatch.setenv("REPRO_SPILL_ENABLE", "1")
    monkeypatch.setenv("REPRO_SPILL_BLOCKS", "7")
    env_eng = ContinuousEngine(cfg, params, **_KW)
    assert env_eng.spill_tier is not None
    assert env_eng.spill_tier.capacity_blocks == 7

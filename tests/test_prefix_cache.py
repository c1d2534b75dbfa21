"""Radix prefix cache + refcounted COW blocks + session API tests.

Covers the PR-2 tentpole acceptance criteria:
  * refcount invariants: conservation with shared blocks, no double-free
    via release, free() rejects shared blocks;
  * radix tree match/insert semantics: block-granular walk, partial-tail
    match, content dedup on insert, LRU eviction order, prefix property
    (a parent is never evicted before its children);
  * COW fork isolation: a writer diverging inside a shared block never
    mutates the cached copy (later readers of the original prefix still
    match byte-identically);
  * engine oracle parity: greedy outputs byte-identical with the prefix
    cache ON vs OFF, including duplicate prompts, mid-block divergence,
    chunked prefill, and eviction under pool pressure;
  * chunked prefill actually interleaves with decode steps;
  * AgentSession: per-turn reuse, pinning, clean teardown;
  * hybrid family in the ContinuousEngine: per-slot mamba2 reset on
    admission, byte-identical to the static oracle;
  * RolloutEngine.generate_batch: engine-backed rollouts share the system
    prompt prefill and record TITO fragments with logprobs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.obs.trace import Tracer
from repro.serving import (AgentSession, CacheFull, ContinuousEngine,
                           PagedKVCache, PrefixCache, Request, RequestShed,
                           ServingEngine)


def _tiny_gqa():
    return get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None)


@pytest.fixture(scope="module")
def gqa_setup():
    cfg = _tiny_gqa()
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# refcount invariants (no model)
# ---------------------------------------------------------------------------

def test_refcount_conservation_with_shared_blocks():
    kv = PagedKVCache(num_blocks=8, block_size=4)
    a = kv.alloc(3)
    kv.retain(a)                       # second reader of a shared prefix
    kv.retain([a[0]])                  # third reader of the first block
    assert [kv.refcount(b) for b in a] == [3, 2, 2]
    assert kv.free_blocks + kv.used_blocks == kv.num_blocks
    kv.release(a)                      # reader 2 leaves: nothing freed
    assert kv.used_blocks == 3 and kv.free_blocks == 5
    kv.release(a)                      # reader 1 leaves: a[1], a[2] freed
    assert kv.used_blocks == 1 and kv.refcount(a[0]) == 1
    kv.release([a[0]])
    assert kv.free_blocks == kv.num_blocks and kv.used_blocks == 0


def test_release_rejects_double_and_foreign_free():
    kv = PagedKVCache(num_blocks=4, block_size=4)
    a = kv.alloc(2)
    kv.release(a)
    with pytest.raises(ValueError):        # double release == double free
        kv.release(a)
    with pytest.raises(ValueError):        # never-allocated block
        kv.release([99])
    with pytest.raises(ValueError):        # duplicates within one call
        b = kv.alloc(1)
        kv.release([b[0], b[0]])


def test_free_rejects_shared_blocks():
    kv = PagedKVCache(num_blocks=4, block_size=4)
    a = kv.alloc(2)
    kv.retain(a)
    with pytest.raises(ValueError):        # free() requires exclusivity
        kv.free(a)
    assert [kv.refcount(b) for b in a] == [2, 2]   # atomic: untouched
    kv.release(a)
    kv.free(a)                             # now exclusively held: ok
    assert kv.free_blocks == kv.num_blocks


# ---------------------------------------------------------------------------
# radix tree semantics (no model)
# ---------------------------------------------------------------------------

def _insert_seq(cache, kv, tokens):
    """Allocate covering blocks and insert ``tokens`` as a retired seq."""
    n = kv.blocks_for(len(tokens))
    blocks = kv.alloc(n)
    cache.insert(tokens, blocks)
    return blocks


def test_radix_match_full_partial_and_dedup():
    kv = PagedKVCache(num_blocks=16, block_size=4)
    cache = PrefixCache(kv)
    _insert_seq(cache, kv, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10])  # 2 full + [9,10]
    assert cache.cached_blocks == 3

    m, blocks = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12])
    assert m == 10 and len(blocks) == 3    # 2 full blocks + partial tail
    assert all(kv.refcount(b) == 2 for b in blocks)   # retained for caller
    kv.release(blocks)

    m, blocks = cache.match([1, 2, 3, 4, 5, 6, 9, 9])  # diverges mid-block 2
    assert m == 6 and len(blocks) == 2     # full block + 2-token overlap
    kv.release(blocks)

    m, blocks = cache.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 9])  # partial tail
    assert m == 9 and len(blocks) == 3     # overlaps [9, 10] by one token
    kv.release(blocks)

    m, blocks = cache.match([7, 7, 7])                # cold prompt
    assert m == 0 and blocks == []

    # re-inserting identical content deduplicates: blocks released, not kept
    free_before = kv.free_blocks
    dup = _insert_seq(cache, kv, [1, 2, 3, 4, 5, 6, 7, 8])
    assert cache.cached_blocks == 3 and kv.free_blocks == free_before
    assert all(kv.refcount(b) == 0 for b in dup)      # returned to free list


def test_radix_lru_eviction_order_and_prefix_property():
    kv = PagedKVCache(num_blocks=4, block_size=4)
    cache = PrefixCache(kv)
    _insert_seq(cache, kv, list(range(0, 8)))      # chain A: 2 blocks
    _insert_seq(cache, kv, list(range(100, 108)))  # chain B: 2 blocks
    assert kv.free_blocks == 0
    m, blocks = cache.match(list(range(0, 8)))     # touch chain A (MRU)
    kv.release(blocks)

    # evict 1: must take chain B's LEAF (LRU), never a parent-with-child
    assert cache.evict(1) == 1
    m, blocks = cache.match(list(range(100, 108)))
    assert m == 4                                  # B's root block survives
    kv.release(blocks)
    # evict the rest of B, then A tail-first
    assert cache.evict(10) == 3
    assert cache.cached_blocks == 0
    assert kv.free_blocks == kv.num_blocks


def test_radix_eviction_skips_referenced_blocks():
    kv = PagedKVCache(num_blocks=4, block_size=4)
    cache = PrefixCache(kv)
    _insert_seq(cache, kv, list(range(8)))
    m, held = cache.match(list(range(8)))          # a reader holds refs
    assert cache.evict(10) == 0                    # nothing evictable
    kv.release(held)
    assert cache.evict(10) == 2                    # now the chain unwinds


def test_alloc_evicts_cached_blocks_instead_of_cachefull():
    kv = PagedKVCache(num_blocks=4, block_size=4)
    cache = PrefixCache(kv)
    _insert_seq(cache, kv, list(range(16)))        # cache fills the pool
    assert kv.free_blocks == 0
    got = kv.alloc(3)                              # evicts LRU tail blocks
    assert len(got) == 3 and cache.cached_blocks == 1
    with pytest.raises(CacheFull):                 # 1 cached + 0 free < 2
        kv.alloc(2)


# ---------------------------------------------------------------------------
# engine oracle parity: cache ON == cache OFF, byte-identical greedy
# ---------------------------------------------------------------------------

def _shared_prefix_workload(cfg, rng):
    sys_p = rng.integers(3, cfg.vocab_size, size=21).astype(np.int32)
    prompts = [np.concatenate([
        sys_p, rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (5, 9, 3, 13)]
    prompts.append(prompts[0].copy())              # exact duplicate prompt
    prompts.append(rng.integers(3, cfg.vocab_size, size=7).astype(np.int32))
    maxnew = [4, 6, 3, 5, 4, 2]
    return [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]


def _clone(reqs):
    return [Request(prompt=r.prompt, max_new=r.max_new,
                    temperature=r.temperature) for r in reqs]


@pytest.mark.parametrize("chunk", [None, 8])
def test_engine_parity_cache_on_vs_off(gqa_setup, chunk):
    cfg, params = gqa_setup
    reqs = _shared_prefix_workload(cfg, np.random.default_rng(1))
    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    oreqs = _clone(reqs)
    oracle.serve(oreqs)

    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=32, max_len=64, prefill_chunk=chunk)
    served = _clone(reqs)
    eng.serve(served)
    for r, o in zip(served, oreqs):
        np.testing.assert_array_equal(r.out, o.out)
    # reuse + COW actually happened (incl. duplicate-prompt full-hit path,
    # capped at plen-1 so the first sampled token always has fresh logits)
    assert eng.stats["cached_tokens"] > 0
    assert eng.stats["cow_forks"] > 0
    # conservation: free + cached covers the pool, no sequence refs leak
    assert eng.kv.free_blocks + eng.cached_blocks == eng.kv.num_blocks
    eng.reset_cache()
    assert eng.kv.free_blocks == eng.kv.num_blocks


def test_cow_isolation_original_prefix_survives_divergence(gqa_setup):
    """Writer's divergence never mutates the cached copy: after serving a
    diverging prompt (COW fork mid-block), re-serving the ORIGINAL prompt
    still matches the oracle byte-for-byte."""
    cfg, params = gqa_setup
    rng = np.random.default_rng(3)
    base = rng.integers(3, cfg.vocab_size, size=13).astype(np.int32)
    fork = base.copy()
    fork[10] = (fork[10] + 1) % cfg.vocab_size     # diverge inside block 2
    eng = ContinuousEngine(cfg, params, max_batch=1, block_size=8,
                           num_blocks=16, max_len=64)
    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    for prompt in (base, fork, base, fork):
        r = Request(prompt=prompt, max_new=4)
        o = Request(prompt=prompt, max_new=4)
        eng.serve([r])
        oracle.serve([o])
        np.testing.assert_array_equal(r.out, o.out)
    assert eng.stats["cow_forks"] >= 2


def test_engine_eviction_under_pool_pressure(gqa_setup):
    """Distinct prompts churn through a pool smaller than their union: the
    radix LRU must evict instead of raising CacheFull, and results stay
    correct."""
    cfg, params = gqa_setup
    rng = np.random.default_rng(4)
    eng = ContinuousEngine(cfg, params, max_batch=1, block_size=8,
                           num_blocks=8, max_len=64)
    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    for _ in range(6):
        p = rng.integers(3, cfg.vocab_size, size=17).astype(np.int32)
        r, o = Request(prompt=p, max_new=4), Request(prompt=p, max_new=4)
        eng.serve([r])
        oracle.serve([o])
        np.testing.assert_array_equal(r.out, o.out)
    assert eng.prefix.stats["evictions"] > 0
    assert eng.kv.free_blocks + eng.cached_blocks == eng.kv.num_blocks


def test_chunked_prefill_interleaves_with_decode(gqa_setup):
    """A long prompt admitted mid-flight is prefilled in chunks WHILE the
    resident sequence keeps decoding — the same step advances both."""
    cfg, params = gqa_setup
    rng = np.random.default_rng(5)
    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=32, max_len=128, prefill_chunk=8,
                           prefix_cache=False)
    eng.submit(Request(prompt=rng.integers(3, cfg.vocab_size, size=9).astype(
        np.int32), max_new=12))
    eng.submit(Request(prompt=rng.integers(3, cfg.vocab_size, size=57).astype(
        np.int32), max_new=2))
    both = 0
    while eng.waiting or any(s is not None for s in eng.slots):
        before = (eng.stats["chunk_steps"], eng.stats["decode_tokens"])
        eng.step()
        chunked = eng.stats["chunk_steps"] - before[0]
        decoded = eng.stats["decode_tokens"] - before[1]
        if chunked and decoded:
            both += 1
    assert both >= 3        # 57-token prompt = several chunks, all overlapped


# ---------------------------------------------------------------------------
# agent sessions
# ---------------------------------------------------------------------------

def test_agent_session_reuses_history_and_matches_oracle(gqa_setup):
    cfg, params = gqa_setup
    rng = np.random.default_rng(6)
    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=64, max_len=256)
    off = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=64, max_len=256, prefix_cache=False)
    sess = AgentSession(eng)
    conv = []
    for turn in range(4):
        msg = rng.integers(3, cfg.vocab_size, size=9).astype(np.int32)
        out = sess.send(msg, max_new=5)
        ref = Request(prompt=np.asarray(conv + list(msg), np.int32),
                      max_new=5)
        off.serve([ref])
        np.testing.assert_array_equal(out, ref.out)
        conv = conv + list(msg) + list(ref.out)
        if turn > 0:
            # turn N+1 prefills ~the new message, not the whole history
            assert sess.last_turn["cached_tokens"] > 0
            assert sess.last_turn["prefill_tokens"] \
                < sess.last_turn["prompt_tokens"]
        assert sess.pinned_blocks > 0
    sess.close()
    assert sess.pinned_blocks == 0
    eng.reset_cache()
    assert eng.kv.free_blocks == eng.kv.num_blocks


def test_session_pin_survives_eviction_pressure(gqa_setup):
    """A pinned conversation cannot be LRU-evicted: cold traffic that needs
    more blocks than remain is shed with a typed error rather than allowed
    to reclaim the session's history."""
    cfg, params = gqa_setup
    eng = ContinuousEngine(cfg, params, max_batch=1, block_size=8,
                           num_blocks=8, max_len=64)
    sess = AgentSession(eng)
    sess.send(np.arange(3, 19, dtype=np.int32), max_new=4)   # pins blocks
    pinned = sess.pinned_blocks
    assert pinned > 0
    [cold] = eng.serve([Request(prompt=np.full(40, 7, np.int32), max_new=8)])
    assert cold.status == "shed"
    assert isinstance(cold.error, RequestShed) and cold.out is None
    assert sess.pinned_blocks == pinned                      # untouched
    # after the session releases, the same request fits via eviction
    sess.close()
    eng.serve([Request(prompt=np.full(40, 7, np.int32), max_new=8)])


# ---------------------------------------------------------------------------
# hybrid family: per-slot mamba2 reset on admission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [None, 8])
def test_hybrid_continuous_engine_matches_oracle(chunk):
    cfg = get_smoke_config("zamba2_2p7b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, ssm_state=8, dsa=None)
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    plens, maxnew = [5, 17, 9, 12], [3, 6, 4, 5]
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n in plens]
    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    oreqs = [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]
    oracle.serve(oreqs)

    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=24, max_len=64, prefill_chunk=chunk,
                           tracer=Tracer(enabled=True))
    assert eng.prefix is None      # recurrent state cannot be re-aliased
    reqs = [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]
    eng.serve(reqs)
    for r, o in zip(reqs, oreqs):
        np.testing.assert_array_equal(r.out, o.out)
    # 4 requests through 2 slots: slot REUSE (and so mamba2 state reset on
    # admission) must have happened mid-flight
    assert any(e["args"]["step"] > 0
               for e in eng.tracer.export()["traceEvents"]
               if e["name"] == "req.admitted")
    assert eng.kv.free_blocks == eng.kv.num_blocks


# ---------------------------------------------------------------------------
# engine-backed RL rollouts
# ---------------------------------------------------------------------------

def test_rollout_generate_batch_shares_system_prompt(gqa_setup):
    from repro.async_rl.rollout import RolloutEngine
    cfg, params = gqa_setup
    rng = np.random.default_rng(8)
    # float32 engine and oracle: byte parity between two DIFFERENT
    # programs (paged engine vs static gather) is a float32 property; in
    # bf16 no XLA version promises the same greedy tokens
    eng = RolloutEngine(cfg, params, engine_dtype=jnp.float32, seed=0)
    sys_p = rng.integers(3, cfg.vocab_size, size=32)
    prompts = [np.concatenate([
        sys_p, rng.integers(3, cfg.vocab_size, size=6)]).astype(np.int32)
        for _ in range(4)]
    rids = [eng.gateway.new_rollout("bench") for _ in prompts]
    outs = eng.generate_batch(rids, prompts, max_new=5, temperature=0.0,
                              max_batch=2, num_blocks=64, max_len=128)

    f32_params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    oracle = ServingEngine(cfg, f32_params, max_batch=1, max_len=128)
    oreqs = [Request(prompt=p, max_new=5) for p in prompts]
    oracle.serve(oreqs)
    for out, o in zip(outs, oreqs):
        np.testing.assert_array_equal(out, o.out)
    # the shared system prompt was prefilled once, not 4 times
    serving = eng.serving_engine(max_batch=2, num_blocks=64, max_len=128)
    assert serving.stats["cached_tokens"] >= 2 * len(sys_p)
    # geometry is fixed per worker: a mismatched rebuild must fail loudly
    with pytest.raises(ValueError):
        eng.serving_engine(max_batch=4, num_blocks=64, max_len=128)
    # TITO contract: fragments carry tokens + finite behavior logprobs
    for rid, p, out in zip(rids, prompts, outs):
        traj = eng.gateway.finish(rid, "bench", p, reward=0.0)
        np.testing.assert_array_equal(traj.tokens, out)
        assert traj.logprobs.shape == out.shape
        assert np.isfinite(traj.logprobs).all()
        # greedy convention matches generate(): argmax lp ~= 0 (t=1e-6)
        assert np.allclose(traj.logprobs, 0.0, atol=1e-3)
        assert traj.versions == [0]

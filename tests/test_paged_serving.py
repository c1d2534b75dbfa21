"""Paged KV cache + continuous-batching scheduler tests.

Covers the PR-1 tentpole acceptance criteria:
  * PagedKVCache alloc/free invariants (conservation, double-alloc/-free,
    capacity error path);
  * block-table gather == contiguous cache on random fill patterns;
  * continuous engine greedy outputs byte-identical to a one-request-at-a-
    time oracle on a mixed-length workload, including requests admitted
    mid-flight;
  * DSA sparse decode through the paged cache matches the token-selector
    path on a contiguous cache within fp32 tolerance;
  * hybrid (mamba2 + shared attention) paged decode parity;
  * pd_sim: static lock-step batching degrades tail latency vs continuous.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DSAConfig
from repro.core.paging import blocks_for, paged_update, paged_view
from repro.models import get_model
from repro.obs.trace import Tracer
from repro.serving import (CacheFull, ContinuousEngine, PagedKVCache,
                           Request, ServingEngine)


def _tiny_gqa(dsa=False):
    cfg = get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256,
        dsa=DSAConfig(index_heads=2, index_head_dim=16, top_k=32,
                      block_size=16) if dsa else None)
    return cfg


@pytest.fixture(scope="module")
def gqa_setup():
    cfg = _tiny_gqa(dsa=False)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_paged_alloc_free_invariants():
    kv = PagedKVCache(num_blocks=8, block_size=16)
    a = kv.alloc(3)
    b = kv.alloc(5)
    assert sorted(a + b) == list(range(8))          # no double-allocation
    assert kv.free_blocks == 0 and kv.used_blocks == 8
    with pytest.raises(CacheFull):                  # capacity error path
        kv.alloc(1)
    kv.free(a)
    assert kv.free_blocks == 3 and kv.used_blocks == 5
    with pytest.raises(ValueError):                 # double free
        kv.free(a)
    with pytest.raises(ValueError):                 # foreign block
        kv.free([99])
    c = kv.alloc(3)
    assert sorted(c) == sorted(a)                   # recycled, not invented
    # conservation after churn: every block accounted for exactly once
    kv.free(b)
    kv.free(c)
    assert kv.free_blocks == kv.num_blocks and kv.used_blocks == 0
    assert kv.blocks_for(1) == 1 and kv.blocks_for(16) == 1
    assert kv.blocks_for(17) == 2


def test_blocks_for():
    assert blocks_for(0, 8) == 1       # even an empty prompt owns a block
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2


# ---------------------------------------------------------------------------
# gather/scatter parity with a contiguous cache
# ---------------------------------------------------------------------------

def test_block_table_gather_matches_contiguous_random_fill():
    rng = np.random.default_rng(0)
    B, mb, bs, H, dh = 3, 4, 8, 2, 16
    T = mb * bs
    contiguous = rng.standard_normal((B, T, H, dh)).astype(np.float32)
    # disjoint shuffled blocks per sequence + one trash block at the end
    nb = B * mb + 1
    ids = rng.permutation(nb - 1)
    tables = jnp.asarray(ids[:B * mb].reshape(B, mb).astype(np.int32))
    pool = jnp.zeros((nb, bs, H, dh), jnp.float32)
    # write in a RANDOM order of position chunks (fill pattern stress)
    order = rng.permutation(T)
    for start in range(0, T, 8):
        pos = np.sort(order[start:start + 8])
        positions = jnp.asarray(np.tile(pos, (B, 1)).astype(np.int32))
        pool = paged_update(pool, jnp.asarray(contiguous[:, pos]),
                            tables, positions)
    view = paged_view(pool, tables)
    np.testing.assert_array_equal(np.asarray(view), contiguous)


# ---------------------------------------------------------------------------
# continuous engine vs one-at-a-time oracle
# ---------------------------------------------------------------------------

def test_continuous_engine_matches_oracle_mixed_lengths(gqa_setup):
    cfg, params = gqa_setup
    rng = np.random.default_rng(1)
    plens = [5, 17, 9, 33, 1, 26]
    maxnew = [3, 9, 5, 12, 1, 7]       # heterogeneous max_new incl. 1
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n in plens]

    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=24, max_len=64,
                           tracer=Tracer(enabled=True))
    reqs = [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]
    eng.serve(reqs)

    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    oreqs = [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]
    oracle.serve(oreqs)

    for r, o in zip(reqs, oreqs):
        np.testing.assert_array_equal(r.out, o.out)   # byte-identical greedy
    # 6 requests through 2 slots: some admissions MUST happen mid-flight
    assert any(e["args"]["step"] > 0
               for e in eng.tracer.export()["traceEvents"]
               if e["name"] == "req.admitted")
    # conservation: every block is either free or held by the radix prefix
    # cache (retired sequences donate their blocks to it by default)
    assert eng.kv.free_blocks + eng.cached_blocks == eng.kv.num_blocks


def test_continuous_engine_per_request_temperature(gqa_setup):
    cfg, params = gqa_setup
    rng = np.random.default_rng(2)
    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=16, max_len=64, seed=3)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, size=n).astype(
        np.int32), max_new=m, temperature=t)
        for n, m, t in [(6, 4, 0.0), (11, 6, 1.0), (4, 2, 0.7)]]
    eng.serve(reqs)
    for r in reqs:
        assert r.out is not None and len(r.out) == r.max_new
        assert ((0 <= r.out) & (r.out < cfg.vocab_size)).all()


def test_continuous_engine_rejects_oversized_request(gqa_setup):
    cfg, params = gqa_setup
    eng = ContinuousEngine(cfg, params, max_batch=1, block_size=8,
                           num_blocks=4, max_len=32)
    with pytest.raises(ValueError):    # exceeds max_len (table width)
        eng.submit(Request(prompt=np.arange(30, dtype=np.int32), max_new=8))
    # fits the table but not the pool -> capacity error, not a hang
    eng2 = ContinuousEngine(cfg, params, max_batch=1, block_size=8,
                            num_blocks=2, max_len=64)
    with pytest.raises(CacheFull):
        eng2.submit(Request(prompt=np.arange(20, dtype=np.int32),
                            max_new=12))


# ---------------------------------------------------------------------------
# DSA sparse decode through the paged cache
# ---------------------------------------------------------------------------

def test_dsa_paged_decode_matches_contiguous():
    cfg = _tiny_gqa(dsa=True)
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    B, plen, steps, bs, mb = 2, 11, 4, 8, 6
    toks = rng.integers(3, cfg.vocab_size, size=(B, plen)).astype(np.int32)

    cache, _ = model.init_cache(cfg, B, mb * bs)
    lg_c, cache = model.prefill(params, jnp.asarray(toks), cfg, cache)

    pool, _ = model.init_paged_cache(cfg, B * mb + 1, bs)
    ids = rng.permutation(B * mb)      # shuffled block assignment
    tables = jnp.asarray(ids.reshape(B, mb).astype(np.int32))
    lg_p, pool = model.prefill(params, jnp.asarray(toks), cfg, pool,
                               block_tables=tables,
                               cache_index=jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p[:, plen - 1:plen]),
                               rtol=1e-5, atol=1e-5)

    tok = jnp.argmax(lg_c[:, -1], -1)[:, None].astype(jnp.int32)
    lengths = jnp.full((B,), plen, jnp.int32)
    for t in range(steps):
        lg_c, cache = model.decode_step(params, tok, cfg, cache,
                                        jnp.asarray(plen + t, jnp.int32))
        lg_p, pool = model.decode_step(params, tok, cfg, pool, lengths,
                                       block_tables=tables)
        # sparse (token-selector) decode: paged == contiguous in fp32
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                                   rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(lg_c[:, -1], -1)[:, None].astype(jnp.int32)
        lengths = lengths + 1


# ---------------------------------------------------------------------------
# hybrid family: paged shared-attention KV + per-slot ssm state
# ---------------------------------------------------------------------------

def test_hybrid_paged_decode_matches_contiguous():
    cfg = get_smoke_config("zamba2_2p7b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, ssm_state=8, dsa=None)
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(4)
    B, plen, steps, bs, mb = 2, 9, 3, 8, 4
    toks = rng.integers(3, cfg.vocab_size, size=(B, plen)).astype(np.int32)

    cache, _ = model.init_cache(cfg, B, mb * bs)
    lg_c, cache = model.prefill(params, jnp.asarray(toks), cfg, cache)

    pool, _ = model.init_paged_cache(cfg, B * mb + 1, bs, batch=B)
    ids = rng.permutation(B * mb)
    tables = jnp.asarray(ids.reshape(B, mb).astype(np.int32))
    lg_p, pool = model.prefill(params, jnp.asarray(toks), cfg, pool,
                               block_tables=tables,
                               cache_index=jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_c),
                               np.asarray(lg_p[:, plen - 1:plen]),
                               rtol=1e-5, atol=1e-5)

    tok = jnp.argmax(lg_c[:, -1], -1)[:, None].astype(jnp.int32)
    lengths = jnp.full((B,), plen, jnp.int32)
    for t in range(steps):
        lg_c, cache = model.decode_step(params, tok, cfg, cache,
                                        jnp.asarray(plen + t, jnp.int32))
        lg_p, pool = model.decode_step(params, tok, cfg, pool, lengths,
                                       block_tables=tables)
        np.testing.assert_allclose(np.asarray(lg_c), np.asarray(lg_p),
                                   rtol=1e-5, atol=1e-5)
        tok = jnp.argmax(lg_c[:, -1], -1)[:, None].astype(jnp.int32)
        lengths = lengths + 1


# ---------------------------------------------------------------------------
# pd_sim: static lock-step batching hurts the tail
# ---------------------------------------------------------------------------

def test_pd_sim_static_batching_degrades_latency():
    from repro.serving.pd_sim import ServingConfig, Workload, simulate
    w = Workload(n_rollouts=48, turns=2)
    cont = simulate(w, ServingConfig(pd_disaggregated=True,
                                     continuous_batching=True), seed=0)
    stat = simulate(w, ServingConfig(pd_disaggregated=True,
                                     continuous_batching=False,
                                     decode_batch=8), seed=0)
    assert cont["p99_s"] <= stat["p99_s"]
    assert cont["mean_s"] < stat["mean_s"]

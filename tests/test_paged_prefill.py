"""Paged flash-prefill kernel + scan-invariant pool tests (PR-4 tentpole).

Covers the acceptance criteria:
  * prefill-kernel-vs-ref parity (GQA incl. window/softcap, MLA absorbed
    latent, DSA span indexer scores) on RAGGED START OFFSETS — start 0,
    mid-block, exact block boundary — via BOTH in-place impls (Pallas
    interpret mode and the XLA blocked twin) against the gather oracle;
  * chunked-vs-whole-suffix greedy byte-parity through the engine for all
    four families (incl. radix-cached suffixes starting mid-block), and
    in-place-vs-ref prefill byte-parity under chunking;
  * the scan-invariant pool: a scanned decode step computes the same
    math as the contiguous cache (its donated-pool aliasing is checked on
    the v5e compile in tests/test_tpu_compile.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DSAConfig
from repro.kernels.paged_attention import ref as pref
from repro.kernels.paged_attention.ops import (_blocked_gqa_prefill,
                                               _blocked_indexer_prefill,
                                               _blocked_mla_prefill)
from repro.kernels.paged_attention.kernel import indexer_span_scores
from repro.kernels.paged_attention.prefill import (paged_prefill_gqa,
                                                   paged_prefill_mla)
from repro.models import get_model
from repro.serving import ContinuousEngine, Request


def _pool_setup(rng, B, mb, bs, feat):
    nb = B * mb + 1
    pool = jnp.asarray(rng.standard_normal((nb, bs) + feat), jnp.float32)
    ids = rng.permutation(nb - 1)
    tables = jnp.asarray(ids[:B * mb].reshape(B, mb).astype(np.int32))
    return pool, tables


# ragged start offsets: fresh sequence (0), mid-block, EXACT block
# boundary, one-off-boundary, deep in the table
def _ragged_starts(B, mb, bs, S):
    starts = [0, bs - 1, bs, 2 * bs + 3, (mb - 1) * bs - S]
    return jnp.asarray((starts * B)[:B], jnp.int32)


# ---------------------------------------------------------------------------
# prefill kernel vs ref parity on ragged start offsets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,softcap", [(0, 0.0), (0, 30.0), (5, 0.0)])
def test_gqa_prefill_matches_gather_ref(window, softcap):
    rng = np.random.default_rng(0)
    B, KVH, G, d, bs, mb, S = 5, 2, 2, 32, 8, 6, 5
    kp, tables = _pool_setup(rng, B, mb, bs, (KVH, d))
    vp, _ = _pool_setup(rng, B, mb, bs, (KVH, d))
    q = jnp.asarray(rng.standard_normal((B, S, KVH * G, d)), jnp.float32)
    starts = _ragged_starts(B, mb, bs, S)
    ref = np.asarray(pref.paged_gqa_prefill_reference(
        q, kp, vp, tables, starts, window=window, softcap=softcap))
    qg = q.reshape(B, S, KVH, G, d)
    out_b = np.asarray(_blocked_gqa_prefill(
        qg, kp, vp, tables, starts, window=window, softcap=softcap)
    ).reshape(B, S, KVH * G, d)
    qp = jnp.asarray(qg.transpose(0, 2, 1, 3, 4).reshape(B, KVH, S * G, d))
    out_k = np.asarray(paged_prefill_gqa(
        qp, kp, vp, tables, starts, groups=G, window=window,
        softcap=softcap, interpret=True))
    out_k = out_k.reshape(B, KVH, S, G, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, KVH * G, d)
    np.testing.assert_allclose(out_b, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out_k, ref, atol=2e-5, rtol=2e-5)


def test_mla_prefill_matches_gather_ref():
    rng = np.random.default_rng(1)
    B, H, L, R, bs, mb, S = 5, 4, 16, 8, 8, 6, 5
    cp, tables = _pool_setup(rng, B, mb, bs, (L,))
    krp, _ = _pool_setup(rng, B, mb, bs, (R,))
    ql = jnp.asarray(rng.standard_normal((B, S, H, L)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, S, H, R)), jnp.float32)
    starts = _ragged_starts(B, mb, bs, S)
    ref = np.asarray(pref.paged_mla_prefill_reference(
        ql, qr, cp, krp, tables, starts, scale=0.17))
    out_b = np.asarray(_blocked_mla_prefill(ql, qr, cp, krp, tables,
                                            starts, scale=0.17))
    out_k = np.asarray(paged_prefill_mla(
        ql.reshape(B, S * H, L), qr.reshape(B, S * H, R), cp, krp, tables,
        starts, heads=H, scale=0.17, interpret=True)).reshape(B, S, H, L)
    np.testing.assert_allclose(out_b, ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out_k, ref, atol=2e-5, rtol=2e-5)


def test_indexer_prefill_matches_on_live_positions():
    rng = np.random.default_rng(2)
    B, Hi, Di, bs, mb, S = 5, 2, 16, 8, 6, 5
    kp, tables = _pool_setup(rng, B, mb, bs, (Di,))
    qi = jnp.asarray(rng.standard_normal((B, S, Hi, Di)), jnp.float32)
    w = jnp.asarray(jax.nn.softmax(
        jnp.asarray(rng.standard_normal((B, S, Hi))), -1), jnp.float32)
    starts = _ragged_starts(B, mb, bs, S)
    s_ref = np.asarray(pref.paged_indexer_prefill_reference(
        qi, w, kp, tables, starts))
    s_b = np.asarray(_blocked_indexer_prefill(qi, w, kp, tables, starts))
    s_k = np.asarray(indexer_span_scores(
        qi.reshape(B, S * Hi, Di), w.reshape(B, S * Hi), kp, tables,
        starts, heads=Hi, interpret=True))
    # the selector only reads positions <= each query's position; the
    # in-place impls must match there and dead blocks must sort last
    qpos = np.asarray(starts)[:, None] + np.arange(S)[None]
    live = np.arange(mb * bs)[None, None, :] <= qpos[:, :, None]
    np.testing.assert_allclose(np.where(live, s_b, 0.0),
                               np.where(live, s_ref, 0.0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.where(live, s_k, 0.0),
                               np.where(live, s_ref, 0.0),
                               atol=2e-5, rtol=2e-5)
    dead_block = (np.arange(mb * bs)[None] // bs) \
        > ((np.asarray(starts) + S - 1)[:, None] // bs)
    assert (s_k[dead_block[:, None, :].repeat(S, 1)] <= -1e29).all()


@pytest.mark.parametrize("S", [16, 13], ids=["whole_tiles", "padded"])
@pytest.mark.parametrize("family", ["gqa", "mla", "indexer"])
def test_prefill_query_tiles_match_gather_ref(family, S, monkeypatch):
    """A span longer than one query tile: the kernels walk the block table
    once per tile, each tile with its own causal range; a span that is not
    a whole number of tiles is padded to one."""
    from repro.kernels.paged_attention import kernel as pk
    from repro.kernels.paged_attention import prefill as pp
    monkeypatch.setattr(pp, "MAX_TILE_ROWS", 16)
    monkeypatch.setattr(pk, "INDEXER_TILE_ROWS", 16)
    rng = np.random.default_rng(3)
    B, bs, mb = 5, 8, 6
    # the last span ends at the table's end: its padding runs past it
    starts = _ragged_starts(B, mb, bs, S).at[-1].set(mb * bs - S)
    if family == "gqa":
        KVH, G, d = 2, 2, 16        # 8-token tiles
        kp, tables = _pool_setup(rng, B, mb, bs, (KVH, d))
        vp, _ = _pool_setup(rng, B, mb, bs, (KVH, d))
        q = jnp.asarray(rng.standard_normal((B, S, KVH * G, d)), jnp.float32)
        want = pref.paged_gqa_prefill_reference(q, kp, vp, tables, starts)
        qp = q.reshape(B, S, KVH, G, d).transpose(0, 2, 1, 3, 4) \
            .reshape(B, KVH, S * G, d)
        got = paged_prefill_gqa(qp, kp, vp, tables, starts, groups=G,
                                interpret=True)
        got = got.reshape(B, KVH, S, G, d).transpose(0, 2, 1, 3, 4) \
            .reshape(B, S, KVH * G, d)
        live = True
    elif family == "mla":
        H, L, R = 2, 16, 8          # 8-token tiles
        cp, tables = _pool_setup(rng, B, mb, bs, (L,))
        krp, _ = _pool_setup(rng, B, mb, bs, (R,))
        ql = jnp.asarray(rng.standard_normal((B, S, H, L)), jnp.float32)
        qr = jnp.asarray(rng.standard_normal((B, S, H, R)), jnp.float32)
        want = pref.paged_mla_prefill_reference(ql, qr, cp, krp, tables,
                                                starts, scale=0.17)
        got = paged_prefill_mla(
            ql.reshape(B, S * H, L), qr.reshape(B, S * H, R), cp, krp,
            tables, starts, heads=H, scale=0.17,
            interpret=True).reshape(B, S, H, L)
        live = True
    else:
        Hi, Di = 2, 16              # 8-token tiles
        kp, tables = _pool_setup(rng, B, mb, bs, (Di,))
        qi = jnp.asarray(rng.standard_normal((B, S, Hi, Di)), jnp.float32)
        w = jax.nn.softmax(jnp.asarray(rng.standard_normal((B, S, Hi)),
                                       jnp.float32), -1)
        want = pref.paged_indexer_prefill_reference(qi, w, kp, tables,
                                                    starts)
        got = indexer_span_scores(qi.reshape(B, S * Hi, Di),
                                  w.reshape(B, S * Hi), kp, tables,
                                  starts, heads=Hi, interpret=True)
        qpos = np.asarray(starts)[:, None] + np.arange(S)[None]
        live = np.arange(mb * bs)[None, None, :] <= qpos[:, :, None]
    assert pk.query_tile(S, 2, 16) == 8       # every family: two tiles
    np.testing.assert_allclose(np.where(live, np.asarray(got), 0.0),
                               np.where(live, np.asarray(want), 0.0),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# engine: chunked vs whole-suffix byte-parity, all four families
# ---------------------------------------------------------------------------

_KW = dict(max_batch=2, block_size=8, num_blocks=32, max_len=64)


def _family_cfg(name):
    if name == "gqa" or name == "dsa":
        return get_smoke_config("yi_6b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=256,
            dsa=DSAConfig(index_heads=2, index_head_dim=16, top_k=32,
                          block_size=16) if name == "dsa" else None)
    if name == "mla":
        return get_smoke_config("glm5_744b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, d_ff=256,
            vocab_size=256, num_experts=0, num_shared_experts=0, mtp=None,
            first_k_dense=1)
    return get_smoke_config("zamba2_2p7b").replace(      # hybrid
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, ssm_state=8, dsa=None)


def _serve_shared_prefix(cfg, params, impl, chunk):
    """Two sequential requests sharing an 11-token prefix: the second one
    (prefix cache permitting) prefills ONLY a suffix starting mid-block."""
    rng = np.random.default_rng(7)
    shared = rng.integers(3, cfg.vocab_size, size=11).astype(np.int32)
    tails = [rng.integers(3, cfg.vocab_size, size=k).astype(np.int32)
             for k in (3, 6)]
    eng = ContinuousEngine(cfg, params, attn_impl=impl, prefill_chunk=chunk,
                           **_KW)
    reqs = [Request(prompt=np.concatenate([shared, t]), max_new=4)
            for t in tails]
    for r in reqs:                      # sequential: 2nd hits the prefix
        eng.serve([r])
    return [r.out for r in reqs], eng


@pytest.mark.parametrize("family", ["gqa", "dsa", "mla", "hybrid"])
def test_engine_chunked_vs_whole_suffix_byte_identical(family):
    cfg = _family_cfg(family)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    o_whole, _ = _serve_shared_prefix(cfg, params, "pallas", None)
    o_chunk, _ = _serve_shared_prefix(cfg, params, "pallas", 8)
    o_ref, _ = _serve_shared_prefix(cfg, params, "ref", None)
    for a, b, c in zip(o_whole, o_chunk, o_ref):
        np.testing.assert_array_equal(a, b)     # chunked == whole suffix
        np.testing.assert_array_equal(a, c)     # in-place == gather oracle


# ---------------------------------------------------------------------------
# scan-invariant pool
# ---------------------------------------------------------------------------

def _scanned_cfg():
    # first_k_dense=0: every layer rides the layer lax.scan
    return get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None, num_layers=2, first_k_dense=0)


def test_scanned_decode_matches_contiguous():
    """Layer-major flat pool + offset tables compute the same math as the
    contiguous cache on a scanned config (paged parity beyond the
    first_k_dense configs the decode suite already covers)."""
    cfg = _scanned_cfg()
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(5)
    B, plen, steps, bs, mb = 2, 11, 3, 8, 6
    toks = rng.integers(3, cfg.vocab_size, size=(B, plen)).astype(np.int32)
    cache, _ = model.init_cache(cfg, B, mb * bs)
    lg_c, cache = model.prefill(params, jnp.asarray(toks), cfg, cache)
    pool, _ = model.init_paged_cache(cfg, B * mb + 1, bs)
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

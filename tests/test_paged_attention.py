"""Paged-attention decode kernel tests (PR-3 tentpole).

Covers the acceptance criteria:
  * kernel-vs-ref parity (GQA incl. window/softcap, MLA absorbed latent,
    DSA indexer scores) on ragged lengths including len==1 and
    len==block_size boundaries, via BOTH in-place impls (Pallas interpret
    mode and the XLA blocked twin) against the gather oracle;
  * trash-block isolation: garbage scattered into the reserved trash block
    never leaks into live sequences' outputs;
  * COW ``copy_block`` parity with the old whole-pool ``at[].set`` copy,
    plus engine-level fork refcount/aliasing behavior;
  * engine greedy byte-parity old-gather (attn_impl='ref') vs in-place
    kernel (attn_impl='pallas') for the GQA, DSA, MLA and hybrid families;
  * the re-jitting hazard: decode keeps ONE compilation across
    admit/retire/occupancy changes (compile-count hook on the jit cache).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import DSAConfig
from repro.core.paging import copy_block, paged_take, paged_view
from repro.kernels.paged_attention import ref as pref
from repro.kernels.paged_attention.kernel import (
    paged_decode_gqa, paged_decode_mla, paged_indexer_scores_kernel)
from repro.kernels.paged_attention.ops import (_blocked_gqa, _blocked_mla,
                                               _blocked_indexer)
from repro.models import get_model
from repro.obs.trace import Tracer
from repro.serving import ContinuousEngine, Request


def _pool_setup(rng, B, mb, bs, feat, *, shuffled=True):
    """Random pool + disjoint per-sequence tables (+1 trash block at nb-1)."""
    nb = B * mb + 1
    pool = jnp.asarray(rng.standard_normal((nb, bs) + feat), jnp.float32)
    ids = (rng.permutation(nb - 1) if shuffled else np.arange(nb - 1))
    tables = jnp.asarray(ids[:B * mb].reshape(B, mb).astype(np.int32))
    return pool, tables


# boundary-heavy ragged lengths: 1-token sequence (qpos 0), exactly one
# full block (qpos bs-1), first token of a fresh block (qpos bs), full table
def _ragged_lens(B, mb, bs):
    lens = [0, bs - 1, bs, mb * bs - 1, bs + 3]
    return jnp.asarray((lens * B)[:B], jnp.int32)


# ---------------------------------------------------------------------------
# kernel vs ref parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,softcap", [(0, 0.0), (0, 30.0), (5, 0.0)])
def test_gqa_kernel_matches_gather_ref(window, softcap):
    rng = np.random.default_rng(0)
    B, KVH, G, d, bs, mb = 5, 2, 2, 32, 8, 4
    kp, tables = _pool_setup(rng, B, mb, bs, (KVH, d))
    vp, _ = _pool_setup(rng, B, mb, bs, (KVH, d))
    q = jnp.asarray(rng.standard_normal((B, 1, KVH * G, d)), jnp.float32)
    lens = _ragged_lens(B, mb, bs)
    ref = np.asarray(pref.paged_gqa_reference(
        q, kp, vp, tables, lens, window=window, softcap=softcap))
    ref = ref[:, 0].reshape(B, KVH, G, d)
    qg = q[:, 0].reshape(B, KVH, G, d)
    out_k = paged_decode_gqa(qg, kp, vp, tables, lens, window=window,
                             softcap=softcap, interpret=True)
    out_b = _blocked_gqa(qg, kp, vp, tables, lens, window=window,
                         softcap=softcap)
    np.testing.assert_allclose(np.asarray(out_k), ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_b), ref, atol=2e-5, rtol=2e-5)


def test_mla_kernel_matches_gather_ref():
    rng = np.random.default_rng(1)
    B, H, L, R, bs, mb = 5, 4, 16, 8, 8, 4
    cp, tables = _pool_setup(rng, B, mb, bs, (L,))
    krp, _ = _pool_setup(rng, B, mb, bs, (R,))
    ql = jnp.asarray(rng.standard_normal((B, 1, H, L)), jnp.float32)
    qr = jnp.asarray(rng.standard_normal((B, 1, H, R)), jnp.float32)
    lens = _ragged_lens(B, mb, bs)
    ref = np.asarray(pref.paged_mla_reference(
        ql, qr, cp, krp, tables, lens, scale=0.17))[:, 0]
    out_k = paged_decode_mla(ql[:, 0], qr[:, 0], cp, krp, tables, lens,
                             scale=0.17, interpret=True)
    out_b = _blocked_mla(ql[:, 0], qr[:, 0], cp, krp, tables, lens,
                         scale=0.17)
    np.testing.assert_allclose(np.asarray(out_k), ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_b), ref, atol=2e-5, rtol=2e-5)


def test_indexer_scores_match_on_live_positions():
    rng = np.random.default_rng(2)
    B, Hi, Di, bs, mb = 5, 2, 16, 8, 4
    kp, tables = _pool_setup(rng, B, mb, bs, (Di,))
    qi = jnp.asarray(rng.standard_normal((B, Hi, Di)), jnp.float32)
    w = jnp.asarray(jax.nn.softmax(
        jnp.asarray(rng.standard_normal((B, Hi))), -1), jnp.float32)
    lens = _ragged_lens(B, mb, bs)
    s_ref = np.asarray(pref.paged_indexer_reference(qi, w, kp, tables, lens))
    s_k = np.asarray(paged_indexer_scores_kernel(qi, w, kp, tables, lens,
                                                 interpret=True))
    s_b = np.asarray(_blocked_indexer(qi, w, kp, tables, lens))
    # the selector's causal mask only ever reads positions <= qpos: the
    # in-place impls must match there; dead blocks must sort last (NEG_INF)
    live = np.arange(mb * bs)[None] <= np.asarray(lens)[:, None]
    np.testing.assert_allclose(np.where(live, s_k, 0.0),
                               np.where(live, s_ref, 0.0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.where(live, s_b, 0.0),
                               np.where(live, s_ref, 0.0),
                               atol=2e-5, rtol=2e-5)
    # per-row dead blocks sort last under the kernel; the blocked twin's
    # shared loop bound only guarantees that beyond the batch-max length
    # (everything in between is excluded by the selector's mask anyway)
    dead_block = (np.arange(mb * bs)[None] // bs) \
        > (np.asarray(lens)[:, None] // bs)
    assert (s_k[dead_block] <= -1e29).all()
    beyond_max = np.arange(mb * bs) // bs > int(np.asarray(lens).max()) // bs
    assert (s_b[:, beyond_max] <= -1e29).all()


def test_paged_take_matches_view_gather():
    rng = np.random.default_rng(3)
    B, bs, mb, f = 3, 8, 4, 5
    pool, tables = _pool_setup(rng, B, mb, bs, (f,))
    idx = jnp.asarray(rng.integers(0, mb * bs, size=(B, 7)).astype(np.int32))
    view = paged_view(pool, tables)
    want = np.take_along_axis(np.asarray(view),
                              np.asarray(idx)[..., None], axis=1)
    got = np.asarray(paged_take(pool, tables, idx))
    np.testing.assert_array_equal(got, want)


def test_trash_block_isolation():
    """Garbage in the trash block (idle slots' scatter target) must not
    perturb live rows — the kernels clamp dead table walks to live blocks
    and the masks zero out anything beyond each row's length."""
    rng = np.random.default_rng(4)
    B, KVH, G, d, bs, mb = 3, 2, 2, 16, 8, 4
    kp, tables = _pool_setup(rng, B, mb, bs, (KVH, d))
    vp, _ = _pool_setup(rng, B, mb, bs, (KVH, d))
    q = jnp.asarray(rng.standard_normal((B, 1, KVH * G, d)), jnp.float32)
    lens = jnp.asarray([3, bs, 2 * bs + 1], jnp.int32)
    trash = kp.shape[0] - 1                 # no table row points at it
    qg = q[:, 0].reshape(B, KVH, G, d)
    outs = {}
    for fill in (0.0, 1e6):
        kf = kp.at[trash].set(fill)
        vf = vp.at[trash].set(fill)
        outs[fill] = (np.asarray(paged_decode_gqa(qg, kf, vf, tables, lens,
                                                  interpret=True)),
                      np.asarray(_blocked_gqa(qg, kf, vf, tables, lens,
                                              window=0, softcap=0.0)))
    np.testing.assert_array_equal(outs[0.0][0], outs[1e6][0])
    np.testing.assert_array_equal(outs[0.0][1], outs[1e6][1])
    assert np.isfinite(outs[1e6][0]).all()


# ---------------------------------------------------------------------------
# COW copy_block
# ---------------------------------------------------------------------------

def test_copy_block_matches_whole_pool_copy():
    rng = np.random.default_rng(5)
    flat = jnp.asarray(rng.standard_normal((6, 8, 2, 4)), jnp.float32)
    stacked = jnp.asarray(rng.standard_normal((3, 6, 8, 5)), jnp.float32)
    src, dst = jnp.asarray(1), jnp.asarray(4)
    np.testing.assert_array_equal(
        np.asarray(copy_block(flat, src, dst, axis=0)),
        np.asarray(flat.at[dst].set(flat[src])))
    np.testing.assert_array_equal(
        np.asarray(copy_block(stacked, src, dst, axis=1)),
        np.asarray(stacked.at[:, dst].set(stacked[:, src])))
    # only the dst block changed
    out = np.asarray(copy_block(flat, src, dst, axis=0))
    unchanged = [i for i in range(6) if i != 4]
    np.testing.assert_array_equal(out[unchanged], np.asarray(flat)[unchanged])


def test_engine_cow_fork_refcount_and_isolation():
    """A mid-block prefix fork through the donated single-block copy keeps
    the old semantics: cache-on outputs byte-equal cache-off, the shared
    source block's writer is forked (cow_forks>0), and block accounting
    conserves."""
    cfg = get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    rng = np.random.default_rng(6)
    shared = rng.integers(3, cfg.vocab_size, size=11).astype(np.int32)
    reqs = lambda: [Request(prompt=np.concatenate(   # noqa: E731
        [shared, rng.integers(3, cfg.vocab_size, size=k).astype(np.int32)]),
        max_new=4) for k in (3, 5)]
    rng = np.random.default_rng(6)
    r_off = reqs()
    rng = np.random.default_rng(6)
    r_on = reqs()
    kw = dict(max_batch=2, block_size=8, num_blocks=24, max_len=64)
    # serve sequentially so the second request hits the retired prefix
    eng_off = ContinuousEngine(cfg, params, prefix_cache=False, **kw)
    for r in r_off:
        eng_off.serve([r])
    eng_on = ContinuousEngine(cfg, params, prefix_cache=True, **kw)
    for r in r_on:
        eng_on.serve([r])
    for a, b in zip(r_off, r_on):
        np.testing.assert_array_equal(a.out, b.out)
    # prompt 2 shares 11 tokens = 1 full block + 3 mid-block -> a COW fork
    assert eng_on.stats["cow_forks"] >= 1
    assert eng_on.kv.free_blocks + eng_on.cached_blocks == \
        eng_on.kv.num_blocks


# ---------------------------------------------------------------------------
# engine greedy byte-parity: old gather vs in-place kernel, all families
# ---------------------------------------------------------------------------

def _serve(cfg, params, impl, plens, maxnew, **kw):
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n in plens]
    eng = ContinuousEngine(cfg, params, attn_impl=impl, **kw)
    reqs = [Request(prompt=p, max_new=m) for p, m in zip(prompts, maxnew)]
    eng.serve(reqs)
    return [r.out for r in reqs], eng


_KW = dict(max_batch=2, block_size=8, num_blocks=24, max_len=64)
_PLENS, _MAXNEW = [5, 17, 9, 1], [3, 6, 4, 2]


def _family_cfg(name):
    if name == "gqa" or name == "dsa":
        return get_smoke_config("yi_6b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=256,
            dsa=DSAConfig(index_heads=2, index_head_dim=16, top_k=32,
                          block_size=16) if name == "dsa" else None)
    if name == "mla":
        # glm-5 MLA geometry; experts off keeps the decode-path focus
        return get_smoke_config("glm5_744b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, d_ff=256,
            vocab_size=256, num_experts=0, num_shared_experts=0, mtp=None,
            first_k_dense=1)
    return get_smoke_config("zamba2_2p7b").replace(      # hybrid
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, ssm_state=8, dsa=None)


@pytest.mark.parametrize("family", ["gqa", "dsa", "mla", "hybrid"])
def test_engine_greedy_byte_parity_gather_vs_inplace(family):
    cfg = _family_cfg(family)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    o_ref, e_ref = _serve(cfg, params, "ref", _PLENS, _MAXNEW, **_KW)
    o_pal, e_pal = _serve(cfg, params, "pallas", _PLENS, _MAXNEW, **_KW)
    for a, b in zip(o_ref, o_pal):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("family,kernel", [("gqa", "_gqa_jit"),
                                           ("dsa", "_indexer_jit")])
def test_decode_program_keeps_the_kernel_names_traces_are_read_by(family,
                                                                  kernel):
    """Device traces name a kernel's ops after its jitted function; the
    per-kernel roofline readers find the kernels by these names."""
    cfg = _family_cfg(family)
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    eng = ContinuousEngine(cfg, params, attn_impl="pallas", **_KW)
    lowered = eng._decode.lower(
        eng.params, jnp.zeros((eng.max_batch, 1), jnp.int32), eng.pool,
        jnp.asarray(eng.tables), jnp.asarray(eng.lengths),
        *eng._sampler_args(np.zeros((eng.max_batch,), np.float32)))
    assert kernel in lowered.as_text()


def test_decode_compiles_once_across_admit_retire():
    """The re-jitting hazard: block_tables/seq_lens keep static shapes, so
    the decode step compiles exactly once no matter how occupancy churns
    (6 requests through 2 slots force mid-flight admits + retires)."""
    cfg = _family_cfg("gqa")
    params, _ = get_model(cfg).init(jax.random.key(0), cfg)
    _, eng = _serve(cfg, params, None, [5, 17, 9, 33, 1, 26],
                    [3, 9, 5, 12, 1, 7], tracer=Tracer(enabled=True), **_KW)
    steps = [e["args"]["step"] for e in eng.tracer.export()["traceEvents"]
             if e["name"] == "req.admitted"]
    assert any(s > 0 for s in steps)                       # churn happened
    assert eng._decode._cache_size() == 1

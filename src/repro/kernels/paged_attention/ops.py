"""Dispatch for paged attention: in-place block reads vs gather.

``impl`` selects the algorithm family (decode default from
``repro.flags.paged_attention_impl`` — env ``REPRO_PAGED_ATTN_IMPL``;
prefill spans from ``paged_prefill_impl`` — ``REPRO_PAGED_PREFILL_IMPL``,
falling back to the decode env):

* ``"pallas"`` — read KV blocks in place (O(live tokens) traffic):
    - TPU backend: the compiled Pallas kernels (``kernel.py`` for decode,
      ``prefill.py`` for spans; one indexer kernel, in ``kernel.py``,
      scores decode and spans alike);
    - CPU with ``JAX_PALLAS_INTERPRET=1``: the same kernels in interpret
      mode (CI parity coverage of the kernel code itself);
    - CPU otherwise: an XLA twin — a ``fori_loop`` over live blocks whose
      trip count is traced (the step compiles ONCE regardless of
      occupancy) with the identical online-softmax accumulation.  It
      keeps the O(live) property and is what benchmarks measure off-TPU.
* ``"ref"`` — the original full-view gather path (``ref.py``), byte-
  compatible with the pre-kernel engine.

All functions take the pool + (B, max_blocks) block table + per-sequence
position vectors (``seq_lens`` for decode, ``starts`` for spans) of
``repro.core.paging`` and are shape-static in everything but the span
length: occupancy changes never recompile.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.flags import paged_attention_impl, paged_prefill_impl
from repro.kernels.paged_attention import kernel as _k
from repro.kernels.paged_attention import prefill as _p
from repro.kernels.paged_attention import ref as _ref

NEG_INF = -1e30


def _resolve(impl: str) -> str:
    if impl == "ref":
        return "ref"
    if impl != "pallas":
        raise ValueError(f"impl must be 'pallas' or 'ref', got {impl!r}")
    if jax.default_backend() == "tpu":
        return "pallas"
    if os.environ.get("JAX_PALLAS_INTERPRET", "").lower() not in \
            ("", "0", "false"):
        return "pallas_interpret"
    return "blocked"


def resolve_impl(impl: Optional[str]) -> str:
    """'ref' | 'pallas' | 'pallas_interpret' | 'blocked' (effective decode
    path)."""
    return _resolve(paged_attention_impl() if impl is None else impl)


def resolve_prefill_impl(impl: Optional[str]) -> str:
    """Effective PREFILL path — same values as ``resolve_impl`` but
    defaulting from ``repro.flags.paged_prefill_impl``.  An explicit
    ``impl`` (e.g. the engine's ``attn_impl=``) covers both phases."""
    return _resolve(paged_prefill_impl() if impl is None else impl)


def _fold_blocks(n_live, body, init):
    """fori_loop over live blocks; dynamic trip count, static shapes."""
    return jax.lax.fori_loop(0, n_live, body, init)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def _blocked_gqa(q, k_pool, v_pool, tables, lens, *, window, softcap):
    """XLA twin of ``kernel.paged_decode_gqa`` (same math, same masks)."""
    B, KVH, G, d = q.shape
    bs = k_pool.shape[1]
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    n_live = jnp.max(lens) // bs + 1

    def body(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)      # (B,)
        kb = k_pool[blk].astype(jnp.float32)      # (B, bs, KVH, d)
        vb = v_pool[blk].astype(jnp.float32)
        s = jnp.einsum("bkgd,btkd->bkgt", qf, kb) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bs + jnp.arange(bs)
        mask = k_pos[None, :] <= lens[:, None]
        if window > 0:
            mask &= (lens[:, None] - k_pos[None, :]) < window
        mask = mask[:, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgt,btkd->bkgd", p, vb)
        return m_new, l, acc

    init = (jnp.full((B, KVH, G), NEG_INF, jnp.float32),
            jnp.zeros((B, KVH, G), jnp.float32),
            jnp.zeros((B, KVH, G, d), jnp.float32))
    m, l, acc = _fold_blocks(n_live, body, init)
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def paged_gqa_attend(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, seq_lens: jax.Array, *,
                     window: int = 0, softcap: float = 0.0,
                     impl: Optional[str] = None) -> jax.Array:
    """Decode-step GQA attention through the block table.

    q (B, 1, H, d) model layout; pools (nb, bs, KVH, d); returns
    (B, 1, H, d).  ``seq_lens[b]`` is the query position (see kernel.py's
    addressing contract).  ``impl`` is resolved EAGERLY (env/backend read
    here, not inside the trace) so the jit cache is keyed on the effective
    path — flipping REPRO_PAGED_ATTN_IMPL between calls takes effect.
    """
    return _gqa_jit(q, k_pool, v_pool, block_tables, seq_lens,
                    window=window, softcap=softcap, eff=resolve_impl(impl))


# Read by name: device traces call these ops `_gqa_jit`; do not rename.
@functools.partial(jax.jit, static_argnames=("window", "softcap", "eff"))
def _gqa_jit(q, k_pool, v_pool, block_tables, seq_lens, *,
             window: int, softcap: float, eff: str) -> jax.Array:
    B, S, H, d = q.shape
    KVH = k_pool.shape[2]
    if eff == "ref":
        return _ref.paged_gqa_reference(q, k_pool, v_pool, block_tables,
                                        seq_lens, window=window,
                                        softcap=softcap)
    qg = q[:, 0].reshape(B, KVH, H // KVH, d)            # head-group packing
    if eff == "blocked":
        out = _blocked_gqa(qg, k_pool, v_pool, block_tables, seq_lens,
                           window=window, softcap=softcap)
    else:
        out = _k.paged_decode_gqa(qg, k_pool, v_pool, block_tables,
                                  seq_lens, window=window, softcap=softcap,
                                  interpret=eff == "pallas_interpret")
    return out.reshape(B, 1, H, d)


# ---------------------------------------------------------------------------
# MLA (absorbed latent decode)
# ---------------------------------------------------------------------------

def _blocked_mla(q_lat, q_rope, c_pool, kr_pool, tables, lens, *, scale):
    B, H, L = q_lat.shape
    bs = c_pool.shape[1]
    ql = q_lat.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    n_live = jnp.max(lens) // bs + 1

    def body(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)
        cb = c_pool[blk].astype(jnp.float32)             # (B, bs, L)
        krb = kr_pool[blk].astype(jnp.float32)
        s = (jnp.einsum("bhl,btl->bht", ql, cb)
             + jnp.einsum("bhr,btr->bht", qr, krb)) * scale
        k_pos = j * bs + jnp.arange(bs)
        mask = (k_pos[None, :] <= lens[:, None])[:, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bht,btl->bhl", p, cb)
        return m_new, l, acc

    init = (jnp.full((B, H), NEG_INF, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, L), jnp.float32))
    m, l, acc = _fold_blocks(n_live, body, init)
    return acc / jnp.maximum(l, 1e-20)[..., None]


def paged_mla_attend(q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
                     kr_pool: jax.Array, block_tables: jax.Array,
                     seq_lens: jax.Array, *, scale: float,
                     impl: Optional[str] = None) -> jax.Array:
    """Absorbed MLA decode ``probs · c`` over the paged latent cache.

    q_lat/q_rope (B, 1, H, ·) -> out_lat (B, 1, H, lora) fp32; the caller
    applies W^UV / W^O (see ``repro.core.mla.mla_decode_paged``).  ``impl``
    resolves eagerly, like ``paged_gqa_attend``.
    """
    return _mla_jit(q_lat, q_rope, c_pool, kr_pool, block_tables, seq_lens,
                    scale=scale, eff=resolve_impl(impl))


@functools.partial(jax.jit, static_argnames=("scale", "eff"))
def _mla_jit(q_lat, q_rope, c_pool, kr_pool, block_tables, seq_lens, *,
             scale: float, eff: str) -> jax.Array:
    if eff == "ref":
        return _ref.paged_mla_reference(q_lat, q_rope, c_pool, kr_pool,
                                        block_tables, seq_lens, scale=scale)
    if eff == "blocked":
        out = _blocked_mla(q_lat[:, 0], q_rope[:, 0], c_pool, kr_pool,
                           block_tables, seq_lens, scale=scale)
    else:
        out = _k.paged_decode_mla(q_lat[:, 0], q_rope[:, 0], c_pool,
                                  kr_pool, block_tables, seq_lens,
                                  scale=scale,
                                  interpret=eff == "pallas_interpret")
    return out[:, None]


# ---------------------------------------------------------------------------
# DSA indexer scores
# ---------------------------------------------------------------------------

def _blocked_indexer(q_idx, w_head, k_pool, tables, lens):
    B, Hi, Di = q_idx.shape
    bs = k_pool.shape[1]
    mb = tables.shape[1]
    scale = Di ** -0.5
    qf = q_idx.astype(jnp.float32)
    wf = w_head.astype(jnp.float32)
    n_live = jnp.max(lens) // bs + 1

    def body(j, out):
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)
        kb = k_pool[blk].astype(jnp.float32)             # (B, bs, Di)
        dots = jax.nn.relu(jnp.einsum("bhd,btd->bht", qf, kb)) * scale
        s = jnp.einsum("bht,bh->bt", dots, wf)
        return jax.lax.dynamic_update_slice(out, s, (0, j * bs))

    out0 = jnp.full((B, mb * bs), NEG_INF, jnp.float32)
    return _fold_blocks(n_live, body, out0)


def paged_indexer_scores(q_idx: jax.Array, w_head: jax.Array,
                         k_pool: jax.Array, block_tables: jax.Array,
                         seq_lens: jax.Array, *,
                         impl: Optional[str] = None) -> jax.Array:
    """DSA decode indexer scores in view coordinates (B, mb*bs) fp32.

    q_idx (B, Hi, Di); w_head (B, Hi); k_pool (nb, bs, Di).  Dead blocks
    score NEG_INF under the in-place impls and stale values under ``ref``
    — both are excluded by the selector's causal mask, so top-k is
    identical.  ``impl`` resolves eagerly, like ``paged_gqa_attend``.
    """
    return _indexer_jit(q_idx, w_head, k_pool, block_tables, seq_lens,
                        eff=resolve_impl(impl))


# Read by name: device traces call these ops `_indexer_jit`; do not rename.
@functools.partial(jax.jit, static_argnames=("eff",))
def _indexer_jit(q_idx, w_head, k_pool, block_tables, seq_lens, *,
                 eff: str) -> jax.Array:
    if eff == "ref":
        return _ref.paged_indexer_reference(q_idx, w_head, k_pool,
                                            block_tables, seq_lens)
    if eff == "blocked":
        return _blocked_indexer(q_idx, w_head, k_pool, block_tables,
                                seq_lens)
    return _k.paged_indexer_scores_kernel(
        q_idx, w_head, k_pool, block_tables, seq_lens,
        interpret=eff == "pallas_interpret")


# ===========================================================================
# PREFILL spans: S-token queries at per-sequence start offsets
# ===========================================================================

def _span_n_live(starts, S: int, bs: int):
    """Blocks any span in the batch attends: trip count for the twins."""
    return (jnp.max(starts) + S - 1) // bs + 1


def _blocked_gqa_prefill(q, k_pool, v_pool, tables, starts, *, window,
                         softcap):
    """XLA twin of ``prefill.paged_prefill_gqa`` (same math, same masks).

    q (B, S, KVH, G, d) span queries -> (B, S, KVH, G, d).
    """
    B, S, KVH, G, d = q.shape
    bs = k_pool.shape[1]
    scale = d ** -0.5
    qf = q.astype(jnp.float32)
    qpos = starts[:, None] + jnp.arange(S)[None]          # (B, S)
    n_live = _span_n_live(starts, S, bs)

    def body(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)      # (B,)
        kb = k_pool[blk].astype(jnp.float32)      # (B, bs, KVH, d)
        vb = v_pool[blk].astype(jnp.float32)
        s = jnp.einsum("bskgd,btkd->bskgt", qf, kb) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * bs + jnp.arange(bs)
        mask = k_pos[None, None, :] <= qpos[:, :, None]
        if window > 0:
            mask &= (qpos[:, :, None] - k_pos[None, None, :]) < window
        mask = mask[:, :, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bskgt,btkd->bskgd", p, vb)
        return m_new, l, acc

    init = (jnp.full((B, S, KVH, G), NEG_INF, jnp.float32),
            jnp.zeros((B, S, KVH, G), jnp.float32),
            jnp.zeros((B, S, KVH, G, d), jnp.float32))
    m, l, acc = _fold_blocks(n_live, body, init)
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def paged_gqa_prefill(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, starts: jax.Array, *,
                      window: int = 0, softcap: float = 0.0,
                      impl: Optional[str] = None) -> jax.Array:
    """Span-prefill GQA attention through the block table, in place.

    q (B, S, H, d) model layout — query i of row b sits at absolute
    position ``starts[b] + i`` and its K/V was scattered before the call;
    attention is causal by absolute position (full attention to the cached
    prefix + causal within the span).  Returns (B, S, H, d).  ``impl``
    resolves EAGERLY like ``paged_gqa_attend`` (jit cache keyed on the
    effective path).
    """
    return _gqa_prefill_jit(q, k_pool, v_pool, block_tables, starts,
                            window=window, softcap=softcap,
                            eff=resolve_prefill_impl(impl))


@functools.partial(jax.jit, static_argnames=("window", "softcap", "eff"))
def _gqa_prefill_jit(q, k_pool, v_pool, block_tables, starts, *,
                     window: int, softcap: float, eff: str) -> jax.Array:
    B, S, H, d = q.shape
    KVH = k_pool.shape[2]
    G = H // KVH
    if eff == "ref":
        return _ref.paged_gqa_prefill_reference(
            q, k_pool, v_pool, block_tables, starts, window=window,
            softcap=softcap)
    qg = q.reshape(B, S, KVH, G, d)
    if eff == "blocked":
        out = _blocked_gqa_prefill(qg, k_pool, v_pool, block_tables,
                                   starts, window=window, softcap=softcap)
        return out.reshape(B, S, H, d)
    # head-group packing: (B, KVH, S*G, d) rows are (token i, group g)
    qp = qg.transpose(0, 2, 1, 3, 4).reshape(B, KVH, S * G, d)
    out = _p.paged_prefill_gqa(qp, k_pool, v_pool, block_tables, starts,
                               groups=G, window=window, softcap=softcap,
                               interpret=eff == "pallas_interpret")
    return out.reshape(B, KVH, S, G, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, d)


def _blocked_mla_prefill(q_lat, q_rope, c_pool, kr_pool, tables, starts, *,
                         scale):
    """q_lat (B, S, H, L); q_rope (B, S, H, R) -> (B, S, H, L) fp32."""
    B, S, H, L = q_lat.shape
    bs = c_pool.shape[1]
    ql = q_lat.astype(jnp.float32)
    qr = q_rope.astype(jnp.float32)
    qpos = starts[:, None] + jnp.arange(S)[None]
    n_live = _span_n_live(starts, S, bs)

    def body(j, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)
        cb = c_pool[blk].astype(jnp.float32)             # (B, bs, L)
        krb = kr_pool[blk].astype(jnp.float32)
        s = (jnp.einsum("bshl,btl->bsht", ql, cb)
             + jnp.einsum("bshr,btr->bsht", qr, krb)) * scale
        k_pos = j * bs + jnp.arange(bs)
        mask = (k_pos[None, None, :] <= qpos[:, :, None])[:, :, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bsht,btl->bshl", p, cb)
        return m_new, l, acc

    init = (jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32),
            jnp.zeros((B, S, H, L), jnp.float32))
    m, l, acc = _fold_blocks(n_live, body, init)
    return acc / jnp.maximum(l, 1e-20)[..., None]


def paged_mla_prefill(q_lat: jax.Array, q_rope: jax.Array,
                      c_pool: jax.Array, kr_pool: jax.Array,
                      block_tables: jax.Array, starts: jax.Array, *,
                      scale: float, impl: Optional[str] = None) -> jax.Array:
    """Absorbed MLA span prefill ``probs · c`` over the paged latent cache.

    q_lat/q_rope (B, S, H, ·) -> out_lat (B, S, H, lora) fp32; the caller
    applies W^UV / W^O (see ``repro.core.mla.mla_decode_paged``).
    """
    return _mla_prefill_jit(q_lat, q_rope, c_pool, kr_pool, block_tables,
                            starts, scale=scale,
                            eff=resolve_prefill_impl(impl))


@functools.partial(jax.jit, static_argnames=("scale", "eff"))
def _mla_prefill_jit(q_lat, q_rope, c_pool, kr_pool, block_tables, starts,
                     *, scale: float, eff: str) -> jax.Array:
    B, S, H, L = q_lat.shape
    if eff == "ref":
        return _ref.paged_mla_prefill_reference(
            q_lat, q_rope, c_pool, kr_pool, block_tables, starts,
            scale=scale)
    if eff == "blocked":
        return _blocked_mla_prefill(q_lat, q_rope, c_pool, kr_pool,
                                    block_tables, starts, scale=scale)
    out = _p.paged_prefill_mla(
        q_lat.reshape(B, S * H, L),
        q_rope.reshape(B, S * H, q_rope.shape[-1]),
        c_pool, kr_pool, block_tables, starts, heads=H, scale=scale,
        interpret=eff == "pallas_interpret")
    return out.reshape(B, S, H, L)


def _blocked_indexer_prefill(q_idx, w_head, k_pool, tables, starts):
    """q_idx (B, S, Hi, Di); w_head (B, S, Hi) -> (B, S, mb*bs) fp32."""
    B, S, Hi, Di = q_idx.shape
    bs = k_pool.shape[1]
    mb = tables.shape[1]
    scale = Di ** -0.5
    qf = q_idx.astype(jnp.float32)
    wf = w_head.astype(jnp.float32)
    n_live = _span_n_live(starts, S, bs)

    def body(j, out):
        blk = jax.lax.dynamic_index_in_dim(tables, j, axis=1,
                                           keepdims=False)
        kb = k_pool[blk].astype(jnp.float32)             # (B, bs, Di)
        dots = jax.nn.relu(jnp.einsum("bshd,btd->bsht", qf, kb)) * scale
        s = jnp.einsum("bsht,bsh->bst", dots, wf)
        return jax.lax.dynamic_update_slice(out, s, (0, 0, j * bs))

    out0 = jnp.full((B, S, mb * bs), NEG_INF, jnp.float32)
    return _fold_blocks(n_live, body, out0)


def paged_indexer_prefill(q_idx: jax.Array, w_head: jax.Array,
                          k_pool: jax.Array, block_tables: jax.Array,
                          starts: jax.Array, *,
                          impl: Optional[str] = None) -> jax.Array:
    """DSA span indexer scores in view coordinates (B, S, mb*bs) fp32.

    q_idx (B, S, Hi, Di); w_head (B, S, Hi) softmaxed; k_pool (nb, bs, Di).
    Dead blocks score NEG_INF under the in-place impls and stale values
    under ``ref`` — both are excluded by the selector's causal mask.
    """
    return _indexer_prefill_jit(q_idx, w_head, k_pool, block_tables,
                                starts, eff=resolve_prefill_impl(impl))


@functools.partial(jax.jit, static_argnames=("eff",))
def _indexer_prefill_jit(q_idx, w_head, k_pool, block_tables, starts, *,
                         eff: str) -> jax.Array:
    B, S, Hi, Di = q_idx.shape
    if eff == "ref":
        return _ref.paged_indexer_prefill_reference(
            q_idx, w_head, k_pool, block_tables, starts)
    if eff == "blocked":
        return _blocked_indexer_prefill(q_idx, w_head, k_pool, block_tables,
                                        starts)
    return _k.indexer_span_scores(
        q_idx.reshape(B, S * Hi, Di), w_head.reshape(B, S * Hi),
        k_pool, block_tables, starts, heads=Hi,
        interpret=eff == "pallas_interpret")

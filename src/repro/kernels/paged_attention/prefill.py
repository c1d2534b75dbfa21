"""Pallas TPU paged-attention PREFILL kernels: span queries, in-place KV.

Chunked/suffix prefill used to materialize each sequence's KV with
``paged_view`` — the same O(pool capacity) ``pool[block_tables]`` gather the
decode kernels killed in PR 3 — then run dense attention over the padded
view.  These kernels extend the flash-decode machinery to multi-token query
spans: the BlockSpec index maps walk the block table directly (scalar
prefetch), DMAing only the blocks a span actually attends, with an
online-softmax accumulator folding one KV block at a time.

Span addressing contract (extends ``kernel.py``'s decode contract):

* a span is S consecutive tokens of one sequence; query ``i`` of row ``b``
  sits at absolute position ``starts[b] + i``, and its K/V (and indexer
  keys) were scattered through the table by ``paged_update`` BEFORE the
  kernel runs;
* attention is causal by absolute position: query ``i`` covers every
  cached position ``<= starts[b] + i`` — full attention to the prior
  context (a radix-cached prefix, earlier chunks) plus causal attention
  within the span, which is exactly the gather path's mask since view
  index == absolute position;
* nothing beyond ``starts[b] + S - 1`` is ever read, so spans need no
  right-padding to whole blocks — masking comes from ``starts`` alone
  (the scheduler's old padded-tail trick is dead);
* the ragged-tail / trash-block rules of the decode contract apply
  unchanged: dead grid programs early-exit via ``pl.when`` and their
  index maps clamp onto a live block so the elided DMA never touches a
  dead one.

The S-token query block is the small-S machinery MTP verification needs
(accept_length 2-4 per step) — a verify step is just a prefill span whose
queries are the draft tokens.

Target: TPU v5e.  Validated on CPU in interpret mode against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.kernel import (NEG_INF,
                                                  _online_softmax_step,
                                                  pad_tokens, query_tile)

# query rows one tile keeps resident (with its f32 accumulator) in VMEM
MAX_TILE_ROWS = 512


def _span_live(j: jax.Array, bs: int, start: jax.Array, S: int,
               window: int) -> jax.Array:
    """Does block ``j`` hold any position SOME query of the span attends?"""
    live = j * bs <= start + S - 1          # causal: last query's position
    if window > 0:
        live &= (j + 1) * bs - 1 >= start - window + 1   # first query's win
    return live


def _span_clamp(j: jax.Array, bs: int, start: jax.Array, S: int,
                window: int) -> jax.Array:
    """Clamp dead block walks onto the span's live range (re-targets the
    elided DMA at an already-resident block, mirroring decode)."""
    jc = jnp.minimum(j, (start + S - 1) // bs)
    if window > 0:
        jc = jnp.maximum(jc, jnp.maximum(start - window + 1, 0) // bs)
    return jc


# ---------------------------------------------------------------------------
# GQA / MQA span prefill
# ---------------------------------------------------------------------------

def _gqa_prefill_kernel(tables_ref, starts_ref, q_ref, k_ref, v_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, bs: int, mb: int, tq: int,
                        G: int, kvh: int, window: int, softcap: float,
                        scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b] + pl.program_id(1) * tq        # tile's first query

    @pl.when(_span_live(j, bs, start, tq, window))
    def _block():
        # row r of the packed (tq*G, ·) tile is query i = r // G at
        # absolute position start + i; keys live at j*bs + t
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (tq * G, bs), 0) // G
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (tq * G, bs), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        for h in range(kvh):                              # static head loop
            q = q_ref[0, h].astype(jnp.float32)           # (tq*G, d)
            k = k_ref[0, :, h].astype(jnp.float32)        # (bs, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            v = v_ref[0, :, h].astype(jnp.float32)        # (bs, d)
            _online_softmax_step(s, mask, v, m_ref.at[h], l_ref.at[h],
                                 acc_ref.at[h])

    @pl.when(j == mb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_prefill_gqa(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                      block_tables: jax.Array, starts: jax.Array, *,
                      groups: int, window: int = 0, softcap: float = 0.0,
                      interpret: bool = False) -> jax.Array:
    """q (B, KVH, S*G, d) packed span queries; k/v pools (nb, bs, KVH, d);
    tables (B, mb) int32; starts (B,) int32 -> out (B, KVH, S*G, d).

    ``groups`` (= G = H // KVH) recovers S from the packed axis — row
    ``i*G + g`` is group-query ``g`` of span token ``i`` (head-group
    packing, MXU rows).  grid = (B, query tiles, mb): each program streams
    ONE whole (bs, KVH, d) pool block against a resident query tile and
    loops over the kv-heads in VMEM.
    """
    B, KVH, SG, d = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    G = groups
    assert SG % G == 0, (SG, G)
    S = SG // G
    tq = query_tile(S, G, MAX_TILE_ROWS)
    kern = functools.partial(_gqa_prefill_kernel, bs=bs, mb=mb, tq=tq, G=G,
                             kvh=KVH, window=window, softcap=softcap,
                             scale=d ** -0.5)

    def blk(b, qt, j, tables, starts):
        jc = _span_clamp(j, bs, starts[b] + qt * tq, tq, window)
        return (tables[b, jc], 0, 0, 0)

    qp = pad_tokens(q, S, tq, axis=2)
    q_spec = pl.BlockSpec((1, KVH, tq * G, d),
                          lambda b, qt, j, t, st: (b, 0, qt, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, qp.shape[2] // (tq * G), mb),
        in_specs=[q_spec,
                  pl.BlockSpec((1, bs, KVH, d), blk),
                  pl.BlockSpec((1, bs, KVH, d), blk)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KVH, tq * G), jnp.float32),
            pltpu.VMEM((KVH, tq * G), jnp.float32),
            pltpu.VMEM((KVH, tq * G, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=interpret,
    )(block_tables, starts, qp, k_pool, v_pool)[:, :, :SG]


# ---------------------------------------------------------------------------
# MLA absorbed span prefill (latent pool)
# ---------------------------------------------------------------------------

def _mla_prefill_kernel(tables_ref, starts_ref, ql_ref, qr_ref, c_ref,
                        kr_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int,
                        mb: int, tq: int, H: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = starts_ref[b] + pl.program_id(1) * tq        # tile's first query

    @pl.when(_span_live(j, bs, start, tq, 0))
    def _block():
        ql = ql_ref[0].astype(jnp.float32)                # (tq*H, lora)
        qr = qr_ref[0].astype(jnp.float32)                # (tq*H, rope)
        c = c_ref[0].astype(jnp.float32)                  # (bs, lora)
        kr = kr_ref[0].astype(jnp.float32)                # (bs, rope)
        dn = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dn,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, dn,
                                   preferred_element_type=jnp.float32))
        s = s * scale                                     # (tq*H, bs)
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (tq * H, bs), 0) // H
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (tq * H, bs), 1)
        _online_softmax_step(s, k_pos <= q_pos, c, m_ref, l_ref, acc_ref)

    @pl.when(j == mb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = acc_ref[...] / l[:, None]


def paged_prefill_mla(q_lat: jax.Array, q_rope: jax.Array,
                      c_pool: jax.Array, kr_pool: jax.Array,
                      block_tables: jax.Array, starts: jax.Array, *,
                      heads: int, scale: float,
                      interpret: bool = False) -> jax.Array:
    """Absorbed MQA span prefill over the paged latent cache, in place.

    q_lat (B, S*H, lora) packed (row i*H + h = head h of span token i);
    q_rope (B, S*H, rope); c/kr pools (nb, bs, ·) -> out_lat (B, S*H, lora)
    fp32 (``probs · c``; caller applies W^UV, W^O).  grid = (B, query
    tiles, mb): VMEM holds one tile of whole tokens and its accumulator.
    """
    B, SH, L = q_lat.shape
    R = q_rope.shape[-1]
    S = SH // heads
    bs = c_pool.shape[1]
    mb = block_tables.shape[1]
    tq = query_tile(S, heads, MAX_TILE_ROWS)
    rows = tq * heads
    kern = functools.partial(_mla_prefill_kernel, bs=bs, mb=mb, tq=tq,
                             H=heads, scale=scale)

    def blk(b, qt, j, tables, starts):
        jc = _span_clamp(j, bs, starts[b] + qt * tq, tq, 0)
        return (tables[b, jc], 0, 0)

    q_lat = pad_tokens(q_lat, S, tq)
    q_rope = pad_tokens(q_rope, S, tq)
    q_rows = lambda b, qt, j, t, st: (b, qt, 0)           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, q_lat.shape[1] // rows, mb),
        in_specs=[
            pl.BlockSpec((1, rows, L), q_rows),
            pl.BlockSpec((1, rows, R), q_rows),
            pl.BlockSpec((1, bs, L), blk),
            pl.BlockSpec((1, bs, R), blk),
        ],
        out_specs=pl.BlockSpec((1, rows, L), q_rows),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, L), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, jnp.float32),
        interpret=interpret,
    )(block_tables, starts, q_lat, q_rope, c_pool, kr_pool)[:, :SH]


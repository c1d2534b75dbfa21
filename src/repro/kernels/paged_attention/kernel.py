"""Pallas TPU paged-attention DECODE kernels: read KV blocks in place.

The continuous-batching engine used to materialize every sequence's KV with
``paged_view`` — a ``pool[block_tables]`` gather that copies the whole
padded view (B × max_blocks × block_size) to HBM every decode step, so
decode traffic scaled with pool capacity instead of live tokens.  These
kernels walk each sequence's block table with *scalar prefetch* (the same
mechanism as ``repro.kernels.sparse_attention``): the BlockSpec index_map
DMAs exactly the live KV blocks HBM→VMEM and an online-softmax accumulator
(flash-decode) folds them one block at a time.

Block-table addressing contract (see ``repro.core.paging``):

* blocks are assigned in *position order*, so absolute token position ``p``
  of row ``b`` lives at ``(tables[b, p // bs], p % bs)`` and the view index
  equals the absolute position — masking only needs ``seq_lens``;
* ``seq_lens[b]`` is the query's position: the new token was scattered at
  ``seq_lens[b]`` by ``paged_update`` before the kernel runs, and attention
  covers positions ``<= seq_lens[b]`` (the causal mask of a 1-token step);
* the *ragged tail*: the last live block of row ``b`` is block
  ``seq_lens[b] // bs``; positions beyond ``seq_lens[b]`` inside it are
  masked in-kernel, so stale pool contents there are never read into the
  softmax;
* idle scheduler slots point every table entry at a reserved *trash block*
  and carry length 0 — they attend position 0 of the trash block and their
  output is discarded host-side, identical to the gather path's semantics.

Ragged early-exit: grid programs with ``blk_idx * block_size > seq_len``
skip all compute via ``pl.when``, and their index_map clamps to the last
live block so the (elided) DMA re-targets an already-resident block instead
of touching a dead one.  Decode traffic is therefore O(live tokens).

Target: TPU v5e.  Validated on CPU in interpret mode against
``ref.py`` (the gather path these kernels replace).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(s, mask, v, m_ref, l_ref, acc_ref):
    """One flash-decode accumulation: s (R, bs) scores, v (bs, dv)."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _live(j: jax.Array, bs: int, qpos: jax.Array, window: int) -> jax.Array:
    """Does block ``j`` hold any position this query attends to?"""
    live = j * bs <= qpos
    if window > 0:
        live &= (j + 1) * bs - 1 >= qpos - window + 1
    return live


# ---------------------------------------------------------------------------
# GQA / MQA decode
# ---------------------------------------------------------------------------

def _gqa_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bs: int, mb: int, kvh: int,
                window: int, softcap: float, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = lens_ref[b]

    @pl.when(_live(j, bs, qpos, window))
    def _block():
        for h in range(kvh):                              # static head loop
            q = q_ref[0, h].astype(jnp.float32)           # (G, d)
            k = k_ref[0, :, h].astype(jnp.float32)        # (bs, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap > 0:
                s = softcap * jnp.tanh(s / softcap)
            k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = k_pos <= qpos
            if window > 0:
                mask &= (qpos - k_pos) < window
            v = v_ref[0, :, h].astype(jnp.float32)        # (bs, d)
            _online_softmax_step(s, mask, v, m_ref.at[h], l_ref.at[h],
                                 acc_ref.at[h])

    @pl.when(j == mb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def paged_decode_gqa(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, seq_lens: jax.Array, *,
                     window: int = 0, softcap: float = 0.0,
                     interpret: bool = False) -> jax.Array:
    """q (B, KVH, G, d); k/v pools (nb, bs, KVH, d); tables (B, mb) int32;
    seq_lens (B,) int32 -> out (B, KVH, G, d) in q.dtype.

    grid = (B, mb); each program streams ONE whole (bs, KVH, d) pool block
    (its last two dims are full dims, as Mosaic's tiling requires) and
    loops over the kv-heads in VMEM; the G group-queries of a kv-head are
    packed as MXU rows (head-group packing).
    """
    B, KVH, G, d = q.shape
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    kern = functools.partial(_gqa_kernel, bs=bs, mb=mb, kvh=KVH,
                             window=window, softcap=softcap, scale=d ** -0.5)

    def blk(b, j, tables, lens):
        # clamp dead walks onto the live range so their (elided) DMA
        # re-targets a resident block: above the tail, and — on windowed
        # layers — below the first in-window block
        jc = jnp.minimum(j, lens[b] // bs)
        if window > 0:
            jc = jnp.maximum(jc, jnp.maximum(lens[b] - window + 1, 0) // bs)
        return (tables[b, jc], 0, 0, 0)

    q_spec = pl.BlockSpec((1, KVH, G, d), lambda b, j, t, L: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mb),
        in_specs=[q_spec,
                  pl.BlockSpec((1, bs, KVH, d), blk),
                  pl.BlockSpec((1, bs, KVH, d), blk)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KVH, G), jnp.float32),
            pltpu.VMEM((KVH, G), jnp.float32),
            pltpu.VMEM((KVH, G, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# MLA absorbed decode (latent pool)
# ---------------------------------------------------------------------------

def _mla_kernel(tables_ref, lens_ref, ql_ref, qr_ref, c_ref, kr_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bs: int, mb: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = lens_ref[b]

    @pl.when(_live(j, bs, qpos, 0))
    def _block():
        ql = ql_ref[0].astype(jnp.float32)                # (H, lora)
        qr = qr_ref[0].astype(jnp.float32)                # (H, rope)
        c = c_ref[0].astype(jnp.float32)                  # (bs, lora)
        kr = kr_ref[0].astype(jnp.float32)                # (bs, rope)
        dn = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql, c, dn,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, dn,
                                   preferred_element_type=jnp.float32))
        s = s * scale                                     # (H, bs)
        k_pos = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (ql.shape[0], bs), 1)
        _online_softmax_step(s, k_pos <= qpos, c, m_ref, l_ref, acc_ref)

    @pl.when(j == mb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = acc_ref[...] / l[:, None]


def paged_decode_mla(q_lat: jax.Array, q_rope: jax.Array, c_pool: jax.Array,
                     kr_pool: jax.Array, block_tables: jax.Array,
                     seq_lens: jax.Array, *, scale: float,
                     interpret: bool = False) -> jax.Array:
    """Absorbed MQA decode over the paged latent cache, in place.

    q_lat (B, H, lora) = q_nope·W^UK; q_rope (B, H, rope); c_pool
    (nb, bs, lora); kr_pool (nb, bs, rope) -> out_lat (B, H, lora) fp32
    (``probs · c``; the caller applies W^UV and W^O).  All H heads share
    the single latent KV, so the grid is (B, mb) with the full head block
    resident.
    """
    B, H, L = q_lat.shape
    bs = c_pool.shape[1]
    mb = block_tables.shape[1]
    kern = functools.partial(_mla_kernel, bs=bs, mb=mb, scale=scale)

    def blk(b, j, tables, lens):
        jc = jnp.minimum(j, lens[b] // bs)
        return (tables[b, jc], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, mb),
        in_specs=[
            pl.BlockSpec((1, H, L), lambda b, j, t, lens: (b, 0, 0)),
            pl.BlockSpec((1, H, q_rope.shape[-1]),
                         lambda b, j, t, lens: (b, 0, 0)),
            pl.BlockSpec((1, bs, L), blk),
            pl.BlockSpec((1, bs, kr_pool.shape[-1]), blk),
        ],
        out_specs=pl.BlockSpec((1, H, L), lambda b, j, t, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, L), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, L), jnp.float32),
        interpret=interpret,
    )(block_tables, seq_lens, q_lat, q_rope, c_pool, kr_pool)


# ---------------------------------------------------------------------------
# DSA lightning-indexer scores over the paged k_idx pool
# ---------------------------------------------------------------------------

LANES = 128


def blocks_per_step(bs: int) -> int:
    """Pool blocks one indexer grid step scores: enough to fill a 128-lane
    tile, since the minor dim of a TPU output block must be a multiple of
    128 unless it spans the whole array."""
    return LANES // bs if bs < LANES and LANES % bs == 0 else 1


# query rows (tokens x indexer heads) one indexer tile keeps in VMEM
INDEXER_TILE_ROWS = 2048


def query_tile(S: int, rows_per_token: int, max_rows: int) -> int:
    """Tokens per query tile: the whole span when its rows fit
    ``max_rows``; otherwise tiles of equal size, a multiple of 8 tokens
    (sublane-aligned), each at most ``max_rows`` rows where 8 tokens fit.
    The span is padded to whole tiles (``pad_tokens``)."""
    if S * rows_per_token <= max_rows:
        return S
    most = max(8, max_rows // rows_per_token // 8 * 8)
    return pl.cdiv(pl.cdiv(S, pl.cdiv(S, most)), 8) * 8


def pad_tokens(x: jax.Array, S: int, tq: int, axis: int = 1) -> jax.Array:
    """Zero-pad the packed token axis of ``x`` (S tokens of equal row
    count) to whole tiles of ``tq`` tokens.  Padded queries sit past the
    span; their outputs are sliced off by the caller."""
    rows = x.shape[axis] // S
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, -S % tq * rows)
    return jnp.pad(x, pad)


def _indexer_kernel(tables_ref, starts_ref, q_ref, w_ref, *refs, bs: int,
                    nsub: int, tq: int, hi: int, scale: float):
    k_refs, o_ref = refs[:nsub], refs[nsub]
    b = pl.program_id(0)
    j = pl.program_id(2)
    last = starts_ref[b] + (pl.program_id(1) + 1) * tq - 1   # tile's last q
    live = j * nsub * bs <= last

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32)                  # (tq*Hi, Di)
        w = w_ref[0].astype(jnp.float32)                  # (tq*Hi, 1)
        k = jnp.concatenate([r[0].astype(jnp.float32) for r in k_refs],
                            axis=0)                       # (nsub*bs, Di)
        dots = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        dots = jax.nn.relu(dots) * scale * w              # (tq*Hi, nsub*bs)
        s = dots.reshape(tq, hi, nsub * bs).sum(axis=1)   # (tq, nsub*bs)
        k_blk = (j * nsub * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)) // bs
        o_ref[0] = jnp.where(k_blk <= last // bs, s, NEG_INF)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0] = jnp.full((tq, nsub * bs), NEG_INF, jnp.float32)


def indexer_span_scores(q_idx: jax.Array, w_head: jax.Array,
                        k_pool: jax.Array, block_tables: jax.Array,
                        starts: jax.Array, *, heads: int,
                        interpret: bool = False) -> jax.Array:
    """DSA indexer scores of an S-token span against the k_idx pool.

    q_idx (B, S*Hi, Di) packed (row i*Hi + h = head h of token i); w_head
    (B, S*Hi) softmaxed; k_pool (nb, bs, Di); starts (B,) -> scores
    (B, S, mb*bs) fp32 in view coordinates (index == absolute position).

    grid = (B, query tiles, block groups): each step scores
    ``blocks_per_step(bs)`` pool blocks into one lane-dense tile.  Blocks
    past the tile's last query position emit NEG_INF (the selector's
    causal mask excludes them anyway).
    """
    B, SHi, Di = q_idx.shape
    S = SHi // heads
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    nsub = blocks_per_step(bs)
    steps = pl.cdiv(mb, nsub)
    tq = query_tile(S, heads, INDEXER_TILE_ROWS)
    kern = functools.partial(_indexer_kernel, bs=bs, nsub=nsub, tq=tq,
                             hi=heads, scale=Di ** -0.5)

    def k_spec(i):
        def idx(b, qt, j, tables, starts):
            # clamp onto the tile's last live block, and inside the table
            # (group walks and padded tiles can run past its end)
            last = jnp.minimum((starts[b] + (qt + 1) * tq - 1) // bs, mb - 1)
            return (tables[b, jnp.minimum(j * nsub + i, last)], 0, 0)
        return pl.BlockSpec((1, bs, Di), idx)

    q_idx = pad_tokens(q_idx, S, tq)
    w_head = pad_tokens(w_head, S, tq)
    n_tiles = q_idx.shape[1] // (tq * heads)
    q_rows = lambda b, qt, j, t, st: (b, qt, 0)           # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_tiles, steps),
        in_specs=[pl.BlockSpec((1, tq * heads, Di), q_rows),
                  pl.BlockSpec((1, tq * heads, 1), q_rows),
                  *[k_spec(i) for i in range(nsub)]],
        out_specs=pl.BlockSpec((1, tq, nsub * bs),
                               lambda b, qt, j, t, st: (b, qt, j)),
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_tiles * tq, steps * nsub * bs),
                                       jnp.float32),
        interpret=interpret,
    )(block_tables, starts, q_idx, w_head[..., None], *[k_pool] * nsub)
    return out[:, :S, :mb * bs]


def paged_indexer_scores_kernel(q_idx: jax.Array, w_head: jax.Array,
                                k_pool: jax.Array, block_tables: jax.Array,
                                seq_lens: jax.Array, *,
                                interpret: bool = False) -> jax.Array:
    """DSA decode indexer scores against the k_idx pool, in place: the
    one-token span at ``seq_lens``.

    q_idx (B, Hi, Di); w_head (B, Hi) (softmaxed); k_pool (nb, bs, Di) ->
    scores (B, mb*bs) fp32 in VIEW coordinates (index == absolute
    position).  Dead blocks emit NEG_INF; the selector masks them anyway.
    """
    return indexer_span_scores(q_idx, w_head, k_pool, block_tables,
                               seq_lens, heads=q_idx.shape[1],
                               interpret=interpret)[:, 0]

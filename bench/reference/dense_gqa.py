"""Plain reference of a dense GQA decoder, with optional DSA selection.

Follows the layer equations the configuration files state: token
embedding; per layer RMSNorm -> GQA attention with rotary embeddings
(half-split rotation, base ``rope_theta``) -> residual -> RMSNorm -> MLP
(SwiGLU for ``hidden_act == "silu"``, squared ReLU for ``"relu2"``) ->
residual; final RMSNorm; untied LM head.  With ``dsa`` set, each query
attends only to the ``top_k`` causal keys with the highest lightning-
indexer score I[t,s] = sum_h w_h(x_t) * ReLU(q_h(x_t) . k(x_s)) / sqrt(Di),
w = softmax over heads; a query with fewer causal keys attends to all, and
keys tied at the k-th score are taken lowest position first.

Selection here is a threshold mask over dense scores (the k-th largest
indexer score per query), not a gather: the same set by a different
method.  It imports nothing of the system under test and takes only the
benchmark's own flat weights (``bench.harness.model.weight_shapes``).

``mode="f32"``: float32 throughout, matmuls at HIGHEST precision.
``mode="fp8"``: the control.  Every linear layer's weights (per tensor)
and inputs (per row) are rounded to float8_e4m3 before the matmul, the
precision step below the bf16 that the configurations state.

Work is blocked so that it fits beside the served weights: rows in chunks
for the linear layers, queries in blocks for attention, the vocabulary in
chunks for the head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
ROW_CHUNK = 1024
Q_BLOCK = 128
PAD = 1024


def _q8(x: jax.Array, axis) -> jax.Array:
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, base):
    """x (T, heads, d), pos (T,): rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dims(cfg):
    dsa = cfg.get("dsa")
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["vocab_size"], cfg["hidden_act"] == "silu",
            float(cfg["rope_theta"]),
            float(cfg.get("rms_norm_eps", cfg.get("norm_eps"))),
            None if dsa is None else (dsa["index_heads"],
                                      dsa["index_head_dim"], dsa["top_k"]))


def _chunks(fn, xs, n):
    """Map ``fn`` over ``n``-row chunks of every array in ``xs``."""
    T = jax.tree.leaves(xs)[0].shape[0]
    r = jax.tree.map(lambda a: a.reshape((T // n, n) + a.shape[1:]), xs)
    out = jax.lax.map(fn, r)
    return jax.tree.map(lambda a: a.reshape((T,) + a.shape[2:]), out)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer(h, w, l, pos, *, dims, mode):
    L, D, H, KVH, dh, V, gated, base, eps, dsa = dims
    lw = {k: jax.lax.dynamic_index_in_dim(v, l, 0, keepdims=False)
          for k, v in w.items() if v.ndim >= 2 and v.shape[0] == L}
    T = h.shape[0]
    G = H // KVH

    def project(args):
        hc, pc = args
        a = _rms(hc, lw["attn_norm"], eps)
        q = _rope(_mm(a, lw["wq"], mode).reshape(-1, H, dh), pc, base)
        k = _rope(_mm(a, lw["wk"], mode).reshape(-1, KVH, dh), pc, base)
        v = _mm(a, lw["wv"], mode).reshape(-1, KVH, dh)
        out = dict(q=q, k=k, v=v)
        if dsa is not None:
            hi, di, _ = dsa
            out["qi"] = _mm(a, lw["wq_idx"], mode).reshape(-1, hi, di)
            out["ki"] = _mm(a, lw["wk_idx"], mode)
            out["wh"] = jax.nn.softmax(_mm(a, lw["w_head"], mode), -1)
        return out

    p = _chunks(project, (h, pos), ROW_CHUNK)

    def attend(args):
        qb, pb, extra = args
        causal = pos[None, :] <= pb[:, None]                  # (Bq, T)
        sel = causal
        if dsa is not None:
            hi, di, top_k = dsa
            dots = jnp.einsum("qhd,td->qht", extra["qi"], p["ki"],
                              precision=HIGHEST)
            score = jnp.einsum("qht,qh->qt", jax.nn.relu(dots) * di ** -0.5,
                               extra["wh"], precision=HIGHEST)
            score = jnp.where(causal, score, -jnp.inf)
            k = min(top_k, T)
            kth = jax.lax.top_k(score, k)[0][:, -1:]
            # ties at the k-th score go to the lowest positions (ReLU
            # makes exact zeros common where few heads score positive)
            above = score > kth
            tie = score == kth
            need = k - above.sum(-1, keepdims=True)
            sel = causal & (above | (tie & (jnp.cumsum(tie, -1) <= need)))
        qg = qb.reshape(-1, KVH, G, dh)
        s = jnp.einsum("qkgd,tkd->kgqt", qg, p["k"], precision=HIGHEST)
        s = jnp.where(sel[None, None], s * dh ** -0.5, -jnp.inf)
        o = jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(s, -1), p["v"],
                       precision=HIGHEST)
        return o.reshape(-1, H * dh)

    extra = {k: p[k] for k in ("qi", "wh") if k in p}
    att = _chunks(attend, (p["q"], pos, extra), Q_BLOCK)

    def finish(args):
        hc, ac = args
        hc = hc + _mm(ac, lw["wo"], mode)
        m = _rms(hc, lw["mlp_norm"], eps)
        up = _mm(m, lw["w_up"], mode)
        act = jax.nn.silu(_mm(m, lw["w_gate"], mode)) * up if gated \
            else jnp.square(jax.nn.relu(up))
        return hc + _mm(act, lw["w_down"], mode)

    return _chunks(finish, (h, att), ROW_CHUNK)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(h, scale, *, eps):
    return _rms(h, scale, eps)


@functools.partial(jax.jit, static_argnames=("mode", "vchunk"))
def _head(h, unembed, *, mode, vchunk):
    D, V = unembed.shape
    ws = unembed.reshape(D, V // vchunk, vchunk).transpose(1, 0, 2)
    out = jax.lax.map(lambda wc: _mm(h, wc, mode), ws)    # (nv, R, vchunk)
    return out.transpose(1, 0, 2).reshape(h.shape[0], V)


def hidden(w: dict, cfg: dict, tokens: np.ndarray, mode: str = "f32"
           ) -> jax.Array:
    """Final-normed hidden states (T, D) float32 of a token sequence."""
    dims = _dims(cfg)
    T = len(tokens)
    Tp = -(-T // PAD) * PAD           # causal: padding at the end is inert
    tok = np.zeros((Tp,), np.int32)
    tok[:T] = tokens
    h = w["embedding"][jnp.asarray(tok)].astype(jnp.float32)
    pos = jnp.arange(Tp, dtype=jnp.int32)
    stacked = {k: v for k, v in w.items() if k not in
               ("embedding", "unembed", "final_norm")}
    for l in range(dims[0]):
        h = _layer(h, stacked, jnp.int32(l), pos, dims=dims, mode=mode)
    return _final(h[:T], w["final_norm"], eps=dims[8])


def logits(w: dict, cfg: dict, h_rows: jax.Array, mode: str = "f32"
           ) -> jax.Array:
    """Next-token logits (R, V) float32 for rows of ``hidden``."""
    V = cfg["vocab_size"]
    vchunk = next(c for c in (16000, 8000, 4000, 1000, 500, 250, 100, V)
                  if V % c == 0)
    return _head(h_rows, w["unembed"], mode=mode, vchunk=vchunk)

"""Mixture-of-Experts FFN with expert-parallel sharding.

Two execution paths:

* ``dense`` — every expert computed for every token via capacity-free einsum
  over a small expert count.  Used by smoke configs (<=4 experts) and as the
  numerical oracle for the EP path.
* ``expert_parallel`` — production path.  Experts are sharded over the
  'model' mesh axis (EP = paper §3.6.2 "EP64" analogue).  Activations are
  replicated across 'model' (DP-attention style, exactly the paper's serving
  layout): each model-rank selects the tokens routed to ITS experts with a
  capacity-bounded sort-free dispatch (gather), runs a batched expert GEMM,
  and the partial outputs are combined with a psum over 'model'.  Lowers to
  one all-reduce per MoE layer — visible in the roofline collective term.

Router: softmax top-k with normalized gates + load-balance auxiliary loss
(Switch-style, coefficient cfg.router_aux_coef).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.rules import Builder


def build_moe(b: Builder, cfg: ModelConfig):
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    b.param("router", (D, E), ("embed", None), scale=0.02)
    b.param("w_gate", (E, D, F), ("experts", "embed_fsdp", "moe_mlp"))
    b.param("w_up", (E, D, F), ("experts", "embed_fsdp", "moe_mlp"))
    b.param("w_down", (E, F, D), ("experts", "moe_mlp", "embed_fsdp"))
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        b.param("ws_gate", (D, Fs), ("embed_fsdp", "mlp"))
        b.param("ws_up", (D, Fs), ("embed_fsdp", "mlp"))
        b.param("ws_down", (Fs, D), ("mlp", "embed_fsdp"))


def router_topk(params, x: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (T, D) -> gates (T, k), expert ids (T, k), aux loss (scalar)."""
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: E * sum_e f_e * p_e
    E = cfg.num_experts
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)  # (T, E)
    ce = jnp.mean(one_hot, axis=0) / cfg.experts_per_token
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(w_gate, w_up, w_down, x, activation: str):
    """x (E, C, D) through per-expert SwiGLU: returns (E, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", x, w_up)
    if activation == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", x, w_gate)
        h = jax.nn.silu(g) * h
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_dense(params, x2d: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, jax.Array]:
    """Oracle path: run all experts on all tokens (small E only)."""
    gates, idx, aux = router_topk(params, x2d, cfg)
    outs = _expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                       jnp.broadcast_to(x2d, (cfg.num_experts,) + x2d.shape),
                       cfg.mlp_activation)                     # (E, T, D)
    sel = jnp.take_along_axis(
        outs.transpose(1, 0, 2),                               # (T, E, D)
        idx[..., None].astype(jnp.int32), axis=1)              # (T, k, D)
    y = jnp.sum(sel * gates[..., None].astype(sel.dtype), axis=1)
    return y.astype(x2d.dtype), aux


def _dispatch_local(idx: jax.Array, gates: jax.Array, T: int,
                    e_lo: jax.Array, E_local: int, capacity: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """Capacity-bounded dispatch for the experts in [e_lo, e_lo+E_local).

    ``e_lo`` may be traced (axis_index inside shard_map); ``E_local`` must
    be static.  Returns (token_gather_idx (E_local, C), slot_gate
    (E_local, C)).  Tokens over capacity are dropped (capacity_factor
    guards this).
    """
    flat_e = idx.reshape(-1)                     # (T*k,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), idx.shape[1])
    local = (flat_e >= e_lo) & (flat_e < e_lo + E_local)
    le = jnp.where(local, flat_e - e_lo, E_local)     # E_local = trash row
    # position of each assignment within its expert (stable order)
    onehot = jax.nn.one_hot(le, E_local + 1, dtype=jnp.int32)   # (Tk, El+1)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    slot = (pos.sum(-1) - 1)                      # (Tk,) 0-based within expert
    ok = local & (slot < capacity)
    dest = jnp.where(ok, le * capacity + slot, E_local * capacity)
    gather_tok = jnp.full((E_local * capacity + 1,), T, jnp.int32)
    gather_tok = gather_tok.at[dest].set(jnp.where(ok, flat_t, T))
    gate_buf = jnp.zeros((E_local * capacity + 1,), flat_g.dtype)
    gate_buf = gate_buf.at[dest].set(jnp.where(ok, flat_g, 0.0))
    return (gather_tok[:-1].reshape(E_local, capacity),
            gate_buf[:-1].reshape(E_local, capacity))


def _moe_ep_shard(x2d, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
                  model_axis: str):
    """shard_map body: x2d (T, D) replicated over model; expert weights local."""
    E_local = w_up.shape[0]
    rank = jax.lax.axis_index(model_axis)
    e_lo = rank * E_local
    T = x2d.shape[0]
    params = {"router": router_w}
    gates, idx, aux = router_topk(params, x2d, cfg)
    capacity = max(1, int(math.ceil(
        T * cfg.experts_per_token / cfg.num_experts * cfg.capacity_factor)))
    tok_idx, slot_gate = _dispatch_local(idx, gates, T, e_lo, E_local,
                                         capacity)
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, x2d.shape[1]), x2d.dtype)], 0)
    xe = x_pad[tok_idx]                                   # (E_local, C, D)
    ye = _expert_ffn(w_gate, w_up, w_down, xe, cfg.mlp_activation)
    # combine: scatter-add weighted outputs back to token positions
    y = jnp.zeros((T + 1, x2d.shape[1]), jnp.float32)
    y = y.at[tok_idx.reshape(-1)].add(
        (ye * slot_gate[..., None].astype(ye.dtype)
         ).reshape(-1, ye.shape[-1]).astype(jnp.float32))
    y = jax.lax.psum(y[:T], model_axis)
    return y.astype(x2d.dtype), aux


def apply_moe(params, x: jax.Array, cfg: ModelConfig, *,
              mesh: Optional[Mesh] = None) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (y (B, S, D), aux loss scalar)."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    batch_axes_sz = 1
    if mesh is not None:
        for a in ("pod", "data"):
            batch_axes_sz *= _axis(mesh, a)
    use_ep = (cfg.moe_impl == "expert_parallel" or
              (cfg.moe_impl == "auto" and mesh is not None
               and "model" in mesh.axis_names
               and cfg.num_experts % _axis(mesh, "model") == 0
               and _axis(mesh, "model") > 1)) \
        and (B * S) % max(batch_axes_sz, 1) == 0
    if use_ep:
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

        def body(x2d_l, router_w, w_gate, w_up, w_down):
            y, aux = _moe_ep_shard(x2d_l, router_w, w_gate, w_up, w_down,
                                   cfg, "model")
            if batch_axes:
                aux = jax.lax.pmean(aux, batch_axes)
            return y, aux

        in_specs = (P(batch_axes if batch_axes else None, None),
                    P(None, None),
                    P("model", None, None), P("model", None, None),
                    P("model", None, None))
        out_specs = (P(batch_axes if batch_axes else None, None), P())
        y2d, aux = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(x2d, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])
    else:
        y2d, aux = _moe_dense(params, x2d, cfg)

    if cfg.num_shared_experts:
        h = x2d @ params["ws_up"]
        g = x2d @ params["ws_gate"]
        y2d = y2d + (jax.nn.silu(g) * h) @ params["ws_down"]
    return y2d.reshape(B, S, D), aux * cfg.router_aux_coef


def _axis(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)

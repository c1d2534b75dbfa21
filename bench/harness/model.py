"""Weights from the seed, and the program built to a configuration file.

The benchmark draws the weights itself, in its own flat layout, on the
device in one jitted call, in the type they are served in.  The program
gets the same arrays re-nested into its parameter tree (no copy); the
plain reference reads the flat layout and imports nothing of the program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.harness.work import dims


def norm_eps(cfg: dict) -> float:
    return float(cfg.get("rms_norm_eps", cfg.get("norm_eps")))


def weight_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat layout: name -> (shape, init std); per-layer leaves are stacked
    on a leading layer axis.  A norm scale has std 0 about a mean of 1."""
    d = dims(cfg)
    L, D, H, KVH, dh, F, V = (d[k] for k in "L D H KVH dh F V".split())
    s = {"embedding": ((V, D), 1.0), "unembed": ((D, V), D ** -0.5),
         "final_norm": ((D,), None),
         "attn_norm": ((L, D), None), "mlp_norm": ((L, D), None),
         "wq": ((L, D, H * dh), D ** -0.5), "wk": ((L, D, KVH * dh), D ** -0.5),
         "wv": ((L, D, KVH * dh), D ** -0.5),
         "wo": ((L, H * dh, D), (H * dh) ** -0.5),
         "w_up": ((L, D, F), D ** -0.5), "w_down": ((L, F, D), F ** -0.5)}
    if d["gated"]:
        s["w_gate"] = ((L, D, F), D ** -0.5)
    if d["dsa"]:
        hi, di = d["dsa"]["index_heads"], d["dsa"]["index_head_dim"]
        s.update(wq_idx=((L, D, hi * di), D ** -0.5),
                 wk_idx=((L, D, di), D ** -0.5),
                 w_head=((L, D, hi), D ** -0.5))
    return s


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Every weight from ``seed``, drawn on the device under one jit.
    Norm scales are 1 + 0.1*N(0,1), so a misapplied scale shows."""
    shapes = weight_shapes(cfg)
    names = sorted(shapes)

    def draw(key):
        # drawn in the served dtype: no float32 copy of a leaf ever exists,
        # which a 12 GB model on a 16 GB chip could not hold
        keys = jax.random.split(key, len(names))
        out = {}
        for k, n in zip(keys, names):
            shape, std = shapes[n]
            z = jax.random.normal(k, shape, dtype)
            out[n] = (1 + jnp.asarray(0.1, dtype) * z if std is None
                      else jnp.asarray(std, dtype) * z)
        return out

    # the seed may exceed 32 bits: fold its high and low words in turn
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(draw)(key)


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, checked against
    every size the file states."""
    from repro.configs import get_config
    from repro.configs.base import DSAConfig
    prog = cfg["program"]
    pc = get_config(prog["arch"])
    kw = dict(prog.get("set", {}))
    if "dsa" in kw and kw["dsa"] is not None:
        kw["dsa"] = DSAConfig(**kw["dsa"])
    pc = pc.replace(**kw)
    d = dims(cfg)
    want = dict(num_layers=d["L"], d_model=d["D"], num_heads=d["H"],
                num_kv_heads=d["KVH"], head_dim=d["dh"], d_ff=d["F"],
                vocab_size=d["V"], rope_base=float(cfg["rope_theta"]),
                norm_eps=norm_eps(cfg),
                mlp_activation="swiglu" if d["gated"] else cfg["hidden_act"],
                tie_embeddings=bool(cfg["tie_word_embeddings"]),
                attention_type="gqa", attention_pattern=("global",),
                first_k_dense=0, num_experts=0, qk_norm=False,
                attn_logit_softcap=0.0, final_logit_softcap=0.0,
                sliding_window=0, mtp=None)
    bad = {k: (getattr(pc, k), v) for k, v in want.items()
           if getattr(pc, k) != v}
    if (pc.dsa is None) != (d["dsa"] is None):
        bad["dsa"] = (pc.dsa, d["dsa"])
    elif pc.dsa is not None:
        for k in ("index_heads", "index_head_dim", "top_k"):
            if getattr(pc.dsa, k) != d["dsa"][k]:
                bad[f"dsa.{k}"] = (getattr(pc.dsa, k), d["dsa"][k])
        if pc.dsa.selector != "token" or not pc.dsa.deterministic_topk:
            bad["dsa.selector"] = (pc.dsa.selector, "token")
    if float(cfg.get("partial_rotary_factor", 1.0)) != 1.0:
        bad["partial_rotary_factor"] = (1.0, cfg["partial_rotary_factor"])
    if bad:
        raise ValueError(f"program config {prog} departs from "
                         f"{cfg['name']}: {bad}")
    return pc


def program_params(cfg: dict, w: Dict[str, jax.Array], pc) -> dict:
    """Re-nest the flat weights into the program's parameter tree, and
    check the tree against ``model.init``'s abstract shapes."""
    from repro.models import get_model
    tree = {"embed": {"embedding": w["embedding"], "unembed": w["unembed"]},
            "final_norm": w["final_norm"],
            "slot0": {"attn_norm": w["attn_norm"], "mlp_norm": w["mlp_norm"],
                      "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                      "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")
                              if k in w}}}
    if "wq_idx" in w:
        tree["slot0"]["idx"] = {k: w[k] for k in ("wq_idx", "wk_idx",
                                                  "w_head")}
    model = get_model(pc)
    want = jax.eval_shape(lambda k: model.init(k, pc, w["wq"].dtype)[0],
                          jax.random.key(0))
    got_s = jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    want_s = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_s != want_s:
        raise ValueError(f"parameter tree mismatch:\n{got_s}\nvs\n{want_s}")
    return tree


def kv_token_bytes(cfg: dict) -> int:
    """Bytes of paged KV state per token (all layers, bf16)."""
    d = dims(cfg)
    per = 2 * d["KVH"] * d["dh"] + (d["dsa"]["index_head_dim"]
                                    if d["dsa"] else 0)
    return d["L"] * per * 2

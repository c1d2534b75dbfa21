"""Operations and bytes the algorithm needs, from shapes and live lengths.

These count what the mathematics requires, not what a kernel happens to
read or compute, so a later kernel cannot make them stale.  ``cfg`` is a
configuration file's contents (Hugging Face key names).
"""
from __future__ import annotations

BF16 = 2


def dims(cfg: dict) -> dict:
    d = dict(L=cfg["num_hidden_layers"], D=cfg["hidden_size"],
             H=cfg["num_attention_heads"], KVH=cfg["num_key_value_heads"],
             dh=cfg["head_dim"], F=cfg["intermediate_size"],
             V=cfg["vocab_size"], gated=cfg["hidden_act"] == "silu")
    dsa = cfg.get("dsa")
    d["dsa"] = dsa
    return d


def indexer_decode(cfg: dict, ctx: int) -> tuple:
    """One query row of the DSA indexer over ``ctx`` keys, one layer:
    (flops, bytes).  2*Hi*Di*ctx multiply-adds; reads the ctx cached
    index keys (Di bf16 each), the query (Hi*Di) and head weights (Hi,
    fp32), writes ctx fp32 scores."""
    dsa = cfg["dsa"]
    hi, di = dsa["index_heads"], dsa["index_head_dim"]
    flops = 2 * hi * di * ctx
    nbytes = ctx * di * BF16 + hi * di * BF16 + hi * 4 + ctx * 4
    return flops, nbytes


def gqa_decode(cfg: dict, ctx: int) -> tuple:
    """One query row of GQA attention over ``ctx`` cached keys, one
    layer: (flops, bytes).  4*H*d*ctx (QK^T and PV); reads the live K and
    V (ctx*KVH*d bf16 each) and the query, writes the output."""
    d = dims(cfg)
    flops = 4 * d["H"] * d["dh"] * ctx
    nbytes = 2 * ctx * d["KVH"] * d["dh"] * BF16 + 2 * d["H"] * d["dh"] * BF16
    return flops, nbytes


def params_per_token(cfg: dict) -> int:
    """Non-embedding parameters plus the LM head (each used once per
    token: 2 FLOPs per parameter)."""
    d = dims(cfg)
    D, H, KVH, dh, F = d["D"], d["H"], d["KVH"], d["dh"], d["F"]
    attn = D * H * dh * 2 + D * KVH * dh * 2
    mlp = D * F * (3 if d["gated"] else 2)
    idx = 0
    if d["dsa"]:
        hi, di = d["dsa"]["index_heads"], d["dsa"]["index_head_dim"]
        idx = D * hi * di + D * di + D * hi
    return d["L"] * (attn + mlp + idx) + D * d["V"]


def token_flops(cfg: dict, pos: int) -> float:
    """Model FLOPs of computing one token at absolute position ``pos``:
    2*(non-embedding params + LM head), attention over min(ctx, top_k)
    keys (all ctx without DSA) and the indexer over ctx, ctx = pos + 1."""
    d = dims(cfg)
    ctx = pos + 1
    keys = min(ctx, d["dsa"]["top_k"]) if d["dsa"] else ctx
    per_layer = 4 * d["H"] * d["dh"] * keys
    if d["dsa"]:
        per_layer += 2 * d["dsa"]["index_heads"] * \
            d["dsa"]["index_head_dim"] * ctx
    return 2.0 * params_per_token(cfg) + d["L"] * per_layer


def span_flops(cfg: dict, start: int, n: int) -> float:
    """Model FLOPs of a prefill span of ``n`` tokens from ``start``."""
    d = dims(cfg)
    base = 2.0 * params_per_token(cfg) * n
    # sum over positions p in [start, start+n) of keys(p) and ctx(p)
    ctx_sum = n * start + n * (n + 1) // 2
    if d["dsa"]:
        k = d["dsa"]["top_k"]
        keys_sum = sum(min(p + 1, k) for p in range(start, start + n)) \
            if start < k else n * k
        idx = 2 * d["dsa"]["index_heads"] * d["dsa"]["index_head_dim"] \
            * ctx_sum
    else:
        keys_sum, idx = ctx_sum, 0
    return base + d["L"] * (4 * d["H"] * d["dh"] * keys_sum + idx)

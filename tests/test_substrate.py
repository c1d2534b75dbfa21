"""Substrate tests: sharding rules, Muon optimizer, losses, data pipeline,
checkpointing, MTP, context management."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.core.mtp import speculative_accept_length
from repro.models import get_model
from repro.models.losses import chunked_softmax_xent
from repro.optim import muon
from repro.sharding.rules import Builder, make_rules, resolve_spec


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_resolve_spec_divisibility_guard():
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    rules = {"heads": "model"}
    # size-1 axes always divide; use a fake 16-way mesh via rules math
    spec = resolve_spec(("heads", None), (8, 4), rules, mesh)
    assert isinstance(spec, P)


def test_builder_specs_mirror_params():
    b = Builder(jax.random.key(0))
    b.param("w", (4, 8), ("embed", "mlp"))
    sub = b.sub("inner")
    sub.param("v", (8,), ("mlp",))
    assert set(b.params) == set(b.specs) == {"w", "inner"}
    assert b.specs["inner"]["v"] == ("mlp",)
    assert b.params["inner"]["v"].shape == (8,)


def test_abstract_init_no_materialization():
    cfg = get_smoke_config("kimi_k2_1t")
    model = get_model(cfg)
    params, specs = model.init(jax.random.key(0), cfg, abstract=True)
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Muon
# ---------------------------------------------------------------------------

def test_newton_schulz_orthogonalizes():
    X = jax.random.normal(jax.random.key(0), (64, 32))
    O = muon.newton_schulz(X)
    sv = jnp.linalg.svd(O, compute_uv=False)
    assert 0.3 < float(sv.min()) and float(sv.max()) < 1.6


def test_muon_split_per_head():
    """Muon-Split must orthogonalize each head slice independently: the
    per-head slices of the direction should each be near-orthogonal."""
    cfg = ModelConfig(num_heads=4, num_kv_heads=4, d_model=64, head_dim=16)
    m_buf = jax.random.normal(jax.random.key(1), (64, 64))  # (D, H*dh)
    d_split = muon._muon_direction(m_buf, ("embed_fsdp", "heads"), cfg,
                                   split=True)
    d_fused = muon._muon_direction(m_buf, ("embed_fsdp", None), cfg,
                                   split=True)
    assert d_split.shape == d_fused.shape == (64, 64)
    assert not np.allclose(np.asarray(d_split), np.asarray(d_fused))
    for h in range(4):
        sl = d_split[:, h * 16:(h + 1) * 16]
        sv = jnp.linalg.svd(sl / muon._rms_scale((64, 16)),
                            compute_uv=False)
        assert float(sv.max()) < 1.6 and float(sv.min()) > 0.3


def test_muon_trains_tiny_model():
    cfg = get_smoke_config("yi_6b").replace(dsa=None)
    model = get_model(cfg)
    params, specs = model.init(jax.random.key(0), cfg)
    state = muon.init(params)
    tok = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:]}

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(lambda pp: model.loss(pp, batch, cfg)[0])(p)
        g, _ = muon.global_norm_clip(g, 1.0)
        p, s = muon.update(p, g, specs, s, lr=3e-3, cfg=cfg)
        return p, s, l

    losses = []
    for _ in range(6):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.9


# ---------------------------------------------------------------------------
# chunked CE loss
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.sampled_from([32, 64, 128]),
       st.sampled_from([16, 32, 64]))
def test_chunked_ce_equals_unchunked(B, S, chunk):
    D, V = 16, 97
    ks = jax.random.split(jax.random.key(B * S + chunk), 3)
    h = jax.random.normal(ks[0], (B, S, D))
    w = jax.random.normal(ks[1], (D, V)) * 0.1
    t = jax.random.randint(ks[2], (B, S), 0, V)
    m = (t % 3 != 0).astype(jnp.float32)
    l1, c1 = chunked_softmax_xent(h, w, t, m, chunk=chunk)
    l2, c2 = chunked_softmax_xent(h, w, t, m, chunk=S)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    assert float(c1) == float(c2)


# ---------------------------------------------------------------------------
# MTP
# ---------------------------------------------------------------------------

def test_accept_length():
    drafts = jnp.array([[5, 6, 7], [5, 9, 7], [1, 2, 3]])
    verify = jnp.array([[5, 6, 7], [5, 6, 7], [9, 9, 9]])
    acc = speculative_accept_length(drafts, verify)
    np.testing.assert_array_equal(np.asarray(acc), [4, 2, 1])


def test_mtp_param_sharing_counts():
    cfg = get_smoke_config("glm5_744b")
    model = get_model(cfg)
    p_shared, _ = model.init(jax.random.key(0), cfg)
    cfg2 = cfg.replace(mtp=cfg.mtp.__class__(num_predict=3,
                                             share_params=False))
    p_sep, _ = model.init(jax.random.key(0), cfg2)
    n_shared = sum(x.size for x in jax.tree.leaves(p_shared["mtp"]))
    n_sep = sum(x.size for x in jax.tree.leaves(p_sep["mtp"]))
    assert n_sep > 2.5 * n_shared     # 3 blocks vs 1 shared block


# ---------------------------------------------------------------------------
# data + checkpoint
# ---------------------------------------------------------------------------

def test_markov_stream_deterministic_and_learnable():
    from repro.data.synthetic import markov_stream
    a = next(markov_stream(64, 32, 4, seed=7))
    b = next(markov_stream(64, 32, 4, seed=7))
    np.testing.assert_array_equal(a, b)


def test_needle_batch_targets():
    from repro.data.needle import needle_accuracy, needle_batch
    nb = needle_batch(4, 256, 128, seed=3)
    # oracle predictions = the true next tokens -> accuracy 1
    preds = np.roll(nb.tokens, -1, axis=1)
    assert needle_accuracy(preds, nb) == 1.0


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import io as ck
    cfg = get_smoke_config("whisper_base")
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    ck.save(tmp_path / "step_1", {"params": params}, step=1)
    restored, step = ck.restore(tmp_path / "step_1", {"params": params})
    assert step == 1
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# context management
# ---------------------------------------------------------------------------

def test_keep_recent_folds_old_observations():
    from repro.agents.context_mgmt import (Context, KeepRecentK, Round,
                                           FOLDED)
    ctx = Context(question="q", q_tokens=10)
    strat = KeepRecentK(2)
    for i in range(5):
        ctx = strat.add_round(ctx, Round("r", "a", f"obs{i}", 5, 2, 100))
    assert sum(r.observation == FOLDED for r in ctx.rounds) == 3
    assert ctx.rounds[-1].observation == "obs4"


def test_hierarchical_discards_over_threshold():
    from repro.agents.context_mgmt import Context, Hierarchical, Round
    strat = Hierarchical(k=2, threshold=300)
    ctx = Context(question="q", q_tokens=10)
    for i in range(10):
        ctx = strat.add_round(ctx, Round("r", "a", "o", 30, 10, 50))
    assert ctx.restarts >= 1
    assert strat.keep.k == 2


# ---------------------------------------------------------------------------
# MoE expert-parallel path
# ---------------------------------------------------------------------------

def test_moe_ep_matches_dense_oracle():
    """The shard_map EP dispatch (capacity-bounded gather + psum combine)
    must equal the dense all-experts oracle when capacity is ample."""
    from repro.layers.moe import apply_moe
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_model

    cfg = get_smoke_config("qwen3_moe_235b").replace(capacity_factor=8.0)
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    lp = jax.tree.map(lambda x: x[0], params["slot0"])["moe"]
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model)) * 0.1
    y_dense, aux_d = apply_moe(lp, x, cfg.replace(moe_impl="dense"),
                               mesh=None)
    mesh = make_host_mesh()
    with mesh:
        y_ep, aux_e = jax.jit(lambda l, xx: apply_moe(
            l, xx, cfg.replace(moe_impl="expert_parallel"), mesh=mesh))(lp, x)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ep),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(float(aux_d), float(aux_e), rtol=1e-5)

"""MTP speculative decode through the paged engine (PR-5 tentpole).

Covers the acceptance criteria:
  * engine greedy outputs BYTE-IDENTICAL with ``spec_steps`` in {0, 2, 4}
    for the transformer (GQA), DSA, and MLA families — including
    mid-flight admit/retire (staggered budgets through a 2-slot engine)
    and radix-suffix admission (a shared prefix served sequentially, so
    the second request COW-forks a cached block and prefills mid-block);
  * paged rollback invariants: a hypothesis property test that
    draft-then-reject workloads conserve refcounts and the free list (no
    leaked / double-freed blocks), plus targeted rollback-across-a-block-
    boundary and rollback-on-a-COW-forked-sequence checks (shared cached
    blocks' pool bytes untouched by a speculating neighbor);
  * accept-length semantics: ``speculative_accept_length`` unit
    properties (accept of 0 / all / middle mismatch), and the offline
    measurement path: ``measure_accept_length(impl="paged")`` — the O(n)
    span-verify path — byte-matches the old ``impl="ref"`` full-re-run
    oracle (accept lengths AND spliced verify tokens);
  * composition: spec_steps under chunked prefill and AgentSession turns,
    with per-turn draft/accept accounting;
  * guards: hybrid / missing-MTP / temperature>0 are rejected loudly.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.configs.base import DSAConfig, MTPConfig
from repro.core.mtp import speculative_accept_length
from repro.core.paging import blocks_for
from repro.models import get_model
from repro.serving import ContinuousEngine, Request
from repro.serving.session import AgentSession

_KW = dict(max_batch=2, block_size=8, num_blocks=32, max_len=64)
_MTP = MTPConfig(num_predict=3, share_params=True)


def _family_cfg(name):
    if name in ("gqa", "dsa"):
        return get_smoke_config("yi_6b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
            vocab_size=256, mtp=_MTP,
            dsa=DSAConfig(index_heads=2, index_head_dim=16, top_k=32,
                          block_size=16) if name == "dsa" else None)
    if name == "mla":
        return get_smoke_config("glm5_744b").replace(
            d_model=128, num_heads=2, num_kv_heads=2, d_ff=256,
            vocab_size=256, num_experts=0, num_shared_experts=0, mtp=_MTP,
            first_k_dense=1)
    return get_smoke_config("zamba2_2p7b").replace(      # hybrid
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, ssm_state=8, dsa=None)


@functools.lru_cache(maxsize=None)
def _family_params(name):
    cfg = _family_cfg(name)
    return cfg, get_model(cfg).init(jax.random.key(0), cfg)[0]


def _workload(cfg):
    """Mid-flight churn: 4 requests with staggered prompt lengths and
    budgets through a 2-slot engine — admits/retires interleave."""
    rng = np.random.default_rng(3)
    lens = (11, 5, 17, 7)
    news = (6, 9, 3, 7)
    return [Request(prompt=rng.integers(3, cfg.vocab_size, size=k)
                    .astype(np.int32), max_new=m)
            for k, m in zip(lens, news)]


def _serve_workload(cfg, params, spec):
    eng = ContinuousEngine(cfg, params, spec_steps=spec, **_KW)
    reqs = _workload(cfg)
    eng.serve(reqs)
    # radix-suffix admission: a second serve whose prompt extends the
    # first request's (now cached) prompt — match ends mid-block, COW fork
    tail = np.asarray([7, 9, 11], np.int32)
    suffix_req = Request(
        prompt=np.concatenate([reqs[0].prompt, tail]), max_new=5)
    eng.serve([suffix_req])
    return [r.out for r in reqs] + [suffix_req.out], eng


@functools.lru_cache(maxsize=None)
def _oracle_outputs(name):
    cfg, params = _family_params(name)
    outs, _ = _serve_workload(cfg, params, 0)
    return outs


# ---------------------------------------------------------------------------
# byte-identical greedy, spec on vs off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["gqa", "dsa", "mla"])
@pytest.mark.parametrize("spec", [2, 4])
def test_engine_spec_greedy_byte_identical(family, spec):
    cfg, params = _family_params(family)
    outs, eng = _serve_workload(cfg, params, spec)
    for a, b in zip(_oracle_outputs(family), outs):
        np.testing.assert_array_equal(a, b)
    # speculation actually ran, and its bookkeeping is sane
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["draft_tokens"] > 0
    assert eng.stats["accepted_tokens"] >= eng.stats["spec_rounds"]
    assert eng.stats["accepted_tokens"] <= \
        eng.stats["draft_tokens"] + eng.stats["spec_rounds"]
    assert 1.0 <= eng.rolling_accept_length <= spec + 1


def test_engine_spec_fewer_steps_when_accepting():
    """With drafts forced to the model's own greedy (share the trunk
    weights' continuation via a spy), every draft accepts — here we only
    check the structural consequence on a real model: scheduler steps with
    spec on never exceed spec off, and decode_tokens match total out."""
    cfg, params = _family_params("gqa")
    _, e0 = _serve_workload(cfg, params, 0)
    _, e4 = _serve_workload(cfg, params, 4)
    assert e4.stats["steps"] <= e0.stats["steps"]
    assert e4.stats["decode_tokens"] >= e4.stats["accepted_tokens"]


def test_engine_spec_composes_with_chunked_prefill():
    cfg, params = _family_params("gqa")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(3, cfg.vocab_size, size=k).astype(np.int32)
               for k in (19, 11)]

    def serve(spec, chunk):
        eng = ContinuousEngine(cfg, params, spec_steps=spec,
                               prefill_chunk=chunk, **_KW)
        reqs = [Request(prompt=p, max_new=6) for p in prompts]
        eng.serve(reqs)
        return [r.out for r in reqs]

    ref = serve(0, None)
    for a, b in zip(ref, serve(4, 8)):      # chunked prefill + spec
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_spec_rejects_hybrid():
    cfg = _family_cfg("hybrid")
    with pytest.raises(ValueError, match="hybrid"):
        ContinuousEngine(cfg, None, spec_steps=2, **_KW)


def test_spec_requires_mtp_head():
    cfg = _family_cfg("gqa").replace(mtp=None)
    with pytest.raises(ValueError, match="MTP"):
        ContinuousEngine(cfg, None, spec_steps=2, **_KW)


def test_spec_rejects_sampled_requests():
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spec_steps=2, **_KW)
    with pytest.raises(ValueError, match="greedy"):
        eng.submit(Request(prompt=np.asarray([5, 6], np.int32),
                           max_new=2, temperature=0.7))


def test_spec_rejects_unshared_depth_overflow():
    cfg = _family_cfg("gqa").replace(
        mtp=MTPConfig(num_predict=2, share_params=False))
    with pytest.raises(ValueError, match="share_params"):
        ContinuousEngine(cfg, None, spec_steps=4, **_KW)


# ---------------------------------------------------------------------------
# accept-length semantics
# ---------------------------------------------------------------------------

def test_accept_length_unit_properties():
    v = jnp.asarray([[4, 5, 6, 7]] * 4)
    drafts = jnp.asarray([
        [4, 5, 6, 7],        # all accepted
        [9, 5, 6, 7],        # slot 0 mismatch
        [4, 5, 9, 7],        # middle mismatch: trailing match ignored
        [4, 5, 6, 9],        # last mismatch
    ])
    acc = np.asarray(speculative_accept_length(drafts, v))
    np.testing.assert_array_equal(acc, [5, 1, 3, 4])


def test_measure_paged_matches_ref_oracle():
    """The O(n)-per-round paged verification path (span prefill through
    the block table) reproduces the old O(prefix^2) full-re-run oracle:
    same accept lengths, byte-identical spliced verify tokens."""
    from repro.serving.speculative import measure_accept_length
    cfg, params = _family_params("gqa")
    prompts = jax.random.randint(jax.random.key(2), (2, 12), 3,
                                 cfg.vocab_size)
    ref = measure_accept_length(params, cfg, prompts, n_steps=2,
                                impl="ref")
    pag = measure_accept_length(params, cfg, prompts, n_steps=2,
                                impl="paged")
    assert ref["accept_length"] == pytest.approx(pag["accept_length"])
    np.testing.assert_array_equal(ref["tokens"], pag["tokens"])
    assert 1.0 <= pag["accept_length"] <= 1 + cfg.mtp.num_predict


# ---------------------------------------------------------------------------
# rollback invariants
# ---------------------------------------------------------------------------

def _check_conservation(eng):
    kv = eng.kv
    assert kv.free_blocks + kv.used_blocks == kv.num_blocks
    assert len(set(kv._free)) == kv.free_blocks          # no double-free
    assert all(c >= 1 for c in kv._ref.values())         # no zombie refs


def test_spec_rollback_across_block_boundary():
    """First speculative round of a 7-token prompt (block_size 8) writes
    positions 7..11 — crossing the block-0/1 boundary; the rollback must
    truncate back to the accept point without any block changing hands."""
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spec_steps=4, prefix_cache=False,
                           **_KW)
    rng = np.random.default_rng(11)
    req = Request(prompt=rng.integers(3, cfg.vocab_size, size=7)
                  .astype(np.int32), max_new=8)
    eng.submit(req)
    used_before = None
    eng.step()                               # admit + prefill + spec round
    slot = next(i for i, s in enumerate(eng.slots) if s is not None)
    acc = eng.stats["accepted_tokens"]
    assert 1 <= acc <= 5
    assert eng.lengths[slot] == 7 + acc      # truncated to the accept point
    used_before = eng.kv.used_blocks
    _check_conservation(eng)
    while any(s is not None for s in eng.slots) or eng.waiting:
        eng.step()
        assert eng.kv.used_blocks <= used_before     # rollbacks never alloc
        _check_conservation(eng)
    # and the speculated output equals the plain-decode one
    e0 = ContinuousEngine(cfg, params, spec_steps=0, prefix_cache=False,
                          **_KW)
    ref = Request(prompt=req.prompt.copy(), max_new=8)
    e0.serve([ref])
    np.testing.assert_array_equal(ref.out, req.out)


def _block_rows(eng, block):
    """Every pool row holding ``block`` (all layers of layer-major leaves,
    ssm excluded), concatenated — the COW-isolation fingerprint."""
    stride = eng.kv.num_blocks + 1
    rows = []
    for key, sub in eng.pool.items():
        if key == "ssm":
            continue
        for leaf in jax.tree.leaves(sub):
            layers = leaf.shape[0] // stride
            base = np.arange(layers) * stride
            rows.append(np.asarray(leaf[base + block], np.float32).ravel())
    return np.concatenate(rows)


def test_spec_rollback_on_cow_fork_preserves_shared_blocks():
    """A speculating sequence admitted over a radix-cached prefix must
    never write the shared blocks: drafts and rollbacks touch only its
    COW-forked tail and lifetime blocks."""
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spec_steps=3, **_KW)
    rng = np.random.default_rng(13)
    shared = rng.integers(3, cfg.vocab_size, size=11).astype(np.int32)
    first = Request(prompt=shared, max_new=4)
    eng.serve([first])                       # retires into the radix tree
    m, mblocks = eng.prefix.match(list(map(int, shared)))
    assert m >= 8 and mblocks                # at least one full cached block
    full = mblocks[:m // eng.block_size]
    snaps = {b: _block_rows(eng, b) for b in full}
    eng.kv.release(mblocks)                  # undo the probe's retain
    second = Request(
        prompt=np.concatenate([shared, np.asarray([5, 6, 7], np.int32)]),
        max_new=6)
    eng.serve([second])                      # aliases + COW-forks + spec
    assert eng.stats["cow_forks"] >= 1
    for b, before in snaps.items():
        np.testing.assert_array_equal(_block_rows(eng, b), before)
    _check_conservation(eng)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6),
       st.lists(st.tuples(st.integers(min_value=1, max_value=20),
                          st.integers(min_value=1, max_value=10)),
                min_size=1, max_size=4),
       st.booleans())
def test_spec_workloads_conserve_blocks_and_match_oracle(seed, sizes,
                                                        share):
    """Property: any spec workload (random prompts, optionally sharing a
    radix prefix, staggered budgets — so every step drafts and rolls back)
    leaves the allocator conserved and the greedy outputs byte-identical
    to the plain-decode engine.  Engines are built PER EXAMPLE so a
    failing example reproduces standalone (shrinking must not replay
    against another example's radix/allocator state); each example serves
    its workload TWICE, so the second pass admits over the first pass's
    cached prefixes (COW forks + aliasing under speculation)."""
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spec_steps=3, **_KW)
    oracle = ContinuousEngine(cfg, params, spec_steps=0, **_KW)
    rng = np.random.default_rng(seed)
    base = rng.integers(3, cfg.vocab_size, size=9).astype(np.int32)
    prompts = []
    for plen, _ in sizes:
        if share:
            prompts.append(np.concatenate([
                base, rng.integers(3, cfg.vocab_size, size=plen)
                .astype(np.int32)]))
        else:
            prompts.append(rng.integers(3, cfg.vocab_size, size=plen)
                           .astype(np.int32))
    for _ in range(2):          # 2nd pass reuses the 1st pass's prefixes
        reqs = [Request(prompt=p.copy(), max_new=mnew)
                for p, (_, mnew) in zip(prompts, sizes)]
        refs = [Request(prompt=p.copy(), max_new=mnew)
                for p, (_, mnew) in zip(prompts, sizes)]
        eng.serve(reqs)
        _check_conservation(eng)
        oracle.serve(refs)
        for a, b in zip(refs, reqs):
            np.testing.assert_array_equal(a.out, b.out)


def test_spec_capture_logprobs_shapes():
    """Greedy TITO logprobs flow through spec rounds: one lp per emitted
    token, same convention as the plain decode path."""
    cfg, params = _family_params("gqa")
    eng = ContinuousEngine(cfg, params, spec_steps=3,
                           capture_logprobs=True, **_KW)
    req = Request(prompt=np.asarray([5, 6, 7, 8], np.int32), max_new=7)
    eng.serve([req])
    assert req.out_logprobs is not None
    assert req.out_logprobs.shape == (7,)
    assert np.all(req.out_logprobs <= 0.0)


def test_spec_steps_env_default(monkeypatch):
    cfg, params = _family_params("gqa")
    monkeypatch.setenv("REPRO_SPEC_STEPS", "2")
    eng = ContinuousEngine(cfg, params, **_KW)
    assert eng.spec_steps == 2
    monkeypatch.delenv("REPRO_SPEC_STEPS")
    assert ContinuousEngine(cfg, params, **_KW).spec_steps == 0


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------

def test_spec_agent_session_turns_byte_identical():
    cfg, params = _family_params("gqa")
    rng = np.random.default_rng(17)
    msgs = [rng.integers(3, cfg.vocab_size, size=k).astype(np.int32)
            for k in (9, 5)]

    def converse(spec):
        eng = ContinuousEngine(cfg, params, spec_steps=spec, **_KW)
        sess = AgentSession(eng)
        replies = [sess.send(m, max_new=5) for m in msgs]
        stats = dict(sess.last_turn)
        sess.close()
        return replies, stats

    ref, stats0 = converse(0)
    out, stats4 = converse(4)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
    # per-turn speculative accounting flows through the session API
    assert stats0["draft_tokens"] == 0 and stats0["accepted_tokens"] == 0
    assert stats4["draft_tokens"] > 0
    assert stats4["accepted_tokens"] >= 1

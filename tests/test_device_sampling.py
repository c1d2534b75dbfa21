"""Sampling on the device, inside the decode and prefill programs.

  * ``sample_rows`` draws from softmax(float32(logits) / T) over the whole
    vocabulary (chi-square at T = 1.0 and 0.7, keys folded from a counter
    as the engine folds them);
  * greedy rows are byte-identical to ``ServingEngine``'s host
    ``sample_token``, with sampled rows in the same batch;
  * both logprob conventions agree with the host's arithmetic within 1e-5;
  * per-row temperatures that mix and change from step to step compile
    nothing new;
  * ``device_sampled_rows`` counts the decode rows plus the first tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model
from repro.serving import ContinuousEngine, Request, ServingEngine
from repro.serving.engine import sample_rows

# chi-square 0.999 quantile at 15 degrees of freedom (a 16-id vocabulary)
_CHI2_999_DF15 = 37.697


def _tiny():
    cfg = get_smoke_config("yi_6b").replace(
        d_model=128, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab_size=256, dsa=None)
    return cfg, get_model(cfg).init(jax.random.key(0), cfg)[0]


@pytest.fixture(scope="module")
def tiny():
    return _tiny()


def _host_logprob(row, tok, temperature, convention):
    """The host's formula (``ContinuousEngine._host_sample``), float32."""
    z = row - row.max()
    if convention == "scaled":
        z = z / max(temperature, 1e-6)
    return float(z[tok] - np.log(np.exp(z).sum()))


@pytest.mark.parametrize("temperature", [1.0, 0.7])
def test_draws_follow_the_float32_softmax(temperature):
    V, B, steps = 16, 1000, 20
    logits = np.random.default_rng(0).normal(0, 1.5, V).astype(np.float32)
    rows = jnp.asarray(np.tile(logits, (B, 1)), jnp.bfloat16)
    temps = jnp.full((B,), temperature, jnp.float32)
    draw = jax.jit(lambda k, n: sample_rows(
        rows, temps, jax.random.fold_in(k, n))[0])
    key = jax.random.wrap_key_data(jnp.asarray([0, 7], jnp.uint32))
    toks = np.concatenate([np.asarray(draw(key, n)) for n in range(steps)])
    x = np.asarray(rows[0], np.float32) / temperature
    p = np.exp(x - x.max())
    p /= p.sum()
    seen = np.bincount(toks, minlength=V)
    want = p * len(toks)
    chi2 = float(((seen - want) ** 2 / want).sum())
    assert chi2 < _CHI2_999_DF15, (chi2, seen, want.round())
    # and not the distribution of another temperature
    q = np.exp(x * temperature / 0.4 - (x * temperature / 0.4).max())
    q /= q.sum()
    other = float(((seen - q * len(toks)) ** 2 / (q * len(toks))).sum())
    assert other > 10 * _CHI2_999_DF15


@pytest.mark.parametrize("convention", ["true", "scaled"])
def test_logprobs_match_the_host_formula(convention):
    rng = np.random.default_rng(1)
    V = 256
    logits = rng.normal(0, 3, (6, V)).astype(np.float32)
    temps = np.asarray([0.0, 1.0, 0.7, 0.0, 1.3, 0.25], np.float32)
    tok, lp = sample_rows(jnp.asarray(logits), jnp.asarray(temps),
                          jax.random.key(3), convention)
    tok, lp = np.asarray(tok), np.asarray(lp)
    assert tok.dtype == np.int32 and lp.dtype == np.float32
    for i in range(len(temps)):
        if temps[i] <= 0:
            assert tok[i] == logits[i].argmax()
        want = _host_logprob(logits[i], tok[i], temps[i], convention)
        assert abs(lp[i] - want) < 1e-5, (i, lp[i], want)
    none = sample_rows(jnp.asarray(logits), jnp.asarray(temps),
                       jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(none[0]), tok)
    assert not np.asarray(none[1]).any()


def test_greedy_ties_go_to_the_first_index():
    row = np.zeros((1, 32), np.float32)
    row[0, [5, 9, 20]] = 2.0
    tok, _ = sample_rows(jnp.asarray(row), jnp.zeros((1,), jnp.float32),
                         jax.random.key(0))
    assert int(tok[0]) == int(row[0].argmax()) == 5


@pytest.mark.parametrize("convention", ["true", "scaled"])
def test_engine_logprobs_match_the_host_formula(tiny, convention):
    """Through the engine: every recorded logprob is the host formula's
    on the logits the model gives for that token's context."""
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, max_batch=3, block_size=8,
                           num_blocks=24, max_len=64, seed=2**33 + 5,
                           capture_logprobs=True,
                           true_logprobs=convention == "true")
    rng = np.random.default_rng(4)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new=m, temperature=t)
            for n, m, t in [(7, 5, 1.0), (12, 4, 0.0), (5, 6, 0.7)]]
    eng.serve(reqs)
    model = get_model(cfg)
    for r in reqs:
        seq = np.concatenate([r.prompt, r.out])
        lg = np.asarray(model.logits(params, jnp.asarray(seq[None]), cfg)[0],
                        np.float32)
        for j, tok in enumerate(r.out):
            want = _host_logprob(lg[len(r.prompt) - 1 + j], tok,
                                 r.temperature, convention)
            assert abs(r.out_logprobs[j] - want) < 1e-4, (j, want)


def test_greedy_rows_match_serving_engine_beside_sampled_rows(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(5)
    spec = [(9, 6, 0.0), (14, 8, 1.0), (4, 7, 0.0), (21, 5, 0.7),
            (11, 9, 0.0)]
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n, _, _ in spec]
    eng = ContinuousEngine(cfg, params, max_batch=3, block_size=8,
                           num_blocks=32, max_len=64, prefill_chunk=8)
    reqs = [Request(prompt=p, max_new=m, temperature=t)
            for p, (_, m, t) in zip(prompts, spec)]
    eng.serve(reqs)
    oracle = ServingEngine(cfg, params, max_batch=1, max_len=64)
    for p, r in zip(prompts, reqs):
        if r.temperature > 0:
            continue
        o = Request(prompt=p, max_new=r.max_new)
        oracle.serve([o])
        assert r.out.tobytes() == o.out.tobytes()


def test_mixed_and_changing_temperatures_do_not_recompile(tiny):
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, max_batch=3, block_size=8,
                           num_blocks=32, max_len=64)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(3, cfg.vocab_size, size=n).astype(np.int32)
               for n in (6, 10, 13)]

    def serve(temps):
        reqs = [Request(prompt=p, max_new=5, temperature=t)
                for p, t in zip(prompts, temps)]
        eng.serve(reqs)
        return reqs

    serve((0.0, 0.0, 0.0))
    eng.reset_cache()
    warm = eng.stats["compiles"]
    for temps in [(1.0, 0.0, 0.7), (0.3, 1.5, 0.0), (2.0, 1.0, 0.9)]:
        serve(temps)
        eng.reset_cache()
    assert eng.stats["compiles"] == warm


@pytest.mark.parametrize("chunk", [None, 8])
def test_device_sampled_rows_counts_decode_rows_and_first_tokens(tiny,
                                                                 chunk):
    cfg, params = tiny
    eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                           num_blocks=32, max_len=64, prefill_chunk=chunk)
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=rng.integers(3, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new=m, temperature=t)
            for n, m, t in [(11, 4, 1.0), (19, 1, 0.0), (6, 7, 0.5)]]
    eng.serve(reqs)
    st = eng.stats
    assert st["device_sampled_rows"] == st["decode_tokens"] + len(reqs)
    assert st["device_sampled_rows"] == sum(r.max_new for r in reqs)


def test_programs_do_not_depend_on_the_seed(tiny):
    """The seed reaches the sampling programs as an argument: engines of
    two seeds lower to the same decode, prefill and draw programs, so one
    persistent compile cache serves every seed, and the programs keep the
    module names traces are read by."""
    cfg, params = tiny
    texts = []
    for seed in (3, 2**33 + 11):
        eng = ContinuousEngine(cfg, params, max_batch=2, block_size=8,
                               num_blocks=16, max_len=64, seed=seed,
                               capture_logprobs=True, true_logprobs=True)
        tables = jnp.asarray(eng.tables)
        dec = eng._decode.lower(
            params, jnp.zeros((2, 1), jnp.int32), eng.pool, tables,
            jnp.asarray(eng.lengths),
            *eng._sampler_args(np.zeros(2, np.float32))).as_text()
        pre = eng._prefill.lower(
            params, jnp.zeros((1, 8), jnp.int32), eng.pool, tables[:1],
            jnp.zeros((1,), jnp.int32)).as_text()
        draw = eng._draw.lower(
            jnp.zeros((1, cfg.vocab_size), jnp.float32),
            *eng._sampler_args(np.ones(1, np.float32))).as_text()
        assert dec.startswith("module @jit__decode_fn")
        assert pre.startswith("module @jit__prefill_fn")
        texts.append((dec, pre, draw))
    assert texts[0] == texts[1]

"""RL algorithm unit + property tests (GRPO/IcePop, double-sided IS,
cross-stage distillation, staleness, group padding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.rl.async_is import (async_is_loss, calibration_mask,
                               pad_or_drop_group, staleness_keep)
from repro.rl.distill import onpolicy_distill_loss
from repro.rl.grpo import group_advantages, grpo_icepop_loss, pop_mask


def test_pop_mask_bounds():
    rho = jnp.array([0.1, 0.5, 1.0, 2.0, 2.01, 10.0])
    m = pop_mask(rho, beta=2.0)
    np.testing.assert_array_equal(np.asarray(m), [0, 1, 1, 1, 0, 0])


def test_group_advantages_zero_mean_unit_std():
    r = jax.random.normal(jax.random.key(0), (8, 32)) * 3 + 1
    a = group_advantages(r)
    np.testing.assert_allclose(np.asarray(a.mean(1)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a.std(1)), 1.0, atol=1e-3)


def test_grpo_gradient_direction():
    """Positive-advantage tokens must have their logprob pushed UP."""
    B, T = 4, 8
    logp = jnp.full((B, T), -1.0)
    adv = jnp.array([1.0, 1.0, -1.0, -1.0])
    mask = jnp.ones((B, T))

    def loss(lp):
        return grpo_icepop_loss(lp, jax.lax.stop_gradient(lp),
                                jax.lax.stop_gradient(lp), adv, mask).loss

    g = jax.grad(loss)(logp)
    assert bool(jnp.all(g[:2] < 0))    # minimizing loss raises logp
    assert bool(jnp.all(g[2:] > 0))


def test_icepop_masks_mismatched_tokens():
    B, T = 2, 6
    logp = jnp.zeros((B, T))
    logp_infer = jnp.zeros((B, T)).at[0, 0].set(-5.0)  # huge mismatch
    st_ = grpo_icepop_loss(logp, logp, logp_infer, jnp.ones(B),
                           jnp.ones((B, T)))
    assert float(st_.kept_frac) == pytest.approx(11 / 12)


def test_async_is_stop_gradient_structure():
    """Gradient must flow ONLY through logπ_θ; masked tokens contribute 0."""
    B, T = 2, 4
    logp_roll = jnp.zeros((B, T))
    adv = jnp.ones(B)
    mask = jnp.ones((B, T))

    def loss(lp):
        return async_is_loss(lp, logp_roll, adv, mask).loss

    lp = jnp.zeros((B, T)).at[0, 0].set(1.0)   # ratio e^1 > 1.2 -> masked
    g = jax.grad(loss)(lp)
    assert float(g[0, 0]) == 0.0
    assert float(g[0, 1]) != 0.0


def test_distill_advantage_sign():
    """Tokens where teacher >> student get positive advantage (pushed up)."""
    B, T = 1, 4
    lp_s = jnp.full((B, T), -2.0)
    lp_t = jnp.array([[-0.5, -2.0, -4.0, -2.0]])

    def loss(lp):
        return onpolicy_distill_loss(lp, lp_t, jax.lax.stop_gradient(lp),
                                     jnp.ones((B, T))).loss

    g = jax.grad(loss)(lp_s)
    assert float(g[0, 0]) < 0      # teacher better -> raise student logp
    assert float(g[0, 2]) > 0      # teacher worse -> lower


def test_staleness():
    vmin = jnp.array([0, 3, 7, 9])
    keep = staleness_keep(vmin, current_version=10, tau=4)
    np.testing.assert_array_equal(np.asarray(keep), [False, False, True,
                                                     True])


@settings(max_examples=100, deadline=None)
@given(st.lists(st.booleans(), min_size=2, max_size=16))
def test_pad_or_drop_group_properties(valid_list):
    valid = jnp.array(valid_list)
    counts = pad_or_drop_group(valid)
    G = len(valid_list)
    n_valid = sum(valid_list)
    if n_valid > G // 2:
        assert int(counts.sum()) == G           # padded back to full group
        assert bool(jnp.all((counts == 0) | valid))  # only valid replicated
    else:
        assert int(counts.sum()) == 0           # whole group dropped


def test_calibration_mask_double_sided():
    r = jnp.array([0.5, 0.81, 1.0, 1.19, 1.3])
    m = calibration_mask(r, 0.2, 0.2)
    np.testing.assert_array_equal(np.asarray(m), [0, 1, 1, 1, 0])

"""DiPO objective properties (paper Eq. 6-8)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.dipo import dipo_loss, group_advantages
from repro.core.trajectory import RolloutBatch


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), P=st.integers(1, 4), G=st.integers(2, 6))
def test_group_advantages_zero_mean(seed, P, G):
    key = jax.random.PRNGKey(seed)
    rewards = jax.random.normal(key, (P * G,))
    group = jnp.repeat(jnp.arange(P, dtype=jnp.int32), G)
    adv = group_advantages(rewards, group, P)
    for p in range(P):
        m = float(adv[group == p].mean())
        assert abs(m) < 1e-5


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_group_advantages_std_normalised(seed):
    key = jax.random.PRNGKey(seed)
    rewards = jax.random.normal(key, (8,)) * 7.0 + 3.0
    group = jnp.zeros((8,), jnp.int32)
    adv = group_advantages(rewards, group, 1, normalize_std=True)
    assert abs(float(adv.std()) - 1.0) < 0.05


def _roll(B, L, rewards):
    return RolloutBatch(
        tokens=jnp.zeros((B, L), jnp.int32),
        steps=jnp.zeros((B, L), jnp.int32),
        prompt_mask=jnp.zeros((B, L), bool),
        valid=jnp.ones((B, L), bool),
        rewards=jnp.asarray(rewards), group=jnp.zeros((B,), jnp.int32))


def test_online_gradient_direction():
    """Online DiPO (pi_old = sg(pi)): gradient pushes up the logprob of
    positively-advantaged trajectories and down the negative ones."""
    B, L = 2, 8
    roll = _roll(B, L, [1.0, 0.0])  # adv = +0.5, -0.5
    logp0 = jnp.log(jnp.full((B, L), 0.5))

    def loss_fn(delta):
        loss, _ = dipo_loss(logp0 + delta, roll, n_groups=1)
        return loss

    g = jax.grad(loss_fn)(jnp.zeros((B, L)))
    assert bool((g[0] < 0).all())   # minimising => increase logp of winner
    assert bool((g[1] > 0).all())


def test_clipping_stops_gradient():
    """Ratios beyond 1+eps with positive advantage contribute no gradient."""
    B, L = 1, 4
    roll = _roll(B, L, [1.0])
    roll = RolloutBatch(roll.tokens, roll.steps, roll.prompt_mask,
                        roll.valid, roll.rewards, roll.group)
    old = jnp.log(jnp.full((B, L), 0.1))

    def loss_fn(lp):
        # force adv > 0 via two groups trick: single traj adv = 0 -> use
        # explicit old_logp and rewards pair
        r2 = _roll(2, L, [1.0, 0.0])
        lp2 = jnp.concatenate([lp, jnp.log(jnp.full((1, L), 0.1))])
        old2 = jnp.concatenate([old, jnp.log(jnp.full((1, L), 0.1))])
        loss, _ = dipo_loss(lp2, r2, old_logp=old2, n_groups=1, eps=0.2)
        return loss

    # ratio = exp(lp - old) = 3.0 >> 1.2 -> clipped, zero grad
    lp_hi = jnp.log(jnp.full((B, L), 0.3))
    g = jax.grad(loss_fn)(lp_hi)
    np.testing.assert_allclose(np.asarray(g), 0.0, atol=1e-7)
    # ratio inside the clip window -> nonzero grad
    lp_in = jnp.log(jnp.full((B, L), 0.105))
    g2 = jax.grad(loss_fn)(lp_in)
    assert float(jnp.abs(g2).max()) > 1e-4


def test_kl_penalty_nonnegative_and_zero_at_ref():
    B, L = 2, 8
    roll = _roll(B, L, [1.0, 0.0])
    logp = jnp.log(jax.random.uniform(jax.random.PRNGKey(0), (B, L),
                                      minval=0.05, maxval=0.9))
    _, m_same = dipo_loss(logp, roll, ref_logp=logp, n_groups=1, beta=0.1)
    assert abs(float(m_same["kl_ref"])) < 1e-6
    _, m_diff = dipo_loss(logp, roll, ref_logp=logp - 0.5, n_groups=1,
                          beta=0.1)
    assert float(m_diff["kl_ref"]) > 0


def test_seq_vs_token_aggregation():
    """Eq.6 (per-seq mean) and Eq.8 (global token mean) differ exactly when
    sequence lengths differ."""
    B, L = 2, 8
    roll = _roll(B, L, [1.0, 0.0])
    valid = roll.valid.at[1, 4:].set(False)  # seq 1 half length
    roll = RolloutBatch(roll.tokens, roll.steps, roll.prompt_mask, valid,
                        roll.rewards, roll.group)
    old = jnp.log(jnp.full((B, L), 0.2))
    lp = old + jnp.array([[0.1] * L, [0.05] * L])
    l_tok, _ = dipo_loss(lp, roll, old_logp=old, n_groups=1,
                         aggregate="token")
    l_seq, _ = dipo_loss(lp, roll, old_logp=old, n_groups=1,
                         aggregate="seq")
    assert abs(float(l_tok) - float(l_seq)) > 1e-6

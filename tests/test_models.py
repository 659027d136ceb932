"""Model-substrate unit tests: RoPE, norms, MoE, caches, SSM invariants."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import attention as attn
from repro.models import ffn, ssm
from repro.models.config import ModelConfig
from repro.models.modules import apply_rope, rmsnorm, init_rmsnorm


# --------------------------- RoPE ------------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), shift=st.integers(0, 64))
def test_rope_relative_position_invariance(seed, shift):
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (1, 1, 1, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 32))
    def dot_at(i, j):
        qi = apply_rope(q, jnp.array([[i]]), 10000.0)
        kj = apply_rope(k, jnp.array([[j]]), 10000.0)
        return float(jnp.sum(qi * kj))
    assert dot_at(5, 3) == pytest.approx(dot_at(5 + shift, 3 + shift),
                                         rel=1e-4, abs=1e-4)


def test_rope_norm_preserving():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    y = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)


# --------------------------- RMSNorm ---------------------------------------


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(0.5, 4.0))
def test_rmsnorm_scale_invariance(seed, scale):
    # exact only in the eps -> 0 limit, so keep |x| well above sqrt(eps)
    p = init_rmsnorm(16)
    x = jax.random.normal(jax.random.PRNGKey(seed), (3, 16)) + 0.5
    np.testing.assert_allclose(np.asarray(rmsnorm(p, x)),
                               np.asarray(rmsnorm(p, x * scale)),
                               atol=1e-3)


# --------------------------- MoE -------------------------------------------


@pytest.mark.parametrize("E,groups", [(4, 1), (4, 4), (16, 2)])
def test_moe_matches_dense_oracle(E, groups):
    cfg = ModelConfig(arch_type="moe", n_experts=E, top_k=2, moe_d_ff=32,
                      d_model=16, capacity_factor=8.0, moe_groups=groups,
                      n_shared_experts=1, vocab_size=64)
    p = ffn.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    y, aux = ffn.moe(p, x, cfg)
    y_ref = ffn.moe_dense_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    assert float(aux["drop_fraction"]) == 0.0
    assert float(aux["aux_loss"]) > 0


def test_moe_capacity_drops_reported():
    cfg = ModelConfig(arch_type="moe", n_experts=8, top_k=2, moe_d_ff=16,
                      d_model=16, capacity_factor=0.6, vocab_size=64)
    p = ffn.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
    _, aux = ffn.moe(p, x, cfg)
    assert float(aux["drop_fraction"]) > 0


# --------------------------- caches ----------------------------------------


def test_ring_cache_wraps():
    cache = attn.make_attn_cache(1, 4, 1, 8, 8, jnp.float32)
    k = jnp.ones((1, 2, 1, 8))
    c1 = attn.cache_write(cache, k * 1, k * 1, jnp.array([[0, 1]]))
    c2 = attn.cache_write(c1, k * 2, k * 2, jnp.array([[4, 5]]))  # wraps
    np.testing.assert_array_equal(np.asarray(c2.pos[0]), [4, 5, 2**31 - 1 if False else -1, -1])
    assert float(c2.k[0, 0, 0, 0]) == 2.0


def test_write_prefill_cache_tail_only():
    cache = attn.make_attn_cache(1, 4, 1, 8, 8, jnp.float32)
    k = jnp.arange(6, dtype=jnp.float32)[None, :, None, None] * jnp.ones((1, 6, 1, 8))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    c = attn.write_prefill_cache(cache, k, k, pos)
    # ring of 4 holds the last 4 positions (2..5) at idx pos%4
    got = sorted(int(p) for p in np.asarray(c.pos[0]))
    assert got == [2, 3, 4, 5]


# --------------------------- SSM invariants --------------------------------


@pytest.mark.parametrize("kind", ["rwkv6", "mamba"])
def test_ssm_boundary_state_consistency(kind):
    """Running [block0 ++ block1] in one scan == running block1 from the
    boundary state collected after block0."""
    cfg = ModelConfig(arch_type="ssm", ssm_kind=kind, d_model=32,
                      rwkv_head_dim=8, d_state=8, vocab_size=64,
                      block_size=8)
    fwd = ssm.rwkv6_forward if kind == "rwkv6" else ssm.mamba_forward
    init = (ssm.init_rwkv6 if kind == "rwkv6" else ssm.init_mamba)(
        jax.random.PRNGKey(0), cfg)
    zero = (ssm.rwkv6_zero_state if kind == "rwkv6"
            else ssm.mamba_zero_state)(cfg, 2)
    zero = {k: v for k, v in zero.items() if k != "cm_shift"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))

    y_full, _, bounds = fwd(init, x, zero, cfg, n_blocks=2)
    state1 = jax.tree.map(lambda a: a[1], bounds)   # entry of block 1
    y_blk1, _, _ = fwd(init, x[:, 8:], state1, cfg)
    np.testing.assert_allclose(np.asarray(y_full[:, 8:]),
                               np.asarray(y_blk1), atol=2e-4)


def test_rwkv6_decay_in_unit_interval():
    cfg = ModelConfig(arch_type="ssm", ssm_kind="rwkv6", d_model=32,
                      rwkv_head_dim=8, vocab_size=64)
    p = ssm.init_rwkv6(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32)) * 5
    r, k, v, w, g, _ = ssm._rwkv6_projections(p, x,
                                              jnp.zeros((1, 32)), cfg)
    assert bool((w > 0).all() and (w < 1).all())

"""Mask algebra: visibility predicate invariants (hypothesis) + layouts."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import masks as M
from repro.kernels import ops as kops


def _rand_inputs(seed, B, L, bsz, s_max=4, prompt_blocks=1):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (B, L), 4, 100)
    steps = jax.random.randint(jax.random.fold_in(key, 1), (B, L), 0, s_max)
    valid = jnp.ones((B, L), bool)
    return tokens, steps, valid


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), strict=st.booleans(),
       bsz=st.sampled_from([4, 8]))
def test_no_leakage_invariants(seed, strict, bsz):
    """Core soundness: no query may see (a) copy-A keys of FUTURE blocks,
    (b) same-block copy-A keys revealed at or after its own step, or
    (c) copy-B keys of other blocks."""
    B, L = 1, 32
    tokens, steps, valid = _rand_inputs(seed, B, L, bsz)
    ids, meta, _ = M.dirl_layout(tokens, steps, valid, block_size=bsz,
                                 mask_token=101)
    vis = np.asarray(M.visibility(meta, meta, strict=strict))[0]
    copy = np.asarray(meta.copy)[0]
    blk = np.asarray(meta.block)[0]
    stp = np.asarray(meta.step)[0]
    T = 2 * L
    for q in range(L, T):          # copy-B queries
        for k in range(T):
            if not vis[q, k]:
                continue
            if copy[k] == 0:
                assert blk[k] <= blk[q], "future-block leak"
                if blk[k] == blk[q]:
                    assert not strict, "strict mode must not see A same-block"
                    assert stp[k] < stp[q], "same/later-step A leak"
            else:
                assert blk[k] == blk[q], "cross-block B leak"
                if strict:
                    assert stp[k] == stp[q]
                else:
                    assert stp[k] >= stp[q]
    # copy-A queries are block-causal over copy A only
    for q in range(0, L):
        for k in range(T):
            if vis[q, k]:
                assert copy[k] == 0 and blk[k] <= blk[q]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), window=st.sampled_from([4, 8, 16]))
def test_window_composes(seed, window):
    B, L, bsz = 1, 32, 4
    tokens, steps, valid = _rand_inputs(seed, B, L, bsz)
    _, meta, _ = M.dirl_layout(tokens, steps, valid, block_size=bsz,
                               mask_token=101)
    vis = np.asarray(M.visibility(meta, meta, window=window))[0]
    pos = np.asarray(meta.pos)[0]
    q_idx, k_idx = np.nonzero(vis)
    assert ((pos[q_idx] - pos[k_idx]) < window).all()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), tq=st.sampled_from([4, 8, 16]),
       strict=st.booleans(), window=st.sampled_from([None, 8]))
def test_tile_map_conservative_and_full(seed, tq, strict, window):
    """Every visible element lies in a visited tile; 'full' tiles are
    fully visible (the kernel's skip logic can never drop real work)."""
    B, L, bsz = 2, 32, 8
    tokens, steps, valid = _rand_inputs(seed, B, L, bsz)
    _, meta, _ = M.dirl_layout(tokens, steps, valid, block_size=bsz,
                               mask_token=101)
    qm = kops.pack_meta(meta)
    tm = np.asarray(kops.build_tile_map(qm, qm, tq, tq, window=window))
    vis = np.asarray(M.visibility(meta, meta, strict=strict, window=window))
    T = 2 * L
    vt = vis.reshape(B, T // tq, tq, T // tq, tq)
    any_vis = vt.any(axis=(2, 4))
    all_vis = vt.all(axis=(2, 4))
    assert ((tm > 0) | ~any_vis).all(), "tile map missed visible work"
    # full tiles claimed by the non-strict map must be full in non-strict
    if not strict:
        assert (all_vis | (tm != 2)).all(), "false 'full' tile"


def test_sft_noise_statistics():
    """Masked fraction tracks the sampled block noise level t."""
    key = jax.random.PRNGKey(0)
    B, L, bsz = 64, 128, 16
    tokens = jnp.zeros((B, L), jnp.int32)
    pm = jnp.zeros((B, L), bool)
    valid = jnp.ones((B, L), bool)
    steps, w, t_blk = M.sample_sft_noise(key, tokens, pm, valid,
                                         block_size=bsz)
    frac = steps.reshape(B, L // bsz, bsz).mean(axis=-1)
    err = jnp.abs(frac - t_blk).mean()
    assert float(err) < 0.15
    # weights are 1/t exactly on masked tokens
    w_blk = w.reshape(B, L // bsz, bsz)
    t_rep = jnp.repeat(t_blk[..., None], bsz, axis=-1)
    sel = w_blk > 0
    assert float(jnp.abs(jnp.where(sel, w_blk - 1.0 / t_rep, 0)).max()) < 1e-5


def test_packed_layout_roundtrip():
    B, L, bsz, s_max = 2, 32, 8, 4
    tokens, steps, valid = _rand_inputs(3, B, L, bsz, s_max)
    ids, meta, sel, blk_tok = M.packed_layout(
        tokens, steps, valid, block_size=bsz, mask_token=101, s_max=s_max)
    assert ids.shape == (B, L * (1 + s_max))
    # every valid position selected exactly once across steps
    assert bool((np.asarray(sel).sum(axis=2) == 1).all())
    # copy (k, s) shows token i iff steps[i] < s
    K = L // bsz
    copies = np.asarray(ids[:, L:]).reshape(B, K, s_max, bsz)
    st_ = np.asarray(steps).reshape(B, K, bsz)
    tk = np.asarray(tokens).reshape(B, K, bsz)
    for s in range(s_max):
        shown = copies[:, :, s, :]
        expect = np.where(st_ < s, tk, 101)
        assert (shown == expect).all()

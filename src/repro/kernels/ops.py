"""Dispatcher for block-diffusion attention.

Three implementations of the same contract:

* ``ref``        — dense-mask oracle (O((2L)^2) scores).  This is what a
                   framework *without* the paper's FlexAttention trick pays
                   (the TraceRL-era baseline).
* ``structured`` — pure-jnp decomposition exploiting the mask algebra:
                   copy-A queries run block-causal over copy A; copy-B
                   queries run (i) a strictly-previous-context pass over
                   copy A and (ii) a block-diagonal pass over copy B, the
                   two merged with flash-style (m, l) statistics.  Cuts the
                   score work from 4L^2 to ~2L^2 + L*Bsz and is fully
                   XLA-analysable — this is the path the multi-pod dry-run
                   lowers.
* ``pallas`` / ``pallas_interpret`` — the TPU kernel family
                   (``block_diff_attn.py``), tile-skipping via
                   ``build_tile_map`` (~L^2-ish visited area, the
                   FlexAttention-equivalent fast path).  Fully
                   differentiable: a ``custom_vjp`` pairs the forward
                   with dQ/dKV flash backward kernels that reuse the
                   same tile map, so SFT/DiPO training skips the same
                   empty tiles three times per step.  ``impl="pallas"``
                   auto-selects interpret mode off-TPU (CI runs the
                   real kernel bodies on CPU); ``pallas_interpret``
                   forces it.

All take (q, k, v) in (B, L, H/Hkv, D) layout plus ``SeqMeta``.
Tile sizes are clamped to divisors of the sequence lengths, so the
pallas path works at any block-aligned length without caller padding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.masks import SeqMeta, visibility
from . import ref as _ref
from . import default_interpret
from .block_diff_attn import INVALID_COPY, block_diff_attention

NEG_INF = _ref.NEG_INF


@dataclasses.dataclass(frozen=True)
class TrainExecPlan:
    """How a training attention impl will execute — startup print fodder
    (the training analogue of ``paged_attn.KernelPlan``)."""

    impl: str
    mode: str      # "compiled" | "interpret" | "xla"
    reason: str


def train_exec_plan(impl: str) -> TrainExecPlan:
    """Resolve ``impl`` to its execution mode on the current backend.

    ``pallas`` compiles on TPU and interprets elsewhere;
    ``pallas_interpret`` is the explicit request for interpret mode.
    ``attention`` launches the kernels from this plan, so the reported
    mode is the mode that runs."""
    if impl == "pallas_interpret":
        return TrainExecPlan(impl, "interpret", "interpret requested")
    if impl == "pallas":
        if default_interpret():
            return TrainExecPlan(impl, "interpret",
                                 f"backend={jax.default_backend()} "
                                 "(compiled Mosaic path needs a TPU)")
        return TrainExecPlan(impl, "compiled", "pallas kernels on TPU")
    return TrainExecPlan(impl, "xla", f"pure-jnp {impl} path (XLA)")


# ---------------------------------------------------------------------------
# meta packing & tile maps
# ---------------------------------------------------------------------------


def pack_meta(meta: SeqMeta) -> jax.Array:
    """SeqMeta -> (B, L, 4) int32; invalid positions get copy=INVALID_COPY."""
    copy = jnp.where(meta.valid, meta.copy, INVALID_COPY)
    return jnp.stack(
        [copy, meta.block, meta.step, meta.pos], axis=-1).astype(jnp.int32)


def build_tile_map(q_meta: jax.Array, k_meta: jax.Array, tq: int, tk: int,
                   *, window: int | None = None) -> jax.Array:
    """Conservative block-sparse map, (B, Lq//tq, Lk//tk) int32.

    0 = provably empty (kernel skips), 1 = partial, 2 = provably full.
    Decided from per-tile channel min/max only — never materialises the
    dense mask.  This is the TPU analogue of FlexAttention's BlockMask.
    """
    B, Lq, _ = q_meta.shape
    Lk = k_meta.shape[1]
    qm = q_meta.reshape(B, Lq // tq, tq, 4)
    km = k_meta.reshape(B, Lk // tk, tk, 4)
    qmin, qmax = qm.min(axis=2), qm.max(axis=2)      # (B, nq, 4)
    kmin, kmax = km.min(axis=2), km.max(axis=2)      # (B, nk, 4)

    def ch(a, i):
        return a[..., i]

    # broadcast (B, nq, 1) vs (B, 1, nk)
    def q_(a, i):
        return ch(a, i)[:, :, None]

    def k_(a, i):
        return ch(a, i)[:, None, :]

    COPY, BLOCK, STEP, POS = 0, 1, 2, 3
    any_a_q = q_(qmin, COPY) <= 0
    any_b_q = (q_(qmin, COPY) <= 1) & (q_(qmax, COPY) >= 1)
    any_a_k = k_(kmin, COPY) <= 0
    any_b_k = (k_(kmin, COPY) <= 1) & (k_(kmax, COPY) >= 1)

    c1 = any_a_q & any_a_k & (k_(kmin, BLOCK) <= q_(qmax, BLOCK))
    c2 = any_b_q & any_a_k & (k_(kmin, BLOCK) <= q_(qmax, BLOCK))
    c3 = (any_b_q & any_b_k
          & (k_(kmin, BLOCK) <= q_(qmax, BLOCK))
          & (k_(kmax, BLOCK) >= q_(qmin, BLOCK))
          & (k_(kmax, STEP) >= q_(qmin, STEP)))
    needed = c1 | c2 | c3
    if window is not None:
        needed = needed & ((q_(qmin, POS) - k_(kmax, POS)) < window)

    all_a_q = q_(qmax, COPY) == 0
    all_b_q = (q_(qmin, COPY) == 1) & (q_(qmax, COPY) == 1)
    all_a_k = k_(kmax, COPY) == 0
    full_aa = all_a_q & all_a_k & (k_(kmax, BLOCK) <= q_(qmin, BLOCK))
    full_ba = all_b_q & all_a_k & (k_(kmax, BLOCK) < q_(qmin, BLOCK))
    full = full_aa | full_ba
    if window is not None:
        full = full & ((q_(qmax, POS) - k_(kmin, POS)) < window)

    return (needed.astype(jnp.int32) + (needed & full).astype(jnp.int32))


def tile_map_stats(tile_map: jax.Array) -> dict:
    """Fraction of visited / partial / full tiles — feeds the roofline
    notes and the trainer/scheduler ``obs`` gauges."""
    total = tile_map.size
    visited = int((tile_map > 0).sum())
    full = int((tile_map == 2).sum())
    denom = max(total, 1)
    return {"tiles_total": total, "tiles_visited": visited,
            "tiles_full": full, "visit_fraction": visited / denom,
            "partial_fraction": (visited - full) / denom,
            "full_fraction": full / denom}


def layout_tile_stats(meta: SeqMeta, *, tq: int = 128, tk: int = 128,
                      window: int | None = None) -> dict:
    """Host-side tile stats for a layout's self-attention (the sparsity
    the pallas kernels exploit), with the same tile-size clamping as the
    ``attention`` dispatcher."""
    pm = pack_meta(meta)
    L = pm.shape[1]
    tq = _pick_tile(L, tq)
    tk = _pick_tile(L, tk)
    return tile_map_stats(build_tile_map(pm, pm, tq, tk, window=window))


def layout_tile_counts(meta: SeqMeta, *, tq: int = 128, tk: int = 128,
                       window: int | None = None) -> dict:
    """Jittable twin of ``layout_tile_stats``: the same tile map, counted
    on the device.  ``tiles_visited`` (> 0) and ``tiles_full`` (== 2) are
    int32 scalars; ``tiles_total`` is static, a Python int."""
    pm = pack_meta(meta)
    L = pm.shape[1]
    tile_map = build_tile_map(pm, pm, _pick_tile(L, tq), _pick_tile(L, tk),
                              window=window)
    return {"tiles_total": tile_map.size,
            "tiles_visited": (tile_map > 0).sum(dtype=jnp.int32),
            "tiles_full": (tile_map == 2).sum(dtype=jnp.int32)}


# ---------------------------------------------------------------------------
# structured jnp path (flash-style two-part merge, no Pallas)
# ---------------------------------------------------------------------------


def _part_scores(q, k, mask, *, scale, softcap):
    """Unnormalised flash stats for one key segment.

    q: (B, Lq, H, D), k: (B, Lk, Hkv, D), mask: (B, Lq, Lk).
    Returns (p (B,H,Lq,Lk) exp-shifted, m (B,H,Lq,1), l (B,H,Lq,1)).
    """
    B, Lq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qf = q.reshape(B, Lq, Hkv, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k,
                   preferred_element_type=jnp.float32) * scale
    s = s.reshape(B, H, Lq, -1)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # avoid -inf rows
    p = jnp.exp(s - m) * mask[:, None]
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p, m, l


def _part_out(p, v):
    B, H, Lq, Lk = p.shape
    Hkv = v.shape[2]
    g = H // Hkv
    pv = p.reshape(B, Hkv, g, Lq, Lk).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", pv, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, Lq, -1)


def _merge(parts):
    """Merge [(o_unnorm, m, l), ...] flash statistics."""
    m = parts[0][1]
    for _, mi, _ in parts[1:]:
        m = jnp.maximum(m, mi)
    o = 0.0
    l = 0.0
    for oi, mi, li in parts:
        a = jnp.exp(mi - m)
        o = o + oi * a
        l = l + li * a
    l = jnp.where(l == 0.0, 1.0, l)
    return o / l


# ---------------------------------------------------------------------------
# chunked (memory-bounded flash-in-jnp) path
# ---------------------------------------------------------------------------


def _pick_chunk(length: int, target: int) -> int:
    """Largest divisor of ``length`` that is <= target."""
    c = min(target, length)
    while length % c:
        c -= 1
    return c


def _pick_tile(length: int, target: int) -> int:
    """Largest divisor of ``length`` <= target that is a multiple of 8,
    else ``length`` itself: Mosaic takes a block's second-minor dim only
    as a multiple of the 8-row sublane tile or as the whole dim."""
    for c in range(min(target, length) // 8 * 8, 0, -8):
        if length % c == 0:
            return c
    return length


def chunked_masked_attention(q, k, v, q_meta: SeqMeta, k_meta: SeqMeta, *,
                             scale=None, softcap=None, window=None,
                             strict: bool = False,
                             q_chunk: int = 512, k_chunk: int = 1024,
                             return_stats: bool = False):
    """Flash-style attention in pure jnp: scan over q/kv chunks with running
    (m, l) statistics; never materialises more than (q_chunk, k_chunk)
    scores per head.  The mask predicate is evaluated per chunk pair from
    ``SeqMeta`` — this is the same algorithm the Pallas kernel runs, in
    XLA-lowerable form (the multi-pod dry-run lowers this path).

    Returns (B, Lq, H, Dv), or unnormalised ((B,H,Lq,Dv), m, l) stats if
    ``return_stats`` (used by the structured decomposition to merge parts).
    """
    B, Lq, H, D = q.shape
    _, Lk, Hkv, Dv = v.shape
    g = H // Hkv
    if scale is None:
        scale = D ** -0.5
    qc = _pick_chunk(Lq, q_chunk)
    kc = _pick_chunk(Lk, k_chunk)
    nq, nk = Lq // qc, Lk // kc

    qh = q.reshape(B, Lq, Hkv, g, D)
    kh, vh = k, v

    def q_step(qi):
        qs = jax.lax.dynamic_slice_in_dim(qh, qi * qc, qc, axis=1)
        qm = q_meta.slice_t(qi * qc, qc)

        def kv_step(carry, ki):
            acc, m, l = carry
            ks = jax.lax.dynamic_slice_in_dim(kh, ki * kc, kc, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vh, ki * kc, kc, axis=1)
            km = k_meta.slice_t(ki * kc, kc)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qs, ks,
                           preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            vis = visibility(qm, km, window=window, strict=strict)
            s = jnp.where(vis[:, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new) * vis[:, None, None]
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vs.dtype), vs,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        init = (jnp.zeros((B, Hkv, g, qc, Dv), jnp.float32),
                jnp.full((B, Hkv, g, qc, 1), NEG_INF, jnp.float32),
                jnp.zeros((B, Hkv, g, qc, 1), jnp.float32))
        (acc, m, l), _ = jax.lax.scan(kv_step, init,
                                      jnp.arange(nk, dtype=jnp.int32))
        return acc, m, l

    acc, m, l = jax.lax.map(q_step, jnp.arange(nq, dtype=jnp.int32))
    # (nq, B, Hkv, g, qc, X) -> (B, H, Lq, X)
    def fold(x):
        x = jnp.moveaxis(x, 0, 3)                        # B,Hkv,g,nq,qc,X
        return x.reshape(B, H, Lq, x.shape[-1])

    acc, m, l = fold(acc), fold(m), fold(l)
    if return_stats:
        return acc, m, l
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)                      # (B, H, Lq, Dv)
    return out.transpose(0, 2, 1, 3)


def structured_dup_attention(q, k, v, meta: SeqMeta, L: int,
                             block_size: int, *, scale=None, softcap=None,
                             window=None, strict: bool = False,
                             q_chunk: int = 512, k_chunk: int = 1024):
    """Memory-bounded structured evaluation of the DiRL duplicated layout.

    copy-A queries: block-causal over copy A (chunked).
    copy-B queries: chunked context pass over copy A, merged with the small
    block-diagonal pass over copy B.  Total score work ~2L^2 + L*block_size
    instead of the oracle's 4L^2.
    """
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    assert T == 2 * L and L % block_size == 0
    if scale is None:
        scale = D ** -0.5
    K = L // block_size
    mA, mB = meta.slice_t(0, L), meta.slice_t(L, L)
    qA, qB = q[:, :L], q[:, L:]
    kA, vA = k[:, :L], v[:, :L]
    kB, vB = k[:, L:], v[:, L:]

    oA = chunked_masked_attention(qA, kA, vA, mA, mA, scale=scale,
                                  softcap=softcap, window=window,
                                  strict=strict, q_chunk=q_chunk,
                                  k_chunk=k_chunk)

    acc1, m1, l1 = chunked_masked_attention(
        qB, kA, vA, mB, mA, scale=scale, softcap=softcap, window=window,
        strict=strict, q_chunk=q_chunk, k_chunk=k_chunk, return_stats=True)

    def blockify(x):
        return x.reshape(B * K, block_size, *x.shape[2:])

    mBb = jax.tree.map(lambda a: a.reshape(B * K, block_size), mB)
    visBB = visibility(mBb, mBb, window=window, strict=strict)
    p2, m2, l2 = _part_scores(blockify(qB), blockify(kB), visBB,
                              scale=scale, softcap=softcap)
    o2 = _part_out(p2, blockify(vB))

    def unblock(x):  # (B*K, H, bsz, X) -> (B, H, L, X)
        return x.reshape(B, K, H, block_size, -1).transpose(
            0, 2, 1, 3, 4).reshape(B, H, L, -1)

    oB = _merge([(unblock(o2), unblock(m2), unblock(l2)), (acc1, m1, l1)])
    oB = oB.transpose(0, 2, 1, 3).astype(q.dtype)
    return jnp.concatenate([oA.astype(q.dtype), oB], axis=1)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------


def attention(q, k, v, q_meta: SeqMeta, k_meta: SeqMeta, *,
              impl: str = "structured",
              scale: float | None = None,
              softcap: float | None = None,
              window: int | None = None,
              strict: bool = False,
              dup_len: int | None = None,
              block_size: int | None = None,
              tq: int = 128, tk: int = 128) -> jax.Array:
    """Block-diffusion attention with selectable backend.

    ``dup_len``/``block_size`` enable the structured fast path when the
    layout is the DiRL duplicated layout (copy A = first ``dup_len``
    positions).  ``pallas`` clamps ``tq``/``tk`` to divisors of Lq/Lk
    that are multiples of 8 (or to the whole length, where none is) and
    is differentiable — the custom-VJP backward kernels skip the same
    empty tiles as the forward — so it is valid under ``jax.grad`` and
    ``jax.checkpoint`` in the trainers.
    """
    if impl == "ref":
        vis = visibility(q_meta, k_meta, window=window, strict=strict)
        return _ref.mha_reference(q, k, v, vis, scale=scale, softcap=softcap)
    if impl == "chunked" or (impl == "structured" and dup_len is None):
        return chunked_masked_attention(
            q, k, v, q_meta, k_meta, scale=scale, softcap=softcap,
            window=window, strict=strict)
    if impl == "structured":
        assert block_size is not None
        return structured_dup_attention(
            q, k, v, q_meta, dup_len, block_size,
            scale=scale, softcap=softcap, window=window, strict=strict)
    if impl in ("pallas", "pallas_interpret"):
        # clamp tiles to sublane-aligned divisors so model-layer
        # defaults (128) compile at any length; the exec mode is
        # train_exec_plan's (interpret off-TPU so CI runs the real
        # kernel bodies on CPU)
        tq = _pick_tile(q.shape[1], tq)
        tk = _pick_tile(k.shape[1], tk)
        qm = pack_meta(q_meta)
        km = pack_meta(k_meta)
        tile_map = build_tile_map(qm, km, tq, tk, window=window)
        return block_diff_attention(
            q, k, v, qm, km, tile_map, scale=scale, softcap=softcap,
            window=window, strict=strict, tq=tq, tk=tk,
            interpret=train_exec_plan(impl).mode == "interpret")
    raise ValueError(f"unknown attention impl: {impl}")

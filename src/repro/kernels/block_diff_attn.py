"""Differentiable Pallas TPU flash attention for the block-diffusion mask.

This is the TPU-native adaptation of the paper's FlexAttention usage
(§4.1), now covering *training*, not just inference forwards: the
block-diffusion visibility predicate is evaluated *as code* per
(128 x 128) tile from per-position metadata, and tiles that are provably
empty are skipped via a precomputed block-sparse ``tile_map`` (the
analogue of FlexAttention's BlockMask) — in the forward pass AND in both
halves of the backward pass.  The duplicated-sequence SFT mask attends
only ~1/4 of the dense (2L)^2 score matrix; skipping empty tiles
recovers that factor on the MXU three times per training step.

The grids are *tile-map-sparse*: ``_compact_tiles`` sorts the visited
(b, q_tile, kv_tile) triples into a scalar-prefetched list and the grid
is ``(heads, n_visited)`` with a **dynamic** trailing bound, so skipped
tiles cost no grid steps at all — not on the MXU, and not in the
sequential interpret-mode loop CI runs (where a dense grid would pay
per-iteration overhead even for gated-off tiles).  Rows with no visible
tile carry one gated dummy entry so their output block still
initializes to zero.  Per row the kv tiles stay in ascending order, so
the online-softmax accumulation order — and hence the forward results —
are bitwise identical to the dense-grid kernel.

Kernels (one ``pallas_call`` each, all walking worklists compacted from
the same ``tile_map`` and evaluating the same ``_tile_visibility``
predicate):

``_kernel``      forward: online-softmax flash attention over the
                 q-major visited-tile list, accumulating (acc, m, l)
                 statistics in f32 VMEM scratch between a row's start
                 and end entries.  Under differentiation it
                 additionally emits the per-row logsumexp
                 ``lse = m + log(l)`` (lane-broadcast, the standard
                 flash residual) — the plain inference path is bit
                 identical to the pre-VJP kernel.
``_dq_kernel``   backward dQ: same q-major list/order as the forward;
                 each visited tile recomputes p = exp(s - lse), forms
                 ds = p * (dp - delta) (softcap's tanh handled via
                 1 - (s_capped/c)^2; the window term only ever enters
                 through the mask), and accumulates dq in scratch.
``_dkv_kernel``  backward dKV: the kv-major visited-tile list —
                 accumulating dk/dv per query head in scratch across a
                 kv row's q tiles; grouped (GQA/MQA/MLA) heads are
                 reduced to the Hkv axis outside the kernel.

``block_diff_attention`` wires the three through ``jax.custom_vjp`` with
the standard recomputation residuals (o, per-row lse): primal calls that
are never differentiated run the original two-output-free forward, so
inference callers pay nothing.  Gradients for the integer operands
(meta, tile_map) are symbolic zeros (float0).

Memory plan (per grid step): VMEM q/k/v/do tiles, meta tiles
(TQ|TK, 4) int32, SMEM visited-tile table (6, n_candidates) int32, f32
scratch accumulators plus (TQ, 128)-lane running statistics / residual
tiles.  Validated under ``interpret=True`` on CPU against
``ref.mha_reference`` (forward, bitwise vs the seed kernel) and against
autodiff through the ``structured``/``ref`` paths (gradients,
tolerance-based) — ``default_interpret()`` auto-selects interpret mode
off-TPU so CI runs these real kernel bodies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

DEFAULT_TQ = 128
DEFAULT_TK = 128
_LANES = 128

# meta column indices
COPY, BLOCK, STEP, POS = 0, 1, 2, 3
INVALID_COPY = 2  # matches no predicate clause -> never visible

# _compact_tiles table row indices
TM_B, TM_QI, TM_KI, TM_START, TM_END, TM_NEED = 0, 1, 2, 3, 4, 5


def _compact_tiles(tile_map: jax.Array, *, kv_major: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Sort the visited tiles of ``tile_map`` into a dense worklist.

    Returns ``(tmeta, nv)``: ``tmeta`` is a ``(6, n_candidates)`` int32
    table with rows ``[b, q_tile, kv_tile, row_start, row_end, needed]``,
    sorted
    by (b, major row, minor column) — q-major for the forward/dQ grids,
    kv-major for dKV — and ``nv`` is the (traced) number of live
    entries, which becomes the dynamic grid bound.  Entries past ``nv``
    are never executed.

    Every major row with *no* visible tile contributes one dummy entry
    pointing at its column-0 tile with ``needed = 0``, so the kernel's
    ``needed`` gate skips its compute — the row's output block is still
    initialized and written, keeping empty rows exactly zero.  The flag
    rides in this scalar-prefetched table rather than as a ``tile_map``
    operand: a (1, 1, 1) VMEM block of the tile map breaks Mosaic's
    (8, 128) block rule.
    Within a row, minor columns stay ascending: the flash accumulation
    order is identical to a dense grid's, so results are bitwise equal.
    """
    B, nq, nk = tile_map.shape
    vis = tile_map > 0
    if kv_major:
        vis = vis.transpose(0, 2, 1)
    R, C = vis.shape[1], vis.shape[2]
    rows = B * R
    visf = vis.reshape(-1)
    idx = jnp.arange(rows * C, dtype=jnp.int32)
    row_id, col_id = idx // C, idx % C
    big = jnp.int32(np.iinfo(np.int32).max)
    # live tiles sort by flat (row, col); dead tiles land in the +inf
    # bucket past nv.  One dummy candidate per row sorts after the
    # row's real tiles and goes live only when the row is empty.
    key_real = jnp.where(visf, row_id * (C + 1) + col_id, big)
    rid = jnp.arange(rows, dtype=jnp.int32)
    row_empty = ~jnp.any(vis.reshape(rows, C), axis=1)
    key_dummy = jnp.where(row_empty, rid * (C + 1) + C, big)
    keys = jnp.concatenate([key_real, key_dummy])
    cand_row = jnp.concatenate([row_id, rid])
    cand_col = jnp.concatenate([col_id, jnp.zeros_like(rid)])
    cand_need = jnp.concatenate([jnp.ones_like(row_id),
                                 jnp.zeros_like(rid)])
    order = jnp.argsort(keys)
    skey = keys[order]
    live = skey < big
    srow = jnp.where(live, cand_row[order], -1)
    scol = jnp.where(live, cand_col[order], 0)
    prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), srow[:-1]])
    nxt = jnp.concatenate([srow[1:], jnp.full((1,), -2, jnp.int32)])
    start = (srow != prev).astype(jnp.int32)
    end = (srow != nxt).astype(jnp.int32)
    b_of = jnp.where(live, srow // R, 0)
    major = jnp.where(live, srow % R, 0)
    qi_of, ki_of = (scol, major) if kv_major else (major, scol)
    need = jnp.where(live, cand_need[order], 0)
    tmeta = jnp.stack([b_of, qi_of, ki_of, start, end, need]
                      ).astype(jnp.int32)
    return tmeta, jnp.sum(live.astype(jnp.int32))


def _tile_visibility(qm, km, window: int | None, strict: bool):
    """Evaluate the mask predicate on a (TQ, TK) tile.

    qm: (TQ, 4) int32, km: (TK, 4) int32.  Uses 2D slices only (TPU-safe:
    no 1D vectors inside the kernel).
    """
    qc = qm[:, COPY:COPY + 1]          # (TQ, 1)
    qb = qm[:, BLOCK:BLOCK + 1]
    qs = qm[:, STEP:STEP + 1]
    qp = qm[:, POS:POS + 1]
    kc = km[:, COPY:COPY + 1].T        # (1, TK)
    kb = km[:, BLOCK:BLOCK + 1].T
    ks = km[:, STEP:STEP + 1].T
    kp = km[:, POS:POS + 1].T

    k_is_a = kc == 0
    k_is_b = kc == 1

    vis_a_query = k_is_a & (kb <= qb)
    if strict:
        ctx = k_is_a & (kb < qb)
        own = k_is_b & (kb == qb) & (ks == qs)
    else:
        ctx = k_is_a & ((kb < qb) | ((kb == qb) & (ks < qs)))
        own = k_is_b & (kb == qb) & (ks >= qs)
    # boolean select spelled as and/or: Mosaic cannot lower a select
    # between i1 vectors (it widens to i8 and fails to truncate back)
    q_is_a = qc == 0
    vis = (q_is_a & vis_a_query) | (~q_is_a & (ctx | own))
    # invalid (padding) queries match nothing, mirroring the oracle's
    # q.valid gate — so their rows are empty and their grads exactly 0
    vis = vis & (qc != INVALID_COPY)
    if window is not None:
        vis = vis & ((qp - kp) < window)
    return vis


def _kernel(tmeta_ref, qm_ref, km_ref, q_ref, k_ref, v_ref,
            o_ref, *rest, scale: float, softcap: float | None,
            window: int | None, strict: bool, emit_lse: bool = False):
    if emit_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        (acc_ref, m_ref, l_ref), lse_ref = rest, None
    t = pl.program_id(1)

    @pl.when(tmeta_ref[TM_START, t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(tmeta_ref[TM_NEED, t] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (TK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (TQ, TK)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        vis = _tile_visibility(qm_ref[0], km_ref[0], window, strict)
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_ref[:, :1]                        # (TQ, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # (TQ, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)              # rescale old stats
        p = jnp.exp(s - m_new)                       # (TQ, TK)
        p = jnp.where(vis, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(tmeta_ref[TM_END, t] == 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if emit_lse:
            # empty rows: m = NEG_INF, log(l->1) = 0, so lse = NEG_INF
            # and the backward's exp(NEG_INF - NEG_INF) = 1 is masked off
            lse_ref[0, 0] = m_ref[...] + jnp.log(
                jnp.broadcast_to(l, m_ref.shape))


def _tile_probs(q, k, qm, km, lse, *, scale, softcap, window, strict):
    """Recompute (p, s_capped) for one tile from the lse residual."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    vis = _tile_visibility(qm, km, window, strict)
    p = jnp.exp(jnp.where(vis, s, NEG_INF) - lse)
    p = jnp.where(vis, p, 0.0)
    return p, s


def _tile_dscore(p, s_capped, do, v, delta, *, softcap):
    """d(pre-softcap score) for one tile: the score-gradient chain rule.

    Masked entries have p = 0, so ds = 0 there — the window term and the
    visibility predicate enter the backward only through the mask.
    """
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TQ, TK)
    ds = p * (dp - delta)
    if softcap is not None:
        # s_capped = c * tanh(s/c)  =>  d s = ds_capped * (1 - tanh^2)
        ds = ds * (1.0 - (s_capped / softcap) ** 2)
    return ds


def _dq_kernel(tmeta_ref, qm_ref, km_ref, q_ref, k_ref,
               v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
               scale: float, softcap: float | None, window: int | None,
               strict: bool):
    t = pl.program_id(1)

    @pl.when(tmeta_ref[TM_START, t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tmeta_ref[TM_NEED, t] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                   # (TQ, 1)
        delta = delta_ref[0, 0][:, :1]
        p, s = _tile_probs(q, k, qm_ref[0], km_ref[0], lse, scale=scale,
                           softcap=softcap, window=window, strict=strict)
        ds = _tile_dscore(p, s, do, v, delta, softcap=softcap)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(tmeta_ref[TM_END, t] == 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...]


def _dkv_kernel(tmeta_ref, qm_ref, km_ref, q_ref, k_ref,
                v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, softcap: float | None,
                window: int | None, strict: bool):
    t = pl.program_id(1)

    @pl.when(tmeta_ref[TM_START, t] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(tmeta_ref[TM_NEED, t] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        p, s = _tile_probs(q, k, qm_ref[0], km_ref[0], lse, scale=scale,
                           softcap=softcap, window=window, strict=strict)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # (TK, Dv)
        ds = _tile_dscore(p, s, do, v, delta, softcap=softcap)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (TK, D)

    @pl.when(tmeta_ref[TM_END, t] == 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


def _specs(H, group, tq, tk, D, Dv, *, out_axis: str):
    """Block specs shared by the three launches.

    Index maps route through the scalar-prefetched tile table: grid is
    ``(H, n_visited)``, and entry ``t`` names (b, q_tile, kv_tile).
    ``out_axis`` selects which tile axis the per-head f32 output block
    follows ("q" for o/lse/dq, "k" for dk/dv).
    """
    def qmap(h, t, tm):
        return (tm[TM_B, t], h, tm[TM_QI, t], 0)

    def kmap(h, t, tm):
        return (tm[TM_B, t], h // group, tm[TM_KI, t], 0)

    def qm_map(h, t, tm):
        return (tm[TM_B, t], tm[TM_QI, t], 0)

    def km_map(h, t, tm):
        return (tm[TM_B, t], tm[TM_KI, t], 0)

    def kout(h, t, tm):
        return (tm[TM_B, t], h, tm[TM_KI, t], 0)

    in_specs = [
        pl.BlockSpec((1, tq, 4), qm_map),
        pl.BlockSpec((1, tk, 4), km_map),
        pl.BlockSpec((1, 1, tq, D), qmap),
        pl.BlockSpec((1, 1, tk, D), kmap),
        pl.BlockSpec((1, 1, tk, Dv), kmap),
    ]
    out_map = qmap if out_axis == "q" else kout
    return in_specs, qmap, out_map


def _forward(q, k, v, q_meta, k_meta, tile_map, *, scale, softcap, window,
             strict, tq, tk, interpret, emit_lse):
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    Dv = v.shape[3]
    group = H // Hkv

    # kernel-internal layout: (B, H, L, D)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    tm = tile_map.astype(jnp.int32)
    tmeta, nv = _compact_tiles(tm)

    kern = functools.partial(_kernel, scale=scale, softcap=softcap,
                             window=window, strict=strict,
                             emit_lse=emit_lse)
    in_specs, qmap, out_map = _specs(H, group, tq, tk, D, Dv,
                                     out_axis="q")

    out_specs = pl.BlockSpec((1, 1, tq, Dv), out_map)
    out_shape = jax.ShapeDtypeStruct((B, H, Lq, Dv), q.dtype)
    if emit_lse:
        out_specs = [out_specs, pl.BlockSpec((1, 1, tq, _LANES), qmap)]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B, H, Lq, _LANES), jnp.float32)]

    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nv),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((tq, Dv), jnp.float32),
                pltpu.VMEM((tq, _LANES), jnp.float32),
                pltpu.VMEM((tq, _LANES), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(tmeta, q_meta, k_meta, qh, kh, vh)

    if emit_lse:
        o, lse = res
        return o.transpose(0, 2, 1, 3), lse
    return res.transpose(0, 2, 1, 3)


def _backward(q, k, v, q_meta, k_meta, tile_map, o, lse, do, *, scale,
              softcap, window, strict, tq, tk, interpret):
    """The dQ and dKV kernel launches plus the cheap jnp glue around
    them (delta precompute, grouped-head reduction, dtype restore)."""
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    Dv = v.shape[3]
    group = H // Hkv

    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    doh = do.transpose(0, 2, 1, 3)
    oh = o.transpose(0, 2, 1, 3)
    # delta_i = sum_d do_id * o_id, lane-broadcast like lse
    delta = jnp.sum(oh.astype(jnp.float32) * doh.astype(jnp.float32),
                    axis=-1, keepdims=True)          # (B, H, Lq, 1)
    delta = jnp.broadcast_to(delta, (B, H, Lq, _LANES))
    tm = tile_map.astype(jnp.int32)
    kw = dict(scale=scale, softcap=softcap, window=window, strict=strict)

    in_specs, qmap, _ = _specs(H, group, tq, tk, D, Dv, out_axis="q")
    res_specs = [
        pl.BlockSpec((1, 1, tq, Dv), qmap),          # do
        pl.BlockSpec((1, 1, tq, _LANES), qmap),      # lse
        pl.BlockSpec((1, 1, tq, _LANES), qmap),      # delta
    ]

    # dQ walks the same q-major visited list as the forward
    tmeta_q, nv_q = _compact_tiles(tm)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nv_q),
            in_specs=in_specs + res_specs,
            out_specs=pl.BlockSpec((1, 1, tq, D), qmap),
            scratch_shapes=[pltpu.VMEM((tq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, D), jnp.float32),
        interpret=interpret,
    )(tmeta_q, q_meta, k_meta, qh, kh, vh, doh, lse, delta)

    # dKV walks the kv-major list: each kv row's visited q tiles are
    # consecutive, accumulating dk/dv in scratch
    b_in_specs, _, b_out_map = _specs(H, group, tq, tk, D, Dv,
                                      out_axis="k")
    tmeta_k, nv_k = _compact_tiles(tm, kv_major=True)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nv_k),
            in_specs=b_in_specs + res_specs,
            out_specs=[
                pl.BlockSpec((1, 1, tk, D), b_out_map),
                pl.BlockSpec((1, 1, tk, Dv), b_out_map),
            ],
            scratch_shapes=[pltpu.VMEM((tk, D), jnp.float32),
                            pltpu.VMEM((tk, Dv), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Lk, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(tmeta_k, q_meta, k_meta, qh, kh, vh, doh, lse, delta)

    dq = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    # per-q-head dk/dv -> sum the group axis back onto the kv heads
    dk = dk_h.reshape(B, Hkv, group, Lk, D).sum(axis=2)
    dv = dv_h.reshape(B, Hkv, group, Lk, Dv).sum(axis=2)
    dk = dk.transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_attention_vjp(scale, softcap, window, strict, tq, tk, interpret):
    """custom_vjp closure over the static kernel parameters (cached so
    repeated traces reuse one primitive and never retrace the rules)."""
    kw = dict(scale=scale, softcap=softcap, window=window, strict=strict,
              tq=tq, tk=tk, interpret=interpret)

    @jax.custom_vjp
    def attn(q, k, v, q_meta, k_meta, tile_map):
        return _forward(q, k, v, q_meta, k_meta, tile_map,
                        emit_lse=False, **kw)

    def attn_fwd(q, k, v, q_meta, k_meta, tile_map):
        o, lse = _forward(q, k, v, q_meta, k_meta, tile_map,
                          emit_lse=True, **kw)
        return o, (q, k, v, q_meta, k_meta, tile_map, o, lse)

    def attn_bwd(res, do):
        q, k, v, q_meta, k_meta, tile_map, o, lse = res
        dq, dk, dv = _backward(q, k, v, q_meta, k_meta, tile_map, o, lse,
                               do, **kw)

        def zero(a):  # int operands take float0 symbolic-zero cotangents
            return np.zeros(a.shape, dtype=jax.dtypes.float0)

        return dq, dk, dv, zero(q_meta), zero(k_meta), zero(tile_map)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def block_diff_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         q_meta: jax.Array, k_meta: jax.Array,
                         tile_map: jax.Array, *,
                         scale: float | None = None,
                         softcap: float | None = None,
                         window: int | None = None,
                         strict: bool = False,
                         tq: int = DEFAULT_TQ, tk: int = DEFAULT_TK,
                         interpret: bool = False) -> jax.Array:
    """Differentiable flash attention under the block-diffusion mask.

    q: (B, Lq, H, D);  k, v: (B, Lk, Hkv, D/Dv);
    q_meta: (B, Lq, 4) int32 [copy, block, step, pos] with copy==2 on
    invalid (padding) positions;  k_meta: (B, Lk, 4) likewise;
    tile_map: (B, Lq//tq, Lk//tk) int32 (0 = skip, >0 = compute), from
    ``ops.build_tile_map`` — shared by the forward and both backward
    kernels, so empty tiles are skipped in all three passes.
    """
    B, Lq, H, D = q.shape
    _, Lk, Hkv, _ = k.shape
    assert Lq % tq == 0 and Lk % tk == 0, (Lq, Lk, tq, tk)
    assert H % Hkv == 0
    if scale is None:
        scale = D ** -0.5
    fn = _make_attention_vjp(
        float(scale), None if softcap is None else float(softcap),
        window, bool(strict), int(tq), int(tk), bool(interpret))
    return fn(q, k, v, q_meta, k_meta, tile_map)

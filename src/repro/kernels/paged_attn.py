"""Pallas TPU paged-attention family: read the KV page pool in place.

Both kernels are one flash-attention body (``_kernel``) over a query
tile and a stream of key blocks.  The per-slot block table rides in as
a **scalar-prefetch** operand, so each grid step's BlockSpec index map
resolves "which page does sequence b's block j live in" *before* the
step's DMA is issued, and no dense-width ``paged_gather`` copy of the
pool is ever materialized.  Grid ``(B, Hkv, q_tiles, n_pool + n_self)``
with the key axis innermost (sequential on TPU, accumulating
online-softmax statistics in f32 scratch): steps ``j < n_pool`` stream
page ``table[b, j]`` of kv head ``h``, the remaining steps stream the
queries' own fresh K/V block by block.  All ``group`` query heads of a
kv head ride one page fetch (a per-q-head grid would re-DMA each page
``group`` times — H times for MLA's MQA form).

``paged_decode_attention``
    The decode-mode counterpart of ``block_diff_attn.py``: one
    current-block query tile per sequence attends to its committed KV
    directly in the shared pool (``models.attention.PagedAttnCache``)
    and then to its own block (the bidirectional self-block of
    blockwise dLLM decode).  Per-tick transient decode memory is
    O(page), never O(slots x K*bsz).

``paged_prefill_attention``
    The plain-mode (committed-context) counterpart, serving the
    shared-prefix *suffix prefill* (``core.decoding.prefill_suffix``):
    suffix queries attend to (hit-prefix pages ++ suffix self keys).
    Admission-time transient KV bytes are zero: the gather that the
    ``kernel="ref"`` layout runs per suffix admission is replaced by
    per-page streaming inside the grid.

Pool layout is head-major, ``(P, Hkv, bsz, D)``: a page's block for one
kv head is ``(1, 1, bsz, D)``, whose last two dims equal the array's —
the shape rule Mosaic enforces on every BlockSpec (last two block dims
divisible by (8, 128) or equal to the array's).  Position operands are
laid out the same way for the same reason: key positions as
``(…, 1, bsz)`` rows, query positions and limits as ``(…, rows, 1)``
columns, so the kernel never transposes.

Masking reproduces ``models.attention`` semantics.  A key is visible
iff it is filled (``pos >= 0``), below its row's limit, and inside the
sliding window ``(q_pos - k_pos) < window``.  The limit of a *pool* key
is ``cache_limit[b]`` in decode (committed for this sequence) and the
end of the query's block in prefill (block-causal); the limit of a
*self* key is the end of the query's block in both (bidirectional
inside the block, causal across suffix blocks).  Unmapped blocks
(``table == -1``) fetch the null page and are masked by forcing their
limit to 0.  Scores accumulate in f32 with the same scale -> softcap
-> mask order as the reference.

Numerics: the kernels agree with the gathered reference (dense concat
+ masked softmax for decode, ``kernels.ops.chunked_masked_attention``
for prefill) to f32 rounding, not bitwise — online softmax over pages
sums in a different order than XLA's reduction over the gathered keys,
and a compiled Mosaic kernel never reproduces XLA's reduction order.
tests/test_paged_attn.py bounds the difference tightly enough that a
leaked stale or out-of-window key fails.

Execution planning (``plan_exec`` / ``KernelPlan``): off-TPU the
kernels run with ``interpret=True`` so CPU CI runs the *real* kernel
bodies; on TPU they always compile.  Sub-tile shapes are zero-padded to
the (8, 128) f32 tile — head dims to a lane multiple (exact: the
contraction gains trailing ``+0.0`` terms only), pages to a sublane
multiple with ``pos = -1`` rows the validity mask hides — so padded and
unpadded launches agree bitwise.  ``plan_exec`` is the queryable record
of the choice (mode, reason, padding) that ``serving``/``launch.serve``
surface as a stat.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import default_interpret
from .ops import _pick_chunk
from .ref import NEG_INF

_LANES = 128
_SUBLANES = 8
_Q_CHUNK = 128            # suffix-prefill query rows per tile (per head)
_NO_LIMIT = jnp.iinfo(jnp.int32).max


def _tile_aligned(bsz: int, dk: int, dv: int) -> bool:
    """Shapes the compiled Mosaic path runs without padding: the f32
    min tile is (8, 128).  Sub-tile shapes (small ``block_size``
    configs, non-128-multiple head dims) are zero-padded up to the
    tile by ``plan_exec``'s auto mode."""
    return bsz % _SUBLANES == 0 and dk % _LANES == 0 and dv % _LANES == 0


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """The execution mode a paged kernel will run under, and why.

    ``mode``    "compiled" (Mosaic on TPU) | "interpret" (the same
                kernel body evaluated op-by-op through XLA — the CPU CI
                path, and the explicit-``interpret=True`` path).
    ``reason``  human-readable cause: backend, tile alignment, padding.
    ``padded``  tile padding active (sub-tile shapes lifted to the
                (8, 128) f32 tile; masked/zero padding, bit-exact).
    """
    mode: str
    reason: str
    padded: bool

    @property
    def interpret(self) -> bool:
        return self.mode == "interpret"


def plan_exec(bsz: int, dk: int, dv: int, *,
              interpret: bool | None = None,
              pad: bool | None = None) -> KernelPlan:
    """Resolve (interpret?, pad?) for page shape (bsz, dk, dv).

    ``interpret=None`` compiles on TPU and interprets elsewhere — the
    only backend-driven choice; interpret mode on a TPU happens only
    when a caller asks for it.  ``pad=None`` enables tile padding
    exactly when compiling a sub-tile shape.  Explicit booleans always
    win: tests force ``interpret=True, pad=True`` to pin the padded
    path's bit-parity on CPU, and ``pad=False`` compiles a sub-tile
    shape unpadded.
    """
    aligned = _tile_aligned(bsz, dk, dv)
    forced = interpret is not None
    if interpret is None:
        interpret = default_interpret()
    if pad is None:
        pad = not interpret and not aligned
    padded = bool(pad) and not aligned
    if interpret:
        reason = "interpret requested" if forced else \
            f"backend={jax.default_backend()} (compiled Mosaic path " \
            "needs a TPU)"
        return KernelPlan("interpret", reason, padded)
    if aligned:
        reason = "tile-aligned page shape"
    elif padded:
        reason = (f"sub-tile page shape (bsz={bsz}, dk={dk}, dv={dv}) "
                  "zero-padded to the (8, 128) tile")
    else:
        reason = (f"sub-tile page shape (bsz={bsz}, dk={dk}, dv={dv}) "
                  "compiled unpadded (padding disabled)")
    return KernelPlan("compiled", reason, padded)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_dim(a: jax.Array, axis: int, target: int,
             value=0) -> jax.Array:
    """Pad ``axis`` up to ``target`` with ``value`` (no-op if already
    there).  Zero-padding a contraction dim appends exact ``+0.0``
    terms; position arrays pad with -1 so the validity mask hides the
    rows."""
    if a.shape[axis] == target:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, target - a.shape[axis])
    return jnp.pad(a, widths, constant_values=value)


# ---------------------------------------------------------------------------
# the shared kernel body
# ---------------------------------------------------------------------------


def _kernel(table_ref, q_ref, kp_ref, vp_ref, pp_ref, ks_ref, vs_ref,
            sp_ref, qpos_ref, plim_ref, slim_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, softcap: float | None,
            window: int | None, n_pool: int):
    b = pl.program_id(0)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)         # (R, Dk)
    q_pos = qpos_ref[0, 0]                          # (R, 1)

    def attend(k, v, k_pos, lim):
        """Online-softmax update with one (bsz, D) key block; ``k_pos``
        (1, bsz) key positions, ``lim`` (R, 1) per-row key limit."""
        valid = (k_pos >= 0) & (k_pos < lim)        # (R, bsz)
        if window is not None:
            valid = valid & ((q_pos - k_pos) < window)
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, :1]                       # (R, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)             # rescale old stats
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # exp(NEG-NEG)=1 trap
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j < n_pool)
    def _pool():
        # an unmapped block (-1) fetched the null page: limit 0 hides
        # every key of it
        t = table_ref[b, jnp.clip(j, 0, table_ref.shape[1] - 1)]
        lim = jnp.minimum(plim_ref[0, 0], jnp.where(t >= 0, _NO_LIMIT, 0))
        attend(kp_ref[0, 0], vp_ref[0, 0], pp_ref[0], lim)

    @pl.when(j >= n_pool)
    def _self():
        attend(ks_ref[0, 0, 0], vs_ref[0, 0, 0], sp_ref[0, 0], slim_ref[0, 0])

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _rows(x: jax.Array, nq: int, group: int) -> jax.Array:
    """(B, T) per-query values -> (B, nq, group*qc, 1) columns in the
    kernel's g-major query-row order."""
    B, T = x.shape
    x = x.reshape(B, nq, 1, T // nq)
    return jnp.broadcast_to(x, (B, nq, group, T // nq)).reshape(
        B, nq, -1, 1)


def _launch(q, k_pages, v_pages, pos_pages, table, k_self, v_self,
            positions, pool_limit, *, bsz: int, qc: int, scale, softcap,
            window, plan: KernelPlan):
    """Shared wrapper: lay the operands out for ``_kernel`` and launch.

    q (B, T, H, Dk); pool leaves (P, Hkv, bsz, D) / pos (P, bsz); table
    (B, n_pool); self K/V (B, T, Hkv, D) block-aligned; positions (B, T);
    pool_limit (B, T) per-query limit for pool keys.  Returns
    (B, T, H, Dv)."""
    B, T, H, Dk = q.shape
    P, Hkv = k_pages.shape[:2]
    Dv = v_pages.shape[-1]
    group = H // Hkv
    n_pool = table.shape[1]
    Ts, nq = T // bsz, T // qc
    bp, dkp, dvp = bsz, Dk, Dv
    if plan.padded:
        bp = _ceil_to(bsz, _SUBLANES)
        dkp, dvp = _ceil_to(Dk, _LANES), _ceil_to(Dv, _LANES)

    # queries: (B, Hkv, nq, group*qc, Dk), g-major rows per kv head
    qh = q.reshape(B, nq, qc, Hkv, group, Dk).transpose(0, 3, 1, 4, 2, 5)
    qh = _pad_dim(qh.reshape(B, Hkv, nq, group * qc, Dk), 4, dkp)
    # self keys block-wise, head-major like the pool: (B, Ts, Hkv, bp, D)
    def blocks(a, d):
        a = a.reshape(B, Ts, bsz, Hkv, a.shape[-1]).transpose(0, 1, 3, 2, 4)
        return _pad_dim(_pad_dim(a, 3, bp), 4, d)
    ksh, vsh = blocks(k_self, dkp), blocks(v_self, dvp)
    spos = _pad_dim(positions.astype(jnp.int32).reshape(B, Ts, 1, bsz),
                    3, bp, value=-1)
    kp = _pad_dim(_pad_dim(k_pages, 2, bp), 3, dkp)
    vp = _pad_dim(_pad_dim(v_pages, 2, bp), 3, dvp)
    pp = _pad_dim(pos_pages.astype(jnp.int32), 1, bp,
                  value=-1).reshape(P, 1, bp)
    # per-query columns: position, pool-key limit, self-key limit (the
    # end of the query's block: bidirectional inside it, causal across)
    positions = positions.astype(jnp.int32)
    block_end = (positions // bsz + 1) * bsz
    qpos = _rows(positions, nq, group)
    plim = _rows(pool_limit.astype(jnp.int32), nq, group)
    slim = _rows(block_end, nq, group)
    table = table.astype(jnp.int32)
    if n_pool == 0:           # keep the prefetch operand 2-D and mapped
        table = jnp.full((B, 1), -1, jnp.int32)
    R = group * qc

    def q_map(b, h, qt, j, tr):
        return (b, h, qt, 0, 0)

    def page_map(b, h, qt, j, tr):
        page = tr[b, jnp.minimum(j, max(n_pool - 1, 0))]
        return (jnp.maximum(page, 0), h, 0, 0)

    def ppos_map(b, h, qt, j, tr):
        page = tr[b, jnp.minimum(j, max(n_pool - 1, 0))]
        return (jnp.maximum(page, 0), 0, 0)

    def self_map(b, h, qt, j, tr):
        return (b, jnp.clip(j - n_pool, 0, Ts - 1), h, 0, 0)

    def spos_map(b, h, qt, j, tr):
        return (b, jnp.clip(j - n_pool, 0, Ts - 1), 0, 0)

    def row_map(b, h, qt, j, tr):
        return (b, qt, 0, 0)

    kern = functools.partial(_kernel, scale=scale, softcap=softcap,
                             window=window, n_pool=n_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nq, n_pool + Ts),
        in_specs=[
            pl.BlockSpec((1, 1, 1, R, dkp), q_map),
            pl.BlockSpec((1, 1, bp, dkp), page_map),
            pl.BlockSpec((1, 1, bp, dvp), page_map),
            pl.BlockSpec((1, 1, bp), ppos_map),
            pl.BlockSpec((1, 1, 1, bp, dkp), self_map),
            pl.BlockSpec((1, 1, 1, bp, dvp), self_map),
            pl.BlockSpec((1, 1, 1, bp), spos_map),
            pl.BlockSpec((1, 1, R, 1), row_map),
            pl.BlockSpec((1, 1, R, 1), row_map),
            pl.BlockSpec((1, 1, R, 1), row_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, R, dvp), q_map),
        scratch_shapes=[
            pltpu.VMEM((R, dvp), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
            pltpu.VMEM((R, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, nq, R, dvp), q.dtype),
        interpret=plan.interpret,
    )(table, qh, kp, vp, pp, ksh, vsh, spos, qpos, plim, slim)
    out = out.reshape(B, Hkv, nq, group, qc, dvp).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(B, T, H, dvp)[..., :Dv]


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pos_pages: jax.Array,
                           table: jax.Array, k_self: jax.Array,
                           v_self: jax.Array, positions: jax.Array,
                           cache_limit: jax.Array, *,
                           scale: float,
                           softcap: float | None = None,
                           window: int | None = None,
                           interpret: bool | None = None,
                           pad: bool | None = None) -> jax.Array:
    """Decode attention over (pool pages ++ self block), in place.

    q          (B, n, H, Dk)   current-block queries (n == page size)
    k_pages    (P, Hkv, bsz, Dk) shared pool, rotated keys, head-major
    v_pages    (P, Hkv, bsz, Dv)
    pos_pages  (P, bsz) int32  absolute position ids, -1 = empty slot
    table      (B, K) int32    block -> page, -1 = no page
    k_self     (B, n, Hkv, Dk) the block's own fresh keys
    v_self     (B, n, Hkv, Dv)
    positions  (B, n) int32    the block's absolute positions
    cache_limit (B,) int32     pool keys visible iff pos < limit[b]

    Returns (B, n, H, Dv) in q's dtype.  ``interpret``/``pad`` follow
    ``plan_exec``.  Padding is bit-exact per construction — padded key
    rows carry ``pos = -1`` (masked -> exact ``+0.0`` tail terms in the
    softmax sum and the PV product), padded head dims are zero (exact
    ``+0.0`` tail terms in the QK contraction) — so the padded kernel
    matches the unpadded one bitwise (tests force ``pad=True`` on CPU
    to pin this).
    """
    B, n, H, Dk = q.shape
    P, Hkv, bsz, _ = k_pages.shape
    assert n == bsz, (n, bsz)     # decode block == page granularity
    assert H % Hkv == 0
    plan = plan_exec(bsz, Dk, v_pages.shape[-1], interpret=interpret,
                     pad=pad)
    pool_limit = jnp.broadcast_to(
        cache_limit.astype(jnp.int32)[:, None], (B, n))
    return _launch(q, k_pages, v_pages, pos_pages, table, k_self, v_self,
                   positions, pool_limit, bsz=bsz, qc=n, scale=scale,
                   softcap=softcap, window=window, plan=plan)


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, pos_pages: jax.Array,
                            context_table: jax.Array, k_self: jax.Array,
                            v_self: jax.Array, positions: jax.Array, *,
                            scale: float,
                            softcap: float | None = None,
                            window: int | None = None,
                            interpret: bool | None = None,
                            pad: bool | None = None) -> jax.Array:
    """Plain-mode attention of suffix queries over (prefix pages ++
    suffix self keys), reading the pool in place.

    q             (B, T, H, Dk)   suffix queries, T a block multiple
    k_pages       (P, Hkv, bsz, Dk) shared pool, rotated keys, head-major
    v_pages       (P, Hkv, bsz, Dv)
    pos_pages     (P, bsz) int32  absolute positions, -1 = empty slot
    context_table (B, Kp) int32   hit-prefix block -> page (-1 masked)
    k_self        (B, T, Hkv, Dk) the suffix's own fresh keys
    v_self        (B, T, Hkv, Dv)
    positions     (B, T) int32    absolute suffix positions (all valid
                                  — the ``prefill_suffix`` layout)

    Returns (B, T, H, Dv) in q's dtype.  Agrees with the gathered path
    (``models.attention`` ``_paged_context_kv`` +
    ``kernels.ops.chunked_masked_attention``) to f32 rounding; see the
    module docstring for why it is not bitwise.  ``interpret``/``pad``
    follow ``plan_exec``; padded and unpadded launches agree bitwise.
    """
    B, T, H, Dk = q.shape
    P, Hkv, bsz, _ = k_pages.shape
    assert T % bsz == 0, (T, bsz)
    assert H % Hkv == 0
    plan = plan_exec(bsz, Dk, v_pages.shape[-1], interpret=interpret,
                     pad=pad)
    positions = positions.astype(jnp.int32)
    block_end = (positions // bsz + 1) * bsz
    return _launch(q, k_pages, v_pages, pos_pages, context_table, k_self,
                   v_self, positions, block_end, bsz=bsz,
                   qc=_pick_chunk(T, _Q_CHUNK), scale=scale,
                   softcap=softcap, window=window, plan=plan)

"""Attention sublayers: GQA (+SWA, softcap), absorbed MLA, cross-attention.

Every mixer supports three execution modes (see model.py):

* ``dup``    — one fused pass over the duplicated sequence under the
               block-diffusion mask (the paper's §4.1 fast path);
* ``plain``  — committed-context (block-causal) pass; optionally fills the
               KV cache (prefill / block commit);
* ``decode`` — current-block queries against (cache ++ self-block) keys,
               the inference denoise step.

KV caches store *rotated* keys with explicit position ids so sliding-window
ring buffers and sequence-sharded caches need no extra bookkeeping:
``pos < 0`` marks unfilled slots.

Every paged-KV attention pass — per-step decode *and* admission-time
suffix prefill — dispatches through one **KV-layout object**
(``resolve_kv_layout``), the strategy that decides how a layer's
cached keys reach the attention math:

* ``dense``     (``AttnCache``) — every sequence owns a contiguous
                (S, ...) region (prefill, replay, one-shot generate,
                and the scheduler's ``cache="dense"``); decode
                concatenates (cache ++ self) and runs the masked
                reference.
* ``gathered``  (``PagedAttnCache``, ``kernel="ref"``) — the shared
                page pool is gathered through the per-sequence block
                table into a dense-width copy; decode runs the *same*
                concat path as ``dense`` and suffix prefill runs the
                full-prefill chunked kernel over (gathered prefix ++
                suffix) keys — the portable fallback, byte-identical
                to the dense paths by construction.
* ``paged``     (``PagedAttnCache``, ``kernel="pallas"``) — the
                ``kernels.paged_attn`` family reads the pool
                **in place**: ``paged_decode_attention`` for the
                denoise step and ``paged_prefill_attention`` for the
                shared-prefix suffix prefill, each streaming one page
                per grid step via the scalar-prefetched block table.
                No dense-width K/V copy is ever materialized, so
                transient decode memory stops scaling with
                slots x K*bsz and admission-time transient bytes drop
                to zero (off-TPU the kernels run under
                ``interpret=True``, so CPU CI exercises the real
                path; sub-tile page shapes are zero-padded to the
                (8, 128) f32 tile so real TPUs stay on the compiled
                path — see ``kernels.paged_attn.plan_exec``).

All layouts implement the same masking contract — null page 0,
``pos = -1`` empty slots, per-row ``cache_limit``, sliding window, and
the MLA latent-MQA form.  The gathered layouts are byte-identical to
the dense ones; the in-place kernels agree with them to f32 rounding
(online softmax sums in another order) and, at the tested seeds, give
the same decode tokens (tests/test_paged_attn.py).
``transient_kv_bytes`` quantifies the per-decode-step copy each layout
pays and ``prefill_transient_kv_bytes`` the admission-time gather
width (both 0 for the in-place kernels); ``kernel_exec_plan`` reports
whether the Pallas path would compile or interpret on this backend,
and why.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.masks import SeqMeta, visibility
from repro.kernels import ops as kops
from repro.kernels.ref import mha_reference, NEG_INF
from .config import ModelConfig
from .modules import apply_rope, init_linear, linear, rmsnorm, split_like


class AttnCache(NamedTuple):
    k: jax.Array    # (B, S, Hkv, Dk) rotated
    v: jax.Array    # (B, S, Hkv, Dv)
    pos: jax.Array  # (B, S) int32, -1 = empty


class PagedAttnCache(NamedTuple):
    """A shared pool of ``block_size``-token KV pages (vLLM-style).

    Sequences do not own contiguous cache rows; a per-sequence *block
    table* (carried in ``GenState.table``, shape (B, n_blocks)) maps each
    sequence's block index to the page holding its keys.  Table entry -1
    means "no page": reads of such blocks are masked invalid and writes
    are dumped into page 0 — the *null page*, which an allocator must
    never hand out and whose ``pos`` is forced to -1 on every dump so it
    can never leak into attention.

    Pages store rotated keys with *absolute* position ids, so a prompt
    page is content-addressed: any sequence whose prompt contains the
    same tokens at the same positions can map the page into its table
    and read it verbatim (``serving.prefix_cache``).  Shared pages are
    read-only by construction — a live sequence's commit cursor never
    re-enters its prompt region, and evicted slots dump their idempotent
    re-commits into the null page — so sharing needs refcounts but no
    copy-on-write.

    K/V pages are head-major, (P, Hkv, bsz, D): one kv head's page is a
    contiguous (bsz, D) tile, the block the Pallas kernels DMA per grid
    step (Mosaic requires a block's last two dims to be (8, 128)
    multiples or the array's own, which a (bsz, 1, D) slice of a
    token-major page is not).
    """
    k: jax.Array    # (P, Hkv, bsz, Dk) rotated
    v: jax.Array    # (P, Hkv, bsz, Dv)
    pos: jax.Array  # (P, bsz) int32, -1 = empty


def make_attn_cache(batch: int, seq: int, n_kv: int, dk: int, dv: int,
                    dtype) -> AttnCache:
    return AttnCache(
        k=jnp.zeros((batch, seq, n_kv, dk), dtype),
        v=jnp.zeros((batch, seq, n_kv, dv), dtype),
        pos=jnp.full((batch, seq), -1, jnp.int32))


def make_paged_attn_cache(n_pages: int, block_size: int, n_kv: int,
                          dk: int, dv: int, dtype) -> PagedAttnCache:
    return PagedAttnCache(
        k=jnp.zeros((n_pages, n_kv, block_size, dk), dtype),
        v=jnp.zeros((n_pages, n_kv, block_size, dv), dtype),
        pos=jnp.full((n_pages, block_size), -1, jnp.int32))


def _to_pages(a: jax.Array) -> jax.Array:
    """Token-major blocks (..., bsz, Hkv, D) <-> head-major pages
    (..., Hkv, bsz, D) (the swap is its own inverse)."""
    return jnp.swapaxes(a, -3, -2)


def paged_gather(cache: PagedAttnCache, table: jax.Array):
    """Gather each sequence's pages into key order.

    table (B, K) int32 -> (k, v, pos) with a (B, K*bsz, ...) layout that
    matches a dense full-length cache row block-for-block; unallocated
    blocks (table -1) read the null page with ``pos`` forced to -1, so
    the ordinary pos-validity mask hides them.

    This materializes a dense-width K/V copy, so it survives only where
    that is cheap or unavoidable: the ``kernel="ref"`` decode fallback
    (portability / parity oracle) and the shared-prefix suffix prefill
    (admission-time one-off whose gather width is just the hit prefix).
    The per-step decode path reads the pool in place instead
    (``kernels.paged_attn`` via ``resolve_kv_layout``).
    """
    B, K = table.shape
    idx = jnp.maximum(table, 0)                    # -1 -> null page 0
    # head-major pages back to token-major rows: (B, K, bsz, Hkv, D)
    k, v = _to_pages(cache.k[idx]), _to_pages(cache.v[idx])
    pos = jnp.where(table[:, :, None] >= 0, cache.pos[idx], -1)
    Hkv, bsz = cache.k.shape[1:3]
    return (k.reshape(B, K * bsz, Hkv, k.shape[-1]),
            v.reshape(B, K * bsz, Hkv, v.shape[-1]),
            pos.reshape(B, K * bsz))


def paged_cache_write(cache: PagedAttnCache, k: jax.Array, v: jax.Array,
                      positions: jax.Array,
                      table: jax.Array) -> PagedAttnCache:
    """Commit one block-aligned block per sequence into its own page.

    ``positions`` (B, bsz) must cover exactly one block per row.  Rows
    whose block has no page (table -1 — e.g. an evicted slot idempotently
    re-committing its frozen block) are dumped into the null page with
    ``pos`` = -1, so they can never corrupt a live sequence's page.
    """
    bsz = cache.k.shape[2]
    rows = jnp.arange(k.shape[0], dtype=jnp.int32)
    page = table[rows, positions[:, 0] // bsz]     # (B,)
    safe = jnp.maximum(page, 0)
    pos_w = jnp.where(page[:, None] >= 0, positions.astype(jnp.int32), -1)
    return PagedAttnCache(
        k=cache.k.at[safe].set(_to_pages(k).astype(cache.k.dtype)),
        v=cache.v.at[safe].set(_to_pages(v).astype(cache.v.dtype)),
        pos=cache.pos.at[safe].set(pos_w))


def write_prompt_pages(cache: PagedAttnCache, row: AttnCache,
                       pages: jax.Array) -> PagedAttnCache:
    """Scatter a B=1 dense prefill row into freshly allocated pages.

    ``row`` leaves are (1, L, ...) with L a block multiple (a ring-free
    prefill); ``pages`` (Kp,) holds the page ids for the first Kp blocks.
    """
    bsz = cache.k.shape[2]
    Kp = pages.shape[0]

    def blocks(a):
        L = a.shape[1]
        return a.reshape(L // bsz, bsz, *a.shape[2:])[:Kp]

    return PagedAttnCache(
        k=cache.k.at[pages].set(
            _to_pages(blocks(row.k)).astype(cache.k.dtype)),
        v=cache.v.at[pages].set(
            _to_pages(blocks(row.v)).astype(cache.v.dtype)),
        pos=cache.pos.at[pages].set(blocks(row.pos)))


def write_prompt_pages_grouped(cache: PagedAttnCache, row: AttnCache,
                               pages: jax.Array) -> PagedAttnCache:
    """``write_prompt_pages`` for G-stacked group caches: pool leaves are
    (G, P, ...) and the prefill row's are (G, 1, L, ...)."""
    bsz = cache.k.shape[3]
    Kp = pages.shape[0]

    def blocks(a):
        G, _, L = a.shape[:3]
        return a.reshape(G, L // bsz, bsz, *a.shape[3:])[:, :Kp]

    return PagedAttnCache(
        k=cache.k.at[:, pages].set(
            _to_pages(blocks(row.k)).astype(cache.k.dtype)),
        v=cache.v.at[:, pages].set(
            _to_pages(blocks(row.v)).astype(cache.v.dtype)),
        pos=cache.pos.at[:, pages].set(blocks(row.pos)))


def write_suffix_pages(cache: PagedAttnCache, k: jax.Array, v: jax.Array,
                       positions: jax.Array,
                       pages: jax.Array) -> PagedAttnCache:
    """Commit block-aligned suffix K/V into per-row pages.

    k/v (B, T, ...), positions (B, T) with T a block multiple; ``pages``
    (B, T // bsz) holds each row's freshly allocated page ids (the
    shared-prefix *suffix* of its prompt).  Unlike ``paged_cache_write``
    this writes several blocks per row in one shot and has no null-page
    escape: suffix pages are always freshly allocated.
    """
    bsz = cache.k.shape[2]
    B, T = positions.shape
    Ks = T // bsz

    def blocks(a):
        return a.reshape(B, Ks, bsz, *a.shape[2:]).reshape(
            B * Ks, bsz, *a.shape[2:])

    idx = pages.reshape(-1)
    return PagedAttnCache(
        k=cache.k.at[idx].set(_to_pages(blocks(k)).astype(cache.k.dtype)),
        v=cache.v.at[idx].set(_to_pages(blocks(v)).astype(cache.v.dtype)),
        pos=cache.pos.at[idx].set(blocks(positions.astype(jnp.int32))))


def wipe_pages(cache: PagedAttnCache, pages: jax.Array, *,
               grouped: bool) -> PagedAttnCache:
    """Force ``pos = -1`` on ``pages`` (free-list / reclaim hygiene).

    A page leaving the prefix index or returning to the free list must
    look empty until its next owner writes it: stale positions could
    otherwise pass the ``pos < cache_limit`` validity mask of a page
    that is table-mapped before it is first written.
    """
    pos = cache.pos.at[:, pages].set(-1) if grouped \
        else cache.pos.at[pages].set(-1)
    return cache._replace(pos=pos)


def _paged_context_kv(cache: PagedAttnCache, context_table: jax.Array,
                      k_self: jax.Array, v_self: jax.Array, meta: SeqMeta,
                      block_size: int):
    """(keys, vals, k_meta) = gathered shared-prefix pages ++ suffix self.

    The gather width is exactly the hit prefix (``context_table`` has no
    -1 padding), so the combined key array reproduces the full-prefill
    key layout byte-for-byte: prefix keys at [0, Kp*bsz), suffix keys
    after, no interleaved invalid slots.  That layout equality is what
    makes the chunked kernel's chunk boundaries — and therefore its
    bits — match the full plain pass (see core.decoding.prefill_suffix).
    """
    ck, cv, cpos = paged_gather(cache, context_table)
    keys = jnp.concatenate([ck.astype(k_self.dtype), k_self], axis=1)
    vals = jnp.concatenate([cv.astype(v_self.dtype), v_self], axis=1)
    cvalid = cpos >= 0
    cmeta = SeqMeta(copy=jnp.zeros(cpos.shape, jnp.int32),
                    block=jnp.where(cvalid, cpos // block_size, -1),
                    step=jnp.zeros(cpos.shape, jnp.int32),
                    pos=cpos, valid=cvalid)
    k_meta = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=-1),
                          cmeta, meta)
    return keys, vals, k_meta


def cache_write(cache: AttnCache, k: jax.Array, v: jax.Array,
                positions: jax.Array) -> AttnCache:
    """Write a block of (rotated) keys at ``positions`` (B, n).

    Full caches write at index == position; ring caches (S < max positions)
    write at position % S — both are the same modulo op.
    """
    S = cache.k.shape[1]
    idx = positions % S  # (B, n)
    bidx = jnp.arange(k.shape[0], dtype=jnp.int32)[:, None]
    return AttnCache(
        k=cache.k.at[bidx, idx].set(k.astype(cache.k.dtype)),
        v=cache.v.at[bidx, idx].set(v.astype(cache.v.dtype)),
        pos=cache.pos.at[bidx, idx].set(positions.astype(jnp.int32)))


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig) -> dict:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = split_like(key, ["wq", "wk", "wv", "wo"])
    return {
        "wq": init_linear(ks["wq"], d, H * Dh, dtype=dt),
        "wk": init_linear(ks["wk"], d, Hkv * Dh, dtype=dt),
        "wv": init_linear(ks["wv"], d, Hkv * Dh, dtype=dt),
        "wo": init_linear(ks["wo"], H * Dh, d, dtype=dt),
    }


def _gqa_scale(cfg: ModelConfig) -> float:
    return cfg.query_scale or cfg.resolved_head_dim ** -0.5


def gqa_qkv(p, x, positions, cfg: ModelConfig):
    B, T, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(B, T, H, Dh)
    k = linear(p["wk"], x).reshape(B, T, Hkv, Dh)
    v = linear(p["wv"], x).reshape(B, T, Hkv, Dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_masked(p, x, meta: SeqMeta, cfg: ModelConfig, *,
               window: int | None, dup_len: int | None,
               strict: bool = False
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """dup / plain modes: mask comes from SeqMeta.

    Returns (out, k, v) so prefill can write the cache."""
    B, T, _ = x.shape
    q, k, v = gqa_qkv(p, x, meta.pos, cfg)
    softcap = cfg.attn_logit_softcap or None
    o = kops.attention(
        q, k, v, meta, meta,
        impl=cfg.attn_impl,
        scale=_gqa_scale(cfg), softcap=softcap, window=window,
        strict=strict, dup_len=dup_len, block_size=cfg.block_size)
    return linear(p["wo"], o.reshape(B, T, -1)), k, v


def gqa_plain_paged(p, x, meta: SeqMeta, cache: PagedAttnCache,
                    cfg: ModelConfig, *, window: int | None,
                    context_table: jax.Array, write_pages: jax.Array,
                    kernel: str = "ref"
                    ) -> tuple[jax.Array, PagedAttnCache]:
    """Plain committed pass over a prompt *suffix* against shared pages.

    ``x``/``meta`` cover only the suffix rows (absolute positions);
    attention keys are the shared-prefix pages behind ``context_table``
    followed by the suffix's own K/V — the same key layout and masking
    as the full plain pass, so with ``kernel="ref"`` the computed
    suffix KV (committed into ``write_pages``) is bitwise identical to
    what a full prefill would have produced (when the cache dtype
    equals the activation dtype; see core.decoding.prefill_suffix), and
    with ``kernel="pallas"`` equal to f32 rounding.  ``kernel`` picks
    how the prefix pages are read: ``"ref"`` gathers them into a
    dense-width copy, ``"pallas"`` streams them in place
    (``paged_prefill_attention``), eliminating the admission-time
    transient.
    """
    B, T, _ = x.shape
    q, k, v = gqa_qkv(p, x, meta.pos, cfg)
    o = resolve_kv_layout(cache, kernel).prefill_attend(
        q, k, v, meta, cache,
        context_table=context_table, block_size=cfg.block_size,
        impl=cfg.attn_impl, scale=_gqa_scale(cfg),
        softcap=cfg.attn_logit_softcap or None, window=window)
    new_cache = write_suffix_pages(cache, k, v, meta.pos, write_pages)
    return linear(p["wo"], o.reshape(B, T, -1)), new_cache


def _cache_decode_attention(q, keys, vals, key_pos, key_valid, q_pos, *,
                            scale, softcap, window):
    """q (B,n,H,Dk) vs gathered keys (B,S',Hkv,Dk) with validity mask."""
    mask = key_valid[:, None, :]                       # (B, 1, S')
    mask = jnp.broadcast_to(mask, (q.shape[0], q.shape[1], keys.shape[1]))
    if window is not None:
        mask = mask & ((q_pos[:, :, None] - key_pos[:, None, :]) < window)
    return mha_reference(q, keys, vals, mask, scale=scale, softcap=softcap)


def _decode_key_mask(cache_pos, positions, cache_limit):
    """validity of (cache ++ self) keys given a per-sequence cache limit."""
    cvalid = cache_pos >= 0
    if cache_limit is not None:
        lim = jnp.asarray(cache_limit)
        if lim.ndim == 0:
            lim = lim[None]
        cvalid = cvalid & (cache_pos < lim[:, None])
    svalid = jnp.ones(positions.shape, bool)
    return jnp.concatenate([cvalid, svalid], axis=1)


# ---------------------------------------------------------------------------
# KV layouts — how decode attention reads a layer's cached keys
# ---------------------------------------------------------------------------


class KVLayout:
    """Strategy object behind ``gqa_decode``/``mla_decode`` and the
    ``*_plain_paged`` suffix-prefill passes.

    One layout = one answer to "how do the committed keys reach the
    attention math": read the dense buffer, gather the page pool into a
    dense-width copy, or run the page-aware kernels over the pool in
    place.  Two entry points per layout — ``attend`` (decode step) and
    ``prefill_attend`` (plain pass over a prompt suffix against
    shared-prefix pages).  All layouts share the masking contract
    (``pos = -1`` empty, ``cache_limit``, sliding window, null page)
    and the commit path's write discipline; ``transient_bytes`` /
    ``prefill_transient_bytes`` report the cache-KV copy each pass
    materializes outside the resident cache (the capacity tax the
    in-place kernels remove).
    """

    kind = "?"

    def attend(self, q, k_self, v_self, positions, cache, *, block_table,
               cache_limit, scale, softcap, window):
        raise NotImplementedError

    def prefill_attend(self, q, k_self, v_self, meta, cache, *,
                       context_table, block_size, impl, scale, softcap,
                       window):
        """Plain-mode pass of suffix queries over (shared-prefix pages
        ++ suffix self keys); must match the full-prefill chunked
        kernel over the same key layout — bitwise for the gathered
        layout, to f32 rounding for the in-place kernel (the
        ``serving.prefix_cache`` invariant)."""
        raise NotImplementedError

    def commit(self, cache, k_self, v_self, positions, block_table):
        if isinstance(cache, PagedAttnCache):
            return paged_cache_write(cache, k_self, v_self, positions,
                                     block_table)
        return cache_write(cache, k_self, v_self, positions)

    @staticmethod
    def _concat_attend(ck, cv, cpos, q, k_self, v_self, positions, *,
                       cache_limit, scale, softcap, window):
        """The shared (cache ++ self) reference path."""
        keys = jnp.concatenate([ck.astype(k_self.dtype), k_self], axis=1)
        vals = jnp.concatenate([cv.astype(v_self.dtype), v_self], axis=1)
        key_pos = jnp.concatenate(
            [cpos, positions.astype(jnp.int32)], axis=1)
        key_valid = _decode_key_mask(cpos, positions, cache_limit)
        return _cache_decode_attention(
            q, keys, vals, key_pos, key_valid, positions,
            scale=scale, softcap=softcap, window=window)

    @staticmethod
    def transient_bytes(cache, n_rows: int, n_blocks: int) -> int:
        return 0

    @staticmethod
    def prefill_transient_bytes(cache, n_rows: int,
                                n_ctx_blocks: int) -> int:
        return 0


class _DenseKV(KVLayout):
    """Contiguous per-sequence cache rows; decode concatenates the row
    with the self block (one cache-width copy per layer per step)."""

    kind = "dense"

    def attend(self, q, k_self, v_self, positions, cache, *, block_table,
               cache_limit, scale, softcap, window):
        return self._concat_attend(
            cache.k, cache.v, cache.pos, q, k_self, v_self, positions,
            cache_limit=cache_limit, scale=scale, softcap=softcap,
            window=window)

    @staticmethod
    def transient_bytes(cache, n_rows: int, n_blocks: int) -> int:
        S = cache.k.shape[-3]
        return n_rows * S * _kv_token_bytes(cache)


class _GatheredPagedKV(KVLayout):
    """``kernel="ref"``: gather the pool through the block table into a
    dense-width copy, then run the identical concat / full-prefill
    paths — the portable fallback and the parity oracle for the
    in-place kernels."""

    kind = "gathered"

    def attend(self, q, k_self, v_self, positions, cache, *, block_table,
               cache_limit, scale, softcap, window):
        ck, cv, cpos = paged_gather(cache, block_table)
        return self._concat_attend(
            ck, cv, cpos, q, k_self, v_self, positions,
            cache_limit=cache_limit, scale=scale, softcap=softcap,
            window=window)

    def prefill_attend(self, q, k_self, v_self, meta, cache, *,
                       context_table, block_size, impl, scale, softcap,
                       window):
        keys, vals, k_meta = _paged_context_kv(
            cache, context_table, k_self, v_self, meta, block_size)
        return kops.attention(
            q, keys, vals, meta, k_meta, impl=impl, scale=scale,
            softcap=softcap, window=window, strict=False, dup_len=None,
            block_size=block_size)

    @staticmethod
    def transient_bytes(cache, n_rows: int, n_blocks: int) -> int:
        bsz = cache.k.shape[-2]
        return n_rows * n_blocks * bsz * _kv_token_bytes(cache)

    @staticmethod
    def prefill_transient_bytes(cache, n_rows: int,
                                n_ctx_blocks: int) -> int:
        bsz = cache.k.shape[-2]
        return n_rows * n_ctx_blocks * bsz * _kv_token_bytes(cache)


class _InplacePagedKV(KVLayout):
    """``kernel="pallas"``: the page-aware kernels read the pool in
    place (one page per grid step via the scalar-prefetched block
    table) — no dense-width K/V copy exists at any point, decode or
    admission."""

    kind = "paged"

    def attend(self, q, k_self, v_self, positions, cache, *, block_table,
               cache_limit, scale, softcap, window):
        from repro.kernels.paged_attn import paged_decode_attention
        B = q.shape[0]
        if cache_limit is None:
            lim = jnp.full((B,), jnp.iinfo(jnp.int32).max, jnp.int32)
        else:
            lim = jnp.broadcast_to(
                jnp.asarray(cache_limit, jnp.int32).reshape(-1), (B,))
        return paged_decode_attention(
            q, cache.k, cache.v, cache.pos, block_table,
            k_self, v_self, positions, lim,
            scale=scale, softcap=softcap, window=window)

    def prefill_attend(self, q, k_self, v_self, meta, cache, *,
                       context_table, block_size, impl, scale, softcap,
                       window):
        from repro.kernels.paged_attn import paged_prefill_attention
        return paged_prefill_attention(
            q, cache.k, cache.v, cache.pos, context_table,
            k_self, v_self, meta.pos,
            scale=scale, softcap=softcap, window=window)


_KV_LAYOUTS = {
    ("dense", "ref"): _DenseKV(),
    ("dense", "pallas"): _DenseKV(),   # dense rows: nothing to gather
    ("paged", "ref"): _GatheredPagedKV(),
    ("paged", "pallas"): _InplacePagedKV(),
}


def _kv_token_bytes(cache) -> int:
    """Per-token bytes of one (k, v, pos) cache entry."""
    hkv = cache.k.shape[-3] if isinstance(cache, PagedAttnCache) \
        else cache.k.shape[-2]
    dk = cache.k.shape[-1]
    dv = cache.v.shape[-1]
    return hkv * (dk * cache.k.dtype.itemsize
                  + dv * cache.v.dtype.itemsize) + 4


def resolve_kv_layout(cache, kernel: str = "ref") -> KVLayout:
    """Pick the decode KV layout for ``cache`` under ``kernel``.

    ``kernel="ref"`` — gathered fallback on paged caches, plain concat
    on dense; ``kernel="pallas"`` — the in-place page-aware kernel on
    paged caches (dense caches have no pages to gather, so the choice
    is a no-op there).
    """
    if kernel not in ("ref", "pallas"):
        raise ValueError(f"kernel must be ref|pallas, got {kernel!r}")
    store = "paged" if isinstance(cache, PagedAttnCache) else "dense"
    return _KV_LAYOUTS[(store, kernel)]


def transient_kv_bytes(cache, n_rows: int, n_blocks: int,
                       kernel: str = "ref") -> int:
    """Per-decode-step cache-KV bytes a layout copies out of the
    resident cache for one layer (the ``paged_gather`` / dense-concat
    transient); 0 for the in-place kernel path."""
    return resolve_kv_layout(cache, kernel).transient_bytes(
        cache, n_rows, n_blocks)


def prefill_transient_kv_bytes(cache, n_rows: int, n_ctx_blocks: int,
                               kernel: str = "ref") -> int:
    """Admission-time cache-KV bytes one layer's suffix prefill copies
    out of the resident cache: the shared-prefix gather width
    (``n_rows`` admitted rows x ``n_ctx_blocks`` hit pages) for the
    gathered layout, 0 for the in-place prefill kernel."""
    return resolve_kv_layout(cache, kernel).prefill_transient_bytes(
        cache, n_rows, n_ctx_blocks)


def kernel_exec_plan(cache, kernel: str = "ref"):
    """How the paged kernels would execute on this cache: a
    ``kernels.paged_attn.KernelPlan`` (mode ``compiled``/``interpret``
    plus the reason — backend vs tile shape vs padding), or ``None``
    when the layout never launches a Pallas kernel (``kernel="ref"`` or
    a dense cache)."""
    if kernel != "pallas" or not isinstance(cache, PagedAttnCache):
        return None
    from repro.kernels.paged_attn import plan_exec
    bsz = cache.k.shape[-2]
    return plan_exec(bsz, cache.k.shape[-1], cache.v.shape[-1])


def gqa_decode(p, x, positions, cache, cfg: ModelConfig, *,
               window: int | None, write_cache: bool,
               cache_limit=None, block_table=None, kernel: str = "ref"):
    """decode mode: block queries vs cache ++ self-block (bidirectional).

    ``cache`` is a dense per-sequence ``AttnCache`` or a shared
    ``PagedAttnCache`` (then ``block_table`` (B, K) maps block -> page).
    ``kernel`` selects the KV layout on paged caches: ``"ref"`` gathers
    pages into a dense-width copy, ``"pallas"`` reads the pool in place.
    """
    B, n, _ = x.shape
    q, k_self, v_self = gqa_qkv(p, x, positions, cfg)
    layout = resolve_kv_layout(cache, kernel)
    o = layout.attend(
        q, k_self, v_self, positions, cache, block_table=block_table,
        cache_limit=cache_limit, scale=_gqa_scale(cfg),
        softcap=cfg.attn_logit_softcap or None, window=window)
    new_cache = layout.commit(cache, k_self, v_self, positions,
                              block_table) if write_cache else cache
    return linear(p["wo"], o.reshape(B, n, -1)), new_cache


def write_prefill_cache(cache: AttnCache, k, v, positions) -> AttnCache:
    """Write a full prefill's keys into a (possibly ring) cache buffer.

    If the buffer is shorter than the sequence (sliding-window ring), only
    the last S entries are written (earlier ones would be overwritten
    anyway, and .at[].set with duplicate indices is unspecified)."""
    S = cache.k.shape[1]
    if k.shape[1] > S:
        k, v, positions = k[:, -S:], v[:, -S:], positions[:, -S:]
    return cache_write(cache, k, v, positions)


# ---------------------------------------------------------------------------
# MLA (absorbed form — attention runs over the 576-d latent, MQA-style)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    r, nope, rope, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim,
                         cfg.qk_rope_dim, cfg.v_head_dim)
    dt = jnp.dtype(cfg.param_dtype)
    names = ["wq_a", "wq_b", "w_dkv", "w_kb", "w_vb", "wo"]
    ks = split_like(key, names)
    qin = cfg.q_lora_rank or d
    p = {
        "w_dkv": init_linear(ks["w_dkv"], d, r + rope, dtype=dt),
        "ckv_norm": {"scale": jnp.zeros((r,), dt)},
        "w_kb": init_linear(ks["w_kb"], r, H * nope, dtype=dt),
        "w_vb": init_linear(ks["w_vb"], r, H * dv, dtype=dt),
        "wo": init_linear(ks["wo"], H * dv, d, dtype=dt),
        "wq_b": init_linear(ks["wq_b"], qin, H * (nope + rope), dtype=dt),
    }
    if cfg.q_lora_rank:
        p["wq_a"] = init_linear(ks["wq_a"], d, cfg.q_lora_rank, dtype=dt)
        p["q_norm"] = {"scale": jnp.zeros((cfg.q_lora_rank,), dt)}
    return p


def _mla_q_latent(p, x, positions, cfg: ModelConfig):
    """Absorbed queries: q' = [q_nope @ W_kb^T, rope(q_rope)], (B,T,H,r+rope)."""
    B, T, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    xq = x
    if cfg.q_lora_rank:
        xq = rmsnorm(p["q_norm"], linear(p["wq_a"], x), eps=cfg.norm_eps)
    q = linear(p["wq_b"], xq).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    wkb = p["w_kb"]["w"].reshape(r, H, nope)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                       wkb.astype(jnp.float32)).astype(x.dtype)
    return jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,T,H,r+rope)


def _mla_kv_latent(p, x, positions, cfg: ModelConfig):
    """Latent keys/values: k' = [rms(ckv), rope(k_rope)] (B,T,1,r+rope), v' = ckv."""
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = linear(p["w_dkv"], x)
    c, k_rope = ckv[..., :r], ckv[..., r:]
    c = rmsnorm(p["ckv_norm"], c, eps=cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    k_lat = jnp.concatenate([c[:, :, None, :], k_rope], axis=-1)
    return k_lat, c[:, :, None, :]  # (B,T,1,r+rope), (B,T,1,r)


def _mla_out(p, o, cfg: ModelConfig):
    """o (B,T,H,r) -> absorb W_vb then W_o."""
    B, T, H, r = o.shape
    wvb = p["w_vb"]["w"].reshape(r, H, cfg.v_head_dim)
    ov = jnp.einsum("bthr,rhv->bthv", o.astype(jnp.float32),
                    wvb.astype(jnp.float32))
    return linear(p["wo"], ov.reshape(B, T, -1).astype(o.dtype))


def _mla_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def mla_masked(p, x, meta: SeqMeta, cfg: ModelConfig, *,
               window: int | None, dup_len: int | None,
               strict: bool = False
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = _mla_q_latent(p, x, meta.pos, cfg)
    k, v = _mla_kv_latent(p, x, meta.pos, cfg)
    o = kops.attention(
        q, k, v, meta, meta,
        impl=cfg.attn_impl,
        scale=_mla_scale(cfg), softcap=None, window=window,
        strict=strict, dup_len=dup_len, block_size=cfg.block_size)
    return _mla_out(p, o, cfg), k, v


def mla_plain_paged(p, x, meta: SeqMeta, cache: PagedAttnCache,
                    cfg: ModelConfig, *, window: int | None,
                    context_table: jax.Array, write_pages: jax.Array,
                    kernel: str = "ref"
                    ) -> tuple[jax.Array, PagedAttnCache]:
    """``gqa_plain_paged`` for the absorbed-MLA mixer (latent KV pages):
    the latent MQA form (Hkv = 1, Dk = r+rope != Dv = r) rides the same
    prefill KV layouts."""
    B, T, _ = x.shape
    q = _mla_q_latent(p, x, meta.pos, cfg)
    k, v = _mla_kv_latent(p, x, meta.pos, cfg)
    o = resolve_kv_layout(cache, kernel).prefill_attend(
        q, k, v, meta, cache,
        context_table=context_table, block_size=cfg.block_size,
        impl=cfg.attn_impl, scale=_mla_scale(cfg), softcap=None,
        window=window)
    new_cache = write_suffix_pages(cache, k, v, meta.pos, write_pages)
    return _mla_out(p, o, cfg), new_cache


def mla_decode(p, x, positions, cache, cfg: ModelConfig, *,
               window: int | None, write_cache: bool,
               cache_limit=None, block_table=None, kernel: str = "ref"):
    """``gqa_decode`` for the absorbed-MLA mixer: the latent MQA form
    (Hkv = 1 over the r+rope latent) rides the same KV layouts — the
    page-aware kernel sees it as one shared kv head."""
    q = _mla_q_latent(p, x, positions, cfg)
    k_self, v_self = _mla_kv_latent(p, x, positions, cfg)
    layout = resolve_kv_layout(cache, kernel)
    o = layout.attend(
        q, k_self, v_self, positions, cache, block_table=block_table,
        cache_limit=cache_limit, scale=_mla_scale(cfg), softcap=None,
        window=window)
    new_cache = layout.commit(cache, k_self, v_self, positions,
                              block_table) if write_cache else cache
    return _mla_out(p, o, cfg), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers / enc-dec memory)
# ---------------------------------------------------------------------------


def init_cross(key, cfg: ModelConfig, *, gated: bool) -> dict:
    d, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = split_like(key, ["wq", "wk", "wv", "wo"])
    p = {
        "wq": init_linear(ks["wq"], d, H * Dh, dtype=dt),
        "wk": init_linear(ks["wk"], d, Hkv * Dh, dtype=dt),
        "wv": init_linear(ks["wv"], d, Hkv * Dh, dtype=dt),
        "wo": init_linear(ks["wo"], H * Dh, d, dtype=dt),
    }
    if gated:  # llama-3.2-vision tanh gates
        p["gate"] = jnp.zeros((), dt)
    return p


def cross_attn(p, x, memory, cfg: ModelConfig,
               memory_valid: jax.Array | None = None) -> jax.Array:
    """x (B,T,d) queries attend to memory (B,Ne,d); no positional rotation
    on memory keys (frontend embeddings carry their own positions)."""
    B, T, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(B, T, H, Dh)
    k = linear(p["wk"], memory).reshape(B, memory.shape[1], Hkv, Dh)
    v = linear(p["wv"], memory).reshape(B, memory.shape[1], Hkv, Dh)
    mask = None
    if memory_valid is not None:
        mask = jnp.broadcast_to(memory_valid[:, None, :],
                                (B, T, memory.shape[1]))
    o = mha_reference(q, k, v, mask, scale=Dh ** -0.5)
    y = linear(p["wo"], o.reshape(B, T, -1))
    if "gate" in p:
        y = jnp.tanh(p["gate"].astype(jnp.float32)).astype(y.dtype) * y
    return y

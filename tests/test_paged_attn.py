"""Page-aware kernel family: parity grids across the KV layouts.

Three levels:

* kernel-level decode — ``kernels.paged_attn.paged_decode_attention``
  (run through the real ``resolve_kv_layout`` dispatch) against the
  gathered fallback on raw pools: GQA / MLA-MQA shapes, sliding window,
  softcap, ragged block tables with -1 holes, ``cache_limit`` edges,
  and the null-page no-leak guarantee (bitwise: pool garbage cannot
  change the output);
* kernel-level prefill — ``paged_prefill_attention`` (the in-place
  suffix-prefill kernel) against the gathered plain-paged path to an
  f32 tolerance across GQA/MLA x window x softcap x prefix-hit widths,
  plus the (8, 128) tile-padding parity cases (block_size 4, head dim
  96: the padded launch compiled mode would run on TPU matches the
  unpadded output bitwise) and the ``plan_exec`` execution-planning
  contract;
* scheduler-level — decode TOKENS byte-identical across
  dense / gathered-paged / in-place-pallas pools under admission and
  eviction churn (the acceptance criterion), including sliding-window
  and MLA stacks, prefix-shared pages, partial-hit suffix-prefill
  admissions (with ``admit_transient_kv_bytes`` dropping to 0 in
  place), and mixed SamplingParams with the zero-retrace invariant
  (``n_advance_traces == 1``).

Nature of the token-level contract: the online-softmax kernel and the
plain-softmax fallback are different f32 arithmetic, so *logits* agree
only to ~1e-5 (hence the kernel-level rtol) — token byte-equality holds
because argmax/threshold decisions have margins orders of magnitude
above that rounding, verified empirically for these seeds on the
interpret path (the same empirical-bitwise standard PR 3 used for
``prefill_suffix``).  A failure here after a jax/XLA upgrade or on real
TPU hardware means a *decision boundary* moved — investigate the
numerics before touching the assertion.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.masks import SeqMeta
from repro.kernels.paged_attn import (paged_decode_attention,
                                      paged_prefill_attention, plan_exec)
from repro.models import attention as A
from repro.models.config import ModelConfig
from repro.models.model import BlockDiffLM
from repro.serving.engine import GenerationConfig, RolloutEngine
from repro.serving.api import SamplingParams
from repro.serving.scheduler import SlotScheduler
from repro.serving.server import ModelServer

BSZ = 8
MAX_LEN = 48
_BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
             vocab_size=128, block_size=BSZ, attn_impl="structured")


# ---------------------------------------------------------------------------
# kernel-level parity (raw pools, no model)
# ---------------------------------------------------------------------------


def _pool(key, *, P=11, K=5, Hkv=2, Dk=32, Dv=32, B=3):
    """A random pool + ragged table (with -1 holes and an all-hole row)
    + self block + per-row positions/limits covering the edge cases."""
    ks = jax.random.split(key, 6)
    kp = jax.random.normal(ks[0], (P, Hkv, BSZ, Dk), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, BSZ, Dv), jnp.float32)
    pos = np.arange(P * BSZ).reshape(P, BSZ).astype(np.int32) % (K * BSZ)
    pos[4, 3:] = -1                       # partially filled page
    table = np.full((B, K), -1, np.int32)
    table[0, :3] = [1, 2, 3]              # trailing holes
    table[1] = [5, 6, 7, 8, 9]            # full row
    # row 2: no pages at all — only the self block is visible
    k_self = jax.random.normal(ks[2], (B, BSZ, Hkv, Dk), jnp.float32)
    v_self = jax.random.normal(ks[3], (B, BSZ, Hkv, Dv), jnp.float32)
    # cache_limit edges: 0 (nothing committed), mid-sequence, full
    blk = np.array([0, 3, K], np.int32)
    positions = blk[:, None] * BSZ + np.arange(BSZ)[None, :]
    limit = blk * BSZ
    cache = A.PagedAttnCache(k=kp, v=vp, pos=jnp.asarray(pos))
    return (cache, jnp.asarray(table), k_self, v_self,
            jnp.asarray(positions), jnp.asarray(limit))


def _attend(cache, table, k_self, v_self, positions, limit, q, kernel,
            **kw):
    return A.resolve_kv_layout(cache, kernel).attend(
        q, k_self, v_self, positions, cache, block_table=table,
        cache_limit=limit, **kw)


@pytest.mark.parametrize("shape", ["gqa", "mla"])
@pytest.mark.parametrize("window,softcap", [(None, None), (12, None),
                                            (None, 5.0), (20, 5.0)])
def test_kernel_matches_gathered_reference(shape, window, softcap):
    """In-place kernel vs gathered fallback on the ragged-pool grid:
    GQA and the MLA latent-MQA form (Hkv=1, Dk != Dv), sliding window,
    softcap, -1 table holes, partially filled pages, limit edges."""
    H = 4
    dims = dict(Hkv=2, Dk=32, Dv=32) if shape == "gqa" \
        else dict(Hkv=1, Dk=40, Dv=32)
    cache, table, k_self, v_self, positions, limit = _pool(
        jax.random.PRNGKey(0), **dims)
    q = jax.random.normal(jax.random.PRNGKey(7),
                          (3, BSZ, H, dims["Dk"]), jnp.float32)
    kw = dict(scale=dims["Dk"] ** -0.5, softcap=softcap, window=window)
    o_ref = _attend(cache, table, k_self, v_self, positions, limit, q,
                    "ref", **kw)
    o_pal = _attend(cache, table, k_self, v_self, positions, limit, q,
                    "pallas", **kw)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-6)


def test_null_page_and_holes_never_leak():
    """Bitwise guarantee: garbage in the null page, in unmapped pages,
    and in pos=-1 slots cannot change the kernel output — the masking
    semantics (table -1, pos -1, cache_limit) hide them exactly."""
    cache, table, k_self, v_self, positions, limit = _pool(
        jax.random.PRNGKey(1))
    q = jax.random.normal(jax.random.PRNGKey(8), (3, BSZ, 4, 32),
                          jnp.float32)
    kw = dict(scale=32 ** -0.5, softcap=None, window=None)
    base = _attend(cache, table, k_self, v_self, positions, limit, q,
                   "pallas", **kw)
    # poison everything the mask must hide: the null page, pages no
    # table row maps (e.g. 4 has pos=-1 slots; 10 unmapped), and keys
    # past each row's cache_limit (handled by limit, not contents)
    mapped = {int(p) for p in np.asarray(table).ravel() if p >= 0}
    unmapped = [p for p in range(cache.k.shape[0]) if p not in mapped]
    poison = cache._replace(
        k=cache.k.at[jnp.asarray(unmapped)].set(1e9),
        v=cache.v.at[jnp.asarray(unmapped)].set(-1e9))
    got = _attend(poison, table, k_self, v_self, positions, limit, q,
                  "pallas", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    # limit=0 row sees only its self block: pool contents irrelevant
    poison_all = cache._replace(k=cache.k.at[:].set(1e9))
    got0 = _attend(poison_all, table, k_self, v_self, positions, limit,
                   q, "pallas", **kw)
    np.testing.assert_array_equal(np.asarray(got0)[0],
                                  np.asarray(base)[0])


def test_cache_limit_edges_match_reference():
    """Per-row limits at 0 / one-block / exactly-full agree with the
    gathered fallback (which inherits them from _decode_key_mask)."""
    cache, table, k_self, v_self, positions, _ = _pool(
        jax.random.PRNGKey(2))
    q = jax.random.normal(jax.random.PRNGKey(9), (3, BSZ, 4, 32),
                          jnp.float32)
    kw = dict(scale=32 ** -0.5, softcap=None, window=None)
    for lim in ([0, 0, 0], [BSZ, BSZ, BSZ], [0, 17, 5 * BSZ]):
        lim = jnp.asarray(lim, jnp.int32)
        o_ref = _attend(cache, table, k_self, v_self, positions, lim, q,
                        "ref", **kw)
        o_pal = _attend(cache, table, k_self, v_self, positions, lim, q,
                        "pallas", **kw)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-6)


def test_transient_kv_bytes_accounting():
    """The layout abstraction's copy accounting: gather width for the
    ref fallback, dense concat width for dense rows, 0 in place —
    decode (per-tick) and prefill (per-admission) both."""
    cache, *_ = _pool(jax.random.PRNGKey(3))
    per_tok = 2 * (32 + 32) * 4 + 4          # Hkv*(Dk+Dv)*itemsize + pos
    assert A.transient_kv_bytes(cache, 3, 5, "ref") == 3 * 5 * BSZ * per_tok
    assert A.transient_kv_bytes(cache, 3, 5, "pallas") == 0
    dense = A.make_attn_cache(3, MAX_LEN, 2, 32, 32, jnp.float32)
    assert A.transient_kv_bytes(dense, 3, 5, "ref") \
        == 3 * MAX_LEN * per_tok
    # admission-time suffix-prefill gather: hit-prefix width, 0 in place
    assert A.prefill_transient_kv_bytes(cache, 1, 4, "ref") \
        == 4 * BSZ * per_tok
    assert A.prefill_transient_kv_bytes(cache, 1, 4, "pallas") == 0
    assert A.prefill_transient_kv_bytes(dense, 1, 4, "ref") == 0
    with pytest.raises(ValueError, match="kernel"):
        A.resolve_kv_layout(cache, "cuda")


# ---------------------------------------------------------------------------
# kernel-level suffix-prefill parity (f32 tolerance) + tile padding +
# planning
# ---------------------------------------------------------------------------


def _prefill_pool(key, *, Kp, Ts, Hkv, Dk, Dv, B=2, bsz=BSZ):
    """A pool whose first B*Kp pages hold each row's committed prefix
    (sequential absolute positions) + a Ts-block suffix to prefill."""
    P = B * max(Kp, 1) + 2
    ks = jax.random.split(key, 5)
    pos = np.full((P, bsz), -1, np.int32)
    table = np.zeros((B, Kp), np.int32)
    pg = 1
    for b in range(B):
        for j in range(Kp):
            table[b, j] = pg
            pos[pg] = j * bsz + np.arange(bsz)
            pg += 1
    cache = A.PagedAttnCache(
        k=jax.random.normal(ks[0], (P, Hkv, bsz, Dk), jnp.float32),
        v=jax.random.normal(ks[1], (P, Hkv, bsz, Dv), jnp.float32),
        pos=jnp.asarray(pos))
    T = Ts * bsz
    positions = np.broadcast_to(Kp * bsz + np.arange(T), (B, T))
    q = jax.random.normal(ks[2], (B, T, 4 * Hkv, Dk), jnp.float32)
    k_self = jax.random.normal(ks[3], (B, T, Hkv, Dk), jnp.float32)
    v_self = jax.random.normal(ks[4], (B, T, Hkv, Dv), jnp.float32)
    meta = SeqMeta(copy=jnp.zeros((B, T), jnp.int32),
                   block=jnp.asarray(positions // bsz, jnp.int32),
                   step=jnp.zeros((B, T), jnp.int32),
                   pos=jnp.asarray(positions, jnp.int32),
                   valid=jnp.ones((B, T), bool))
    return cache, jnp.asarray(table), q, k_self, v_self, meta


def _prefill_attend(cache, table, q, k_self, v_self, meta, kernel, *,
                    bsz=BSZ, **kw):
    return A.resolve_kv_layout(cache, kernel).prefill_attend(
        q, k_self, v_self, meta, cache, context_table=table,
        block_size=bsz, impl="chunked", **kw)


@pytest.mark.parametrize("shape", ["gqa", "mla"])
@pytest.mark.parametrize("window,softcap", [(None, None), (12, None),
                                            (None, 5.0)])
@pytest.mark.parametrize("Kp", [0, 1, 3])
def test_prefill_kernel_bitwise_vs_gathered(shape, window, softcap, Kp):
    """The suffix-prefill contract: the in-place kernel matches the
    gathered plain-paged path across GQA and the MLA latent-MQA form
    (Hkv=1, Dk != Dv), sliding window, softcap, and prefix-hit widths
    from zero (pure-suffix) to several pages.

    The comparison is an f32 tolerance, not bitwise: the kernel sums
    its online softmax page by page while XLA reduces over the gathered
    keys, so the two differ in reduction order (one f32 ulp on values
    of order 1), and a compiled Mosaic kernel never reproduces XLA's
    order on the chip either.  The tolerance is four orders of
    magnitude below what one leaked key moves: the control below
    re-runs the reference with the suffix's block-causal mask broken
    and requires it to fail the same check."""
    dims = dict(Hkv=2, Dk=32, Dv=32) if shape == "gqa" \
        else dict(Hkv=1, Dk=40, Dv=32)
    cache, table, q, k_self, v_self, meta = _prefill_pool(
        jax.random.PRNGKey(4), Kp=Kp, Ts=2, **dims)
    kw = dict(scale=dims["Dk"] ** -0.5, softcap=softcap, window=window)
    o_ref = _prefill_attend(cache, table, q, k_self, v_self, meta,
                            "ref", **kw)
    o_pal = _prefill_attend(cache, table, q, k_self, v_self, meta,
                            "pallas", **kw)
    tol = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref), **tol)
    # control: first-block queries now see the second block's keys
    leak = dataclasses.replace(
        meta, block=jnp.full_like(meta.block, meta.block.max()))
    o_leak = _prefill_attend(cache, table, q, k_self, v_self, leak,
                             "ref", **kw)
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_leak),
                                   **tol)


def test_prefill_kernel_ignores_stale_pool_rows():
    """Bitwise guarantee: pool pages outside the context table (and the
    null page) cannot change the prefill output — the kernel streams
    only table-mapped pages and masks pos=-1 rows."""
    cache, table, q, k_self, v_self, meta = _prefill_pool(
        jax.random.PRNGKey(5), Kp=2, Ts=1, Hkv=2, Dk=32, Dv=32)
    kw = dict(scale=32 ** -0.5, softcap=None, window=None)
    base = _prefill_attend(cache, table, q, k_self, v_self, meta,
                           "pallas", **kw)
    mapped = {int(p) for p in np.asarray(table).ravel()}
    unmapped = [p for p in range(cache.k.shape[0]) if p not in mapped]
    poison = cache._replace(
        k=cache.k.at[jnp.asarray(unmapped)].set(1e9),
        v=cache.v.at[jnp.asarray(unmapped)].set(-1e9))
    got = _prefill_attend(poison, table, q, k_self, v_self, meta,
                          "pallas", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def _subtile_decode_pool(key, *, bsz=4, Dk=96, Dv=96, Hkv=2, B=2, K=3):
    P = B * K + 1
    ks = jax.random.split(key, 5)
    kp = jax.random.normal(ks[0], (P, Hkv, bsz, Dk), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, bsz, Dv), jnp.float32)
    pp = jnp.asarray(np.arange(P * bsz).reshape(P, bsz) % (K * bsz),
                     jnp.int32)
    table = jnp.asarray(np.arange(1, B * K + 1).reshape(B, K), jnp.int32)
    k_self = jax.random.normal(ks[2], (B, bsz, Hkv, Dk), jnp.float32)
    v_self = jax.random.normal(ks[3], (B, bsz, Hkv, Dv), jnp.float32)
    positions = jnp.asarray(
        np.broadcast_to(K * bsz + np.arange(bsz), (B, bsz)), jnp.int32)
    limit = jnp.full((B,), K * bsz, jnp.int32)
    q = jax.random.normal(ks[4], (B, bsz, 2 * Hkv, Dk), jnp.float32)
    return q, kp, vp, pp, table, k_self, v_self, positions, limit


@pytest.mark.parametrize("window", [None, 6])
def test_tile_padding_bitwise_decode(window):
    """block_size 4 / head dim 96 (both below the (8, 128) f32 tile):
    the zero-padded launch — the exact operands compiled mode runs on
    TPU — matches the unpadded output bitwise.  Padded self rows carry
    pos=-1 and padded head dims contribute +0.0 terms, so padding is
    arithmetic-exact, not approximate."""
    q, kp, vp, pp, table, ksf, vsf, pos, lim = _subtile_decode_pool(
        jax.random.PRNGKey(6))
    kw = dict(scale=96 ** -0.5, softcap=None, window=window)
    plain = paged_decode_attention(q, kp, vp, pp, table, ksf, vsf, pos,
                                   lim, interpret=True, pad=False, **kw)
    padded = paged_decode_attention(q, kp, vp, pp, table, ksf, vsf, pos,
                                    lim, interpret=True, pad=True, **kw)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(plain))


@pytest.mark.parametrize("softcap", [None, 5.0])
def test_tile_padding_bitwise_prefill(softcap):
    """Prefill counterpart of the padding parity: sub-tile pages
    (block_size 4) and head dim 96, padded vs unpadded bitwise."""
    cache, table, q, k_self, v_self, meta = _prefill_pool(
        jax.random.PRNGKey(7), Kp=3, Ts=2, Hkv=2, Dk=96, Dv=96, bsz=4)
    kw = dict(scale=96 ** -0.5, softcap=softcap, window=None)
    plain = paged_prefill_attention(
        q, cache.k, cache.v, cache.pos, table, k_self, v_self, meta.pos,
        interpret=True, pad=False, **kw)
    padded = paged_prefill_attention(
        q, cache.k, cache.v, cache.pos, table, k_self, v_self, meta.pos,
        interpret=True, pad=True, **kw)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(plain))


def test_plan_exec_contract():
    """Execution planning: tile-aligned shapes compile on TPU, sub-tile
    shapes compile via zero-padding (or unpadded when padding is
    disabled — never a silent interpret fallback), and non-TPU backends
    interpret unless compiled mode is asked for."""
    on_tpu = jax.default_backend() == "tpu"
    # tile-aligned page shape: compiled wherever a TPU exists
    plan = plan_exec(8, 128, 128, interpret=False)
    assert plan.mode == "compiled" and not plan.padded
    assert "tile-aligned" in plan.reason
    # sub-tile: compiled only by padding up to the (8, 128) tile
    plan = plan_exec(4, 96, 96, interpret=False)
    assert plan.mode == "compiled" and plan.padded
    assert "zero-padded" in plan.reason
    # padding disabled -> still compiled, unpadded, with the reason
    plan = plan_exec(4, 96, 96, interpret=False, pad=False)
    assert plan.mode == "compiled" and not plan.padded
    assert "padding disabled" in plan.reason
    # backend-resolved default (this CI host: no TPU -> interpret)
    plan = plan_exec(4, 96, 96)
    assert plan.interpret == (not on_tpu)
    if not on_tpu:
        assert "backend=" in plan.reason and not plan.padded
    # forced interpret always wins
    assert plan_exec(8, 128, 128, interpret=True).mode == "interpret"


def test_kernel_exec_plan_surface():
    """The queryable mode surface: a KernelPlan for pallas on paged
    caches, None wherever no Pallas kernel is ever launched."""
    cache, *_ = _pool(jax.random.PRNGKey(3))
    plan = A.kernel_exec_plan(cache, "pallas")
    assert plan is not None and plan.mode in ("compiled", "interpret")
    assert A.kernel_exec_plan(cache, "ref") is None
    dense = A.make_attn_cache(3, MAX_LEN, 2, 32, 32, jnp.float32)
    assert A.kernel_exec_plan(dense, "pallas") is None


# ---------------------------------------------------------------------------
# scheduler-level: decode tokens byte-identical across the three layouts
# ---------------------------------------------------------------------------


def _drain(model, params, sched, prompt, pblocks, keys, budgets):
    for i in range(len(keys)):
        sched.submit(prompt[i % 4], pblocks[i % 4], keys[i],
                     max_new_blocks=budgets[i % len(budgets)])
    return {c.uid: c for c in sched.run(params)}


def _assert_same_tokens(ref, got):
    assert sorted(ref) == sorted(got)
    for uid, d in ref.items():
        p = got[uid]
        assert d.gen_blocks == p.gen_blocks
        assert d.denoise_steps == p.denoise_steps
        np.testing.assert_array_equal(d.tokens, p.tokens)
        np.testing.assert_array_equal(d.steps, p.steps)


def _three_way(cfg, *, n_pages=13, tau=0.6):
    model = BlockDiffLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 4, 100))
    pblocks = np.array([2, 1, 2, 1], np.int32)
    keys = jax.random.split(jax.random.PRNGKey(13), 6)
    outs = {}
    for cache, kernel in [("dense", "ref"), ("paged", "ref"),
                          ("paged", "pallas")]:
        kw = dict(n_pages=n_pages, prefix_cache=False) \
            if cache == "paged" else {}
        sched = SlotScheduler(model, n_slots=3, max_len=MAX_LEN, s_max=4,
                              mode="dynamic", tau=tau, temperature=1.0,
                              eos_id=1, cache=cache, kernel=kernel, **kw)
        outs[(cache, kernel)] = (
            _drain(model, params, sched, prompt, pblocks, keys,
                   [3, None, 2]),
            sched.stats.transient_kv_bytes)
    ref = outs[("dense", "ref")][0]
    _assert_same_tokens(ref, outs[("paged", "ref")][0])
    _assert_same_tokens(ref, outs[("paged", "pallas")][0])
    assert outs[("paged", "ref")][1] > 0
    assert outs[("paged", "pallas")][1] == 0   # no per-step K/V copy
    assert outs[("dense", "ref")][1] > 0       # dense concat transient


def test_pallas_tokens_match_dense_and_gathered():
    """The acceptance criterion: dense vs gathered-paged vs in-place
    pallas produce byte-identical tokens, step maps and denoise counts
    under mixed-length admission/eviction churn on a tight pool — with
    transient_kv_bytes == 0 only on the in-place path."""
    _three_way(ModelConfig(name="t", **_BASE))


@pytest.mark.parametrize("variant", ["swa", "mla"])
def test_pallas_parity_swa_and_mla(variant):
    """Sliding-window (dense rings vs paged window-masking) and the
    absorbed-MLA latent pool keep three-way byte parity."""
    if variant == "swa":
        cfg = ModelConfig(name="w", sliding_window=16, **_BASE)
    else:
        cfg = ModelConfig(name="m", attn_kind="mla", kv_lora_rank=32,
                          qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                          **_BASE)
    _three_way(cfg, tau=0.8)


def test_pallas_prefix_shared_pages_parity():
    """A DiPO G-group on prefix-shared pages decodes the same bytes
    through the in-place kernel as through the gathered fallback, with
    identical sharing stats (the kernel reads shared pages exactly
    like exclusive ones — refcounts are invisible to attention)."""
    model = BlockDiffLM(ModelConfig(name="t", **_BASE))
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 16), 4, 100))
    keys = jax.random.split(jax.random.PRNGKey(9), 8)
    outs = {}
    for kernel in ["ref", "pallas"]:
        sched = SlotScheduler(model, n_slots=4, max_len=MAX_LEN, s_max=3,
                              mode="dynamic", tau=0.8, temperature=1.0,
                              eos_id=1, cache="paged", n_pages=25,
                              prefix_cache=True, kernel=kernel)
        for i in range(8):      # 2 prompts x G=4, members adjacent
            sched.submit(prompt[i // 4], 2, keys[i], max_new_blocks=3)
        outs[kernel] = ({c.uid: c for c in sched.run(params)},
                        sched.stats)
    _assert_same_tokens(outs["ref"][0], outs["pallas"][0])
    assert outs["pallas"][1].prefix_hit_blocks \
        == outs["ref"][1].prefix_hit_blocks > 0
    assert outs["pallas"][1].transient_kv_bytes == 0


def test_partial_hit_suffix_prefill_parity_and_admit_stats():
    """Partial prefix hits take the suffix-prefill path: a prompt whose
    first blocks are registered but whose tail diverges pays a suffix
    prefill against the hit pages.  Tokens must be byte-identical
    between the gathered admission (kernel="ref") and the in-place
    prefill kernel (kernel="pallas") — and the admission-gather stat
    must be the hit width for ref, exactly 0 in place."""
    model = BlockDiffLM(ModelConfig(name="t", **_BASE))
    params = model.init(jax.random.PRNGKey(0))
    base = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (2, 16), 4, 100))
    ext = np.concatenate([base, (base[:, :BSZ] + 1) % 100], axis=1)
    keys = jax.random.split(jax.random.PRNGKey(23), 8)
    outs = {}
    for kernel in ["ref", "pallas"]:
        sched = SlotScheduler(model, n_slots=4, max_len=MAX_LEN, s_max=3,
                              mode="dynamic", tau=0.8, temperature=1.0,
                              eos_id=1, cache="paged", n_pages=41,
                              prefix_cache=True, kernel=kernel)
        for i in range(8):
            p = i // 4
            if i % 2:   # odd members: 2 hit blocks + 1 divergent block
                sched.submit(ext[p], 3, keys[i], max_new_blocks=2)
            else:       # even members register / fully hit the base
                sched.submit(base[p], 2, keys[i], max_new_blocks=2)
        outs[kernel] = ({c.uid: c for c in sched.run(params)},
                        sched.stats, sched.kernel_plan)
    _assert_same_tokens(outs["ref"][0], outs["pallas"][0])
    s_ref, s_pal = outs["ref"][1], outs["pallas"][1]
    assert s_ref.prefix_hit_blocks == s_pal.prefix_hit_blocks > 0
    # admission gather = 2 hit blocks x token bytes for one B=1 row
    per_tok = 2 * (16 + 16) * 4 + 4
    assert s_ref.admit_transient_kv_bytes == 2 * BSZ * per_tok
    assert s_pal.admit_transient_kv_bytes == 0
    # the queryable execution-mode surface
    assert s_ref.kernel_mode == "" and outs["ref"][2] is None
    plan = outs["pallas"][2]
    assert plan is not None and s_pal.kernel_mode == plan.mode
    if jax.default_backend() != "tpu":
        assert plan.mode == "interpret" and "backend=" in plan.reason


def test_pallas_zero_retrace_mixed_params():
    """Mixed SamplingParams on one pallas pool: a single advance trace
    (the kernel choice is a pool static, request params stay traced
    data) and per-row byte parity with the gathered fallback."""
    model = BlockDiffLM(ModelConfig(name="t", **_BASE))
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 4, 100))
    pblocks = np.array([2, 1, 2, 1], np.int32)
    keys = jax.random.split(jax.random.PRNGKey(21), 6)
    mix = [SamplingParams(tau=0.5, temperature=1.0, max_new_blocks=2),
           SamplingParams(tau=0.95, max_new_blocks=3),
           SamplingParams(mode="static", n_steps=3, temperature=1.0,
                          max_new_blocks=2)]
    outs = {}
    for kernel in ["ref", "pallas"]:
        sched = SlotScheduler(model, n_slots=3, max_len=MAX_LEN, s_max=4,
                              eos_id=1, cache="paged", kernel=kernel)
        for i in range(6):
            sched.submit(prompt[i % 4], int(pblocks[i % 4]), keys[i],
                         params=mix[i % 3])
        outs[kernel] = {c.uid: c for c in sched.run(params)}
        assert sched.n_advance_traces == 1, sched.n_advance_traces
    _assert_same_tokens(outs["ref"], outs["pallas"])


def test_engine_surfaces_transient_kv_bytes():
    """EngineStats mirrors the pool's transient-copy stat; the pallas
    engine keeps the generate_ids static-parity contract."""
    model = BlockDiffLM(ModelConfig(name="t", **_BASE))
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (3, 16), 4, 100))
    pblocks = np.array([2, 1, 2], np.int32)
    rng = jax.random.PRNGKey(17)
    outs, stats = {}, {}
    for mode, cache, kernel in [("static", "dense", "ref"),
                                ("continuous", "paged", "pallas")]:
        eng = RolloutEngine(model, ModelServer(params), GenerationConfig(
            max_len=MAX_LEN, s_max=4, mode="dynamic", tau=0.6,
            temperature=1.0, batching=mode, n_slots=3, cache=cache,
            kernel=kernel))
        outs[mode] = eng.generate_ids(prompt, pblocks, rng)
        stats[mode] = eng.stats
    a, b = outs["static"], outs["continuous"]
    for k in ["tokens", "steps", "gen_blocks", "denoise_steps"]:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert stats["continuous"].transient_kv_bytes == 0
    assert stats["static"].transient_kv_bytes == 0   # no pool built
    assert stats["continuous"].admit_transient_kv_bytes == 0
    assert stats["continuous"].kernel_mode in ("compiled", "interpret")
    assert stats["static"].kernel_mode == ""         # no pool built


def test_kernel_config_validation():
    model = BlockDiffLM(ModelConfig(name="t", **_BASE))
    with pytest.raises(ValueError, match="pallas"):
        SlotScheduler(model, n_slots=2, max_len=MAX_LEN,
                      cache="dense", kernel="pallas")
    with pytest.raises(ValueError, match="kernel"):
        SlotScheduler(model, n_slots=2, max_len=MAX_LEN,
                      cache="paged", kernel="triton")

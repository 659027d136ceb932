"""Slot-based continuous-batching scheduler for blockwise-dLLM decoding.

Architecture
------------
The scheduler owns a fixed pool of ``n_slots`` decode slots backed by one
batched ``core.decoding.GenState`` (tokens / step maps / per-slot block
cursors / per-slot rng keys / decode caches).  Time advances in *ticks*:
one tick = one call of the jitted ``core.decoding.advance_block`` over
the whole pool, i.e. every live slot denoises and commits exactly one
block.  Between ticks — block boundaries, the only points where a
blockwise dLLM can change batch composition without corrupting caches —
the scheduler runs its Python-side control loop:

  admit    queued requests are prefetched into freed slots: a B=1
           ``prefill`` builds the request's cache rows, which are then
           scattered into the pool for that slot together with its
           prompt tokens, rng key, cursor and block budget;
  advance  one jitted pool step (inactive slots are ``done`` and merely
           re-commit their frozen block — idempotent by construction);
  evict    slots whose sequence hit EOS or its block budget are
           harvested into ``Completion`` records and returned to the
           free list.

Cache layouts (``cache=``)
--------------------------
``"dense"``  every slot owns a contiguous ``max_len`` cache region; slot
             count is therefore capped by worst-case length, and a short
             request reserves as much KV memory as the longest one.

``"paged"``  the vLLM-style fix: attention KV lives in one shared pool
             of ``n_pages`` block-sized pages (``models.attention.
             PagedAttnCache``; one page = one ``block_size`` block,
             matching the blockwise commit granularity), addressed
             through a per-slot block table carried in
             ``GenState.table``.  Recurrent/conv states are O(1) per
             sequence and stay per-slot.  Page lifecycle:

               * admission  — one page per true prompt block, filled by
                 scattering the B=1 prefill row block-by-block;
               * advance    — one page per live slot for the block its
                 cursor is about to commit;
               * eviction   — all of a slot's pages return to the free
                 list and its table row is reset to -1, so the slot's
                 subsequent idempotent re-commits dump into the null
                 page (page 0, never allocated) instead of a page that
                 may already belong to another request.

             Admission reserves a request's worst case (``prompt_blocks
             + budget`` pages) up front, so mid-flight allocation can
             never fail and there is no preemption; when the head of the
             queue does not fit, admission *defers* (backpressure,
             counted in ``stats.deferred``) until evictions free pages —
             it never crashes.  Short-budget requests therefore stop
             reserving long-request memory, and slot count decouples
             from ``max_len``.

             How decode *reads* the pool is the orthogonal
             ``kernel=`` knob (the KV layout,
             ``models.attention.resolve_kv_layout``):

               * ``"ref"``    — ``paged_gather`` materializes a
                 dense-width K/V copy per layer per tick (portable
                 fallback / parity oracle);
               * ``"pallas"`` — the page-aware kernels
                 (``kernels.paged_attn``) read pages in place via the
                 scalar-prefetched block table — decode *and* the
                 shared-prefix suffix prefill — so per-step transient
                 KV drops to zero (``stats.transient_kv_bytes``), the
                 admission-time prefix gather disappears
                 (``stats.admit_transient_kv_bytes``) and decode
                 memory stops scaling with slots x K*bsz.  Off-TPU
                 they run under ``interpret=True`` — CI exercises the
                 real kernel path; ``kernel_plan`` records the
                 compiled/interpret choice and why.

             Both layouts are byte-identical in decode tokens to dense
             (tests/test_paged_attn.py), and the kernel choice is a
             pool static like ``s_max`` — it never retraces per
             request.

Shared-prefix layer (``prefix_cache=``, paged only)
---------------------------------------------------
The third cache layer (slots -> pages -> *shared* pages): a refcounted
radix index over committed prompt blocks (``serving.prefix_cache``)
built for DiPO's G-rollouts-per-prompt groups, where every group member
would otherwise prefill and store the identical prompt G times.

  * admission — the index is probed for the longest cached prefix; hit
    blocks map the *existing* pages into the new slot's table
    (refcount++) and only the suffix is prefilled
    (``core.decoding.prefill_suffix`` — byte-identical to the same
    blocks of a full prefill on the gathered layout, equal to f32
    rounding with the in-place kernel; a full hit skips the model
    entirely).
    Freshly prefilled prompt blocks are registered into the index.
  * eviction — a slot releases its prompt-page references; a page
    returns to the free list only when *exclusive* (generated blocks,
    refcount-0 reclaims).  Refcount-0 index entries stay cached for
    future groups and are reclaimed leaf-first in LRU order under page
    pressure, so reservation-based admission keeps its no-deadlock
    guarantee: admission checks ``reserved + live-referenced index
    pages`` against the pool, and every other page is free or
    reclaimable.
  * generated blocks stay private — shared pages are read-only prompt
    blocks by construction (the commit cursor never re-enters the
    prompt region), so there is no copy-on-write.

Requires a pure-attention backbone (recurrent layers carry per-slot
state that pages cannot share); ``prefix_cache=None`` auto-enables
exactly then.  Byte-for-byte token parity between prefix-cache on/off
additionally assumes the cache dtype equals the activation dtype (the
fp32 default) — see ``core.decoding.prefill_suffix``.

Per-request sampling (``serving.api.SamplingParams``)
-----------------------------------------------------
Every decode parameter — tau, temperature, dynamic/static mode, static
n_steps, block budget, stop token, seed — is **request-granular**:
``submit(..., params=SamplingParams(...))`` scatters the request's
values into per-row vectors on the pooled ``GenState`` at admission,
and the jitted ``advance_block`` reads them per row.  One compiled
step therefore serves arbitrarily mixed configurations with zero
retraces (``n_advance_traces`` counts compilations — it stays at 1
after warmup no matter what parameter mix arrives); the pool-level
``s_max`` is the single remaining static.  Mixed-batch outputs are
byte-identical per row to homogeneous runs (tests/
test_sampling_params.py).

Sampling parameters never touch the prefix cache: prompt prefill is
parameter-free, so the radix index keys on prompt *content* only and
requests with different τ/temperature/budgets share prompt pages
freely — a params change can never invalidate cached prompt KV.

Request lifecycle: ``submit() -> queued -> admitted (slot) -> decoding
-> completed`` — completions stream out of ``step()``/``run()`` in
finish order, not arrival order.

DiPO-exactness: every row of ``advance_block`` evolves independently
(per-row caches or per-row block-table entries, per-row rng streams), so
a request's tokens and step map depend only on its own prompt + rng key
— *not* on which other requests happen to share the pool, nor on the
cache layout: paged and dense produce byte-identical tokens and step
maps (tested in tests/test_scheduler.py), so RL rollouts harvested from
the scheduler remain exactly consumable by the DiPO trajectory replay.

Follow-ups tracked in ROADMAP.md: multi-host page pools, batched
same-width admission, and optimistic admission + preemption.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.guards import TraceGuard
from repro.core import decoding
from repro.core.masks import plain_layout
from repro.kernels.ops import layout_tile_stats
from repro.models import attention
from repro.obs import profile
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serving.api import GenerationConfig, Request, SamplingParams
from repro.serving.prefix_cache import PrefixIndex, chain_keys

# distinguishes "caller did not pass max_new_blocks" from an explicit
# None (= decode to cache capacity) in submit()
_UNSET = object()


@dataclasses.dataclass
class Completion:
    """A finished request, harvested at eviction time."""
    uid: int
    tokens: np.ndarray           # (max_len,) prompt ++ generation ++ MASK
    steps: np.ndarray            # (max_len,) per-token reveal-step map
    prompt_blocks: int
    gen_blocks: int
    gen_tokens: int              # generated tokens up to first EOS incl.
    denoise_steps: int           # actual denoise steps executed (dynamic)
    finish_reason: str           # "eos" | "length" (hit block budget)
    admitted_tick: int
    completed_tick: int
    params: SamplingParams = SamplingParams()
    # model-weight version (ModelServer.version) live when the request
    # entered its slot — the staleness tag async RL consumes
    param_version: int = 0
    # per-generated-block weight version (len == gen_blocks): a weight
    # push lands between ticks, so an in-flight request finishes its
    # current block on the old params and picks the new ones up at the
    # next advance — this is the per-block record of that handoff
    block_versions: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int64))

    @property
    def finished_eos(self) -> bool:
        return self.finish_reason == "eos"

    @property
    def latency_ticks(self) -> int:
        """Admit -> finish latency in scheduler ticks."""
        return self.completed_tick - self.admitted_tick


@dataclasses.dataclass
class SchedulerStats:
    """Honest utilization counters (the fig6/serve_bench substrate).

    Every field doubles as the bound storage of an instrument in
    ``self.registry`` (an ``obs.metrics.MetricsRegistry`` under the
    ``dirl_scheduler`` namespace): the hot paths keep mutating plain
    attributes (``stats.ticks += 1`` — one attribute write, no
    instrument dispatch) while exporters read the same values through
    ``registry.collect()``.  A fresh stats object — the established
    warmup reset pattern ``sched.stats = SchedulerStats()`` — therefore
    also resets the exported view, counters included (the
    process-restart analogue that monotonic semantics permit).
    """
    ticks: int = 0               # pool advance steps executed
    slot_ticks: int = 0          # ticks * n_slots (paid compute)
    active_slot_ticks: int = 0   # slot-ticks that advanced a live request
    admitted: int = 0
    completed: int = 0
    gen_tokens: int = 0          # tokens served, cut at first EOS incl.
    denoise_steps: int = 0       # actual denoise steps across requests
    peak_active: int = 0         # max concurrently live slots
    prefill_blocks: int = 0      # prompt blocks actually prefilled
    # per-tick cache-KV bytes the decode layout copies out of the
    # resident cache (max over layers: dense concat / paged gather);
    # 0 on the in-place kernel="pallas" path — static per pool config
    transient_kv_bytes: int = 0
    # peak admission-time cache-KV bytes one suffix prefill gathered
    # out of the pool (the hit-prefix width, max over layers and over
    # admissions so far); 0 on the in-place prefill kernel path
    admit_transient_kv_bytes: int = 0
    # execution mode of the paged Pallas kernels for this pool shape:
    # "compiled" | "interpret" (kernel="pallas") or "" (no kernel)
    kernel_mode: str = ""
    # compilations of the jitted pool advance (TraceGuard counter) —
    # the zero-retrace contract: 1 across any SamplingParams mix
    advance_traces: int = 0
    # paged cache only
    deferred: int = 0            # admissions deferred for lack of pages
    page_allocs: int = 0
    page_frees: int = 0
    peak_pages_in_use: int = 0   # physical peak (incl. idle cached pages)
    peak_pages_live: int = 0     # peak pages referenced by live slots
    # prefix cache only
    prefix_hit_blocks: int = 0   # prompt blocks served from shared pages
    prefix_miss_blocks: int = 0  # prompt blocks that paid a prefill
    shared_pages: int = 0        # peak pages referenced by >= 2 slots
    prefix_evictions: int = 0    # refcount-0 index entries LRU-reclaimed
    # tile-map visit fraction of the most recent admission's prefill
    # attention (block-causal mask at block granularity) — the sparsity
    # the tile-sparse kernel family skips on the serve side
    prefill_tile_visit_fraction: float = 0.0

    # monotonic fields -> Counter; level/peak fields -> Gauge
    _COUNTER_FIELDS = ("ticks", "slot_ticks", "active_slot_ticks",
                       "admitted", "completed", "gen_tokens",
                       "denoise_steps", "prefill_blocks", "deferred",
                       "page_allocs", "page_frees", "prefix_hit_blocks",
                       "prefix_miss_blocks", "prefix_evictions")
    _GAUGE_FIELDS = ("peak_active", "transient_kv_bytes",
                     "admit_transient_kv_bytes", "advance_traces",
                     "peak_pages_in_use", "peak_pages_live",
                     "shared_pages", "prefill_tile_visit_fraction")

    def __post_init__(self):
        # non-field attribute: stays out of dataclasses.fields() and
        # out of __eq__/__repr__, so stats comparisons are value-only
        self.registry = MetricsRegistry("dirl_scheduler")
        for f in self._COUNTER_FIELDS:
            self.registry.counter(f, bind=(self, f))
        for f in self._GAUGE_FIELDS:
            self.registry.gauge(f, bind=(self, f))
        self.registry.info("kernel_mode",
                           "paged-kernel execution mode for this pool",
                           bind=(self, "kernel_mode"))

    @property
    def utilization(self) -> float:
        """Fraction of paid slot-ticks that did useful work."""
        return self.active_slot_ticks / max(self.slot_ticks, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt blocks served from shared pages."""
        total = self.prefix_hit_blocks + self.prefix_miss_blocks
        return self.prefix_hit_blocks / max(total, 1)


class SlotScheduler:
    """Fixed-slot continuous batcher over one jitted block-advance.

    Construction takes one ``GenerationConfig`` (pool shape + cache
    layout + the *default* ``SamplingParams`` for requests that carry
    none) — keyword overrides patch individual fields, so legacy
    ``SlotScheduler(model, n_slots=..., tau=...)`` call sites keep
    working without mirroring every config field through the signature.
    """

    def __init__(self, model, gen_cfg: GenerationConfig | None = None,
                 tracer: Tracer | None = None, **overrides):
        if gen_cfg is None:
            gen_cfg = GenerationConfig()
        if overrides:
            gen_cfg = dataclasses.replace(gen_cfg, **overrides)
        # one tracer per stack: the engine passes its own so scheduler
        # ticks and request lifecycles land in the same export; a
        # standalone scheduler builds one from the config (disabled by
        # default — a disabled tracer records nothing but still times)
        self.tracer = tracer if tracer is not None else Tracer(
            capacity=gen_cfg.trace_capacity, enabled=gen_cfg.trace)
        cfg = model.cfg
        n_slots, max_len = gen_cfg.n_slots, gen_cfg.max_len
        cache = gen_cfg.cache
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if cache not in ("dense", "paged"):
            raise ValueError(f"cache must be dense|paged, got {cache!r}")
        kernel = gen_cfg.kernel
        if kernel not in ("ref", "pallas"):
            raise ValueError(f"kernel must be ref|pallas, got {kernel!r}")
        if kernel == "pallas" and cache != "paged":
            raise ValueError(
                "kernel='pallas' requires cache='paged' — dense rows "
                "have no page pool to read in place")
        assert max_len % cfg.block_size == 0
        self.model = model
        self.gen_cfg = gen_cfg
        self.default_params = gen_cfg.sampling()
        self.n_slots = n_slots
        self.max_len = max_len
        self.n_blocks_total = max_len // cfg.block_size
        self.eos_id = gen_cfg.eos_id        # default stop token
        self.cache = cache
        self.kernel = kernel
        self.stats = SchedulerStats()
        n_pages = gen_cfg.n_pages
        prefix_cache = gen_cfg.prefix_cache

        self.prefix: PrefixIndex | None = None
        if cache == "paged":
            # default: the same KV footprint a dense pool would reserve,
            # plus the never-allocated null page 0
            self.n_pages = n_pages if n_pages is not None \
                else n_slots * self.n_blocks_total + 1
            if self.n_pages < 2:
                raise ValueError("paged cache needs >= 2 pages")
            self._free_pages = list(range(self.n_pages - 1, 0, -1))
            self._table_host = np.full(
                (n_slots, self.n_blocks_total), -1, np.int64)
            self._pages_reserved = 0          # worst case of live slots
            self._slot_resv = [0] * n_slots   # per-slot reserved pages
            self._slot_limit = [0] * n_slots  # per-slot block-cursor cap
            self._slot_blk = [0] * n_slots    # host mirror of state.blk
            # shared-prefix index: auto-on for pure-attention stacks
            # (recurrent layers carry per-slot state pages cannot share)
            if prefix_cache is None:
                prefix_cache = not cfg.ssm_kind
            if prefix_cache:
                if cfg.ssm_kind:
                    raise ValueError(
                        "prefix_cache requires a pure-attention backbone "
                        f"(got ssm_kind={cfg.ssm_kind!r}: recurrent "
                        "boundary states are per-slot, not per-page)")
                self.prefix = PrefixIndex()
            self._slot_nodes: list[list[bytes]] = \
                [[] for _ in range(n_slots)]
        else:
            if prefix_cache:
                raise ValueError("prefix_cache requires cache='paged'")
            self.n_pages = 0

        self._queue: deque[Request] = deque()
        self._admit_info: dict = {}   # labels of the latest admission
        self._slot_req: list[Request | None] = [None] * n_slots
        self._slot_admit_tick: list[int] = [0] * n_slots
        # model-weight versioning (async RL provenance): the version
        # passed to step() is stamped per slot at admission and appended
        # per advance, so a harvest can reconstruct exactly which
        # weights produced each generated block.  One int per pool
        # advance (one model forward), indexed by an absolute counter so
        # the `sched.stats = SchedulerStats()` warmup reset cannot skew
        # it — negligible memory even for very long-lived pools.
        self._slot_admit_version: list[int] = [0] * n_slots
        self._slot_admit_abs: list[int] = [0] * n_slots
        self._tick_versions: list[int] = []
        self._next_uid = 0
        self._state = self._init_pool()
        # pool-static (cache layout + kernel choice fix it at
        # construction); re-stamped into stats every tick so the common
        # warmup pattern `sched.stats = SchedulerStats()` self-heals
        self.transient_kv_bytes = self._transient_kv_bytes()
        self.stats.transient_kv_bytes = self.transient_kv_bytes
        # how the paged Pallas kernels would execute on this pool's
        # page shape (None when kernel="ref" / dense cache)
        self.kernel_plan = self._kernel_plan()
        self.stats.kernel_mode = \
            self.kernel_plan.mode if self.kernel_plan else ""

        # donate the pool state: the old GenState (slot caches included)
        # is always dead after the call, so advance/admit alias their
        # buffers in place instead of holding a 2x-peak copy per tick
        # (backends without donation support just ignore the hint).
        # All sampling parameters live in GenState's per-row vectors;
        # s_max is the single static, so one trace serves every request
        # mix — each TraceGuard counts compilations to prove it (the
        # wrapped body only runs when jax traces it).
        s_max = gen_cfg.s_max

        def _advance_impl(params, st):
            return decoding.advance_block(model, params, st, s_max=s_max,
                                          kv_kernel=self.kernel)

        self._advance = TraceGuard(_advance_impl, donate_argnums=(1,),
                                   name="advance")
        self._admit_jit = TraceGuard(self._admit_impl, donate_argnums=(1,),
                                     name="admit")
        self._admit_hit_jit = TraceGuard(self._admit_hit_impl,
                                         donate_argnums=(0,),
                                         name="admit_hit")
        self._admit_suffix_jit = TraceGuard(self._admit_suffix_impl,
                                            donate_argnums=(1,),
                                            name="admit_suffix")

    @property
    def n_advance_traces(self) -> int:
        """Compilations of the pool advance so far (the zero-retrace
        witness: stays 1 across arbitrary SamplingParams mixes)."""
        return self._advance.n_traces

    def guard_stats(self) -> dict[str, int]:
        """Compile counts per jitted entry point."""
        return {g.name: g.n_traces
                for g in (self._advance, self._admit_jit,
                          self._admit_hit_jit, self._admit_suffix_jit)}

    # ----------------------------------------------------------- state
    def _transient_kv_bytes(self) -> int:
        """Peak per-tick cache-KV copy the decode layout materializes
        (max over attention layers — layers run sequentially under the
        scan, so one layer's gather is live at a time).  0 for the
        in-place ``kernel="pallas"`` path."""
        caches = self._state.caches
        out = 0
        for c in (list(caches["prefix"].values())
                  + list(caches["groups"].values())):
            if isinstance(c, (attention.AttnCache,
                              attention.PagedAttnCache)):
                out = max(out, attention.transient_kv_bytes(
                    c, self.n_slots, self.n_blocks_total, self.kernel))
        return out

    def _attn_caches(self):
        caches = self._state.caches
        return [c for c in (list(caches["prefix"].values())
                            + list(caches["groups"].values()))
                if isinstance(c, (attention.AttnCache,
                                  attention.PagedAttnCache))]

    def _kernel_plan(self):
        """``kernels.paged_attn.KernelPlan`` for this pool's page shape,
        or None when no Pallas kernel is ever launched."""
        for c in self._attn_caches():
            plan = attention.kernel_exec_plan(c, self.kernel)
            if plan is not None:
                return plan
        return None

    def _admit_transient_kv_bytes(self, n_ctx_blocks: int) -> int:
        """Cache-KV bytes one B=1 suffix prefill copies out of the pool
        (the shared-prefix gather width, max over attention layers —
        layers run sequentially, so one gather is live at a time).
        0 for the in-place ``kernel="pallas"`` prefill kernel."""
        out = 0
        for c in self._attn_caches():
            if isinstance(c, attention.PagedAttnCache):
                out = max(out, attention.prefill_transient_kv_bytes(
                    c, 1, n_ctx_blocks, self.kernel))
        return out

    @property
    def n_usable_pages(self) -> int:
        """Allocatable pages (excludes the null page)."""
        return max(self.n_pages - 1, 0)

    @property
    def pages_in_use(self) -> int:
        """Pages off the free list (live-referenced + idle cached)."""
        return self.n_usable_pages - len(self._free_pages) \
            if self.cache == "paged" else 0

    @property
    def pages_live(self) -> int:
        """Pages referenced by live slots (excludes idle cached pages).

        This is the memory a pool *without* prefix retention would need
        at the same instant — the apples-to-apples peak for the
        prefix-cache on/off benchmark.
        """
        idle = self.prefix.n_idle if self.prefix is not None else 0
        return self.pages_in_use - idle

    def _init_pool(self) -> decoding.GenState:
        cfg = self.model.cfg
        S, L = self.n_slots, self.max_len
        MASK = cfg.resolved_mask_token
        if self.cache == "paged":
            caches = self.model.make_paged_caches(S, self.n_pages)
            table = jnp.full((S, self.n_blocks_total), -1, jnp.int32)
        else:
            caches = self.model.make_caches(S, L)
            table = None
        return decoding.GenState(
            tokens=jnp.full((S, L), MASK, jnp.int32),
            steps=jnp.zeros((S, L), jnp.int32),
            caches=caches,
            blk=jnp.zeros((S,), jnp.int32),
            done=jnp.ones((S,), bool),        # all slots start free
            rng=jnp.zeros((S, 2), jnp.uint32),
            limit=jnp.zeros((S,), jnp.int32),
            n_denoise=jnp.zeros((S,), jnp.int32),
            # free slots carry inert sampling rows (eos -1 = disabled);
            # admission overwrites them with the request's params
            **decoding.sampling_vectors(S, tau=0.0, temperature=0.0,
                                        n_steps=1, mode="static",
                                        eos_id=-1),
            table=table)

    @staticmethod
    def _scatter_layer(pool, new, slot, pages, *, grouped: bool):
        """Scatter one layer of a B=1 prefill into the pool.

        Paged attention layers scatter block-by-block into the request's
        freshly allocated pages; per-slot states (SSM/conv/shift) scatter
        into the slot's row as in the dense layout.
        """
        if pool is None:
            return None
        if isinstance(pool, attention.PagedAttnCache):
            fn = attention.write_prompt_pages_grouped if grouped \
                else attention.write_prompt_pages
            return fn(pool, new, pages)
        if grouped:  # group leaves carry a leading (G,) axis
            return jax.tree.map(lambda p, n: p.at[:, slot].set(n[:, 0]),
                                pool, new)
        return jax.tree.map(lambda p, n: p.at[slot].set(n[0]), pool, new)

    @staticmethod
    def _samp_scalars(p: SamplingParams) -> tuple:
        """A request's sampling fields as traced jit scalars — different
        values reuse the compiled admit executables, never retrace."""
        return (jnp.float32(p.tau), jnp.float32(p.temperature),
                jnp.int32(p.n_steps), jnp.bool_(p.dynamic),
                jnp.int32(p.eos_id))

    @staticmethod
    def _scatter_slot(st: decoding.GenState, slot, row, key, limit, blk,
                      caches, table, samp) -> decoding.GenState:
        """Write one admitted request's per-slot state into the pool.

        Every admission path (cold prefill, full prefix hit, suffix
        prefill) funnels through this single GenState constructor, so a
        new per-sequence field only needs threading once.  ``samp`` is
        the ``_samp_scalars`` tuple — the request's SamplingParams
        landing in the pool's per-row vectors.
        """
        tau, temp, n_steps, dynamic, eos = samp
        return decoding.GenState(
            tokens=st.tokens.at[slot].set(row),
            steps=st.steps.at[slot].set(0),
            caches=caches,
            blk=st.blk.at[slot].set(blk),
            done=st.done.at[slot].set(False),
            rng=st.rng.at[slot].set(key),
            limit=st.limit.at[slot].set(limit),
            n_denoise=st.n_denoise.at[slot].set(0),
            tau=st.tau.at[slot].set(tau),
            temperature=st.temperature.at[slot].set(temp),
            n_steps=st.n_steps.at[slot].set(n_steps),
            dynamic=st.dynamic.at[slot].set(dynamic),
            eos=st.eos.at[slot].set(eos),
            table=table)

    def _admit_impl(self, params, st: decoding.GenState, slot,
                    prompt, pblocks, key, limit, samp,
                    pages=None) -> decoding.GenState:
        """Prefill one request (B=1) and scatter it into slot ``slot``.

        Compiles once per distinct true prompt length in blocks; the slot
        index and all per-request scalars are traced, so steady-state
        admission is a single cached executable.  ``pages`` (paged cache
        only) holds one page id per prompt block.
        """
        cfg = self.model.cfg
        MASK = cfg.resolved_mask_token
        paged = self.cache == "paged"
        caches1 = decoding.prefill(self.model, params, prompt, pblocks,
                                   self.max_len, ring=not paged)
        row = jnp.concatenate(
            [prompt[0].astype(jnp.int32),
             jnp.full((self.max_len - prompt.shape[1],), MASK, jnp.int32)])
        caches = {
            "prefix": {
                lk: self._scatter_layer(c, caches1["prefix"][lk], slot,
                                        pages, grouped=False)
                for lk, c in st.caches["prefix"].items()},
            "groups": {
                lk: self._scatter_layer(c, caches1["groups"][lk], slot,
                                        pages, grouped=True)
                for lk, c in st.caches["groups"].items()},
        }
        table = st.table
        if paged:
            table = table.at[slot, :pages.shape[0]].set(pages)
        return self._scatter_slot(st, slot, row, key, limit, pblocks[0],
                                  caches, table, samp)

    def _admit_hit_impl(self, st: decoding.GenState, slot, row, key,
                        limit, table_row, pblocks,
                        samp) -> decoding.GenState:
        """Admit a full prefix-cache hit: every prompt block is already
        committed in shared pages, so no model call happens at all —
        just scatter the slot's tokens / cursor / rng / block table.
        Compiles once (all shapes are pool-static).
        """
        return self._scatter_slot(st, slot, row, key, limit, pblocks,
                                  st.caches,
                                  st.table.at[slot].set(table_row), samp)

    def _admit_suffix_impl(self, params, st: decoding.GenState, slot,
                           suffix, row, key, limit, ctx_pages, sfx_pages,
                           table_row, samp) -> decoding.GenState:
        """Admit a partial prefix-cache hit: prefill only the suffix.

        ``suffix`` (1, Ls) are the prompt blocks beyond the hit;
        ``ctx_pages`` (h,) the shared pages of the hit prefix;
        ``sfx_pages`` (Ls // bsz,) fresh pages receiving the suffix KV.
        The committed pass reads the prefix through the shared pages
        (``decoding.prefill_suffix``), so the hit blocks are never
        re-prefilled.  Compiles per (hit, suffix) block-count pair.
        """
        bsz = self.model.cfg.block_size
        h = ctx_pages.shape[0]
        pblocks = h + suffix.shape[1] // bsz
        caches = decoding.prefill_suffix(
            self.model, params, suffix, jnp.int32(h), st.caches,
            context_table=ctx_pages[None], write_pages=sfx_pages[None],
            kv_kernel=self.kernel)
        return self._scatter_slot(st, slot, row, key, limit, pblocks,
                                  caches,
                                  st.table.at[slot].set(table_row), samp)

    def _note_prefill_tiles(self, req: Request) -> None:
        """Host-side gauge: tile-map sparsity of this admission's prefill
        attention (block granularity, i.e. the block-causal mask)."""
        bsz = self.model.cfg.block_size
        meta = plain_layout(jnp.asarray(req.prompt, jnp.int32)[None],
                            jnp.ones((1, len(req.prompt)), bool),
                            block_size=bsz)
        stats = layout_tile_stats(meta, tq=bsz, tk=bsz)
        self.stats.prefill_tile_visit_fraction = stats["visit_fraction"]

    def _admit_paged(self, params, slot: int, req: Request,
                     budget: int) -> bool:
        """Admit one request into ``slot`` under the paged allocator.

        Returns False (defer, nothing mutated) when the worst case does
        not fit.  With the prefix index enabled, the feasibility check
        covers the slot's *private* worst case (its generation budget)
        plus the index pages its admission turns live — hit blocks map
        shared pages in (refcount++), and only the suffix is prefilled.
        """
        cfg = self.model.cfg
        bsz = cfg.block_size
        pb = req.prompt_blocks
        limit = pb + budget
        samp = self._samp_scalars(req.params)
        if self.prefix is None:
            if self._pages_reserved + limit > self.n_usable_pages:
                return False
            pages = self._take_pages(pb)
            self._table_host[slot, :pb] = pages
            self._pages_reserved += limit
            self._slot_resv[slot] = limit
            self._slot_limit[slot] = limit
            self._slot_blk[slot] = pb
            self.stats.page_allocs += pb
            self.stats.prefill_blocks += pb
            self._note_prefill_tiles(req)
            self._admit_info = {"path": "cold", "hit_blocks": 0,
                                "new_pages": pb}
            with profile.annotate("prefill"):
                self._state = self._admit_jit(
                    params, self._state, jnp.int32(slot),
                    req.prompt[None], jnp.asarray([pb], jnp.int32),
                    req.rng, jnp.int32(limit), samp,
                    jnp.asarray(pages, jnp.int32))
            return True

        # the prefix index keys on prompt *content* only — sampling
        # params shape decoding, never prompt KV, so requests with
        # different params share (and register) pages identically
        keys = chain_keys(req.prompt, bsz)
        hits = self.prefix.match(keys)
        h = len(hits)
        idle_hits = sum(1 for e in hits if e.refs == 0)
        # invariant kept <= n_usable: live slots' private worst cases
        # (_pages_reserved) + live-referenced index pages (n_active);
        # everything outside it is free or reclaimable, so mid-flight
        # cursor allocation can never fail
        if self._pages_reserved + self.prefix.n_active + budget \
                + (pb - h) + idle_hits > self.n_usable_pages:
            return False
        # acquire before allocating: _take_pages may LRU-reclaim idle
        # entries, and an unreferenced hit would be fair game
        self.prefix.acquire(hits)
        new_pages = self._take_pages(pb - h)
        hit_pages = [e.page for e in hits]
        node_keys = [e.key for e in hits]
        node_keys += self.prefix.register(keys, h, new_pages)
        self._slot_nodes[slot] = node_keys
        self._table_host[slot, :pb] = hit_pages + new_pages
        self._pages_reserved += budget
        self._slot_resv[slot] = budget
        self._slot_limit[slot] = limit
        self._slot_blk[slot] = pb
        self.stats.page_allocs += len(new_pages)
        self.stats.prefix_hit_blocks += h
        self.stats.prefix_miss_blocks += pb - h
        self.stats.prefill_blocks += pb - h
        if pb > h:
            self._note_prefill_tiles(req)
        self.stats.shared_pages = max(self.stats.shared_pages,
                                      self.prefix.n_shared)
        self.stats.peak_pages_in_use = max(self.stats.peak_pages_in_use,
                                           self.pages_in_use)
        self.stats.peak_pages_live = max(self.stats.peak_pages_live,
                                         self.pages_live)

        table_row = jnp.asarray(self._table_host[slot], jnp.int32)
        self._admit_info = {"hit_blocks": h, "new_pages": len(new_pages)}
        if h == 0:
            # cold prompt: the PR-2 path — one B=1 plain prefill,
            # scattered into the fresh pages (then registered above)
            self._admit_info["path"] = "cold"
            with profile.annotate("prefill"):
                self._state = self._admit_jit(
                    params, self._state, jnp.int32(slot),
                    req.prompt[None], jnp.asarray([pb], jnp.int32),
                    req.rng, jnp.int32(limit), samp,
                    jnp.asarray(new_pages, jnp.int32))
            return True
        row = np.full((self.max_len,), cfg.resolved_mask_token, np.int32)
        row[:pb * bsz] = req.prompt
        if h == pb:
            # full hit (the DiPO G-group case): zero prefill
            self._admit_info["path"] = "full_hit"
            self._state = self._admit_hit_jit(
                self._state, jnp.int32(slot), jnp.asarray(row), req.rng,
                jnp.int32(limit), table_row, jnp.int32(pb), samp)
        else:
            self.stats.admit_transient_kv_bytes = max(
                self.stats.admit_transient_kv_bytes,
                self._admit_transient_kv_bytes(h))
            self._admit_info["path"] = "suffix_prefill"
            with profile.annotate("prefill_suffix"):
                self._state = self._admit_suffix_jit(
                    params, self._state, jnp.int32(slot),
                    req.prompt[None, h * bsz:], jnp.asarray(row),
                    req.rng, jnp.int32(limit),
                    jnp.asarray(hit_pages, jnp.int32),
                    jnp.asarray(new_pages, jnp.int32), table_row, samp)
        return True

    def _empty_completion(self, req: Request,
                          param_version: int = 0) -> Completion:
        """Zero-budget request: completes without ever touching a slot.

        The record is explicitly all-prompt: tokens beyond the true
        prompt stay MASK, the reveal-step map is all zero and
        ``gen_blocks == gen_tokens == 0`` — so downstream packaging
        (``rollout_to_batch``) can never mistake the prompt for
        revealed-at-step-0 generation.
        """
        cfg = self.model.cfg
        tokens = np.full((self.max_len,), cfg.resolved_mask_token,
                         np.int32)
        tokens[:req.prompt.shape[0]] = req.prompt
        self.stats.admitted += 1
        self.stats.completed += 1
        return Completion(
            uid=req.uid, tokens=tokens,
            steps=np.zeros((self.max_len,), np.int32),
            prompt_blocks=req.prompt_blocks, gen_blocks=0,
            gen_tokens=0, denoise_steps=0, finish_reason="length",
            admitted_tick=self.stats.ticks,
            completed_tick=self.stats.ticks, params=req.params,
            param_version=param_version)

    # ------------------------------------------------------------- API
    def submit(self, prompt: np.ndarray, prompt_blocks: int, rng=None, *,
               params: SamplingParams | None = None,
               max_new_blocks: int | None = _UNSET) -> int:
        """Queue a request; returns its uid (completions carry it).

        ``params`` carries every per-request decode knob (defaults to
        the pool's ``GenerationConfig`` sampling fields); the legacy
        ``max_new_blocks=`` keyword overrides the params' budget.  An
        explicit ``rng`` key always wins (so batch drivers keep their
        per-row key streams and static/continuous parity regardless of
        params); with ``rng`` omitted, ``params.seed`` derives the key
        — deterministic replay for a front end that cannot thread jax
        keys.

        The prompt is trimmed to its true ``prompt_blocks`` blocks:
        batch-padding blocks beyond that never influence decoding (the
        cache limit masks them and commits overwrite them), and dropping
        them keeps paged admission from allocating pages for padding.
        """
        prompt = np.asarray(prompt, np.int32)
        prompt_blocks = int(prompt_blocks)
        bsz = self.model.cfg.block_size
        assert prompt.ndim == 1 and prompt.shape[0] % bsz == 0
        assert 1 <= prompt_blocks <= self.n_blocks_total
        assert prompt_blocks * bsz <= prompt.shape[0]
        if params is None:
            params = self.default_params
        if max_new_blocks is not _UNSET:
            params = params.replace(max_new_blocks=max_new_blocks)
        if rng is None:
            if params.seed is None:
                raise ValueError("submit needs an rng key or params.seed")
            rng = jax.random.PRNGKey(params.seed)
        uid = self._next_uid
        self._next_uid += 1
        self._queue.append(Request(uid=uid,
                                   prompt=prompt[:prompt_blocks * bsz],
                                   prompt_blocks=prompt_blocks,
                                   rng=jnp.asarray(rng),
                                   params=params))
        # lifecycle span 1/2: queued, closed at admission (or at the
        # zero-budget short circuit) with the wait labeled
        self.tracer.begin(("queued", uid), f"req {uid} queued",
                          cat="request", track="queue", uid=uid,
                          prompt_blocks=prompt_blocks)
        return uid

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or any(
            r is not None for r in self._slot_req)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    # ------------------------------------------------- paged allocator
    def _take_pages(self, n: int) -> list[int]:
        """Pop ``n`` pages: free list first, then LRU prefix reclaims.

        Reclaimed pages held cached prompt KV of idle (refcount-0) index
        entries; their ``pos`` is wiped before reuse so the stale keys
        can never pass a later owner's ``cache_limit`` mask.  Guaranteed
        to succeed by the admission invariant: reserved worst cases plus
        live-referenced index pages never exceed the pool, so everything
        else is free or reclaimable.
        """
        out, reclaimed = [], []
        for _ in range(n):
            if self._free_pages:
                out.append(self._free_pages.pop())
                continue
            page = self.prefix.evict_lru() if self.prefix is not None \
                else None
            if page is None:
                raise RuntimeError(
                    "page pool exhausted — reservation invariant broken")
            reclaimed.append(page)
            out.append(page)
        if reclaimed:
            self.stats.prefix_evictions += len(reclaimed)
            self._invalidate_pages(reclaimed)
        return out

    def _alloc_cursor_pages(self) -> None:
        """Give every live slot a page for the block it commits next.

        Cannot fail: admission reserved each request's worst case, and a
        live slot's cursor is always below its limit, so at least one
        reserved-but-unallocated page remains for it (reclaiming idle
        prefix-cache pages if the free list is dry).
        """
        slots, blks, pages = [], [], []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            b = self._slot_blk[slot]
            if self._table_host[slot, b] < 0:
                pg = self._take_pages(1)[0]
                self._table_host[slot, b] = pg
                slots.append(slot)
                blks.append(b)
                pages.append(pg)
        if slots:
            table = self._state.table.at[
                jnp.asarray(slots, jnp.int32),
                jnp.asarray(blks, jnp.int32)].set(
                    jnp.asarray(pages, jnp.int32))
            self._state = dataclasses.replace(self._state, table=table)
        self.stats.page_allocs += len(slots)
        self.stats.peak_pages_in_use = max(self.stats.peak_pages_in_use,
                                           self.pages_in_use)
        self.stats.peak_pages_live = max(self.stats.peak_pages_live,
                                         self.pages_live)

    def _free_slot_pages(self, slot: int) -> list[int]:
        """Release a slot's pages; returns the *exclusive* pages freed.

        Prompt pages registered in the prefix index are not freed — the
        slot just drops its references and the entries stay cached
        (reclaimed later under pressure).  Generated-block pages are
        always exclusive and return to the free list.
        """
        row = self._table_host[slot]
        pages = [int(p) for p in row[row >= 0]]
        nodes = self._slot_nodes[slot]
        if nodes:
            # row is block-ordered: the first len(nodes) mapped pages
            # are the registered prompt blocks, the rest generation
            self.prefix.release(nodes)
            self._slot_nodes[slot] = []
            pages = pages[len(nodes):]
        self._free_pages.extend(pages)
        self.stats.page_frees += len(pages)
        row[:] = -1
        self._pages_reserved -= self._slot_resv[slot]
        self._slot_resv[slot] = 0
        self._slot_limit[slot] = 0
        return pages

    def _invalidate_pages(self, pages: list[int]) -> None:
        """Free-list hygiene: wipe the ``pos`` of pages being freed.

        A reused page must look empty until its new owner writes it —
        stale positions from the previous request could otherwise pass
        the ``pos < cache_limit`` validity mask of a cursor page that is
        allocated (for the commit) before it is first written.  Applies
        equally to prefix-cache reclaims: a reclaimed page held valid
        cached keys by design, which become stale the moment the entry
        leaves the index.
        """
        idx = jnp.asarray(pages, jnp.int32)

        def wipe(c, grouped):
            if not isinstance(c, attention.PagedAttnCache):
                return c
            return attention.wipe_pages(c, idx, grouped=grouped)

        caches = self._state.caches
        caches = {
            "prefix": {lk: wipe(c, False)
                       for lk, c in caches["prefix"].items()},
            "groups": {lk: wipe(c, True)
                       for lk, c in caches["groups"].items()},
        }
        self._state = dataclasses.replace(self._state, caches=caches)

    # ------------------------------------------------------------ tick
    def step(self, params, param_version: int = 0) -> list[Completion]:
        """One scheduler tick: admit -> advance -> evict.

        ``params`` are the *model weights* (the per-request decode
        parameters ride on each submitted request); ``param_version`` is
        their monotone version tag (``ModelServer.version``) — stamped
        onto admissions and onto every block this tick commits, so
        completions carry exact per-block weight provenance.  Weights
        (and their version) may change between ticks without retracing:
        that block boundary is precisely where the async RL loop lands
        ``update_weights`` without draining the pool.  Returns the
        completions harvested this tick (possibly empty).

        Instrumentation: the tick and its three phases are recorded as
        tracer spans on the ``scheduler`` track; admitted requests get
        lifecycle spans on per-slot tracks.  All span timestamps are
        host wall-clock around jit *dispatch* — the tracer never syncs
        the device, so instrumentation cannot change tokens, retraces,
        or the ``hot-sync`` contract (tests assert byte-parity and
        ``n_advance_traces == 1`` with tracing on).
        """
        if isinstance(params, SamplingParams):
            raise TypeError(
                "step(params=) takes model weights; per-request "
                "SamplingParams belong on submit(..., params=...)")
        with self.tracer.span("tick", cat="scheduler", track="scheduler",
                              tick=self.stats.ticks):
            return self._tick(params, param_version)

    def _tick(self, params, param_version: int = 0) -> list[Completion]:
        self.stats.transient_kv_bytes = self.transient_kv_bytes
        if not self.stats.kernel_mode and self.kernel_plan:
            self.stats.kernel_mode = self.kernel_plan.mode
        # ---- admit queued requests into free slots -------------------
        out: list[Completion] = []
        with self.tracer.span("admit", cat="scheduler",
                              track="scheduler") as adm:
            n_adm = 0
            for slot in range(self.n_slots):
                if not self._queue or self._slot_req[slot] is not None:
                    continue
                req = self._queue[0]
                budget = self.n_blocks_total - req.prompt_blocks
                if req.params.max_new_blocks is not None:
                    budget = min(budget, req.params.max_new_blocks)
                if budget <= 0:
                    # nothing to decode (prompt fills the cache / zero
                    # block budget) — complete immediately, never touch
                    # a slot
                    self._queue.popleft()
                    out.append(self._empty_completion(req, param_version))
                    self.tracer.end(("queued", req.uid), outcome="empty")
                    continue
                limit = req.prompt_blocks + budget
                if self.cache == "paged":
                    if limit > self.n_usable_pages:
                        raise ValueError(
                            f"request {req.uid} needs {limit} pages but "
                            f"the pool only has {self.n_usable_pages}")
                    if not self._admit_paged(params, slot, req, budget):
                        # out of pages: defer the FIFO head until
                        # evictions free some (backpressure, not a crash)
                        self.stats.deferred += 1
                        self.tracer.instant("defer", cat="scheduler",
                                            track="scheduler",
                                            uid=req.uid,
                                            queued=len(self._queue))
                        break
                else:
                    self.stats.prefill_blocks += req.prompt_blocks
                    self._note_prefill_tiles(req)
                    self._admit_info = {"path": "dense", "hit_blocks": 0}
                    with profile.annotate("prefill"):
                        self._state = self._admit_jit(
                            params, self._state, jnp.int32(slot),
                            req.prompt[None],
                            jnp.asarray([req.prompt_blocks], jnp.int32),
                            req.rng, jnp.int32(limit),
                            self._samp_scalars(req.params), None)
                self._queue.popleft()
                self._slot_req[slot] = req
                self._slot_admit_tick[slot] = self.stats.ticks
                self._slot_admit_version[slot] = param_version
                self._slot_admit_abs[slot] = len(self._tick_versions)
                self.stats.admitted += 1
                n_adm += 1
                # lifecycle span 2/2: decode, one track per slot —
                # closed at harvest with the finish labels
                info = self._admit_info
                self.tracer.end(("queued", req.uid), slot=slot, **info)
                self.tracer.begin(
                    ("decode", req.uid), f"req {req.uid}",
                    cat="request", track=f"slot {slot}", uid=req.uid,
                    slot=slot, kernel_mode=self.stats.kernel_mode,
                    prompt_blocks=req.prompt_blocks, budget=budget,
                    **info)
            adm.args["admitted"] = n_adm

        self.stats.peak_active = max(self.stats.peak_active,
                                     self.n_active)
        if not any(r is not None for r in self._slot_req):
            return out

        # ---- advance the whole pool by one block ---------------------
        # span brackets page allocation + jit dispatch; advance_block's
        # result is left unsynced, so dur is dispatch time unless
        # sync_each_tick (engine) or a profiler capture asks for more
        with self.tracer.span("advance", cat="scheduler",
                              track="scheduler", n_active=self.n_active):
            if self.cache == "paged":
                self._alloc_cursor_pages()
            with profile.annotate("advance_block"):
                self._state = self._advance(params, self._state)
        # every live slot committed one block under these weights
        self._tick_versions.append(param_version)
        self.stats.advance_traces = self._advance.n_traces
        self.stats.ticks += 1
        self.stats.slot_ticks += self.n_slots
        self.stats.active_slot_ticks += self.n_active
        if self.cache == "paged":
            # mirror advance_block's cursor update (live slots were all
            # not-done going in): blk <- min(blk + 1, limit)
            for slot, req in enumerate(self._slot_req):
                if req is not None:
                    self._slot_blk[slot] = min(self._slot_blk[slot] + 1,
                                               self._slot_limit[slot])

        # ---- evict finished slots ------------------------------------
        with self.tracer.span("harvest", cat="scheduler",
                              track="scheduler") as hv:
            done = np.asarray(self._state.done)
            evicted: list[int] = []
            freed_pages: list[int] = []
            for slot in range(self.n_slots):
                req = self._slot_req[slot]
                if req is None or not done[slot]:
                    continue
                tokens = np.asarray(self._state.tokens[slot])
                steps = np.asarray(self._state.steps[slot])
                gen_blocks = int(self._state.blk[slot]) \
                    - req.prompt_blocks
                bsz = self.model.cfg.block_size
                lo, hi = req.prompt_blocks * bsz, \
                    (req.prompt_blocks + gen_blocks) * bsz
                # serve-stats count tokens up to and including the first
                # EOS (the *request's* stop token): the rest of an EOS
                # block is padding, not output
                eos_id = req.params.eos_id
                gen_tokens = int(decoding.count_gen_tokens(
                    tokens[None], [req.prompt_blocks], [gen_blocks],
                    eos_id=eos_id, block_size=bsz)[0])
                hit_eos = bool((tokens[lo:hi] == eos_id).any())
                # a live slot advances on every tick from admission to
                # harvest, so its gen blocks map one-to-one onto the
                # tick-version records starting at its admission point
                a0 = self._slot_admit_abs[slot]
                comp = Completion(
                    uid=req.uid, tokens=tokens, steps=steps,
                    prompt_blocks=req.prompt_blocks,
                    gen_blocks=gen_blocks, gen_tokens=gen_tokens,
                    denoise_steps=int(self._state.n_denoise[slot]),
                    finish_reason="eos" if hit_eos else "length",
                    admitted_tick=self._slot_admit_tick[slot],
                    completed_tick=self.stats.ticks, params=req.params,
                    param_version=self._slot_admit_version[slot],
                    block_versions=np.asarray(
                        self._tick_versions[a0:a0 + gen_blocks],
                        np.int64))
                out.append(comp)
                self.tracer.end(("decode", req.uid),
                                finish_reason=comp.finish_reason,
                                gen_tokens=comp.gen_tokens,
                                gen_blocks=comp.gen_blocks,
                                denoise_steps=comp.denoise_steps,
                                latency_ticks=comp.latency_ticks)
                self._slot_req[slot] = None
                evicted.append(slot)
                if self.cache == "paged":
                    freed_pages.extend(self._free_slot_pages(slot))
                self.stats.completed += 1
                self.stats.gen_tokens += gen_tokens
                self.stats.denoise_steps += comp.denoise_steps
            if evicted and self.cache == "paged":
                # reset the device table rows so the freed slots'
                # idempotent re-commits dump into the null page, not
                # into pages that may be re-allocated to other requests
                # (shared prompt pages stay mapped in the *surviving*
                # sharers' rows untouched)
                table = self._state.table.at[
                    jnp.asarray(evicted, jnp.int32)].set(-1)
                self._state = dataclasses.replace(self._state,
                                                  table=table)
                if freed_pages:
                    # exclusive pages only: wiping a still-shared page
                    # would blind the survivors to their own prompt
                    self._invalidate_pages(freed_pages)
            hv.args["completed"] = len(evicted)
        return out

    def run(self, params) -> Iterator[Completion]:
        """Drive ticks until queue + slots drain, streaming completions."""
        while self.has_work:
            yield from self.step(params)

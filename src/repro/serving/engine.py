"""RolloutEngine — continuous-batching blockwise-dLLM inference.

The JetEngine/LMDeploy role, rebuilt on ``serving.scheduler``: requests
enter a queue, a fixed-slot ``SlotScheduler`` admits them into freed
decode slots at block boundaries, and completions stream back in finish
order.  The lock-step one-shot path (every request padded to the batch
max and decoded to drain — the pre-refactor behaviour) is kept as
``batching="static"`` for A/B benchmarking (benchmarks/serve_bench.py).

Request API (``serving.api``): every decode parameter is
request-granular.  ``submit(prompt, params=SamplingParams(...))``
queues a text request with its own τ / temperature / mode / step
budget / block budget / stop token / seed; ``generate_ids(...,
sampling=...)`` runs a whole batch of mixed configurations through one
jitted call (static path) or one slot pool (continuous path) — the
parameters ride in per-row vectors, so serving mixed traffic never
retraces and a row's tokens are bit-identical to a homogeneous run.
``stream()`` yields structured ``RequestOutput`` records (uid, text,
``finish_reason`` "eos" | "length", admit→finish latency in ticks).

Contracts kept:
  * ``generate_ids(prompt_tokens, prompt_blocks, rng) -> gen dict`` —
    row order == input order, token- and step-map-identical between the
    static and continuous paths for the same rng (per-sequence key
    streams; see core.decoding), so rl/trainer.py, launch/serve.py and
    the fig6/fig7 benchmarks run unchanged.
  * ``generate_texts`` — texts trimmed at the first EOS.
  * ``EngineStats`` — throughput counters: ``total_steps`` counts
    the denoise steps that revealed tokens (each block's step-map max
    + 1), not the forwards executed — the pool runs ``s_max + 1`` per
    block whatever it needs (``SchedulerStats.denoise_forwards``);
    ``total_tokens`` counts generated tokens up to the first EOS
    inclusive (not the block-padded tail); continuous runs also record
    slot utilization (active slot-ticks / paid slot-ticks) and
    admit→finish latency (``latency_p50`` / ``latency_p95``, in
    scheduler ticks).

The continuous path's KV layout is selectable: ``cache="dense"`` (each
slot owns a ``max_len`` cache region) or ``cache="paged"`` (slots share
an ``n_pages`` pool of block-sized pages through per-slot block tables —
see serving.scheduler).  Paged pools add a third layer,
``prefix_cache`` (auto-on for pure-attention stacks): a refcounted
radix index shares committed prompt pages across requests, so DiPO's
G-rollouts-per-prompt groups (``generate_group_ids``) prefill each
unique prompt once and hold one copy of its KV.  Sampling params never
affect prompt KV, so mixed-params requests share prefix pages freely.
All layouts produce byte-identical tokens; ``EngineStats.
prefix_hit_rate`` reports the fraction of prompt blocks served from
shared pages.  Paged pools additionally choose how decode *reads* the
pool via ``kernel="ref"|"pallas"`` — the gathered fallback vs the
in-place page-aware Pallas kernels (decode + suffix prefill);
``EngineStats.transient_kv_bytes`` / ``admit_transient_kv_bytes``
report the per-tick and admission-time K/V copies the chosen layout
pays (both 0 in-place), ``kernel_mode`` whether the kernels compile
or interpret on this backend.

The engine reads weights from a ``ModelServer`` (in-place updates) or
``OfflineWeightStore`` (checkpoint baseline) — swapping one for the
other reproduces the paper's Fig. 6 ablation without touching the
engine.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decoding
from repro.data.tokenizer import ByteTokenizer
from repro.data.pipeline import pad_to_block
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import Tracer
from repro.serving.api import (GenerationConfig, RequestOutput,
                               SamplingParams)
from repro.serving.scheduler import Completion, SlotScheduler

__all__ = ["EngineStats", "GenerationConfig", "RequestOutput",
           "RolloutEngine", "SamplingParams"]


@dataclasses.dataclass
class EngineStats:
    """Engine-level throughput/latency counters.

    Like ``SchedulerStats``, every field is bound storage for an
    instrument in ``self.registry`` (namespace ``dirl_engine``): hot
    paths mutate attributes, exporters read ``registry.collect()``,
    and the warmup reset ``engine.stats = EngineStats()`` resets the
    exported view too.

    ``wall_seconds`` covers *engine-side* wall time under one uniform
    definition on every path: the time spent driving the pool plus
    packaging completions, measured around jit dispatch (the
    ``generate_ids`` call body; each ``stream()`` pool tick).  Consumer
    wait between ``stream`` yields is excluded.  With
    ``sync_each_tick`` the measured region includes a device sync, so
    the same field reports honest device latency.
    """
    rollouts: int = 0
    total_tokens: int = 0
    total_steps: int = 0          # denoise steps that revealed tokens
    wall_seconds: float = 0.0
    slot_ticks: int = 0           # continuous: paid slot-steps
    active_slot_ticks: int = 0    # continuous: useful slot-steps
    prefix_hit_blocks: int = 0    # prompt blocks served from shared pages
    prefix_miss_blocks: int = 0   # prompt blocks that paid a prefill
    # continuous: per-tick cache-KV bytes the pool's decode layout
    # copies out of the resident cache (scheduler.stats mirror; 0 on
    # the in-place kernel="pallas" path)
    transient_kv_bytes: int = 0
    # continuous: peak admission-time cache-KV bytes one suffix prefill
    # gathered out of the pool (scheduler.stats mirror; 0 in-place)
    admit_transient_kv_bytes: int = 0
    # execution mode of the paged Pallas kernels ("compiled" |
    # "interpret", "" when no kernel is launched)
    kernel_mode: str = ""
    # continuous: compilations of the scheduler's pool advance
    # (TraceGuard mirror) — the zero-retrace contract keeps this at 1
    # across arbitrary per-request SamplingParams mixes
    advance_traces: int = 0
    # weight version (ModelServer.version) most recently read from the
    # store while driving the pool — the in-place update observability
    # hook: a push mid-stream moves this gauge at the next tick
    param_version: int = 0
    # continuous: per-completion admit -> finish latency, in scheduler
    # ticks (one tick = one block-advance over the pool).  An
    # obs.metrics.Histogram: cumulative count/sum plus a bounded
    # reservoir window for percentiles — a long-lived server keeps the
    # most recent 4096, not every request.  Deque-compatible (append /
    # len / iter), so legacy call sites read/write it unchanged.
    latencies: Histogram = dataclasses.field(
        default_factory=lambda: Histogram(
            "latency_ticks", "admit->finish latency in scheduler ticks",
            reservoir=4096))

    _COUNTER_FIELDS = ("rollouts", "total_tokens", "total_steps",
                       "slot_ticks", "active_slot_ticks",
                       "prefix_hit_blocks", "prefix_miss_blocks")
    _GAUGE_FIELDS = ("wall_seconds", "transient_kv_bytes",
                     "admit_transient_kv_bytes", "advance_traces",
                     "param_version")

    def __post_init__(self):
        self.registry = MetricsRegistry("dirl_engine")
        for f in self._COUNTER_FIELDS:
            self.registry.counter(f, bind=(self, f))
        for f in self._GAUGE_FIELDS:
            self.registry.gauge(f, bind=(self, f))
        self.registry.info("kernel_mode",
                           "paged-kernel execution mode",
                           bind=(self, "kernel_mode"))
        self.registry.adopt("latency_ticks", self.latencies)

    @property
    def tokens_per_step(self) -> float:
        """Generated tokens per revealing denoise step (not per
        forward executed)."""
        return self.total_tokens / max(self.total_steps, 1)

    @property
    def utilization(self) -> float:
        """Fraction of paid slot compute that advanced a live request."""
        return self.active_slot_ticks / max(self.slot_ticks, 1)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt blocks served from shared pages."""
        total = self.prefix_hit_blocks + self.prefix_miss_blocks
        return self.prefix_hit_blocks / max(total, 1)

    @property
    def latency_p50(self) -> float:
        """Median admit -> finish latency in scheduler ticks."""
        return self.latencies.percentile(50)

    @property
    def latency_p95(self) -> float:
        """95th-percentile admit -> finish latency in scheduler ticks."""
        return self.latencies.percentile(95)

    @property
    def latency_p99(self) -> float:
        """Tail (99th-percentile) admit -> finish latency in scheduler
        ticks — the SLO-facing number (over the bounded recent window)."""
        return self.latencies.percentile(99)


class RolloutEngine:
    def __init__(self, model, weight_store, gen_cfg: GenerationConfig,
                 tokenizer: ByteTokenizer | None = None):
        self.model = model
        self.store = weight_store
        self.gen_cfg = gen_cfg
        self.tok = tokenizer or ByteTokenizer()
        self.stats = EngineStats()
        # one tracer for the whole stack: handed to the scheduler so
        # engine drains, tick phases and request lifecycles land in a
        # single export (disabled by default — still used for timing)
        self.tracer = Tracer(capacity=gen_cfg.trace_capacity,
                             enabled=gen_cfg.trace)
        self.last_call: dict = {}
        self._pending: list[Completion] = []   # stream() completions
        # harvested while a generate_ids drain drove the shared pool
        self._rng = jax.random.PRNGKey(0)      # submit() key stream
        # sampling parameters enter as traced (B,) vectors, so one
        # compiled executable serves every config mix; only max_len and
        # s_max (shapes / loop bound) are baked in
        self._gen_jit = jax.jit(functools.partial(
            decoding.generate, model,
            max_len=gen_cfg.max_len, s_max=gen_cfg.s_max))
        self._sched: SlotScheduler | None = None

    @property
    def scheduler(self) -> SlotScheduler:
        """The persistent slot pool (created on first use).

        The whole ``GenerationConfig`` is handed over as one object —
        the scheduler reads the pool fields and derives its default
        ``SamplingParams`` from the decode fields, so a new config knob
        is threaded exactly once.
        """
        if self._sched is None:
            self._sched = SlotScheduler(self.model, self.gen_cfg,
                                        tracer=self.tracer)
            self.stats.transient_kv_bytes = \
                self._sched.transient_kv_bytes
            self.stats.kernel_mode = self._sched.stats.kernel_mode
        return self._sched

    # ------------------------------------------------------- sampling
    def _resolve_sampling(self, B: int, sampling, prompt_blocks):
        """Normalise ``sampling`` to a per-row params list + the vector
        kwargs ``decoding.generate`` consumes (incl. per-row ``limit``).
        """
        if sampling is None:
            plist = [self.gen_cfg.sampling()] * B
        elif isinstance(sampling, SamplingParams):
            plist = [sampling] * B
        else:
            plist = list(sampling)
            if len(plist) != B:
                raise ValueError(
                    f"sampling list has {len(plist)} entries "
                    f"for a batch of {B}")
        nbt = self.gen_cfg.max_len // self.model.cfg.block_size
        pb = np.asarray(prompt_blocks, np.int64)
        limit = np.full((B,), nbt, np.int32)
        for i, p in enumerate(plist):
            if p.max_new_blocks is not None:
                limit[i] = min(nbt, int(pb[i]) + p.max_new_blocks)
        kw = dict(
            tau=np.array([p.tau for p in plist], np.float32),
            temperature=np.array([p.temperature for p in plist],
                                 np.float32),
            n_steps=np.array([p.n_steps for p in plist], np.int32),
            mode=np.array([p.dynamic for p in plist], bool),
            eos_id=np.array([p.eos_id for p in plist], np.int32),
            limit=limit)
        return plist, kw

    # ------------------------------------------------------------------
    def generate_ids(self, prompt_tokens: np.ndarray,
                     prompt_blocks: np.ndarray, rng,
                     sampling=None) -> dict:
        """Run blockwise decode on pre-tokenised prompts.

        ``sampling``: None (config defaults), one ``SamplingParams``
        applied to every row, or a per-row sequence — a mixed batch
        costs no extra compilation on either path.  Row order of the
        returned dict matches the input; the static and continuous
        paths are token-identical for the same ``rng``.
        """
        # one obs span defines wall_seconds for the whole call on both
        # paths (a disabled tracer still times; see EngineStats docs)
        with self.tracer.span("engine.generate_ids", cat="engine",
                              track="engine",
                              batching=self.gen_cfg.batching) as sp:
            self.stats.param_version = getattr(self.store, "version", 0)
            params = self.store.params  # offline store pays a load here
            B = prompt_tokens.shape[0]
            plist, vec_kw = self._resolve_sampling(B, sampling,
                                                   prompt_blocks)
            if self.gen_cfg.batching == "static":
                gen = self._gen_jit(params, jnp.asarray(prompt_tokens),
                                    jnp.asarray(prompt_blocks), rng,
                                    **vec_kw)
                if self.gen_cfg.sync_each_tick:
                    # opt-in: honest wall-clock per call, at dispatch cost
                    jax.block_until_ready(gen["tokens"])  # dirlint: ok(hot-sync)
                self.last_call = {"batching": "static"}
            else:
                gen = self._generate_ids_continuous(
                    params, prompt_tokens, prompt_blocks, rng, plist)
        dt = sp.dur
        self.stats.rollouts += B
        # honest tokens/sec numerator: count only up to the first EOS
        # (each row's own stop token)
        self.stats.total_tokens += int(decoding.count_gen_tokens(
            gen["tokens"], gen["prompt_blocks"], gen["gen_blocks"],
            eos_id=np.array([p.eos_id for p in plist], np.int32),
            block_size=self.model.cfg.block_size).sum())
        self.stats.total_steps += int(jnp.sum(gen["denoise_steps"]))
        self.stats.wall_seconds += dt
        return gen

    def generate_group_ids(self, prompt_tokens: np.ndarray,
                           prompt_blocks: np.ndarray, rng,
                           group_size: int, sampling=None) -> dict:
        """Roll out ``group_size`` trajectories per prompt (DiPO groups).

        Expands (P, Lp) prompts to a (P*G, Lp) batch with each group's G
        members *adjacent*, then runs ``generate_ids`` — identical rng
        layout to repeating the prompts by hand, so results are
        unchanged.  ``sampling`` may be one ``SamplingParams`` or a
        per-*prompt* sequence (length P, expanded across each group) —
        the per-group τ lever DiFFPO trains with.  The point of the
        dedicated entry is the serving side: adjacent identical prompts
        admit back-to-back, so with ``cache="paged"`` + ``prefix_cache``
        the first member registers the prompt's pages and the other G-1
        map them straight into their block tables — one prefill and one
        KV copy per *unique* prompt (sampling params never affect
        prompt KV, so mixed-τ groups share exactly the same).
        """
        toks = np.repeat(np.asarray(prompt_tokens), group_size, axis=0)
        blocks = np.repeat(np.asarray(prompt_blocks), group_size, axis=0)
        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            sampling = [p for p in sampling for _ in range(group_size)]
        return self.generate_ids(toks, blocks, rng, sampling=sampling)

    def _generate_ids_continuous(self, params, prompt_tokens,
                                 prompt_blocks, rng, plist) -> dict:
        """Drain a fixed request batch through the slot pool."""
        sched = self.scheduler
        # re-mirrored every drain from the scheduler's authoritative
        # pool-static value (never the resettable stats snapshot), so
        # the warmup pattern `engine.stats = EngineStats()` keeps it
        self.stats.transient_kv_bytes = sched.transient_kv_bytes
        prompt_tokens = np.asarray(prompt_tokens)
        prompt_blocks = np.asarray(prompt_blocks)
        B, Lp = prompt_tokens.shape
        max_len = self.gen_cfg.max_len
        # the one-shot generate runs every row to its own block budget
        # (EOS, max_new_blocks, or cache capacity), so the slot pool
        # must too — per-row limits, never the padded width
        keys = decoding._per_seq_keys(rng, B)
        uid_to_row = {}
        for i in range(B):
            uid = sched.submit(prompt_tokens[i], int(prompt_blocks[i]),
                               keys[i], params=plist[i])
            uid_to_row[uid] = i

        tokens = np.zeros((B, max_len), np.int32)
        steps = np.zeros((B, max_len), np.int32)
        gen_blocks = np.zeros((B,), np.int32)
        denoise = np.zeros((B,), np.int32)
        done = np.zeros((B,), bool)
        ticks0 = sched.stats.ticks
        slot0, active0 = sched.stats.slot_ticks, \
            sched.stats.active_slot_ticks
        hit0, miss0 = sched.stats.prefix_hit_blocks, \
            sched.stats.prefix_miss_blocks
        version = getattr(self.store, "version", 0)
        n_done = 0
        while n_done < B:
            for comp in sched.step(params, param_version=version):
                row = uid_to_row.pop(comp.uid, None)
                if row is None:
                    # a streaming request finished mid-drain: hold it
                    # for the next stream() pass
                    self._pending.append(comp)
                    continue
                tokens[row] = comp.tokens
                steps[row] = comp.steps
                gen_blocks[row] = comp.gen_blocks
                denoise[row] = comp.denoise_steps
                # static parity: a decoded row completes only at EOS or
                # its limit (both done in the one-shot generate); a
                # zero-budget row (no loop trips) is never flagged done
                done[row] = comp.gen_blocks > 0
                self.stats.latencies.append(comp.latency_ticks)
                n_done += 1
        self.stats.slot_ticks += sched.stats.slot_ticks - slot0
        self.stats.active_slot_ticks += \
            sched.stats.active_slot_ticks - active0
        hit = sched.stats.prefix_hit_blocks - hit0
        miss = sched.stats.prefix_miss_blocks - miss0
        self.stats.prefix_hit_blocks += hit
        self.stats.prefix_miss_blocks += miss
        self.stats.admit_transient_kv_bytes = max(
            self.stats.admit_transient_kv_bytes,
            sched.stats.admit_transient_kv_bytes)
        self.stats.advance_traces = sched.n_advance_traces
        self.last_call = {
            "batching": "continuous",
            "ticks": sched.stats.ticks - ticks0,
            "utilization": (sched.stats.active_slot_ticks - active0)
            / max(sched.stats.slot_ticks - slot0, 1),
            "prefix_hit_rate": hit / max(hit + miss, 1),
        }
        return {"tokens": jnp.asarray(tokens), "steps": jnp.asarray(steps),
                "gen_blocks": jnp.asarray(gen_blocks),
                "prompt_blocks": jnp.asarray(prompt_blocks, jnp.int32),
                "done": jnp.asarray(done),
                "denoise_steps": jnp.asarray(denoise)}

    # ------------------------------------------------- streaming serve
    def _encode_prompt(self, prompt: str) -> tuple[np.ndarray, int]:
        bsz = self.model.cfg.block_size
        enc = pad_to_block(self.tok.encode(prompt, bos=True), bsz,
                           self.tok.pad_id)
        return np.asarray(enc, np.int32), len(enc) // bsz

    def submit(self, prompt: str, rng=None,
               params: SamplingParams | None = None) -> int:
        """Queue one text request on the live pool; returns its uid.

        ``params`` carries the request's own decode configuration
        (pool defaults otherwise).  ``rng`` may be omitted: with
        ``params.seed`` set the key derives from the seed, else the
        engine draws from its internal key stream.
        """
        toks, blocks = self._encode_prompt(prompt)
        if rng is None and (params is None or params.seed is None):
            self._rng, rng = jax.random.split(self._rng)
        return self.scheduler.submit(toks, blocks, rng, params=params)

    def stream_completions(self, params=None) -> Iterator[Completion]:
        """Drive the pool until it drains, yielding raw ``Completion``
        records (full tokens + reveal-step map + per-block weight
        versions) in completion order — new ``submit``s may land
        mid-stream.

        With ``params=None`` the live store weights (and their version)
        are re-read every tick, so in-place server updates take effect
        at the next block boundary with the pool still full — the
        drain-free weight push the async RL producer rides on.  Text
        front ends want ``stream()``, which packages each completion
        into a ``RequestOutput``."""
        if isinstance(params, SamplingParams):
            raise TypeError(
                "stream(params=) takes model weights; per-request "
                "SamplingParams belong on submit(..., params=...)")
        sched = self.scheduler
        self.stats.transient_kv_bytes = sched.transient_kv_bytes
        live = params is None
        while sched.has_work or self._pending:
            if sched.has_work:
                version = getattr(self.store, "version", 0)
                p = self.store.params if live else params
                self.stats.param_version = version
                slot0 = sched.stats.slot_ticks
                active0 = sched.stats.active_slot_ticks
                hit0 = sched.stats.prefix_hit_blocks
                miss0 = sched.stats.prefix_miss_blocks
                # engine-side wall time: pool tick + (stream) completion
                # packaging; consumer wait between yields excluded —
                # the same definition generate_ids uses
                with self.tracer.span("engine.stream_tick", cat="engine",
                                      track="engine") as sp:
                    self._pending.extend(
                        sched.step(p, param_version=version))
                self.stats.wall_seconds += sp.dur
                self.stats.slot_ticks += sched.stats.slot_ticks - slot0
                self.stats.active_slot_ticks += \
                    sched.stats.active_slot_ticks - active0
                self.stats.prefix_hit_blocks += \
                    sched.stats.prefix_hit_blocks - hit0
                self.stats.prefix_miss_blocks += \
                    sched.stats.prefix_miss_blocks - miss0
                self.stats.admit_transient_kv_bytes = max(
                    self.stats.admit_transient_kv_bytes,
                    sched.stats.admit_transient_kv_bytes)
                self.stats.advance_traces = sched.n_advance_traces
            # pop-one/yield-one: if the consumer abandons the generator
            # mid-iteration, undelivered completions stay in _pending
            # for the next stream() call
            while self._pending:
                comp = self._pending.pop(0)
                self.stats.rollouts += 1
                self.stats.total_tokens += comp.gen_tokens
                self.stats.total_steps += comp.denoise_steps
                self.stats.latencies.append(comp.latency_ticks)
                yield comp

    def stream(self, params=None) -> Iterator[RequestOutput]:
        """Drive the pool until it drains, yielding ``RequestOutput``
        records in completion order — new ``submit``s may land
        mid-stream.

        With ``params=None`` the live store weights are re-read every
        tick, so in-place server updates take effect mid-stream."""
        for comp in self.stream_completions(params):
            with self.tracer.span("engine.package", cat="engine",
                                  track="engine", uid=comp.uid) as psp:
                out = self._to_output(comp)
            self.stats.wall_seconds += psp.dur
            yield out

    def _to_output(self, comp: Completion) -> RequestOutput:
        """Package a raw completion into the structured streaming
        record (text and ids trimmed at the request's own stop token)."""
        bsz = self.model.cfg.block_size
        lo = comp.prompt_blocks * bsz
        ids = self._trim_ids(comp.tokens[lo:lo + comp.gen_blocks * bsz],
                             comp.params.eos_id)
        return RequestOutput(
            uid=comp.uid, text=self.tok.decode(ids), token_ids=ids,
            finish_reason=comp.finish_reason,
            prompt_blocks=comp.prompt_blocks,
            gen_blocks=comp.gen_blocks, gen_tokens=comp.gen_tokens,
            denoise_steps=comp.denoise_steps,
            admitted_tick=comp.admitted_tick,
            completed_tick=comp.completed_tick, params=comp.params,
            param_version=comp.param_version)

    @staticmethod
    def _trim_ids(ids: np.ndarray, eos_id: int) -> np.ndarray:
        """Cut a generated region at the first EOS token (exclusive)."""
        eos = np.flatnonzero(ids == eos_id)
        return ids[:eos[0]] if eos.size else ids

    def _trim_eos(self, ids: np.ndarray, eos_id: int | None = None) -> str:
        """Decode a completion, trimmed at the first EOS token."""
        if eos_id is None:
            eos_id = self.gen_cfg.eos_id
        return self.tok.decode(self._trim_ids(ids, eos_id))

    # ----------------------------------------------------- batch texts
    def generate_texts(self, prompts: Sequence[str], rng,
                       sampling=None) -> list[str]:
        bsz = self.model.cfg.block_size
        encs = [self._encode_prompt(p) for p in prompts]
        lp = max(e.shape[0] for e, _ in encs)
        toks = np.zeros((len(prompts), lp), np.int32)
        blocks = np.zeros((len(prompts),), np.int32)
        for i, (e, nb) in enumerate(encs):
            toks[i, :e.shape[0]] = e
            blocks[i] = nb
        # resolve once; generate_ids treats the normalised per-row list
        # as-is, so the params seen here and there cannot drift
        plist, _ = self._resolve_sampling(len(prompts), sampling, blocks)
        gen = self.generate_ids(toks, blocks, rng, sampling=plist)
        outs = []
        for i in range(len(prompts)):
            start = int(blocks[i]) * bsz
            end = start + int(gen["gen_blocks"][i]) * bsz
            outs.append(self._trim_eos(
                np.asarray(gen["tokens"][i, start:end]),
                eos_id=plist[i].eos_id))
        return outs

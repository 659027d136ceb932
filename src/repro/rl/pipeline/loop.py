"""Bounded-staleness DiPO consumer — the async pipeline's train loop.

``AsyncDiPOTrainer`` owns the same objects as the synchronous
``DiPOTrainer`` (params, optimizer state, the fused donating step from
``rl.trainer.make_dipo_step`` — literally the same jaxpr) but consumes
rollout groups from a :class:`~repro.rl.pipeline.replay.ReplayQueue`
fed by a :class:`~repro.rl.pipeline.producer.RolloutProducer` instead
of generating them inline.  Per update:

1. **fill** — open the bounded-staleness admission gate (submit up to
   K batches ahead) and pump the pool until P groups are ready.  This
   is where the overlap lives: while update ``b``'s stragglers decode,
   batches ``b+1..b+K`` already occupy the freed slots, so the pool
   never pays the synchronous tail-drain idle.
2. **train** — pop P groups (FIFO, re-sorted to prompt order),
   assemble the flat ``RolloutBatch`` and dispatch the fused step.
   With ``staleness_k > 0`` every row rides in with an ``old_logp``
   entry plus a per-row ``fresh`` flag: sealed groups carry their
   stored behaviour log-probs (Eq. 6 importance ratio), fresh groups
   — rolled out under the *current* params — are marked and the step
   substitutes ``stop_gradient(logp)`` in-trace (exactly Eq. 7, no
   behaviour forward ever paid for them).  One executable covers both,
   so mixed fresh/sealed, mixed-version batches never retrace
   (``step_traces == 1``).  At ``K = 0`` old_logp/fresh are None
   (pure Eq. 7, exactly the sync path).  Before dispatch the queue
   backlog is *sealed* (``producer.seal_queued``): any group about to
   cross this version boundary gets its behaviour log-probs computed
   now, while its harvest-window params are still live — the backlog
   is empty at steady state, so this forward almost never runs.
3. **update** — land ``ModelServer.update_weights(..., sync=False)``
   immediately after dispatch.  The step donated the old param buffers
   (which the server shares), so *nothing may tick the pool or read
   server params between dispatch and this push* — the loop is
   single-threaded and does neither (sealing happened pre-dispatch);
   ``params_at`` raises loudly if a consumer ever caches across the
   swap.  In-flight requests pick the new weights up at their next
   block boundary (drain-free push; the per-block version record on
   each ``Completion`` witnesses it).

Metric pulls are deferred to the end of ``run`` — the per-update hot
path never calls ``block_until_ready``, letting host-side fill work
overlap the device step (the sync trainer syncs every step for honest
phase timing; here the overlap *is* the product).

``staleness_k = 0`` reproduces ``DiPOTrainer.run`` parameter updates
bitwise (tests/test_async_rl.py pins it over multiple steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decoding
from repro.core.masks import packed_layout
from repro.core.trajectory import trajectory_logprobs
from repro.kernels.ops import layout_tile_stats
from repro.obs.metrics import MetricsRegistry
from repro.optim import adamw
from repro.rl.pipeline.producer import RolloutProducer
from repro.rl.pipeline.replay import ReplayQueue
from repro.rl.trainer import DiPOConfig, make_dipo_step
from repro.serving.engine import RolloutEngine


class AsyncDiPOTrainer:
    def __init__(self, model, engine: RolloutEngine,
                 opt_cfg: adamw.AdamWConfig, rl_cfg: DiPOConfig, params,
                 *, staleness_k: int = 1, policy: str = "importance",
                 queue_capacity: int | None = None):
        self.model = model
        self.engine = engine
        self.rl_cfg = rl_cfg
        self.opt_cfg = opt_cfg
        self.params = params
        self.opt_state = adamw.init_state(opt_cfg, params)
        self.ref_params = jax.tree.map(jnp.copy, params) \
            if rl_cfg.beta else None
        self.staleness_k = staleness_k
        self.policy = policy
        # capacity rarely binds — the admission gate (K batches ahead)
        # is the real backpressure; an explicit capacity adds a hard
        # memory bound on top for long-running deployments
        self.queue_capacity = queue_capacity or 4096
        self.timings: list[dict] = []
        self.tracer = engine.tracer
        # one shared namespace for the whole pipeline: queue gauges /
        # staleness histogram (registered by ReplayQueue) + the
        # consumer's own instruments
        self.metrics = MetricsRegistry("dirl_pipeline")
        self._updates = self.metrics.counter(
            "updates", "DiPO updates landed on the server")
        self._step_traces = self.metrics.gauge(
            "step_traces", "compilations of the fused DiPO step")
        self._batches_ahead = self.metrics.gauge(
            "batches_ahead", "submitted-but-unconsumed prompt batches")
        # tile-map sparsity of the consumed batch's packed-layout
        # forward (incl. the sealing forward) — set *before* the step
        # dispatch so the gauge never syncs the overlapped device work
        self._tile_gauges = {
            f: self.metrics.gauge(
                f"attn_tile_{f}",
                f"attention tile-map {f.replace('_', ' ')} this update")
            for f in ("visit_fraction", "partial_fraction",
                      "full_fraction")}
        self._stats_layout = (
            rl_cfg.logprob_scheme == "packed"
            or (rl_cfg.logprob_scheme == "auto"
                and not model.cfg.ssm_kind))
        s_max = engine.gen_cfg.s_max
        # the sync trainer's fused step, verbatim — same jaxpr, same
        # donation contract; old_logp switches Eq. 7 <-> Eq. 6
        self._step = make_dipo_step(model, opt_cfg, rl_cfg, s_max)
        self._ref_logp = jax.jit(functools.partial(
            trajectory_logprobs, model, s_max=s_max,
            scheme=rl_cfg.logprob_scheme))
        self.queue: ReplayQueue | None = None
        self.producer: RolloutProducer | None = None

    # ------------------------------------------------------------------
    def _fill(self, producer: RolloutProducer, queue: ReplayQueue,
              n_groups: int, max_batches: int) -> int:
        """Pump the pipeline until ``n_groups`` groups are ready.

        Submission happens opportunistically whenever the staleness
        gate opens, so the pool backfills freed slots with future
        batches while the current one finishes.  Returns the server
        version the ready check was made at.
        """
        while True:
            version = getattr(self.engine.store, "version", 0)
            while producer.next_batch < max_batches and \
                    producer.can_submit(version):
                producer.submit_next()
            self._batches_ahead.set(
                producer.next_batch - self._updates.value)
            if queue.n_ready(version) >= n_groups:
                return version
            if producer.pump() == 0:
                raise RuntimeError(
                    f"async pipeline stalled: {queue.n_ready(version)}/"
                    f"{n_groups} groups ready, nothing in flight and "
                    f"the admission gate is closed (batch "
                    f"{producer.next_batch}, version {version}) — "
                    "discard-policy evictions may have outrun the "
                    "prompt budget")

    def run(self, prompt_batches, steps: int, rng, *, log_every: int = 1,
            verbose: bool = True) -> list[dict]:
        cfg = self.rl_cfg
        G = cfg.group_size
        bsz = self.model.cfg.block_size
        queue = ReplayQueue(self.queue_capacity, self.staleness_k,
                            self.policy, registry=self.metrics)
        # the producer consumes the master key exactly like the sync
        # run loop (one split per prompt batch) — the substrate of the
        # K = 0 bitwise-equivalence contract
        producer = RolloutProducer(self.engine, queue, cfg,
                                   prompt_batches, rng)
        self.queue, self.producer = queue, producer
        raw: list[dict] = []
        P = producer.submit_next()        # first batch defines P
        for i in range(steps):
            with self.tracer.span("dipo.fill", cat="consumer",
                                  track="consumer", update=i) as sp_fill:
                version = self._fill(producer, queue, P, steps)

            with self.tracer.span("dipo.train", cat="consumer",
                                  track="consumer", update=i) as sp_train:
                groups = queue.pop_batch(P, version)
                # FIFO pop order is completion order; restore prompt
                # order so row layout matches the sync trainer's
                groups.sort(key=lambda g: g.prompt_id)
                gen = {k: jnp.asarray(
                    np.concatenate([g.gen[k] for g in groups]))
                    for k in groups[0].gen}
                rewards = np.concatenate([g.rewards for g in groups])
                gid = np.repeat(np.arange(P, dtype=np.int32), G)
                roll = decoding.rollout_to_batch(
                    gen, jnp.asarray(rewards), jnp.asarray(gid), bsz)
                if self._stats_layout:
                    with self.tracer.span("dipo.tile_stats",
                                          cat="consumer", track="consumer"):
                        _, meta, _, _ = packed_layout(
                            roll.tokens, roll.steps, roll.valid,
                            block_size=bsz,
                            mask_token=self.model.cfg.resolved_mask_token,
                            s_max=self.engine.gen_cfg.s_max)
                        stats = layout_tile_stats(meta)
                        for f, g in self._tile_gauges.items():
                            g.set(stats[f])
                old_logp = fresh = None
                if self.staleness_k > 0:
                    # one executable for any fresh/sealed mix: sealed
                    # rows carry stored behaviour, fresh rows (still
                    # on-policy, old_logp never materialised) are
                    # flagged and the step substitutes
                    # stop_gradient(logp) in-trace — Eq. 7 for free
                    L = int(gen["tokens"].shape[1])
                    old_logp = jnp.asarray(np.concatenate(
                        [np.zeros((g.group_size, L), np.float32)
                         if g.old_logp is None else g.old_logp
                         for g in groups]))
                    fresh = jnp.asarray(np.concatenate(
                        [np.full((g.group_size,), g.old_logp is None)
                         for g in groups]))
                    # seal the backlog BEFORE dispatch: the step below
                    # donates the very buffers the queued groups'
                    # harvest-window behaviour must be evaluated under
                    producer.seal_queued()
                ref_logp = None
                if self.ref_params is not None:
                    ref_logp = jax.lax.stop_gradient(
                        self._ref_logp(self.ref_params, roll))
                with self.tracer.span("dipo.dispatch", cat="consumer",
                                      track="consumer"):
                    self.params, self.opt_state, metrics = self._step(
                        self.params, self.opt_state, roll, old_logp,
                        fresh, ref_logp, P)
                # NO block_until_ready here: metric pulls are deferred
                # to the end of run, so the next fill's host work
                # overlaps this step's device compute

            # donation window: the step above donated the buffers the
            # server still references — land the push before anything
            # can tick the pool or read server params
            with self.tracer.span("dipo.update", cat="consumer",
                                  track="consumer", update=i) as sp_upd:
                new_version = self.engine.store.update_weights(
                    self.params, sync=False)

            self._updates.inc()
            self._step_traces.set(self._step.n_traces)
            stale = [g.staleness(version) for g in groups]
            timing = {"fill_s": sp_fill.dur, "train_s": sp_train.dur,
                      "update_s": sp_upd.dur}
            self.timings.append(timing)
            raw.append({"metrics": metrics, "rewards": rewards,
                        "stale": stale, "depth": queue.depth,
                        "version": new_version, "timing": timing})
            if verbose and (i % log_every == 0 or i == steps - 1):
                print(f"[adipo {i:3d}] v{new_version} "
                      f"stale={max(stale)} depth={queue.depth} "
                      f"inflight={producer.inflight} "
                      f"(fill {timing['fill_s']:.2f}s "
                      f"train {timing['train_s']:.2f}s)")

        # deferred metric pull: one sync at the end instead of one per
        # update (float() blocks on each device value)
        history = []
        for r in raw:
            with self.tracer.span("dipo.metric_pull", cat="consumer",
                                  track="consumer"):
                m = {k: float(v) for k, v in r["metrics"].items()}
            m.update(r["timing"])
            m["reward_mean"] = float(np.mean(r["rewards"]))
            m["acc"] = float(np.mean(r["rewards"] >= 1.0))
            m["staleness_max"] = int(max(r["stale"]))
            m["staleness_mean"] = float(np.mean(r["stale"]))
            m["queue_depth"] = int(r["depth"])
            m["param_version"] = int(r["version"])
            m["step_traces"] = self._step.n_traces
            history.append(m)
        return history

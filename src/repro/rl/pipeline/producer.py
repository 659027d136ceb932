"""Async rollout producer — keeps the slot pool full across updates.

Drives the engine's ``submit`` / ``stream_completions`` surface: prompt
batches are expanded to G adjacent group members (one prefill + one KV
copy per unique prompt under ``cache="paged"`` + ``prefix_cache``, same
as ``generate_group_ids``) and submitted into the *live* pool, then the
pool is pumped one completion at a time.  Because ``stream_completions``
re-reads ``ModelServer.params`` every tick, weight pushes land at block
boundaries with the pool still full — in-flight requests finish their
current block on the old weights and pick the new ones up at the next
advance.  Finished groups are scored (``math_rewards``), tagged with
the harvest-time param version and pushed into the ``ReplayQueue``.

Bounded staleness is enforced at *admission*: prompt batch ``b`` may be
submitted only once ``server.version - base_version + staleness_k >=
b`` — the consumer lands exactly one update per batch, so nothing a
newly admitted rollout produces can exceed the window.  ``K = 0``
degenerates to fully serial produce→consume, which reproduces the
synchronous ``DiPOTrainer`` *bitwise*: the rng layout below is
identical to ``train_step``'s (master-key split per batch, one extra
split, then per-sequence keys), each row's tokens depend only on its
own prompt + key + params (per-row rng independence), and every batch
then rolls out under exactly the weights the sync loop would have used.

For ``K >= 1`` the behaviour policy's trajectory log-probs (π_old of
the importance-corrected update) are stored *lazily*: a group consumed
within its harvest window has ratio identically 1 (behaviour == current
policy) and needs no stored values at all — the consumer's ``fresh``
mask realises Eq. 7 for it inside the fused step.  Only groups still
queued when the consumer is about to land a weight push get *sealed*
(``seal_queued``): one jitted ``trajectory_logprobs`` forward per such
group, under the harvest-window weights while they are still live.  At
steady state the backlog at a boundary is empty or tiny, so the
behaviour forward — a real per-update cost when computed eagerly at
harvest — almost never runs.  Within-flight drift (a request finishing
on newer weights than it started on) is recorded exactly via the
``Completion`` per-block version vector (``version_min`` /
``version_max`` on the group) but the sealed behaviour is evaluated
once under the harvest version — the standard one-policy-per-sample
approximation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import decoding
from repro.core.trajectory import trajectory_logprobs
from repro.rl.pipeline.replay import ReplayQueue, RolloutGroup
from repro.rl.rewards import math_rewards
from repro.serving.engine import RolloutEngine


class RolloutProducer:
    """Streams DiPO rollout groups into a replay queue.

    Single-threaded cooperative design: the consumer loop calls
    ``submit_next()`` when the admission gate opens and ``pump()`` to
    advance the pool — there is no background thread, so the donation
    invariant (never tick the pool between the train step's dispatch
    and the ``update_weights`` push) is structural, not locked.
    """

    def __init__(self, engine: RolloutEngine, queue: ReplayQueue,
                 rl_cfg, prompt_batches, rng, *,
                 base_version: int | None = None):
        self.engine = engine
        self.queue = queue
        self.rl_cfg = rl_cfg
        self._batches = prompt_batches
        self._rng = rng
        self.base_version = base_version if base_version is not None \
            else getattr(engine.store, "version", 0)
        self.staleness_k = queue.staleness_k
        self.next_batch = 0                    # next batch index to submit
        self._inflight: dict[int, tuple[int, int]] = {}  # uid -> (pid, g)
        self._partial: dict[int, dict] = {}    # pid -> group assembly
        self._n_prompts = 0                    # global prompt_id counter
        self._stream = None
        self.tracer = engine.tracer
        # behaviour log-probs (π_old) for importance-corrected
        # consumption — run only by seal_queued, i.e. only for groups
        # that actually cross a version boundary while queued.  Never
        # runs at K = 0 (fully serial: the queue is empty at every
        # boundary), keeping K = 0 bitwise equal to the sync trainer
        # AND free of the extra compile.
        self._behavior_logp = jax.jit(functools.partial(
            trajectory_logprobs, engine.model,
            s_max=engine.gen_cfg.s_max, scheme=rl_cfg.logprob_scheme))

    # ----------------------------------------------------------- state
    @property
    def inflight(self) -> int:
        """Requests currently owned by the pool (submitted, unharvested)."""
        return len(self._inflight)

    def can_submit(self, version: int) -> bool:
        """Bounded-staleness admission gate for the *next* prompt batch.

        Batch ``b`` is consumed by update ``b`` (FIFO, one update per
        batch), i.e. at version ``base + b`` — so admitting while
        ``b <= (version - base) + K`` caps consumption staleness at K.
        Never deadlocks: the batch the consumer needs next is
        ``b = version - base``, which always satisfies the gate.
        Queue capacity backpressures on top.
        """
        return (not self.queue.full) and \
            self.next_batch <= (version - self.base_version) + \
            self.staleness_k

    # ------------------------------------------------------------- ops
    def submit_next(self) -> int:
        """Pull the next prompt batch and submit its P*G group rollouts
        into the live pool (group members adjacent).  Returns P."""
        cfg = self.rl_cfg
        self._rng, k = jax.random.split(self._rng)
        batch = next(self._batches)
        P = batch.prompt_tokens.shape[0]
        G = cfg.group_size
        # rng layout — byte-identical to DiPOTrainer.train_step: the
        # run loop's split handed us k; train_step splits once more and
        # fans the second key out per sequence
        _, kr = jax.random.split(k)
        keys = decoding._per_seq_keys(kr, P * G)
        toks = np.repeat(np.asarray(batch.prompt_tokens), G, axis=0)
        blocks = np.repeat(np.asarray(batch.prompt_blocks), G, axis=0)
        sampling = None
        if cfg.group_taus:
            sampling = [self.engine.gen_cfg.sampling(
                tau=cfg.group_taus[p % len(cfg.group_taus)])
                for p in range(P) for _ in range(G)]
        plist, _ = self.engine._resolve_sampling(P * G, sampling, blocks)
        sched = self.engine.scheduler
        with self.tracer.span("producer.submit_batch", cat="producer",
                              track="producer", batch=self.next_batch,
                              prompts=P):
            for p in range(P):
                pid = self._n_prompts + p
                self._partial[pid] = {"comps": [None] * G, "n": 0,
                                      "answer": int(batch.answers[p]),
                                      "batch": self.next_batch}
                for g in range(G):
                    i = p * G + g
                    uid = sched.submit(toks[i], int(blocks[i]), keys[i],
                                       params=plist[i])
                    self._inflight[uid] = (pid, g)
        self._n_prompts += P
        self.next_batch += 1
        return P

    def pump(self) -> int:
        """Advance the pool until one completion is harvested; finalize
        its group if that completion was the last member.  Returns the
        number of completions harvested (0 = nothing in flight)."""
        if not self._inflight:
            return 0
        if self._stream is None:
            self._stream = self.engine.stream_completions()
        try:
            comp = next(self._stream)
        except StopIteration:
            self._stream = None
            return 0
        pid, g = self._inflight.pop(comp.uid)
        slot = self._partial[pid]
        slot["comps"][g] = comp
        slot["n"] += 1
        if slot["n"] == len(slot["comps"]):
            self._finalize(pid)
        return 1

    def _finalize(self, pid: int) -> None:
        """Assemble a finished group, score it, tag it, queue it."""
        slot = self._partial.pop(pid)
        comps = slot["comps"]
        G = len(comps)
        bsz = self.engine.model.cfg.block_size
        gen = {
            "tokens": np.stack([c.tokens for c in comps]),
            "steps": np.stack([c.steps for c in comps]),
            "gen_blocks": np.array([c.gen_blocks for c in comps],
                                   np.int32),
            "prompt_blocks": np.array([c.prompt_blocks for c in comps],
                                      np.int32),
            # drain-path parity: a zero-budget row is never flagged done
            "done": np.array([c.gen_blocks > 0 for c in comps], bool),
            "denoise_steps": np.array([c.denoise_steps for c in comps],
                                      np.int32),
        }
        answers = np.full((G,), slot["answer"], np.int64)
        versions = [int(v) for c in comps
                    for v in (c.param_version, *c.block_versions)]
        with self.tracer.span("producer.finalize_group", cat="producer",
                              track="producer", prompt_id=pid,
                              batch=slot["batch"]):
            rewards = math_rewards(self.engine.tok, gen, answers, bsz)
            version = getattr(self.engine.store, "version", 0)
            # old_logp stays None until (unless) the group crosses a
            # version boundary in the queue — see seal_queued
            self.queue.push(RolloutGroup(
                prompt_id=pid, gen=gen, rewards=rewards,
                version=version, version_min=min(versions),
                version_max=max(versions)))

    def seal_queued(self) -> int:
        """Seal behaviour log-probs onto queued groups about to cross a
        version boundary.

        The consumer calls this immediately before landing
        ``update_weights`` — the only moment a queued group's
        harvest-window params are still live but about to be donated.
        Groups consumed within their window never pay this forward
        (ratio ≡ 1; the fused step's ``fresh`` mask applies Eq. 7 to
        them), so at steady state — empty backlog at every boundary —
        sealing costs nothing.  Returns the number of groups sealed.
        """
        todo = [g for g in self.queue.groups() if g.old_logp is None]
        if not todo:
            return 0
        store = self.engine.store
        if hasattr(store, "params_versioned"):
            version, params = store.params_versioned()
        else:
            version, params = getattr(store, "version", 0), store.params
        bsz = self.engine.model.cfg.block_size
        with self.tracer.span("producer.seal_backlog", cat="producer",
                              track="producer", groups=len(todo)):
            for g in todo:
                if g.version != version:
                    raise RuntimeError(
                        f"group {g.prompt_id} harvested at version "
                        f"{g.version} was never sealed before version "
                        f"{version} — its behaviour params are gone")
                roll = decoding.rollout_to_batch(
                    {k: jnp.asarray(v) for k, v in g.gen.items()},
                    jnp.zeros((g.group_size,), jnp.float32),
                    jnp.zeros((g.group_size,), jnp.int32), bsz)
                g.old_logp = np.asarray(jax.lax.stop_gradient(
                    self._behavior_logp(params, roll)))
        self.queue.registry.get("groups_sealed").inc(len(todo))
        return len(todo)

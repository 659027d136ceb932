"""DiPO RL trainer — the paper's Fig. 5b online loop.

Per step: pull fresh prompts -> rollout G trajectories per prompt through
the RolloutEngine (reading the live server weights) -> verifiable rewards
-> trajectory-exact log-probs -> DiPO update -> push params in place into
the server.  The per-phase wall-clock breakdown is recorded, which is what
benchmarks/fig6 compares against the offline-checkpoint baseline.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.guards import TraceGuard
from repro.core import decoding
from repro.core.dipo import dipo_loss
from repro.core.masks import packed_layout
from repro.core.trajectory import trajectory_logprobs
from repro.kernels.ops import layout_tile_stats
from repro.obs.metrics import MetricsRegistry
from repro.rl.rewards import math_rewards
from repro.optim import adamw
from repro.serving.engine import RolloutEngine


@dataclasses.dataclass
class DiPOConfig:
    group_size: int = 8          # G rollouts per prompt
    eps: float = 0.2
    beta: float = 0.0            # KL-to-reference coefficient
    aggregate: str = "token"     # Eq.8 (DAPO) | "seq" (Eq.6)
    normalize_std: bool = False
    logprob_scheme: str = "auto"  # packed | replay | fused_approx
    # optional per-group denoise thresholds (DiFFPO's "reason fast and
    # furious" lever): prompt group p rolls out with tau
    # ``group_taus[p % len(group_taus)]`` instead of the engine default
    # — request-granular SamplingParams, so the mixed-τ batch shares
    # one pool with zero retraces and prompt pages still dedupe per
    # group (params never touch prompt KV).  None = engine default τ.
    group_taus: tuple[float, ...] | None = None


def make_dipo_step(model, opt_cfg: adamw.AdamWConfig, rl_cfg: DiPOConfig,
                   s_max: int) -> TraceGuard:
    """Build the fused, donating DiPO update step.

    One definition serves both the synchronous ``DiPOTrainer`` and the
    async ``rl.pipeline`` consumer, so the two paths compile the *same*
    jaxpr — the substrate of the pipeline's K=0 bitwise-equivalence
    contract.  ``old_logp`` is the behaviour policy's per-token
    log-probs: ``None`` selects the online Eq. 7 stop-gradient variant
    (fresh on-policy rollouts); an array selects the explicit Eq. 6
    importance ratio ``exp(logp - old_logp)`` — the off-policy
    correction bounded-staleness consumption relies on.  ``fresh`` is a
    per-row bool mask accompanying an ``old_logp`` array: True rows
    were rolled out under the *current* params, so their behaviour IS
    the current policy and the stored value is replaced with
    ``stop_gradient(logp)`` — exactly Eq. 7 for that row, at zero
    extra forwards.  A mixed batch (some rows sealed with stored
    behaviour, some fresh) therefore needs only ONE executable, and the
    common all-fresh case never pays a behaviour forward at all.
    Versions never enter the traced computation (staleness is host-side
    bookkeeping; ``old_logp``/``fresh`` are plain per-row data), so
    mixed-version batches reuse one compiled executable — ``n_traces``
    witnesses it.
    """
    def step_fn(params, opt_state, roll, old_logp, fresh, ref_logp,
                n_groups):
        def loss_fn(p):
            logp = trajectory_logprobs(
                model, p, roll, s_max=s_max,
                scheme=rl_cfg.logprob_scheme)
            ol = old_logp
            if ol is not None and fresh is not None:
                ol = jnp.where(fresh[:, None],
                               jax.lax.stop_gradient(logp), ol)
            return dipo_loss(
                logp, roll, old_logp=ol, ref_logp=ref_logp,
                n_groups=n_groups, eps=rl_cfg.eps, beta=rl_cfg.beta,
                aggregate=rl_cfg.aggregate,
                normalize_std=rl_cfg.normalize_std)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params, opt_state, om = adamw.apply_updates(
            opt_cfg, params, grads, opt_state)
        return params, opt_state, {**metrics, **om, "loss": loss}

    # TraceGuard preserves step_fn's signature (functools.wraps),
    # so static_argnames still resolves n_groups when it is passed
    # positionally; n_traces witnesses one compile per n_groups
    return TraceGuard(step_fn, donate_argnums=(0, 1),
                      static_argnames=("n_groups",), name="dipo_step")


class DiPOTrainer:
    def __init__(self, model, engine: RolloutEngine,
                 opt_cfg: adamw.AdamWConfig, rl_cfg: DiPOConfig, params):
        self.model = model
        self.engine = engine
        self.rl_cfg = rl_cfg
        self.opt_cfg = opt_cfg
        self.params = params
        self.opt_state = adamw.init_state(opt_cfg, params)
        # real copy: the train step donates its params buffers, and the
        # reference policy must survive every update
        self.ref_params = jax.tree.map(jnp.copy, params) \
            if rl_cfg.beta else None
        self.timings: list[dict] = []
        # phase spans land on the engine's tracer (track "trainer", so
        # one export shows rollout / reward / train / update intervals
        # interleaved with the serving ticks they drive); aggregates go
        # to the dirl_trainer metrics namespace
        self.tracer = engine.tracer
        self.metrics = MetricsRegistry("dirl_trainer")
        self._phase_seconds = self.metrics.histogram(
            "phase_seconds", "per-phase wall time per train step",
            labelnames=("phase",))
        self._steps_total = self.metrics.counter(
            "steps", "train steps executed")
        self._step_traces = self.metrics.gauge(
            "step_traces", "compilations of the fused DiPO step")
        # tile-map sparsity of the packed-layout logprob forward — what
        # the pallas training kernels visit/skip on this step's batch
        self._tile_gauges = {
            f: self.metrics.gauge(
                f"attn_tile_{f}",
                f"attention tile-map {f.replace('_', ' ')} this step")
            for f in ("visit_fraction", "partial_fraction",
                      "full_fraction")}
        # packed is the layout the attention backbones actually run;
        # replay/fused_approx never build the packed mask
        self._stats_layout = (
            rl_cfg.logprob_scheme == "packed"
            or (rl_cfg.logprob_scheme == "auto"
                and not model.cfg.ssm_kind))
        s_max = engine.gen_cfg.s_max
        # the same fused step the async pipeline consumer runs (always
        # called with old_logp=None here: fresh rollouts every step are
        # exactly on-policy — Eq. 7)
        self._step = make_dipo_step(model, opt_cfg, rl_cfg, s_max)
        self._ref_logp = jax.jit(functools.partial(
            trajectory_logprobs, model, s_max=s_max,
            scheme=rl_cfg.logprob_scheme))

    def train_step(self, prompt_batch, rng) -> dict:
        with self.tracer.span("dipo.step", cat="trainer", track="trainer"):
            return self._train_step(prompt_batch, rng)

    def _train_step(self, prompt_batch, rng) -> dict:
        cfg = self.rl_cfg
        bsz = self.model.cfg.block_size
        P = prompt_batch.prompt_tokens.shape[0]
        G = cfg.group_size
        span = self.tracer.span

        # ---- rollout (G per prompt) ----------------------------------
        # the group entry keeps each group's members adjacent, so a
        # paged + prefix-cache engine prefills and stores every unique
        # prompt once instead of G times (rng layout identical to the
        # old np.repeat + generate_ids path — rollouts are unchanged).
        # obs spans replace the old perf_counter pairs: same intervals,
        # but they also land on the shared tracer (track "trainer") and
        # aggregate into the dirl_trainer phase histogram.
        with span("dipo.rollout", cat="trainer", track="trainer") as sp_roll:
            answers = np.repeat(prompt_batch.answers, G, axis=0)
            rng, kr = jax.random.split(rng)
            sampling = None
            if cfg.group_taus:
                # per-group τ: one SamplingParams per prompt, expanded
                # to the group's G adjacent members
                sampling = [self.engine.gen_cfg.sampling(
                    tau=cfg.group_taus[p % len(cfg.group_taus)])
                    for p in range(P)]
            gen = self.engine.generate_group_ids(
                prompt_batch.prompt_tokens, prompt_batch.prompt_blocks,
                kr, G, sampling=sampling)

        # ---- rewards ---------------------------------------------------
        with span("dipo.reward", cat="trainer", track="trainer") as sp_rew:
            rewards = math_rewards(self.engine.tok, gen, answers, bsz)
            group = np.repeat(np.arange(P, dtype=np.int32), G)
            roll = decoding.rollout_to_batch(
                gen, jnp.asarray(rewards), jnp.asarray(group), bsz)

        # ---- logits + policy update -----------------------------------
        with span("dipo.train", cat="trainer", track="trainer") as sp_train:
            ref_logp = None
            if self.ref_params is not None:
                ref_logp = jax.lax.stop_gradient(
                    self._ref_logp(self.ref_params, roll))
            with span("dipo.dispatch", cat="trainer", track="trainer"):
                self.params, self.opt_state, metrics = self._step(
                    self.params, self.opt_state, roll, None, None,
                    ref_logp, P)
            with span("dipo.sync", cat="trainer", track="trainer"):
                # deliberate: t_train must measure the real step, and
                # metrics are pulled to host right below anyway
                jax.block_until_ready(metrics["loss"])  # dirlint: ok(hot-sync)

        # ---- in-place server update ------------------------------------
        with span("dipo.update", cat="trainer", track="trainer") as sp_upd:
            self.engine.store.update_weights(self.params)
            # offline stores pay the reload on the *next* rollout;
            # in-place stores are done here.

        timing = {"rollout_s": sp_roll.dur, "reward_s": sp_rew.dur,
                  "train_s": sp_train.dur, "update_s": sp_upd.dur}
        for phase in ("rollout", "reward", "train", "update"):
            self._phase_seconds.labels(phase=phase).observe(
                timing[f"{phase}_s"])
        self._steps_total.inc()
        self._step_traces.set(self._step.n_traces)
        if self._stats_layout:
            # host-side rebuild of the packed mask metadata (cheap: meta
            # only, no forward) -> per-step sparsity gauges
            with span("dipo.tile_stats", cat="trainer", track="trainer"):
                _, meta, _, _ = packed_layout(
                    roll.tokens, roll.steps, roll.valid, block_size=bsz,
                    mask_token=self.model.cfg.resolved_mask_token,
                    s_max=self.engine.gen_cfg.s_max)
                stats = layout_tile_stats(meta)
                for f, g in self._tile_gauges.items():
                    g.set(stats[f])
        if self.engine.last_call.get("batching") == "continuous":
            timing["rollout_util"] = self.engine.last_call["utilization"]
            timing["prefix_hit_rate"] = \
                self.engine.last_call["prefix_hit_rate"]
        self.timings.append(timing)
        with span("dipo.metric_pull", cat="trainer", track="trainer"):
            out = {k: float(v) for k, v in metrics.items()}
        out.update(timing)
        out["step_traces"] = self._step.n_traces
        out["reward_mean"] = float(np.mean(rewards))
        out["acc"] = float(np.mean(rewards >= 1.0))
        return out

    def run(self, prompt_batches, steps: int, rng, *, log_every: int = 1,
            verbose: bool = True) -> list[dict]:
        history = []
        for i in range(steps):
            rng, k = jax.random.split(rng)
            m = self.train_step(next(prompt_batches), k)
            history.append(m)
            if verbose and (i % log_every == 0 or i == steps - 1):
                print(f"[dipo {i:3d}] loss={m['loss']:.4f} "
                      f"acc={m['acc']:.3f} reward={m['reward_mean']:.3f} "
                      f"clip={m['clip_frac']:.3f} "
                      f"(roll {m['rollout_s']:.2f}s train {m['train_s']:.2f}s "
                      f"update {m['update_s']:.3f}s)")
        return history

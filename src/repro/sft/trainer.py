"""SFT trainer: blockwise-diffusion NELBO with the fused dup-layout pass."""

from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp

from repro.analysis.guards import TraceGuard
from repro.core.block_diffusion import sft_loss
from repro.core.masks import dirl_layout, sample_sft_noise
from repro.kernels.ops import layout_tile_counts
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.optim import adamw


# the step's raw tile counts: pulled with its metrics, turned into the
# attn_tile_* fractions on the host, never returned themselves
_TILE_COUNTS = ("tiles_total", "tiles_visited", "tiles_full")


def _step_tile_counts(cfg, batch: dict, rng) -> dict:
    """Tile counts of the step's own layout (same rng, so the same
    sampled noise as ``sft_loss``), computed inside the jitted step."""
    steps, _, _ = sample_sft_noise(
        rng, batch["tokens"], batch["prompt_mask"], batch["valid"],
        block_size=cfg.block_size)
    _, meta, _ = dirl_layout(
        batch["tokens"], steps, batch["valid"],
        block_size=cfg.block_size, mask_token=cfg.resolved_mask_token,
        noised=True)
    return layout_tile_counts(meta)


@dataclasses.dataclass
class SFTConfig:
    steps: int = 100
    log_every: int = 10
    layout: str = "dirl"   # dirl | tracer (Fig 4a baseline)


class SFTTrainer:
    """Supervised trainer over the fused NELBO step.

    Observability: each ``train_step`` is an ``sft.step`` span (track
    ``"trainer"``; shared with the serving stack when a caller passes an
    engine's tracer via ``tracer=``) over its five phases:
    ``sft.batch_put`` (inputs to the device), ``sft.dispatch``,
    ``sft.sync`` (the deliberate post-step sync), ``sft.metric_pull``
    and ``sft.tile_stats``.  Step wall times — start to the end of the
    sync, the real device step — aggregate into the ``dirl_trainer``
    metrics namespace.  The dirl layout's attention tile map is counted
    inside the jitted step and pulled with its metrics in one transfer;
    nothing of the layout is replayed on the host.
    """

    def __init__(self, model, opt_cfg: adamw.AdamWConfig, params, *,
                 layout: str = "dirl", tracer: Tracer | None = None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.params = params
        self.opt_state = adamw.init_state(opt_cfg, params)
        self.layout = layout
        self.step_seconds: list[float] = []
        self.tracer = tracer if tracer is not None else Tracer(
            enabled=False)
        self.metrics = MetricsRegistry("dirl_trainer")
        self._phase_seconds = self.metrics.histogram(
            "phase_seconds", "per-phase wall time per train step",
            labelnames=("phase",))
        self._steps_total = self.metrics.counter(
            "steps", "train steps executed")
        self._step_traces = self.metrics.gauge(
            "step_traces", "compilations of the fused SFT step")
        # tile-map sparsity of this step's attention mask — the exact
        # fraction the pallas kernels visit/skip (layer-window effects
        # excluded, so these are per-step upper bounds)
        self._tile_gauges = {
            f: self.metrics.gauge(
                f"attn_tile_{f}",
                f"attention tile-map {f.replace('_', ' ')} this step")
            for f in ("visit_fraction", "partial_fraction",
                      "full_fraction")}

        def step_fn(params, opt_state, batch, rng):
            def loss_fn(p):
                return sft_loss(model, p, batch, rng, layout=layout)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            params, opt_state, om = adamw.apply_updates(
                opt_cfg, params, grads, opt_state)
            metrics = {**metrics, **om, "loss": loss}
            if layout == "dirl":
                metrics.update(_step_tile_counts(model.cfg, batch, rng))
            return params, opt_state, metrics

        # zero-retrace witness: fixed batch/rng shapes keep this at 1
        self._step = TraceGuard(step_fn, donate_argnums=(0, 1),
                                name="sft_step")

    def train_step(self, batch: dict, rng) -> dict:
        span = self.tracer.span
        with span("sft.step", cat="trainer", track="trainer") as sp:
            with span("sft.batch_put", cat="trainer", track="trainer"):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with span("sft.dispatch", cat="trainer", track="trainer"):
                self.params, self.opt_state, metrics = self._step(
                    self.params, self.opt_state, batch, rng)
            with span("sft.sync", cat="trainer", track="trainer") as sync:
                # deliberate: step_seconds must measure the real step,
                # and metrics are pulled to host right below anyway
                jax.block_until_ready(metrics["loss"])  # dirlint: ok(hot-sync)
            step_s = sync.t1 - sp.t0
            self.step_seconds.append(step_s)
            self._phase_seconds.labels(phase="train").observe(step_s)
            self._steps_total.inc()
            self._step_traces.set(self._step.n_traces)
            with span("sft.metric_pull", cat="trainer", track="trainer"):
                # deliberate: one device-to-host transfer for every
                # metric, tile counts included, after the sync above
                host = jax.device_get(metrics)  # dirlint: ok(hot-sync)
                counts = {k: host.pop(k) for k in _TILE_COUNTS
                          if k in host}
                out = {k: float(v) for k, v in host.items()}
            out["step_traces"] = self._step.n_traces
            with span("sft.tile_stats", cat="trainer", track="trainer"):
                out.update(self._tile_stats(counts))
            if sp.recording:
                # B x L positions, padding included (a count of `valid`
                # would be one more device op and pull inside the step)
                sp.args["tokens"] = int(batch["tokens"].size)
        return out

    def _tile_stats(self, counts: dict) -> dict:
        """The step's pulled tile counts -> tile-map sparsity gauges
        (``tile_map_stats``'s arithmetic on the same integers)."""
        if not counts:
            return {}
        total, visited, full = (int(counts[k]) for k in _TILE_COUNTS)
        denom = max(total, 1)
        stats = {"visit_fraction": visited / denom,
                 "partial_fraction": (visited - full) / denom,
                 "full_fraction": full / denom}
        out = {}
        for f, g in self._tile_gauges.items():
            g.set(stats[f])
            out[f"attn_tile_{f}"] = stats[f]
        return out

    def run(self, batches: Iterator, steps: int, rng, *,
            log_every: int = 10, verbose: bool = True) -> list[dict]:
        history = []
        for i in range(steps):
            rng, k = jax.random.split(rng)
            m = self.train_step(next(batches).asdict(), k)
            history.append(m)
            if verbose and (i % log_every == 0 or i == steps - 1):
                print(f"[sft {i:4d}] loss={m['loss']:.4f} "
                      f"ce={m['masked_ce']:.4f} gnorm={m['grad_norm']:.3f}")
        return history

"""The SFT cell's comparison fails each fault a training step can have
on one chip, and its bfloat16 control."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.tests.smoke import rehearse

CELL = "danube3-4b.L2.sft_2k"


class _Step:
    """Stands in for the trainer's compiled step."""

    def __init__(self, guard, fn):
        self.guard, self.fn = guard, fn

    @property
    def n_traces(self):
        return self.guard.n_traces

    def __call__(self, params, opt_state, batch, rng):
        return self.fn(params, opt_state, batch, rng)


def test_a_step_that_returns_its_state_unchanged_is_caught():
    def faults(trainer):
        g = trainer._step

        def fn(p, o, b, r):
            cp = jax.tree.map(jnp.copy, (p, o))
            _, _, metrics = g(*cp, b, r)
            return p, o, metrics
        trainer._step = _Step(g, fn)
    out = rehearse(CELL, faults=faults)
    assert out["checks"]["delta_norm_gap"]["value"] > 0.99
    assert out["correct"] is False


def test_half_the_batch_left_out_is_caught():
    def faults(trainer):
        g = trainer._step

        def fn(p, o, b, r):
            half = {k: v[: v.shape[0] // 2] for k, v in b.items()}
            return g(p, o, half, r)
        trainer._step = _Step(g, fn)
    out = rehearse(CELL, faults=faults)
    assert out["correct"] is False


def test_the_bfloat16_weights_control_is_caught():
    """The control: the program's own path one precision step down,
    weights stored in bfloat16, against the float32 reference; the
    harness's own comparison refuses it."""
    out = rehearse(CELL, model={"param_dtype": "bfloat16"})
    c = out["checks"]["delta_norm_gap"]
    assert out["correct"] is False
    assert c["value"] > c["limit"], out["checks"]

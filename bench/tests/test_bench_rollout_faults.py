"""The rollout cell's comparison fails a token altered where it is
produced, a reveal schedule that commits whole blocks at once, and its
control: the float8 reference put in the program's place."""

from __future__ import annotations

import dataclasses

from bench.tests.smoke import rehearse

CELL = "sdar-8b.L4.rollout_g8"


def test_a_served_token_altered_is_caught():
    def faults(engine):
        sched = engine.scheduler
        orig = sched.step
        V = engine.model.cfg.vocab_size

        def step(params, param_version=0):
            out = orig(params, param_version)
            for c in out:
                i = c.prompt_blocks * engine.model.cfg.block_size + 1
                c.tokens = c.tokens.copy()
                c.tokens[i] = (c.tokens[i] + 1) % (V - 1)
            return out
        sched.step = step
    out = rehearse(CELL, faults=faults)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_whole_blocks_committed_at_the_first_step_are_caught():
    """The program reveals every masked position at step 0 (the dynamic
    rule's threshold at 0): each served token is still its step's best,
    so only the schedule's check can see it."""
    def faults(engine):
        sched = engine.scheduler
        orig = sched.submit

        def submit(prompt, blocks, key, params):
            return orig(prompt, blocks, key,
                        params=dataclasses.replace(params, tau=0.0))
        sched.submit = submit
    out = rehearse(CELL, faults=faults)
    c = out["checks"]
    assert out["correct"] is False
    assert c["reveal_gap"]["value"] > c["reveal_gap"]["limit"]
    assert c["logit_gap"]["value"] <= c["logit_gap"]["limit"]


def test_the_float8_control_is_not_correct():
    """The control: at each served position the reference with float8
    matmul operands puts its own best token first; on a wide vocabulary
    its near-ties break otherwise than the float32 reference's, and the
    harness's own comparison refuses it."""
    out = rehearse(CELL, control=True, model={"vocab_size": 32000})
    c = out["checks"]
    assert out["correct"] is False
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]
    assert c["checked_tokens"]["value"] >= c["checked_tokens"]["limit"]["min"]

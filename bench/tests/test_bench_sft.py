"""CPU rehearsal of the SFT cell at smoke size: the result line's
schema, and a window holding a compile comes out not correct."""

from __future__ import annotations

import jax
import pytest

from bench.harness import cli, common
from bench.tests.smoke import rehearse

CELL = "danube3-4b.L2.sft_2k"


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_sft_rehearsal_prints_the_result_line(trace):
    out = rehearse(CELL, trace=trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = common.find_cell(CELL, 1, 1.0, trace, 0.0)
    if trace:
        assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert "mfu.sft" in out["metrics"]
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(out["metrics"]) == {"sft_tok_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(out["checks"]) == set(cli.limits_for(cell)) | {
        "window_compiles"}


def test_sft_window_holding_a_compile_is_not_correct():
    def faults(trainer):
        orig = trainer.train_step
        calls = [0]

        def step(batch, rng):
            calls[0] += 1
            if calls[0] == 12:      # well inside the 2 s smoke window
                jax.clear_caches()
            return orig(batch, rng)
        trainer.train_step = step
    out = rehearse(CELL, faults=faults)
    assert out["checks"]["window_compiles"]["value"] > 0
    assert out["correct"] is False

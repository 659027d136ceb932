"""CPU rehearsal of the rollout cell at smoke size: the result line's
schema, traced and not."""

from __future__ import annotations

import pytest

from bench.harness import common
from bench.tests.smoke import rehearse

CELL = "sdar-8b.L4.rollout_g8"


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_rollout_rehearsal_prints_the_result_line(trace):
    out = rehearse(CELL, trace=trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = common.find_cell(CELL, 1, 1.0, trace, 0.0)
    if trace:
        assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert "mfu.rollout" in out["metrics"]
        assert {"busy_s", "window_s"} <= set(out["device"])
    else:
        assert set(out["metrics"]) == {"rollout_tok_s", "setup_s"}
    assert out["checks"]["logit_gap"]["value"] >= 0.0
    assert out["checks"]["reveal_gap"]["value"] >= 0.0
    c = out["checks"]["checked_tokens"]
    assert c["value"] >= c["limit"]["min"]
    assert out["checks"]["window_compiles"]["value"] == 0

"""Smoke-size rehearsal of a cell on the CPU, Pallas kernels in
interpret mode: the whole run past the harness's look for a chip."""

from __future__ import annotations

import json
import time

SMOKE_TRAFFIC = {
    "rollout": dict(group_size=4, n_slots=8, prompt_tokens=[16, 32, 48],
                    shared_prefix={"tokens": 16, "n_prefixes": 2},
                    budget_tokens={"median": 16, "sigma": 0.75, "min": 8,
                                   "max": 32, "levels": 8},
                    residual_levels=8, n_groups=20, warm_ticks=3,
                    trace_seconds=1,
                    check={"min_tokens": 64, "wait_seconds": 10}),
    "sft": dict(batch=2, seq_len=64, prompt_tokens={"min": 8, "max": 16},
                ring=2, warm={"min_steps": 2, "max_steps": 3,
                              "settle": 0.5}, trace_steps=1),
}
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_cell(name: str, *, trace: bool = False, seconds: float = 2.0,
               seed: int = 2**33 + 5):
    from bench.harness import common
    cell = common.find_cell(name, seed, seconds, trace, time.perf_counter())
    cell.config["model"].update(cell.config["smoke"])
    cell.traffic.update(SMOKE_TRAFFIC[cell.traffic["kind"]])
    return cell


def rehearse(name: str, *, trace: bool = False, faults=None,
             limits: dict | None = None, seconds: float = 2.0,
             control: bool = False, model: dict | None = None) -> dict:
    import jax
    from bench.harness import cli
    cell = smoke_cell(name, trace=trace, seconds=seconds)
    cell.config["model"].update(model or {})
    if limits is None:
        limits = cli.limits_for(cell)
        if "checked_tokens" in limits:   # the smoke mix's own least
            limits = dict(limits, checked_tokens={
                "min": cell.traffic["check"]["min_tokens"]})
    line = cli.execute(cell, jax.devices()[:1], PEAKS, faults=faults,
                       limits=limits, control=control)
    return json.loads(line)

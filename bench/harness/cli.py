"""``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: set up one cell, measure it, check it, print one line.

Progress, the set-up split and diagnostics go to standard error; the
numbers compared for ``correct`` are its last lines, each beside its
limit.  The last line of standard output is the result.  A run that
finds no TPU, or fewer chips than the cell asks for, prints no result
and exits with 3.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import types

from . import common
from .common import BENCH, log


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: float | None = None) -> int:
    t0 = t0 if t0 is not None else time.perf_counter()
    args = parse(argv)
    cell = common.find_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t0)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"no accelerator for this cell: found {len(devs)} "
            f"{devs[0].platform} device(s), the cell needs {cell.chips} "
            f"TPU chip(s)")
        return 3
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.configure()}")
    out = execute(cell, devs[:cell.chips],
                  common.load_peaks(devs[0].device_kind))
    print(out, flush=True)
    return 0


def limits_for(cell) -> dict:
    path = BENCH / "limits" / f"{cell.name}.json"
    return json.loads(path.read_text())["limits"]


def measure(cell, faults=None, control: bool = False) -> dict:
    """Set up, measure and check ``cell``; the driver's raw result.
    ``faults`` (a hook that breaks the program under test) and
    ``control`` (the reference one precision step down put in the
    program's place, where the program has no such path of its own)
    are for the tests and ``bench/calibrate.py``; the benchmark's own
    runs use neither."""
    timer = common.Timer(cell.t_start)
    compiles = common.CompileCounter()
    driver = importlib.import_module(
        f"{__package__}.{cell.traffic['kind']}")
    try:
        res = driver.run(cell, timer, compiles, faults, control)
    finally:
        compiles.close()
    log(timer.line())
    log(f"compiles: {compiles.total} in {compiles.seconds:.2f} s over the "
        f"run, {res['window_compiles']} inside the window")
    log(f"readings: {res['checks']}")
    return res


def judge(res: dict, e2e: dict, lim: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit: a number
    is at most its limit, or at least the ``min`` of a limit given as
    ``{"min": x}``."""
    checks = {}
    for name, v in res["checks"].items():
        if name in lim:
            checks[name] = {"value": v, "limit": lim[name]}
    checks["window_compiles"] = {"value": res["window_compiles"],
                                 "limit": 0}

    def holds(c):
        if isinstance(c["limit"], dict):
            return c["value"] >= c["limit"]["min"]
        return c["value"] <= c["limit"]
    correct = all(holds(c) for c in checks.values()) \
        and res["failed"] == 0 and all(
            common.finite(v) for v in e2e.values())
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    return correct, checks


def execute(cell, devs, peaks: dict, faults=None,
            limits: dict | None = None, control: bool = False) -> str:
    """Run ``cell`` on ``devs``; returns the result line."""
    res = measure(cell, faults, control)
    e2e = dict(res["e2e"])
    metrics = {}
    if not cell.trace:
        for mt in cell.end_to_end:
            metrics[mt["name"]] = {"value": e2e[mt["name"]],
                                   "unit": mt["unit"]}
    device = common.device_info(devs, cell.chips, res["peak"])
    breakdown = None
    s = res["summary"]
    if cell.trace:
        ctx = types.SimpleNamespace(
            e2e=e2e, summary=s, counters=res["counters"], peaks=peaks,
            model=cell.model, chips=cell.chips, counts=common.counts)
        for mt in cell.per_layer:
            mod = common.load_module(BENCH / "metrics" / f"{mt['name']}.py")
            v = mod.read(ctx)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        breakdown = {"device_ops": s.top_ops(10),
                     "idle_gaps": s.idle_by_span(10)}
    lim = limits if limits is not None else limits_for(cell)
    correct, checks = judge(res, e2e, lim)
    return common.result_line(correct, res["attempted"], res["failed"],
                              metrics, device, checks, breakdown)


if __name__ == "__main__":
    sys.exit(main())

"""Readings for setting a cell's limits, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 8 [--control] [--fault half_batch]

For each seed it runs the cell as ``bench/run.py`` does (a short
window) and prints one JSON line: the numbers compared, and ``correct``
as the cell's limits judge them.  ``--control`` (serving) puts the
reference with float8 matmul operands in the program's place, the
rollout cell's control; ``--param-dtype bfloat16`` runs the program's
own lower-precision path (weights stored in bfloat16), the SFT cell's
control.
``--fault half_batch`` (SFT) leaves half of every batch out of the step,
the mean taken over the rest.
Not part of the benchmark's runs.
"""

import argparse
import json
import sys
import time
from pathlib import Path


def half_batch(trainer):
    guard = trainer._step

    class Half:
        n_traces = property(lambda self: guard.n_traces)

        def __call__(self, p, o, b, r):
            return guard(p, o, {k: v[: v.shape[0] // 2]
                                for k, v in b.items()}, r)
    trainer._step = Half()


FAULTS = {"half_batch": half_batch}


def main() -> int:
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root))
    from bench.harness import cli, common
    import jax
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--param-dtype", help="run the program's own path "
                    "with weights stored in this type (its control)")
    args = ap.parse_args()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    from repro.launch import compile_cache
    compile_cache.configure()
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = common.find_cell(args.workload, seed, args.seconds, False,
                                time.perf_counter())
        if args.param_dtype:
            cell.config["model"]["param_dtype"] = args.param_dtype
        res = cli.measure(cell, faults=FAULTS.get(args.fault),
                          control=args.control)
        jax.clear_caches()   # free the loaded programs' reservations
        correct, _ = cli.judge(res, res["e2e"], cli.limits_for(cell))
        print(json.dumps({"seed": seed, "correct": correct,
                          "control": args.control, "fault": args.fault,
                          "param_dtype": args.param_dtype,
                          "e2e": res["e2e"], "readings": res["checks"],
                          "window_compiles": res["window_compiles"],
                          "peak": res["peak"]}), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one entry per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--suite NAME]``

Prints CSV blocks per benchmark.  --full widens sweeps (slower).
``--suite paged_attn`` (or any registered name, with or without the
``_bench`` suffix) runs a single suite; ``--smoke`` shrinks it to tiny
shapes and *validates the emitted JSON artifact* against the shared
schema (``common.validate_bench_json``).  Any suite that raises makes
the run exit nonzero, with or without ``--smoke``.

The roofline/dry-run artifacts (deliverables e/g) are produced separately
by ``python -m repro.launch.dryrun --all`` and summarised by
``python -m repro.launch.rooflines``; this harness reports their status.
"""

from __future__ import annotations

import argparse
import glob
import inspect
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", type=str, default=None,
                    help="run a single registered suite by exact name")
    ap.add_argument("--suite", type=str, default=None,
                    help="run a single suite by short name "
                         "(e.g. paged_attn)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; validate emitted JSON artifacts")
    args = ap.parse_args()
    quick = not args.full

    from . import (analysis_bench, async_rl_bench, fig6_breakdown,
                   fig7_sizes, fig8_tau_sweep, kernel_bench,
                   paged_attn_bench, serve_bench, table1_eval)
    from .common import validate_bench_json

    benches = {
        "analysis_bench": analysis_bench.run,
        "kernel_bench": kernel_bench.run,
        "paged_attn_bench": paged_attn_bench.run,
        "fig7_sizes": fig7_sizes.run,
        "fig6_breakdown": fig6_breakdown.run,
        "table1_eval": table1_eval.run,
        "fig8_tau_sweep": fig8_tau_sweep.run,
        "serve_bench": serve_bench.run,
        "async_rl_bench": async_rl_bench.run,
    }
    # suites that track a cross-PR trajectory artifact: suite short name
    # -> per-entry required keys, checked by --smoke after the run
    json_suites = {
        "kernel_bench": ("block_diff_attn", kernel_bench.ENTRY_KEYS),
        "paged_attn_bench": ("paged_attn", paged_attn_bench.ENTRY_KEYS),
        "async_rl_bench": ("async_rl", async_rl_bench.ENTRY_KEYS),
    }

    only = args.only
    if args.suite:
        only = args.suite if args.suite in benches \
            else f"{args.suite}_bench"
        if only not in benches:
            sys.exit(f"unknown suite {args.suite!r}; registered: "
                     f"{', '.join(sorted(benches))}")

    failed = False
    for name, fn in benches.items():
        if only and only != name:
            continue
        t0 = time.time()
        print(f"\n=== {name} ===")
        try:
            kwargs = {"quick": quick}
            if args.smoke and \
                    "smoke" in inspect.signature(fn).parameters:
                kwargs["smoke"] = True
            for row in fn(**kwargs):
                print(row)
            if args.smoke and name in json_suites:
                suite, keys = json_suites[name]
                print(f"# schema ok: {validate_bench_json(suite, keys)}")
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            failed = True
        print(f"# {name} took {time.time() - t0:.1f}s", flush=True)

    if only is None:
        # dry-run / roofline status summary
        print("\n=== dryrun_status ===")
        root = os.path.join(os.path.dirname(__file__), "..",
                            "experiments", "dryrun")
        recs = [json.load(open(p))
                for p in glob.glob(os.path.join(root, "*.json"))]
        ok = sum(1 for r in recs if r.get("ok"))
        print(f"combos,{len(recs)},ok,{ok}")
        from collections import Counter
        doms = Counter(r["dominant"] for r in recs if r.get("ok"))
        for k, v in sorted(doms.items()):
            print(f"dominant_{k},{v}")

    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Benchmark of DiRL post-training on one TPU chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Cells, metrics and bounds are in BENCHMARK.json at the root of the
checkout; each cell's configuration, traffic, limits and per-layer
metric readers are files under bench/ found by name.
"""

import sys
import time

T0 = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root))
    from bench.harness.cli import main
    sys.exit(main(t0=T0))

"""Child process of a rollout run: ``rollout.record_lengths`` on the CPU,
one JSON line per tick on standard output.

    python3 bench/harness/scatters.py '{"traffic": {...}, "model": {...},
        "seed": n}'
"""

import json
import sys
from pathlib import Path

if __name__ == "__main__":
    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench.harness import rollout
    from repro.launch import compile_cache
    compile_cache.configure()
    arg = json.loads(sys.argv[1])

    def emit(lengths):
        print(json.dumps(lengths), flush=True)
    try:
        rollout.record_lengths(arg["traffic"], arg["model"], arg["seed"],
                               emit)
    except BrokenPipeError:
        pass

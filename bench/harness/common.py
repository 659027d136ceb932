"""Pieces every driver shares: the cell's files, the device, compile
counting, host spans, and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    """Everything one run needs, found by the names in BENCHMARK.json."""
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # metric entries this cell reports
    per_layer: list
    seed: int
    seconds: float
    trace: bool
    t_start: float          # process start (perf_counter)

    @property
    def model(self) -> dict:
        return self.config["model"]


def find_cell(name: str, seed: int, seconds: float, trace: bool,
              t_start: float, spec: dict | None = None) -> Cell:
    spec = spec or load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metric):
        return name in metric.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if mine(m) and m["moves"] in moved]
    return Cell(name=name, chips=w["chips"], config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                seed=seed, seconds=seconds, trace=trace, t_start=t_start)


def load_peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source")
    return table["devices"][device_kind]


def load_module(path: Path):
    """Import a file whose name is a metric or kernel name (dots and
    all), without a package."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counts(kernel: str):
    return load_module(BENCH / "counts" / f"{kernel}.py")


class CompileCounter:
    """Counts backend compilations (persistent-cache hits do not count).

    ``window()`` brackets the measured window: ``in_window`` is the
    number of compilations that landed inside it.
    """

    def __init__(self):
        import jax
        self.total = 0
        self.seconds = 0.0
        self.in_window = 0
        self._open = False

        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += 1
                self.seconds += duration
                if self._open:
                    self.in_window += 1
        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def open(self) -> None:
        self._open = True

    def shut(self) -> None:
        self._open = False

    @contextlib.contextmanager
    def window(self):
        self.open()
        try:
            yield
        finally:
            self.shut()

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listener)


class Spans:
    """Host spans of the benchmark's own calls into the program.

    Each span is a ``jax.profiler.TraceAnnotation`` (so a traced run
    sees it on the device trace's clock); off a traced run ``enabled``
    is False and a span costs one attribute check.
    """

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap(self, obj, attr: str, name: str):
        """Bracket ``obj.attr(...)`` calls in a span (instance-level)."""
        if not self.enabled:
            return
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)


class GuardProxy:
    """Stands in for a program's ``TraceGuard``: runs ``before()`` and
    opens a span around each call, and still reports the guard's
    compile count."""

    def __init__(self, guard, before, span, name: str):
        self.guard, self.before, self.span, self.span_name = \
            guard, before, span, name

    @property
    def n_traces(self) -> int:
        return self.guard.n_traces

    @property
    def name(self) -> str:
        return self.guard.name

    def __call__(self, *args, **kw):
        self.before()
        with self.span(self.span_name):
            return self.guard(*args, **kw)


class Timer:
    """Wall-clock phases of set-up, printed on an earlier line."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.last = time.perf_counter()
        self.phases: list[tuple[str, float]] = [
            ("process_to_harness", self.last - t0)]

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.phases.append((name, now - self.last))
        self.last = now

    def total(self) -> float:
        return time.perf_counter() - self.t0

    def line(self) -> str:
        return "setup split (s): " + ", ".join(
            f"{n} {v:.3f}" for n, v in self.phases)


def quantile(xs, q: float) -> float:
    """Linear-interpolated quantile of all samples (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q * 100))


def device_info(devs, chips: int, peak_bytes: int | None) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": peak_bytes}


def peak_memory(devs) -> int | None:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown: dict | None) -> str:
    """The last line of standard output.  ``checks`` (each compared
    number beside its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)

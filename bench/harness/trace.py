"""From a profiler capture to the numbers the per-layer metrics read.

``capture(dir)`` brackets a window under ``jax.profiler`` and a host
annotation ``bench.window`` that marks its bounds on the trace's clock.
``reduce_trace(dir)`` reads the ``.xplane.pb`` with JAX's own
``ProfileData`` and returns a ``TraceSummary``:

* device operations: the events of each TPU plane's ``XLA Ops`` line,
  clipped to the window;
* busy time: the union of those intervals on each chip, averaged over
  the chips;
* idle gaps: the window minus the busy union, each put down to the
  innermost host span that covers its middle (the benchmark's own
  ``bench.*`` spans and the program's annotations), ``none`` where no
  span does.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def capture(logdir: Path):
    import jax
    jax.profiler.start_trace(str(logdir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Op:
    name: str        # program/operation, as the trace has them
    label: str       # name plus its string stats
    start: int       # ns
    dur: int         # ns
    chip: int
    leaf: bool = True   # False for a loop or call that holds other ops

    @property
    def is_kernel(self) -> bool:
        """A Pallas kernel (a TPU custom call)."""
        return 'custom_call_target="tpu_custom_call"' in self.name

    @property
    def n_operands(self) -> int:
        """Operands of a custom call, from its layout constraints."""
        i = self.name.find("operand_layout_constraints={")
        if i < 0:
            return 0
        body = self.name[i:self.name.find("}}", i) + 1]
        return len(_SHAPE.findall(body))


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")


@dataclasses.dataclass
class TraceSummary:
    window: tuple[int, int]
    n_chips: int
    ops: list[Op]
    busy_ns: float               # mean over chips
    gaps: list[tuple[str, int]]  # (host span, ns) per idle gap

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def top_ops(self, k: int = 10) -> list:
        """The operations that took most device time (loops and calls
        that hold other operations left out), by program and name."""
        tot: dict[str, int] = {}
        for o in self.ops:
            if not o.leaf:
                continue
            key = o.name.split(" = ")[0][:120]
            tot[key] = tot.get(key, 0) + o.dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9 / max(self.n_chips, 1)] for n, v in top]

    def idle_by_span(self, k: int = 10) -> list:
        tot: dict[str, int] = {}
        for n, d in self.gaps:
            tot[n] = tot.get(n, 0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9 / max(self.n_chips, 1)] for n, v in top]


def _stats_text(ev) -> str:
    try:
        items = list(ev.stats)
    except Exception:  # noqa: BLE001 — stats of an odd event kind
        return ""
    out = []
    for it in items:
        v = it[1] if isinstance(it, tuple) and len(it) == 2 else it
        if isinstance(v, str):
            out.append(v)
    return " ".join(out)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device_plane(name: str) -> int | None:
    if not name.startswith("/device:TPU:"):
        return None
    tail = name[len("/device:TPU:"):]
    return int(tail) if tail.isdigit() else None


def read_xplane(logdir: Path):
    """Events of the newest capture under ``logdir``: host spans
    ``[(start, end, name)]`` and, per chip, device operations
    ``[(program/op, stats text, start, duration)]``, in ns."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(logdir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(files[-1])
    host_spans: list[tuple[int, int, str]] = []
    dev_events: dict[int, list] = {}
    for plane in pd.planes:
        chip = _device_plane(plane.name)
        if chip is not None:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.start_ns
                                                 + ev.duration_ns), ev.name)
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else []))
            starts = [s for s, _, _ in mods]
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else []):
                s = int(ev.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                dev_events.setdefault(chip, []).append(
                    (f"{mod}/{ev.name}", _stats_text(ev), s,
                     int(ev.duration_ns)))
        elif plane.name == "/host:CPU":
            # the Python thread: the benchmark's and the program's
            # annotations (runtime threads' events are not what the
            # host program was doing)
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for ev in line.events:
                    if ev.name.startswith("$"):
                        continue   # python frames from the tracer
                    host_spans.append((int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns),
                                       ev.name))
    return host_spans, dev_events


def reduce_trace(logdir: Path) -> TraceSummary:
    return summarize(*read_xplane(logdir))


def summarize(host_spans, dev_events) -> TraceSummary:
    """Clip device operations to the ``bench.window`` span, take each
    chip's busy union, and put each idle gap down to a host span."""
    win = [s for s in host_spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    w0, w1 = win[0][0], win[0][1]
    inner = sorted(s for s in host_spans
                   if s[2] != WINDOW_SPAN and s[1] > w0 and s[0] < w1)
    inner_starts = [s for s, _, _ in inner]
    ops: list[Op] = []
    busy_total = 0
    gaps: list[tuple[str, int]] = []
    for chip, evs in sorted(dev_events.items()):
        iv = []
        for name, text, s, d in evs:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            s2, e2 = max(s, w0), min(e, w1)
            ops.append(Op(name=name, label=f"{name} {text}", start=s2,
                          dur=e2 - s2, chip=chip))
            iv.append((s2, e2))
        mine = sorted((o for o in ops if o.chip == chip),
                      key=lambda o: (o.start, -o.dur))
        for a, b in zip(mine, mine[1:]):
            if b.start < a.start + a.dur:
                a.leaf = False
        busy = _union(iv)
        busy_total += sum(e - s for s, e in busy)
        cur = w0
        for s, e in busy + [(w1, w1)]:
            if s > cur:
                gaps.append((_innermost(inner, inner_starts,
                                        (cur + s) // 2), s - cur))
            cur = max(cur, e)
    n = max(len(dev_events), 1)
    return TraceSummary(window=(w0, w1), n_chips=len(dev_events), ops=ops,
                        busy_ns=busy_total / n, gaps=gaps)


def _innermost(spans, starts, t: int) -> str:
    """The span with the latest start among those covering ``t``."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i][1] > t:
            return spans[i][2]
    return "none"

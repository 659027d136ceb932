"""Span tracer: host spans with counts, on the profiler's clock when
one is capturing.

The substrate of the request-lifecycle and scheduler-tick views that
``obs.export`` turns into Chrome trace-event JSON, and of the per-layer
numbers a profiled run reads back with ``captured()``.  Two recording
styles:

``span(name, ...)``
    Context manager for code the caller brackets directly (a scheduler
    tick phase, an engine drain, a trainer phase).  It *always* measures
    — the yielded ``Span`` carries real ``t0``/``t1`` even when nothing
    records it — so callers that need the duration for their own stats
    (``EngineStats.wall_seconds``, trainer phase timings) read it off
    the span, and the measurement is defined identically whether or not
    anything records.  ``Span.recording`` says whether it will be
    recorded; callers attach counts to ``args`` only then, so an
    unrecorded span computes none.

``begin(key) / end(key)``
    Open-span bookkeeping for lifecycles that start and finish in
    different calls — a request's *queued* span opens at ``submit()``
    and closes at admission; its *decode* span opens at admission and
    closes at harvest.  Keys are caller-chosen hashables
    (``("queued", uid)``); ``end`` merges final labels (finish reason,
    token counts) into the span's args and records it.

When a span records
-------------------
A span records when its tracer is ``enabled`` (into the tracer's ring
buffer) or when a ``jax.profiler`` session is capturing
(``TraceAnnotation.is_enabled()``, a static call).  Spans taken under a
capture also go to one process-wide record: ``captured()`` returns the
spans of the most recent capture, and a new session starts a new
record.  Under a capture each ``span`` also opens a
``jax.profiler.TraceAnnotation`` of the same name whose stats are the
span's start on this tracer's clock in ns (``t_host_ns``) and its
args, so the program's phases sit in the ``.xplane.pb`` beside the
device operations, and ``t_host_ns`` minus an event's start is one
offset that maps every span onto the trace's clock.  ``begin``/``end``
and ``instant`` leave zero-length annotations at their ends: a
lifecycle crosses tick boundaries, and an open annotation across them
would not nest on the thread's line.

``Span.parent`` is the enclosing recorded span of the same thread, so
a layer's self time is its duration minus its children's; the spans of
one request share their ``uid`` arg.

Timing contract: timestamps are taken **around jit dispatch, never
after a device sync** — a span covering the pool advance measures
Python-side dispatch plus whatever the async runtime happened to
overlap, not device latency.  That keeps the tracer legal on per-tick
hot paths (the dirlint ``hot-sync`` and ``obs-in-trace`` contracts);
device time comes from the capture's device planes.

The buffers are ``deque(maxlen=...)``: a long-lived server evicts the
oldest spans instead of growing without bound, and ``dropped`` counts
evictions so exporters can say the window is partial.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from contextlib import contextmanager

from jax import profiler as _jprof
# the running start_trace session, to tell two sessions apart
from jax._src.profiler import _profile_state

__all__ = ["Span", "Tracer", "captured"]

_Annotation = _jprof.TraceAnnotation
_capturing = _Annotation.is_enabled


@dataclasses.dataclass(eq=False)
class Span:
    """One completed (or still-open: ``t1 < 0``) measured interval.

    ``track`` names the display lane the exporters map to a Chrome
    trace thread — ``"scheduler"``, ``"queue"``, ``"slot 3"``,
    ``"trainer"`` — so Perfetto shows one swim-lane per decode slot and
    one per subsystem.  ``args`` are the labels and counts at the
    boundary (slot id, prefix-hit blocks, context blocks, finish
    reason...).
    """
    name: str
    cat: str                    # request | scheduler | engine | trainer
    track: str
    t0: float
    t1: float = -1.0
    args: dict = dataclasses.field(default_factory=dict)
    parent: Span | None = None  # enclosing recorded span, same thread
    recording: bool = False     # lands in a ring buffer or a capture

    @property
    def dur(self) -> float:
        """Wall seconds (0 for instants and still-open spans)."""
        return max(self.t1 - self.t0, 0.0)


class _Capture:
    """The process-wide record of spans taken under a profiler session."""

    CAPACITY = 1 << 18

    def __init__(self):
        self.spans: deque[Span] = deque(maxlen=self.CAPACITY)
        self.session = None      # the start_trace session it belongs to
        self.live = False        # a session was capturing at last look
        self.local = threading.local()

    def record(self) -> deque | None:
        """The running capture's record, or None when none captures.
        A session the last look did not see starts a new record."""
        if not _capturing():
            self.live, self.session = False, None
            return None
        session = _profile_state.profile_session
        if not self.live or session is not self.session:
            self.spans = deque(maxlen=self.CAPACITY)
            self.session, self.live = session, True
        return self.spans

    def stack(self) -> list:
        st = getattr(self.local, "stack", None)
        if st is None:
            st = self.local.stack = []
        return st


_CAPTURE = _Capture()


def captured() -> list[Span]:
    """The spans recorded under the most recent profiler capture (the
    running one, if any), in the order they closed."""
    return list(_CAPTURE.spans)


def _ns(t: float) -> int:
    return int(t * 1e9)


def _mark(name: str, clock, args: dict | None = None) -> float:
    """A zero-length profiler annotation; returns ``clock()`` read as it
    opens (the profiler takes its own start inside ``__enter__``, so the
    two reads sit next to each other)."""
    ann = _Annotation(name)
    ann.__enter__()
    t = clock()
    ann.set_metadata(t_host_ns=_ns(t), **_stat_args(args))
    ann.__exit__(None, None, None)
    return t


def _stat_args(args: dict | None) -> dict:
    if not args:
        return {}
    return {k: v if isinstance(v, (bool, int, float, str)) else str(v)
            for k, v in args.items()}


class Tracer:
    """Bounded span recorder; spans that record nothing still time.

    One tracer instance is shared down a stack (engine → scheduler →
    trainer phases) so a single export holds every track.  All methods
    are cheap host-side operations: with the tracer disabled and no
    capture, a span costs two clock reads, one ``is_enabled()`` and one
    small object, and ``begin``/``end``/``instant`` one
    ``is_enabled()``.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 clock=time.perf_counter):
        self.enabled = enabled
        self.spans: deque[Span] = deque(maxlen=capacity)
        self.dropped = 0          # spans evicted by the ring buffer
        self._open: dict[object, tuple[Span, deque | None]] = {}
        self._clock = clock

    # ------------------------------------------------------------ record
    def _record(self, span: Span, rec: deque | None) -> None:
        if self.enabled:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(span)
        if rec is not None:
            rec.append(span)

    @contextmanager
    def span(self, name: str, cat: str = "span", track: str | None = None,
             **args):
        """Measure the block; record it if the tracer is enabled or a
        profiler session is capturing.

        Always yields a ``Span`` whose ``dur`` is valid after the block
        exits, so callers can feed stats from the same measurement that
        lands in the trace.
        """
        rec = _CAPTURE.record()
        if not self.enabled and rec is None:
            sp = Span(name, cat, track or cat, self._clock(), args=args)
            try:
                yield sp
            finally:
                sp.t1 = self._clock()
            return
        ann = None
        if rec is not None:
            # the clock is read right after the profiler takes the
            # annotation's start, so t_host_ns - start is one offset
            ann = _Annotation(name)
            ann.__enter__()
        sp = Span(name, cat, track or cat, self._clock(), args=args,
                  recording=True)
        stack = _CAPTURE.stack()
        sp.parent = stack[-1] if stack else None
        stack.append(sp)
        try:
            yield sp
        finally:
            sp.t1 = self._clock()
            stack.pop()
            if ann is not None:
                ann.set_metadata(t_host_ns=_ns(sp.t0),
                                 **_stat_args(sp.args))
                ann.__exit__(None, None, None)
                # a span the capture's stop cut is not in the trace
                if not _capturing():
                    rec = None
            self._record(sp, rec)

    def begin(self, key, name: str, cat: str = "span",
              track: str | None = None, **args) -> None:
        """Open a lifecycle span under ``key`` (no-op when nothing
        records).  Re-opening a live key silently replaces the orphan."""
        rec = _CAPTURE.record()
        if not self.enabled and rec is None:
            return
        t0 = self._clock() if rec is None else _mark(name, self._clock)
        self._open[key] = (Span(name, cat, track or cat, t0, args=args,
                                recording=True), rec)

    def end(self, key, **args) -> Span | None:
        """Close and record the open span under ``key``, merging
        ``args`` into its labels.  Unknown keys (nothing recorded at
        ``begin`` time, or evicted bookkeeping) are ignored."""
        entry = self._open.pop(key, None)
        if entry is None:
            return None
        sp, rec = entry
        sp.args.update(args)
        if rec is not None and _CAPTURE.record() is rec:
            sp.t1 = _mark(sp.name, self._clock, sp.args)
        else:
            sp.t1, rec = self._clock(), None
        self._record(sp, rec)
        return sp

    def amend(self, key, **args) -> None:
        """Merge labels into a still-open span (no-op if unknown)."""
        entry = self._open.get(key)
        if entry is not None:
            entry[0].args.update(args)

    def instant(self, name: str, cat: str = "event",
                track: str | None = None, **args) -> None:
        """Record a zero-duration marker (deferral, weight push)."""
        rec = _CAPTURE.record()
        if not self.enabled and rec is None:
            return
        t = self._clock() if rec is None else _mark(name, self._clock, args)
        stack = _CAPTURE.stack()
        sp = Span(name, cat, track or cat, t, t, args,
                  parent=stack[-1] if stack else None, recording=True)
        self._record(sp, rec)

    # ----------------------------------------------------------- queries
    def __len__(self) -> int:
        return len(self.spans)

    @property
    def n_open(self) -> int:
        return len(self._open)

    def snapshot(self) -> list[Span]:
        """The recorded spans, oldest first (open spans excluded)."""
        return list(self.spans)

    def clear(self) -> None:
        self.spans.clear()
        self._open.clear()
        self.dropped = 0

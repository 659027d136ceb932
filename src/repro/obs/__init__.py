"""obs — the unified observability layer for the serving + training stack.

Three cooperating pieces, all host-side and allocation-light so they can
sit on per-tick serving paths without touching the device:

1. **Metrics** (``obs.metrics``): a typed registry of Counter / Gauge /
   Histogram / Info instruments with optional label sets and explicit
   monotonic-vs-resettable semantics.  ``SchedulerStats`` and
   ``EngineStats`` are rebuilt on top of it — every numeric stats field
   is *bound storage* for a registry instrument, so the legacy attribute
   surface (``stats.ticks += 1``, ``sched.stats = SchedulerStats()``)
   keeps working unchanged while exporters read the same values through
   the registry.  Trainer timings register under the same ``dirl_*``
   namespace convention.

2. **Tracing** (``obs.trace``): a span tracer with a bounded ring
   buffer.  Spans are named by layer (``scheduler.tick``,
   ``scheduler.advance``, ``sft.step``...) and carry the counts at
   their boundary and their enclosing span.  The scheduler records
   per-request lifecycle spans (submit → queued → admit → decode →
   harvest, labeled with prefix-hit counts, slot id, kernel mode,
   finish reason) and per-tick sub-spans.  Timestamps are host
   wall-clock taken *around* jit dispatch — spans never call
   ``block_until_ready``, so the ``hot-sync`` dirlint contract holds by
   construction and a span's duration is dispatch + host bookkeeping,
   not device time.  Spans record when the tracer is enabled or when a
   ``jax.profiler`` session is capturing; under a capture they are
   also profiler annotations on the device trace's clock, and
   ``obs.trace.captured()`` returns them.

3. **Profiler capture** (``obs.profile``): ``capture(dir)`` brackets a
   region with a real ``start_trace``/``stop_trace`` profiler session
   (``launch.serve --profile-dir``).

Exporters (``obs.export``) turn both substrates into artifacts: Chrome
trace-event JSON (open in Perfetto / ``chrome://tracing`` — one track
per decode slot, one for the scheduler tick phases, one per trainer
phase), Prometheus-style text exposition, a flat metrics JSON envelope,
and JSONL span dumps.

The matching static contract is the dirlint rule ``obs-in-trace``: no
``obs`` call may be reachable from inside a jitted body —
instrumentation stays host-side, between dispatches, never traced.
"""

from __future__ import annotations

from .metrics import Counter, Gauge, Histogram, Info, MetricsRegistry
from .trace import Span, Tracer
from . import export, profile

__all__ = ["Counter", "Gauge", "Histogram", "Info", "MetricsRegistry",
           "Span", "Tracer", "export", "profile"]

"""Profiler hooks: named XLA scopes and opt-in device trace capture.

The host-side tracer (``obs.trace``) deliberately never syncs the
device, so its spans measure dispatch, not device latency.  When device
time is the question, this module is the answer:

``annotate(name)``
    A ``jax.profiler.TraceAnnotation`` context — a named scope that
    shows up in XLA profiler timelines (TensorBoard / Perfetto) nested
    under the launching op.  The serving scheduler wraps
    ``advance_block`` and the suffix-prefill dispatches; trainers wrap
    their fused step.  When no profiler session is active these scopes
    cost a few hundred nanoseconds, so they stay on permanently.

``capture(logdir)``
    A real profiler session (``jax.profiler.start_trace`` /
    ``stop_trace``) bracketing a region; artifacts land under
    ``logdir`` and open in TensorBoard's profile plugin or Perfetto.
    Wired to ``launch.serve --profile-dir``.  ``logdir=None`` is a
    no-op, so call sites can pass the CLI flag straight through.
"""

from __future__ import annotations

from contextlib import contextmanager

from jax import profiler as _jprof

__all__ = ["annotate", "capture"]


def annotate(name: str):
    """Named profiler scope."""
    return _jprof.TraceAnnotation(name)


@contextmanager
def capture(logdir: str | None):
    """Run the body under an XLA profiler trace written to ``logdir``.

    ``None`` (flag unset) is a plain pass-through so callers need no
    conditional; yields whether a trace is being captured.
    """
    if logdir is None:
        yield False
        return
    _jprof.start_trace(str(logdir))
    try:
        yield True
    finally:
        _jprof.stop_trace()

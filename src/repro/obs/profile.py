"""Opt-in device trace capture.

``capture(logdir)``
    A real profiler session (``jax.profiler.start_trace`` /
    ``stop_trace``) bracketing a region; artifacts land under
    ``logdir`` and open in TensorBoard's profile plugin or Perfetto.
    Wired to ``launch.serve --profile-dir``.  ``logdir=None`` is a
    no-op, so call sites can pass the CLI flag straight through.  While
    it captures, every ``obs.trace`` span is recorded and annotated on
    the profiler's clock (``obs.trace.captured()``).
"""

from __future__ import annotations

from contextlib import contextmanager

from jax import profiler as _jprof

__all__ = ["capture"]


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

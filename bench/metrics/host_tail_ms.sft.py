"""Host milliseconds per SFT step in which the device has no work
queued, over the traced steps: ``sft.batch_put`` plus the rest of
``sft.step`` after ``sft.sync`` ends (the metric pulls and the tile
statistics), averaged over the ``sft.step`` spans the program recorded
under the capture (``repro.obs.trace.captured()``).  Logs each phase's
mean.  A program that keeps no such record gives nothing."""

import sys

PHASES = ("sft.batch_put", "sft.dispatch", "sft.sync", "sft.metric_pull",
          "sft.tile_stats")


def _captured():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return trace.captured() if hasattr(trace, "captured") else None


def read(ctx):
    spans = _captured() or []
    steps = [sp for sp in spans if sp.name == "sft.step"]
    kids: dict = {}
    for sp in spans:
        if sp.name in PHASES and sp.parent is not None:
            kids.setdefault(id(sp.parent), {})[sp.name] = sp
    steps = [sp for sp in steps if {"sft.batch_put", "sft.sync"}
             <= set(kids.get(id(sp), {}))]
    if not steps:
        return None
    tails = [kids[id(sp)]["sft.batch_put"].dur
             + (sp.t1 - kids[id(sp)]["sft.sync"].t1) for sp in steps]
    phase_ms = {p: 1e3 * sum(kids[id(sp)][p].dur for sp in steps
                             if p in kids[id(sp)]) / len(steps)
                for p in PHASES}
    print(f"host_tail_ms.sft: {len(steps)} captured steps (harness "
          f"trace_steps {ctx.counters.get('trace_steps')}); step ms "
          f"{[round(1e3 * sp.dur, 3) for sp in steps]}; phase ms per step "
          f"{ {p: round(v, 3) for p, v in phase_ms.items()} }",
          file=sys.stderr, flush=True)
    return 1e3 * sum(tails) / len(steps)

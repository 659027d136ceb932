"""Share of the pool's forwards that some live row needed, over the
traced ticks: 100 x the forwards an early-exit loop would run (one
commit forward plus the most denoise steps any live row's block used,
``forwards_needed`` on ``scheduler.harvest``) over the forwards run
(``s_max + 1``, ``forwards`` on ``scheduler.advance``), summed over the
ticks the program recorded under the capture
(``repro.obs.trace.captured()``).  A program that keeps no such record
gives nothing."""

import sys


def _captured():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return trace.captured() if hasattr(trace, "captured") else None


def read(ctx):
    ticks: dict = {}
    for sp in _captured() or []:
        if sp.name in ("scheduler.advance", "scheduler.harvest") \
                and sp.parent is not None:
            ticks.setdefault(id(sp.parent), {})[sp.name] = sp
    pairs = [(t["scheduler.advance"].args["forwards"],
              t["scheduler.harvest"].args["forwards_needed"])
             for t in ticks.values()
             if "scheduler.advance" in t and "scheduler.harvest" in t
             and "forwards_needed" in t["scheduler.harvest"].args]
    if not pairs:
        return None
    run = sum(p[0] for p in pairs)
    needed = sum(p[1] for p in pairs)
    print(f"denoise_forward_share.rollout: {len(pairs)} captured ticks, "
          f"forwards needed {needed} over forwards run {run} (per tick: "
          f"{[p[1] for p in pairs]})", file=sys.stderr, flush=True)
    return 100.0 * needed / run

"""Share of the table entries the paged decode kernel walks that hold
live context, over the traced ticks: 100 x the committed blocks of the
live slots going into each advance (``ctx_blocks``) over the entries
its grid visits per forward (``walked_blocks``, every slot's whole
table), summed over the ``scheduler.advance`` spans the program
recorded under the capture (``repro.obs.trace.captured()``).  A program
that keeps no such record gives nothing."""

import sys


def _captured():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return trace.captured() if hasattr(trace, "captured") else None


def read(ctx):
    spans = _captured()
    adv = [sp for sp in spans or []
           if sp.name == "scheduler.advance" and "walked_blocks" in sp.args]
    if not adv:
        return None
    ctx_blocks = sum(sp.args["ctx_blocks"] for sp in adv)
    walked = sum(sp.args["walked_blocks"] for sp in adv)
    c = ctx.counters
    print(f"ctx_walk_share.rollout: {len(adv)} captured advances (harness "
          f"trace_ticks {c.get('trace_ticks')}), ctx_blocks {ctx_blocks} "
          f"(harness trace_ctx_blocks {c.get('trace_ctx_blocks')}) over "
          f"walked_blocks {walked}", file=sys.stderr, flush=True)
    return 100.0 * ctx_blocks / walked if walked else None

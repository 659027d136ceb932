"""95th percentile, over every (request, consecutive block) pair of the
untraced window, of the host-clock gap between the two commits (a
request's first block left out).  A per-layer metric, not an end-to-end
one: ticks that admit a group take ≈90 ms more than the others and are
≈4% of the ticks, so the percentile falls between the two modes and
moves with which tick a window happens to hold."""


def read(ctx):
    return ctx.e2e.get("block_gap_p95_ms")

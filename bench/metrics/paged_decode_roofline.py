"""Share of its roofline that the paged decode kernel reaches.

Kernel time: the device time of the Pallas calls inside the pool
advance program (the only kernel there), from the trace.  Work: what the live slots need
(``counts/paged_decode.py``) over the traced ticks, every denoise
forward and the commit forward.  The bound is the larger of operations
over peak and bytes over bandwidth (bytes, at these shapes)."""

PROGRAM = "_advance_impl"


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or not c.get("trace_active"):
        return None
    secs = sum(o.dur for o in s.ops if o.is_kernel
               and o.name.startswith(f"jit_{PROGRAM}")) / 1e9 \
        / max(s.n_chips, 1)
    if secs <= 0:
        return None
    flops, nbytes = ctx.counts("paged_decode").window(
        ctx.model, c["trace_active"], c["trace_ctx_blocks"],
        c["s_max"] + 1)
    t_min = max(flops / ctx.peaks["flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / secs

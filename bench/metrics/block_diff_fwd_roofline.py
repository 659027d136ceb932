"""Share of its roofline that the block-diffusion forward kernel
reaches in the SFT step: each call's needed work (``counts/
block_diff.py``) over the device time of its calls in the trace.  Under
remat the forward runs twice a step; both runs are calls.

The kernels carry no name in the trace: the forward is the Pallas call
with 7 operands (worklist length, worklist, two metadata, q, k, v), the
dQ and dKV calls take 10."""

N_OPERANDS = 7


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or not c.get("seq_len"):
        return None
    ops = [o for o in s.ops if o.is_kernel and o.n_operands == N_OPERANDS]
    if not ops:
        return None
    secs = sum(o.dur for o in ops) / 1e9
    w = ctx.counts("block_diff").per_call(ctx.model, c["batch"],
                                          c["seq_len"])
    t_min = max(w["fwd_flops"] / ctx.peaks["flops_per_s"],
                w["fwd_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min * len(ops) / secs

"""Share of its roofline that the block-diffusion backward (the dQ and
dKV kernels together) reaches in the SFT step: each layer's needed
backward work over the device time of its dQ and dKV calls.  Both are
the Pallas calls with 10 operands (see ``block_diff_fwd_roofline``);
two calls make one layer's backward."""

N_OPERANDS = 10


def read(ctx):
    s, c = ctx.summary, ctx.counters
    if s is None or not c.get("seq_len"):
        return None
    ops = [o for o in s.ops if o.is_kernel and o.n_operands == N_OPERANDS]
    if not ops or len(ops) % 2:
        return None
    secs = sum(o.dur for o in ops) / 1e9
    w = ctx.counts("block_diff").per_call(ctx.model, c["batch"],
                                          c["seq_len"])
    t_min = max(w["bwd_flops"] / ctx.peaks["flops_per_s"],
                w["bwd_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min * (len(ops) // 2) / secs

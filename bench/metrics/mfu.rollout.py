"""Model operations of one forward per generated token, at the window's
``rollout_tok_s``, over the chip's peak.  The count per token does not
depend on how many forwards the program spends on it."""


def read(ctx):
    c = ctx.counters
    if not c.get("window_active"):
        return None
    m, b = ctx.model, ctx.model["block_size"]
    tokens = c["window_active"] * b
    keys = b * b * (c["window_ctx_blocks"] + c["window_active"])
    flops = ctx.counts("model").decode_flops(m, tokens, keys)
    per_s = flops / tokens * ctx.e2e["rollout_tok_s"]
    return 100.0 * per_s / (ctx.peaks["flops_per_s"] * ctx.chips)

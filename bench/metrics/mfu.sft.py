"""Forward and backward operations the SFT step needs per real token,
at the window's ``sft_tok_s``, over the chip's peak.

Per row of L real tokens the model runs 2L positions (clean and noised
copies) through every layer, the head over the L noised positions, and
attention over the visible pairs of the layout only; the backward is
twice the forward; recomputation under remat does not count."""


def read(ctx):
    c = ctx.counters
    m = ctx.model
    L = c.get("seq_len")
    if not L:
        return None
    model, bd = ctx.counts("model"), ctx.counts("block_diff")
    pairs = bd.pairs(L, m["block_size"], m.get("sliding_window") or 0)
    fwd = model.matmul_flops(m, 2 * L) + model.head_flops(m, L) \
        + model.attn_flops(m, pairs)
    per_token = 3 * fwd / L
    return 100.0 * per_token * ctx.e2e["sft_tok_s"] / (
        ctx.peaks["flops_per_s"] * ctx.chips)

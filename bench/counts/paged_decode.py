"""Work the paged decode kernel needs, from the slots it serves.

One call attends the current block of every slot (``block_size`` query
rows, all heads) to the slot's committed pages and its own block, for
one layer.  Only live slots count: a finished slot's rows are work the
algorithm does not need.  Per live slot with ``c`` committed tokens and
block ``b``: ``4 * b * (c + b) * head_dim * n_heads`` operations (scores
and values), and reading ``(c + b)`` keys and values of every kv head
plus writing ``b`` output rows and reading ``b`` query rows, in the
configuration's ``dtype``.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def per_forward(m: dict, live_slots: int, ctx_blocks: int) -> tuple:
    """(operations, bytes) of one layer's call, summed over live slots
    whose committed blocks add up to ``ctx_blocks``."""
    b, H, Hkv, Dh = (m["block_size"], m["n_heads"], m["n_kv_heads"],
                     m["head_dim"])
    keys = (ctx_blocks + live_slots) * b
    flops = 4 * b * keys * Dh * H
    nbytes = ITEMSIZE[m.get("dtype", "float32")] \
        * (2 * keys * Hkv * Dh + 2 * live_slots * b * H * Dh)
    return flops, nbytes


def window(m: dict, ticks_live: int, ticks_ctx_blocks: int,
           forwards_per_tick: int) -> tuple:
    """(operations, bytes) over a window: live slot-ticks, committed
    blocks summed over them, forwards per tick; all layers."""
    f, n = per_forward(m, ticks_live, ticks_ctx_blocks)
    k = forwards_per_tick * m["n_layers"]
    return f * k, n * k

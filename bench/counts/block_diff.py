"""Work the block-diffusion attention kernels need on the SFT layout.

The duplicated layout holds a clean copy (block-causal) and a noised
copy (each block sees the clean blocks before it and its own noised
block).  ``pairs`` counts the visible (query, key) pairs of one row,
per head, from that rule and the sliding window; the kernels' tiles
cover more, which is work the algorithm does not need.

Forward: scores and values, 4 operations per pair and head dim.
Backward (dQ and dKV kernels): the scores once more, dP, dQ, dK and dV,
10 per pair and head dim.  Bytes: float32 q, k, v, o (and for the
backward do, dq, dk, dv), each read or written once.
"""

import numpy as np


def pairs(L: int, block: int, window: int = 0) -> int:
    pos = np.arange(L)
    blk = pos // block
    qb, kb = blk[:, None], blk[None, :]
    a_rows = kb <= qb                       # clean copy, clean keys
    b_ctx = kb < qb                         # noised copy, clean keys
    b_own = kb == qb                        # noised copy, noised keys
    if window:
        near = (pos[:, None] - pos[None, :]) < window
        a_rows, b_ctx, b_own = a_rows & near, b_ctx & near, b_own & near
    return int(a_rows.sum() + b_ctx.sum() + b_own.sum())


def per_call(m: dict, rows: int, L: int) -> dict:
    """Operations and bytes of one layer's forward and backward calls
    over ``rows`` rows of ``L`` tokens (2L positions each)."""
    H, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    p = pairs(L, m["block_size"], m.get("sliding_window") or 0) * rows
    T = 2 * L * rows
    qo = T * H * Dh * 4
    kv = T * Hkv * Dh * 4
    return {"fwd_flops": 4 * p * Dh * H, "fwd_bytes": 2 * qo + 2 * kv,
            "bwd_flops": 10 * p * Dh * H, "bwd_bytes": 4 * qo + 4 * kv}

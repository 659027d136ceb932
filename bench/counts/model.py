"""Operations of the dense GQA model, from its sizes.

A multiply-add is two operations.  Only the matmuls and attention's two
contractions count; norms, rotary, softmax and the optimizer are left
out (they are a few operations a value, not a few per weight).
"""


def layer_weights(m: dict) -> int:
    """Matmul weights of one layer."""
    d, H, Hkv, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return d * (H + 2 * Hkv) * Dh + H * Dh * d + 3 * d * F


def matmul_flops(m: dict, rows: int) -> int:
    """All layers' matmuls over ``rows`` positions."""
    return 2 * rows * m["n_layers"] * layer_weights(m)


def head_flops(m: dict, rows: int) -> int:
    return 2 * rows * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, pairs: int) -> int:
    """Both contractions (scores and values) over ``pairs`` visible
    (query, key) pairs per head, all heads, all layers."""
    return 4 * pairs * m["head_dim"] * m["n_heads"] * m["n_layers"]


def decode_flops(m: dict, tokens: int, key_tokens: int) -> int:
    """One forward per generated token: ``tokens`` generated, whose
    queries see ``key_tokens`` keys in all (context plus own block)."""
    return matmul_flops(m, tokens) + head_flops(m, tokens) \
        + attn_flops(m, key_tokens)

"""Random weights from a seed, made on the device in one jitted call.

The tree follows the dense GQA layout that both the program and the
plain reference read: layers stacked along a leading axis (one scanned
group per layer), no biases, untied head.  The benchmark makes the
weights itself, so the reference can make the very same ones again
after the program's state is freed without taking anything from it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 2**63 (more than 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def param_shapes(m: dict) -> dict:
    """Leaf shapes of the dense GQA tree for the sizes in ``m``."""
    d, H, Hkv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    Dh, F, V, G = m["head_dim"], m["d_ff"], m["vocab_size"], m["n_layers"]
    tree = {
        "embed": {"table": (V, d)},
        "final_norm": {"scale": (d,)},
        "groups": {"l0": {
            "attn_norm": {"scale": (G, d)},
            "ffn_norm": {"scale": (G, d)},
            "attn": {"wq": {"w": (G, d, H * Dh)},
                     "wk": {"w": (G, d, Hkv * Dh)},
                     "wv": {"w": (G, d, Hkv * Dh)},
                     "wo": {"w": (G, H * Dh, d)}},
            "ffn": {"w_gate": {"w": (G, d, F)},
                    "w_up": {"w": (G, d, F)},
                    "w_down": {"w": (G, F, d)}},
        }},
    }
    if not m["tie_embeddings"]:
        tree["lm_head"] = {"w": (d, V)}
    return tree


def _leaf_init(path: str, shape: tuple, key) -> jax.Array:
    if path.endswith("scale"):
        return jnp.zeros(shape, jnp.float32)
    if path.startswith("embed"):
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _paths(tree: dict, prefix: str = "") -> list[tuple[str, tuple]]:
    out = []
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out += _paths(v, p)
        else:
            out.append((p, v))
    return out


def _set(tree: dict, path: str, value) -> None:
    *head, last = path.split("/")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


def make_params(m: dict, seed: int, dtype=None) -> dict:
    """All weights for sizes ``m`` from ``seed``, in one jitted call,
    stored as ``dtype`` (the configuration's ``param_dtype`` unless
    given); the values are drawn in float32 and rounded once."""
    dtype = jnp.dtype(dtype or m.get("param_dtype", "float32"))
    leaves = _paths(param_shapes(m))

    @jax.jit
    def init(key):
        out: dict = {}
        for i, (p, shape) in enumerate(leaves):
            x = _leaf_init(p, shape, jax.random.fold_in(key, i))
            _set(out, p, x.astype(dtype))
        return out

    return init(seed_key(seed))


def check_tree(m: dict, program_shapes) -> None:
    """Raise unless the benchmark's tree for ``m`` is the program's:
    same keys, shapes and leaf type (``program_shapes``:
    ``jax.eval_shape`` of the program's init)."""
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        program_shapes)
    dt = m.get("param_dtype", "float32")
    got = jax.tree.map(lambda s: (tuple(s), dt), param_shapes(m),
                       is_leaf=lambda s: isinstance(s, tuple))
    if got != want:
        raise ValueError(f"benchmark weights {got} do not match the "
                         f"program's tree {want}")

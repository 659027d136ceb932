"""The Pallas kernels compile for a TPU v5e, at published model widths.

Interpret mode (every other kernel test) never applies Mosaic's rules —
block shapes, tiling, VMEM — so a kernel can pass all of them and still
be refused by the chip's compiler.  These tests compile each launch of
the main path with ``interpret=False`` against a *described* v5e: the
TPU compiler is installed here and compiles for a chip that is not
attached.  Nothing runs, so they say nothing about results or speed.

Widths come from the shipped configs: h2o-danube-3-4b (GQA 32/8, head
dim 120, page 32, sliding window 4096) and sdar-8b (GQA 32/8, head dim
128, page 4).  Both take ``plan_exec``'s zero-padded plan (head dim 120
is not a lane multiple; page 4 is not a sublane multiple).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and the suite runs
with several workers that all import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import h2o_danube3_4b, sdar_8b
from repro.core.masks import SeqMeta
from repro.kernels import ops
from repro.kernels.paged_attn import (paged_decode_attention,
                                      paged_prefill_attention, plan_exec)

MODELS = {"danube": h2o_danube3_4b.config(), "sdar": sdar_8b.config()}
B, K, P = 2, 8, 64          # rows, table width, pool pages
SEQ = 512                   # block-diffusion tokens per row (2x duplicated)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _dims(name):
    cfg = MODELS[name]
    return (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            cfg.block_size, cfg.sliding_window)


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("model", sorted(MODELS))
def test_paged_decode_compiles(model, one_chip, no_persistent_cache):
    H, Hkv, D, bsz, window = _dims(model)
    plan = plan_exec(bsz, D, D, interpret=False)
    assert plan.mode == "compiled" and plan.padded, plan
    f32, i32 = jnp.float32, jnp.int32

    def decode(q, kp, vp, pp, table, ks, vs, pos, lim):
        return paged_decode_attention(q, kp, vp, pp, table, ks, vs, pos,
                                      lim, scale=D ** -0.5, window=window,
                                      interpret=False)

    c = _compile(decode, one_chip,
                 ((B, bsz, H, D), f32), ((P, Hkv, bsz, D), f32),
                 ((P, Hkv, bsz, D), f32), ((P, bsz), i32), ((B, K), i32),
                 ((B, bsz, Hkv, D), f32), ((B, bsz, Hkv, D), f32),
                 ((B, bsz), i32), ((B,), i32))
    # the device holds the output at least at its logical size (the
    # TPU layout may pad head dim 120 to the 128-lane tile)
    assert c.memory_analysis().output_size_in_bytes >= B * bsz * H * D * 4


@pytest.mark.parametrize("Kp", [0, 3])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_paged_prefill_compiles(model, Kp, one_chip, no_persistent_cache):
    """Suffix prefill over ``Kp`` hit-prefix pages (0 = pure suffix)
    plus a 4-block suffix."""
    H, Hkv, D, bsz, window = _dims(model)
    T = 4 * bsz
    f32, i32 = jnp.float32, jnp.int32

    def prefill(q, kp, vp, pp, ctx, ks, vs, pos):
        return paged_prefill_attention(q, kp, vp, pp, ctx, ks, vs, pos,
                                       scale=D ** -0.5, window=window,
                                       interpret=False)

    _compile(prefill, one_chip,
             ((B, T, H, D), f32), ((P, Hkv, bsz, D), f32),
             ((P, Hkv, bsz, D), f32), ((P, bsz), i32), ((B, Kp), i32),
             ((B, T, Hkv, D), f32), ((B, T, Hkv, D), f32), ((B, T), i32))


@pytest.mark.parametrize("odd", [False, True], ids=["even", "odd"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_block_diff_compiles(model, grad, odd, one_chip,
                             no_persistent_cache, monkeypatch):
    """Training attention on the duplicated SFT layout (2 x SEQ keys,
    or 2 x 17 blocks), forward alone and forward + backward through the
    custom VJP, launched by ``ops.attention`` so its tile choice is the
    one compiled.  17 blocks is a length whose largest divisor <= 128
    is not a multiple of 8 (danube: 1088 -> 68)."""
    # the dispatcher asks the backend (this host's CPU); steer it to
    # the compiled launch here, in the test
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    H, Hkv, D, bsz, window = _dims(model)
    T = 2 * (17 * bsz if odd else SEQ)
    meta = SeqMeta(*(jax.ShapeDtypeStruct((1, T), d, sharding=one_chip)
                     for d in (jnp.int32,) * 4 + (jnp.bool_,)))

    def attend(q, k, v, meta):
        return ops.attention(q, k, v, meta, meta, impl="pallas",
                             window=window)

    def loss(q, k, v, meta):
        return jnp.sum(attend(q, k, v, meta))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else attend
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((1, T, H, D), (1, T, Hkv, D), (1, T, Hkv, D))]
    text = jax.jit(fn).lower(*args, meta).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"

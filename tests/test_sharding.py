"""Partition-rule coverage and divisibility sanitisation (no devices —
uses AbstractMesh)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.models.model import BlockDiffLM
from repro.models.modules import tree_paths


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _param_shapes(arch):
    cfg = configs.get_config(arch, dtype="bfloat16", param_dtype="bfloat16")
    model = BlockDiffLM(cfg)
    return cfg, jax.eval_shape(model.init,
                               jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
def test_every_big_param_is_sharded(arch):
    """No >= 1M-element parameter may end up fully replicated."""
    cfg, shapes = _param_shapes(arch)
    specs = shd.sanitize_specs(
        shd.param_specs(shapes, cfg.n_experts), shapes, MESH)
    flat_shapes = dict(tree_paths(shapes))
    flat_specs = dict(tree_paths_specs(specs, shapes))
    for path, leaf in flat_shapes.items():
        if leaf.size < 1_000_000:
            continue
        spec = flat_specs[path]
        assert any(ax is not None for ax in spec), \
            f"{arch}: {path} {leaf.shape} replicated"


def tree_paths_specs(specs, shapes):
    """Pair spec leaves with param paths (specs are P leaves)."""
    flat_sh, _ = jax.tree_util.tree_flatten(shapes)
    flat_sp = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    paths = [p for p, _ in tree_paths(shapes)]
    assert len(paths) == len(flat_sp)
    return list(zip(paths, flat_sp))


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh", [MESH, MESH3])
def test_specs_divide_mesh(arch, mesh):
    """After sanitisation every sharded dim divides its mesh axes — the
    exact condition jit in_shardings enforces."""
    cfg, shapes = _param_shapes(arch)
    specs = shd.sanitize_specs(
        shd.param_specs(shapes, cfg.n_experts), shapes, mesh)
    for (path, leaf), (_, spec) in zip(tree_paths(shapes),
                                       tree_paths_specs(specs, shapes)):
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        for size, ax in zip(leaf.shape, dims):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            assert size % total == 0, (arch, path, leaf.shape, spec)


def test_cache_specs_head_fallback():
    """kv-heads smaller than the model axis shard the sequence instead."""
    cfg = configs.get_config("mixtral-8x22b", dtype="bfloat16",
                             param_dtype="bfloat16")
    model = BlockDiffLM(cfg)
    caches = jax.eval_shape(functools.partial(model.make_caches, 128, 32768))
    specs = shd.cache_specs(caches, MESH, shard_seq=False)
    flat = dict(tree_paths_specs(specs, caches))
    kspec = flat["groups/l0/k"]
    assert kspec[-2] is None and kspec[-3] == "model"  # seq over model


def test_cache_specs_long_context_seq_sharding():
    cfg = configs.get_config("gemma2-27b", dtype="bfloat16",
                             param_dtype="bfloat16")
    model = BlockDiffLM(cfg)
    caches = jax.eval_shape(functools.partial(model.make_caches, 1, 524288))
    specs = shd.cache_specs(caches, MESH, shard_seq=True)
    flat = dict(tree_paths_specs(specs, caches))
    kspec = flat["groups/l0/k"]
    assert kspec[-4] is None  # batch 1 unsharded
    assert "data" in str(kspec[-3])  # sequence over data


def test_sanitizer_drops_indivisible():
    shapes = {"w": jax.ShapeDtypeStruct((10, 32), jnp.float32)}
    specs = {"w": P("model", "data")}
    out = shd.sanitize_specs(specs, shapes, MESH)
    assert out["w"] == P(None, "data")

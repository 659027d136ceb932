"""Substrate layers: optimizer, checkpoint IO, data pipeline, server."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.io import load_pytree, save_pytree
from repro.data.math_tasks import check_answer, parse_answer, sample_problem
from repro.data.pipeline import MathTaskDataset, pad_to_block
from repro.data.tokenizer import ByteTokenizer
from repro.optim import adamw
from repro.optim.schedule import cosine_schedule
from repro.serving.server import ModelServer, OfflineWeightStore

import random


# ------------------------------ optimizer ---------------------------------


def test_adamw_quadratic_convergence():
    cfg = adamw.AdamWConfig(lr=0.1, clip_norm=0.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = adamw.init_state(cfg, params)
    for _ in range(300):
        grads = {"x": 2 * params["x"]}
        params, state, _ = adamw.apply_updates(cfg, params, grads, state)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_adamw_clip_norm():
    cfg = adamw.AdamWConfig(lr=1.0, clip_norm=1.0)
    params = {"x": jnp.zeros((4,))}
    state = adamw.init_state(cfg, params)
    _, _, m = adamw.apply_updates(cfg, params, {"x": jnp.full((4,), 100.0)},
                                  state)
    assert float(m["grad_norm"]) == pytest.approx(200.0)


def test_adamw_bf16_state_dtype():
    cfg = adamw.AdamWConfig(state_dtype="bfloat16")
    params = {"x": jnp.zeros((4,), jnp.bfloat16)}
    state = adamw.init_state(cfg, params)
    assert state["m"]["x"].dtype == jnp.bfloat16


def test_cosine_schedule_shape():
    fn = cosine_schedule(1e-3, 100, warmup_steps=10)
    assert float(fn(jnp.array(5))) == pytest.approx(5e-4)
    assert float(fn(jnp.array(10))) == pytest.approx(1e-3)
    assert float(fn(jnp.array(100))) == pytest.approx(0.0, abs=1e-9)


# ------------------------------ checkpoint --------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "b": {"c": jnp.array([1, 2], jnp.int32)}}
    path = os.path.join(tmp_path, "ck.msgpack")
    save_pytree(path, tree)
    out = load_pytree(path, tree)
    for k, l in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(k, np.float32),
                                      np.asarray(l, np.float32))
        assert k.dtype == l.dtype


# ------------------------------ tokenizer / data --------------------------


@settings(max_examples=50, deadline=None)
@given(st.text(max_size=64))
def test_tokenizer_roundtrip(text):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(text)) == text


def test_math_problem_verifiable():
    rng = random.Random(0)
    for _ in range(100):
        p = sample_problem(rng)
        assert check_answer(p.full, p.answer)
        assert parse_answer("no answer here") is None
        assert not check_answer(p.full, p.answer + 1)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 200), st.sampled_from([4, 8, 16]))
def test_pad_to_block(n, bsz):
    ids = list(range(n))
    out = pad_to_block(ids, bsz, 0)
    assert len(out) % bsz == 0
    assert out[:n] == ids
    assert len(out) - n < bsz


def test_sft_batches_block_aligned():
    tok = ByteTokenizer()
    ds = MathTaskDataset(tok, block_size=16, seq_len=128, seed=0)
    b = next(ds.sft_batches(4))
    assert b.tokens.shape == (4, 128)
    # prompt region ends on a block boundary
    plens = b.prompt_mask.sum(axis=1)
    assert (plens % 16 == 0).all() and (plens > 0).all()
    vlens = b.valid.sum(axis=1)
    assert (vlens % 16 == 0).all()
    # valid covers the prompt + body
    assert ((b.tokens != 0).sum(axis=1) <= vlens).all()


# ------------------------------ server ------------------------------------


def test_server_inplace_update_no_io():
    params = {"w": jnp.ones((8, 8))}
    srv = ModelServer(params)
    assert srv.version == 0
    v = srv.update_weights({"w": jnp.zeros((8, 8))})
    assert v == 1
    assert float(srv.params["w"].sum()) == 0.0


def test_offline_store_roundtrips_through_fs(tmp_path):
    params = {"w": jnp.full((8, 8), 3.0)}
    store = OfflineWeightStore(params, root=str(tmp_path))
    p1 = store.params
    np.testing.assert_array_equal(np.asarray(p1["w"]),
                                  np.asarray(params["w"]))
    store.update_weights({"w": jnp.full((8, 8), 4.0)})
    assert float(store.params["w"][0, 0]) == 4.0
    # the live version's file actually exists on disk (the Fig 5a IO
    # cost is real); superseded ones are reaped (test_offline_store_gc)
    assert os.listdir(tmp_path) == [f"ckpt_{store.version}.msgpack"]
    assert os.path.getsize(tmp_path / f"ckpt_{store.version}.msgpack") > 0
    assert store.load_seconds > 0


# ------------------------------ compile cache ------------------------------


def test_compile_cache_placement(monkeypatch, tmp_path):
    """An outside JAX_COMPILATION_CACHE_DIR wins and nothing is set in
    code; without it the cache goes to the fixed, gitignored
    ``.jax_cache`` of the checkout — never a per-run path."""
    from pathlib import Path
    from repro.launch import compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compile_cache.configure()
        assert first == compile_cache.configure()
        assert jax.config.jax_compilation_cache_dir == first
        root = Path(__file__).resolve().parents[1]
        assert Path(first) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)

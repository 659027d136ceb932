"""SFT step's attention tile counts: counted inside the jitted step and
pulled with its metrics, with no eager replay of the layout on the host."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.block_diffusion import sft_loss
from repro.core.masks import dirl_layout, sample_sft_noise
from repro.kernels.ops import layout_tile_counts, layout_tile_stats
from repro.models.config import ModelConfig
from repro.models.model import BlockDiffLM
from repro.optim import adamw
from repro.sft import trainer as sft_trainer

FRACTIONS = ("visit_fraction", "partial_fraction", "full_fraction")
B, L, BSZ = 2, 256, 8     # the dup layout's 512 positions: 4 x 4 tiles


def _cfg(remat: bool) -> ModelConfig:
    return ModelConfig(d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                       d_ff=64, vocab_size=64, block_size=BSZ,
                       attn_impl="pallas", remat=remat)


def _batch(seed: int, length: int = L) -> dict:
    """Prompts and padding of per-seed lengths (the first row unpadded),
    so the tile maps hold empty, partial and full tiles and differ
    between seeds."""
    rs = np.random.RandomState(seed)
    pos = np.arange(length)[None, :]
    plen = rs.randint(8, length // 2, size=(B, 1))
    vlen = rs.randint(3 * length // 4, length + 1, size=(B, 1))
    vlen[0] = length
    return {"tokens": jnp.asarray(rs.randint(4, 60, size=(B, length))),
            "prompt_mask": jnp.asarray(pos < plen),
            "valid": jnp.asarray(pos < vlen)}


def _host_stats(batch: dict, key) -> tuple:
    """The layout ``sft_loss`` builds for this batch and key, replayed
    eagerly, and its tile stats."""
    steps, _, _ = sample_sft_noise(key, batch["tokens"],
                                   batch["prompt_mask"], batch["valid"],
                                   block_size=BSZ)
    _, meta, _ = dirl_layout(batch["tokens"], steps, batch["valid"],
                             block_size=BSZ,
                             mask_token=_cfg(False).resolved_mask_token,
                             noised=True)
    return meta, layout_tile_stats(meta)


@pytest.fixture(scope="module")
def trainers():
    made = {}

    def get(remat: bool) -> sft_trainer.SFTTrainer:
        if remat not in made:
            model = BlockDiffLM(_cfg(remat))
            made[remat] = sft_trainer.SFTTrainer(
                model, adamw.AdamWConfig(lr=1e-3),
                model.init(jax.random.PRNGKey(1)))
        return made[remat]
    return get


@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("tile", [64, 128])
@pytest.mark.parametrize("seed", [0, 1])
def test_layout_tile_counts_jitted_match_host_stats(seed, tile, window):
    meta, _ = _host_stats(_batch(seed), jax.random.PRNGKey(seed))
    want = layout_tile_stats(meta, tq=tile, tk=tile, window=window)
    got = jax.jit(lambda m: layout_tile_counts(
        m, tq=tile, tk=tile, window=window))(meta)
    assert got["tiles_visited"].dtype == jnp.int32
    assert got["tiles_full"].dtype == jnp.int32
    assert int(got["tiles_total"]) == want["tiles_total"]
    assert int(got["tiles_visited"]) == want["tiles_visited"]
    assert int(got["tiles_full"]) == want["tiles_full"]


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("remat", [False, True])
def test_step_tile_fractions_equal_the_host_replay(trainers, remat, seed):
    """train_step's attn_tile_* are exactly layout_tile_stats of the
    step's own layout (same batch, same key), and the raw counts stay
    inside the trainer."""
    tr = trainers(remat)
    batch, key = _batch(seed), jax.random.PRNGKey(100 + seed)
    out = tr.train_step(batch, key)
    _, want = _host_stats(batch, key)
    assert 0 < want["full_fraction"] < want["visit_fraction"] < 1
    for f in FRACTIONS:
        assert out[f"attn_tile_{f}"] == want[f]
        assert tr.metrics.get(f"attn_tile_{f}").value == want[f]
    assert not [k for k in out if k.startswith("tiles_")]
    assert out["step_traces"] == 1 and np.isfinite(out["loss"])


def test_second_step_does_no_eager_layout_work(trainers, monkeypatch):
    """After the compiling step, a step replays nothing of its layout on
    the host: the layout helpers in the trainer's namespace may raise,
    and ``_tile_stats`` (which the benchmark wraps by name) runs once."""
    tr = trainers(False)
    tr.train_step(_batch(2), jax.random.PRNGKey(2))

    def boom(*a, **kw):
        raise AssertionError("eager layout work after the first step")
    for name in ("sample_sft_noise", "dirl_layout", "layout_tile_stats"):
        monkeypatch.setattr(sft_trainer, name, boom, raising=False)
    calls = []
    tile_stats = tr._tile_stats

    def counted(*a, **kw):
        calls.append(a)
        return tile_stats(*a, **kw)
    monkeypatch.setattr(tr, "_tile_stats", counted)
    out = tr.train_step(_batch(3), jax.random.PRNGKey(3))
    assert len(calls) == 1
    assert out["step_traces"] == 1 and tr._step.n_traces == 1
    assert 0 < out["attn_tile_visit_fraction"] <= 1


def test_tracer_layout_reports_no_tile_fractions(monkeypatch):
    """layout="tracer" counts no tiles: neither attn_tile_* nor raw
    counts reach the step's output."""
    plen = 8

    def static_prompt_loss(model, params, batch, rng, *, layout):
        # the tracer layout needs its prompt length as a Python int
        return sft_loss(model, params, {**batch, "prompt_len_static": plen},
                        rng, layout=layout)
    monkeypatch.setattr(sft_trainer, "sft_loss", static_prompt_loss)
    model = BlockDiffLM(_cfg(False))
    tr = sft_trainer.SFTTrainer(model, adamw.AdamWConfig(lr=1e-3),
                                model.init(jax.random.PRNGKey(1)),
                                layout="tracer")
    n = 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (B, n),
                                          4, 60),
             "prompt_mask": jnp.broadcast_to(jnp.arange(n) < plen, (B, n)),
             "valid": jnp.ones((B, n), bool)}
    out = tr.train_step(batch, jax.random.PRNGKey(5))
    assert np.isfinite(out["loss"]) and out["step_traces"] == 1
    assert not [k for k in out if k.startswith(("attn_tile_", "tiles_"))]

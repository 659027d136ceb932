"""The readers of the program's captured spans (``ctx_walk_share``,
``denoise_forward_share``, ``host_tail_ms``) on a hand-built record,
counted by hand; on an empty record, and on a program that keeps none,
they give nothing."""

from __future__ import annotations

import types

import pytest

from bench.harness import common
from repro.obs import trace
from repro.obs.trace import Span

READERS = ("ctx_walk_share.rollout", "denoise_forward_share.rollout",
           "host_tail_ms.sft")


def reader(name):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


def ctx(**counters):
    return types.SimpleNamespace(counters=counters)


def span(name, t0, t1, parent=None, **args):
    return Span(name, "x", "x", t0, t1, args, parent=parent,
                recording=True)


def rollout_record():
    """Three ticks of a 4-slot pool, table width 10, s_max 4."""
    out = []
    for i, (ctx_blocks, needed) in enumerate([(7, 5), (11, 3), (14, 2)]):
        tick = span("scheduler.tick", 10.0 * i, 10.0 * i + 9)
        out += [span("scheduler.advance", 10.0 * i + 1, 10.0 * i + 2, tick,
                     ctx_blocks=ctx_blocks, walked_blocks=40, forwards=5),
                span("scheduler.harvest", 10.0 * i + 3, 10.0 * i + 8, tick,
                     completed=0, forwards_needed=needed), tick]
    # a tick with no advance, and an advance whose tick left no harvest
    out.append(span("scheduler.tick", 40.0, 41.0))
    orphan = span("scheduler.tick", 50.0, 51.0)
    out.append(span("scheduler.advance", 50.2, 50.4, orphan,
                    ctx_blocks=3, walked_blocks=40, forwards=5))
    return out


def sft_record():
    """Two steps; times in seconds."""
    out = []
    for t, (put, sync_end, end) in enumerate([(0.010, 0.400, 0.470),
                                              (0.020, 0.410, 0.450)]):
        step = span("sft.step", t, t + end, tokens=4096)
        out += [span("sft.batch_put", t, t + put, step),
                span("sft.dispatch", t + put, t + put + 0.005, step),
                span("sft.sync", t + put + 0.005, t + sync_end, step),
                span("sft.metric_pull", t + sync_end, t + sync_end + 0.02,
                     step),
                span("sft.tile_stats", t + sync_end + 0.02, t + end, step),
                step]
    return out


def test_ctx_walk_share_by_hand(monkeypatch):
    monkeypatch.setattr(trace, "captured", rollout_record)
    v = reader("ctx_walk_share.rollout").read(ctx(trace_ticks=4))
    assert v == pytest.approx(100.0 * (7 + 11 + 14 + 3) / (4 * 40))


def test_denoise_forward_share_by_hand(monkeypatch):
    monkeypatch.setattr(trace, "captured", rollout_record)
    v = reader("denoise_forward_share.rollout").read(ctx())
    assert v == pytest.approx(100.0 * (5 + 3 + 2) / (3 * 5))


def test_host_tail_ms_by_hand(monkeypatch):
    monkeypatch.setattr(trace, "captured", sft_record)
    v = reader("host_tail_ms.sft").read(ctx(trace_steps=2))
    tails = [0.010 + (0.470 - 0.400), 0.020 + (0.450 - 0.410)]
    assert v == pytest.approx(1e3 * sum(tails) / 2)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_on_an_empty_record(name, monkeypatch):
    monkeypatch.setattr(trace, "captured", lambda: [])
    assert reader(name).read(ctx()) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_without_a_record(name, monkeypatch):
    """A program with no ``captured()`` (the parent of this reader)."""
    monkeypatch.delattr(trace, "captured")
    assert reader(name).read(ctx()) is None

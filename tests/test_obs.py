"""Observability layer: metrics registry semantics, span tracer
invariants, request-lifecycle completeness through the scheduler, and
exporter schema validation — plus the two properties the layer must not
break: token byte-parity and the zero-retrace contract with tracing on.
"""

import json

import jax
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.model import BlockDiffLM
from repro.obs import export
from repro.obs.metrics import Counter, Histogram, MetricsRegistry
from repro.obs.trace import Span, Tracer
from repro.serving.engine import EngineStats
from repro.serving.scheduler import SlotScheduler

CFG = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab_size=128, block_size=8,
                  attn_impl="structured")
BSZ = CFG.block_size
MAX_LEN = 48


@pytest.fixture(scope="module")
def setup():
    model = BlockDiffLM(CFG)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 4, 100))
    pblocks = np.array([2, 1, 2, 1], np.int32)
    return model, params, prompt, pblocks


# ========================================================== registry


def test_counter_monotonic():
    reg = MetricsRegistry("t")
    c = reg.counter("ticks", "tick count")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registry_reset_spares_counters():
    reg = MetricsRegistry("t")
    c = reg.counter("done")
    g = reg.gauge("active")
    h = reg.histogram("lat", reservoir=8)
    c.inc(5)
    g.set(3)
    h.observe(1.0)
    reg.reset()
    assert c.value == 5          # monotonic: survives registry reset
    assert g.value == 0
    assert len(h) == 0 and h.count == 0


def test_labeled_family():
    reg = MetricsRegistry("t")
    fam = reg.histogram("phase_seconds", labelnames=("phase",))
    fam.labels(phase="rollout").observe(1.0)
    fam.labels(phase="train").observe(2.0)
    assert fam.labels(phase="rollout").count == 1
    by_labels = {s.labels: s.value for s in reg.collect()}
    assert by_labels[(("phase", "rollout"),)]["count"] == 1
    assert by_labels[(("phase", "train"),)]["sum"] == 2.0


def test_bind_storage_views_dataclass_field():
    """The bind=(obj, attr) design: plain attribute mutation and the
    registry see ONE value — the scheduler keeps writing
    ``stats.ticks += 1`` and collect() reports it."""

    class Box:
        ticks = 0

    box = Box()
    reg = MetricsRegistry("t")
    c = reg.counter("ticks", bind=(box, "ticks"))
    box.ticks += 7
    assert c.value == 7
    c.inc(2)
    assert box.ticks == 9
    (s,) = reg.collect()
    assert s.name == "t_ticks" and s.value == 9


def test_histogram_bounded_reservoir_and_percentiles():
    h = Histogram("lat", reservoir=100)
    for v in range(1000):
        h.observe(float(v))
    assert len(h) == 100                      # bounded window
    assert h.count == 1000 and h.sum == sum(range(1000))
    assert h.maxlen == 100
    # recent-window percentiles: values 900..999
    assert h.percentile(50) == pytest.approx(949.5)
    assert 990 <= h.percentile(99) <= 999
    # deque-compatible legacy surface
    h2 = Histogram("lat2", reservoir=4)
    h2.append(1)
    assert list(h2) == [1] and bool(h2)


def test_engine_stats_latency_p99():
    s = EngineStats()
    for v in range(1, 101):
        s.latencies.append(v)
    assert s.latency_p50 == pytest.approx(50.5)
    assert s.latency_p99 == pytest.approx(np.percentile(range(1, 101), 99))
    names = {smp.name for smp in s.registry.collect()}
    assert "dirl_engine_latency_ticks" in names


# ============================================================ tracer


def test_span_nesting_and_tracks():
    tr = Tracer()
    with tr.span("outer", cat="scheduler", track="scheduler"):
        with tr.span("inner", cat="scheduler", track="scheduler"):
            pass
    inner, outer = tr.snapshot()              # inner closes first
    assert inner.name == "inner" and outer.name == "outer"
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_ring_eviction_counts_dropped():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4 and tr.dropped == 6
    assert [sp.name for sp in tr.snapshot()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_disabled_tracer_still_times():
    """Engine wall-time comes from span durations, so a disabled
    tracer must still measure — it just records nothing."""
    tr = Tracer(enabled=False)
    with tr.span("work") as sp:
        sum(range(1000))
    assert sp.dur > 0
    assert len(tr) == 0
    tr.begin("k", "lifecycle")
    assert tr.end("k") is None and tr.n_open == 0


def test_begin_end_lifecycle_merges_args():
    tr = Tracer()
    tr.begin(("req", 0), "req 0", cat="request", track="slot 0", uid=0)
    tr.amend(("req", 0), slot=0)
    sp = tr.end(("req", 0), finish_reason="eos")
    assert sp.args == {"uid": 0, "slot": 0, "finish_reason": "eos"}
    assert tr.end(("req", 0)) is None         # idempotent close


# ==================================== scheduler lifecycle + parity


def _drain(sched, prompt, pblocks, params, budget=3):
    keys = jax.random.split(jax.random.PRNGKey(7), prompt.shape[0])
    for i in range(prompt.shape[0]):
        sched.submit(prompt[i], int(pblocks[i]), keys[i],
                     max_new_blocks=budget)
    return {c.uid % prompt.shape[0]: c for c in sched.run(params)}


def test_lifecycle_completeness_under_deferral(setup):
    """A page pool too small for concurrent admission defers requests;
    every request must still end with a closed decode span carrying the
    finish_reason / slot / prefix-hit / kernel-mode labels, and defer
    markers must land on the scheduler track."""
    model, params, prompt, pblocks = setup
    K = MAX_LEN // BSZ
    sched = SlotScheduler(model, n_slots=4, max_len=MAX_LEN, s_max=4,
                          mode="dynamic", tau=0.6, temperature=1.0,
                          eos_id=1, cache="paged", n_pages=2 * K + 1,
                          kernel="pallas", trace=True)
    comps = _drain(sched, prompt, pblocks, params)
    assert len(comps) == 4
    assert sched.stats.deferred > 0           # the pool did defer
    assert sched.tracer.n_open == 0           # every lifecycle closed
    spans = sched.tracer.snapshot()
    names = {sp.name for sp in spans}
    assert {"scheduler.tick", "scheduler.admit", "scheduler.advance",
            "scheduler.harvest", "scheduler.defer"} <= names
    decode = {sp.args["uid"]: sp for sp in spans
              if sp.cat == "request" and sp.track.startswith("slot")}
    assert sorted(decode) == [0, 1, 2, 3]
    for sp in decode.values():
        for label in ("finish_reason", "slot", "hit_blocks",
                      "kernel_mode"):
            assert label in sp.args, (sp.name, label)
        assert sp.dur > 0
    queued = [sp for sp in spans if sp.track == "queue"]
    assert len(queued) == 4
    defers = [sp for sp in spans if sp.name == "scheduler.defer"]
    assert all(sp.track == "scheduler" for sp in defers)


def test_tracing_preserves_bytes_and_single_trace(setup):
    """Tracing on vs off: token-identical completions and the advance
    still traces exactly once — observability is free of semantic and
    retrace cost."""
    model, params, prompt, pblocks = setup
    comps = {}
    for traced in (False, True):
        sched = SlotScheduler(model, n_slots=2, max_len=MAX_LEN, s_max=4,
                              mode="dynamic", tau=0.6, temperature=1.0,
                              eos_id=1, cache="paged", trace=traced)
        _drain(sched, prompt, pblocks, params)       # warm
        sched.stats = type(sched.stats)()
        comps[traced] = _drain(sched, prompt, pblocks, params)
        assert sched.n_advance_traces == 1, sched.n_advance_traces
    for uid, c in comps[False].items():
        t = comps[True][uid]
        hi = (c.prompt_blocks + c.gen_blocks) * BSZ
        assert c.gen_blocks == t.gen_blocks
        np.testing.assert_array_equal(c.tokens[:hi], t.tokens[:hi])


def test_stats_reset_gives_fresh_registry():
    """The warmup idiom ``sched.stats = type(sched.stats)()`` must
    produce a working registry bound to the NEW object."""
    s1 = EngineStats()
    s1.rollouts += 3
    s2 = type(s1)()
    assert s2.rollouts == 0
    s2.rollouts += 1
    by_name = {smp.name: smp.value for smp in s2.registry.collect()}
    assert by_name["dirl_engine_rollouts"] == 1


# ========================================================== exporters


def _spans():
    tr = Tracer(clock=iter(np.arange(1.0, 9.0, 0.5).tolist()).__next__)
    with tr.span("tick", cat="scheduler", track="scheduler"):
        with tr.span("advance", cat="scheduler", track="scheduler"):
            pass
    tr.begin(("d", 0), "req 0", cat="request", track="slot 0", uid=0)
    tr.begin(("d", 1), "req 1", cat="request", track="slot 1", uid=1)
    tr.end(("d", 0), finish_reason="eos")
    tr.end(("d", 1), finish_reason="budget")
    tr.instant("defer", cat="scheduler", track="scheduler")
    return tr.snapshot()


def test_chrome_trace_schema_and_slot_tracks(tmp_path):
    path = tmp_path / "run.trace.json"
    export.write_chrome_trace(path, _spans(), metadata={"tool": "test"})
    payload = export.validate_chrome_trace(path)
    events = payload["traceEvents"]
    threads = {e["args"]["name"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"scheduler", "slot 0", "slot 1"} <= threads
    complete = [e for e in events if e["ph"] == "X"]
    assert all(isinstance(e["ts"], int) and e["dur"] >= 1
               for e in complete)
    reqs = {e["name"]: e for e in complete if e["cat"] == "request"}
    assert reqs["req 0"]["args"]["finish_reason"] == "eos"
    assert payload["otherData"]["schema_version"] == \
        export.TRACE_SCHEMA_VERSION


def test_chrome_trace_validation_rejects_corruption(tmp_path):
    path = tmp_path / "bad.trace.json"
    export.write_chrome_trace(path, _spans())
    payload = json.loads(path.read_text())
    payload["traceEvents"][1]["ph"] = "Q"
    path.write_text(json.dumps(payload))
    with pytest.raises(ValueError):
        export.validate_chrome_trace(path)


def test_metrics_json_roundtrip(tmp_path):
    s = EngineStats()
    s.rollouts += 2
    s.latencies.append(4)
    path = tmp_path / "m.json"
    export.write_metrics_json(path, s.registry)
    payload = export.validate_metrics_json(path)
    by_name = {m["name"]: m for m in payload["metrics"]}
    assert by_name["dirl_engine_rollouts"]["value"] == 2
    assert by_name["dirl_engine_latency_ticks"]["value"]["count"] == 1


def test_prometheus_text(tmp_path):
    reg = MetricsRegistry("dirl_test")
    reg.counter("ticks", "tick count").inc(3)
    reg.histogram("lat", reservoir=8).observe(2.0)
    reg.info("kernel_mode", "exec mode").set("interpret")
    text = export.prometheus_text(reg)
    assert "# TYPE dirl_test_ticks counter" in text
    assert "dirl_test_ticks 3" in text
    assert "dirl_test_lat_count 1" in text
    assert 'quantile="0.99"' in text
    assert 'dirl_test_kernel_mode_info{value="interpret"} 1' in text


def test_jsonl_dump(tmp_path):
    path = tmp_path / "spans.jsonl"
    n = export.write_jsonl(path, _spans())
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == n == 5
    assert all({"name", "track", "t0", "t1", "dur", "args"} <= set(ln)
               for ln in lines)


# =========================================== spans under a profiler capture


def _read_xplane(logdir):
    """``bench.harness.trace.read_xplane``, loaded by path (the harness
    is not a package of the program)."""
    import importlib.util
    import sys
    from pathlib import Path
    name = "_bench_harness_trace"
    if name not in sys.modules:
        path = Path(__file__).resolve().parents[1] / "bench" / "harness" \
            / "trace.py"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod         # its dataclasses look it up
        spec.loader.exec_module(mod)
    return sys.modules[name].read_xplane(logdir)


class _AdvanceProbe:
    """Stands in for the pool advance's guard: before each call notes
    the live slots' committed blocks (the host mirror) and, after it,
    each live row's denoise steps (``n_denoise`` pulled on both sides)."""

    def __init__(self, sched):
        self.sched, self.guard = sched, sched._advance
        self.ctx, self.needed = [], []

    @property
    def n_traces(self):
        return self.guard.n_traces

    def __call__(self, params, st):
        s = self.sched
        live = [i for i, r in enumerate(s._slot_req) if r is not None]
        self.ctx.append(sum(s._slot_blk[i] for i in live))
        before = np.array(st.n_denoise)
        out = self.guard(params, st)
        used = np.array(out.n_denoise)[live] - before[live]
        self.needed.append(1 + int(used.max()))
        return out


def _churn(sched, params):
    """Six requests over two slots, budgets 1-3 blocks: three prompts
    whose first block is shared, two members each (cold admissions,
    suffix prefills and full hits, evictions every few ticks)."""
    rng = np.random.default_rng(5)
    head = rng.integers(4, 100, BSZ)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    for i in range(6):
        tail = rng.integers(4, 100, BSZ) if i % 2 == 0 else tail
        prompt = np.concatenate([head, tail]).astype(np.int32)
        sched.submit(prompt, 2, keys[i], max_new_blocks=1 + i % 3)
    return {c.uid: c for c in sched.run(params)}


def _sched(model):
    return SlotScheduler(model, n_slots=2, max_len=MAX_LEN, s_max=4,
                         mode="dynamic", tau=0.6, temperature=1.0,
                         eos_id=1, cache="paged", kernel="pallas",
                         prefix_cache=True, n_pages=9)


@pytest.fixture(scope="module")
def captured_run(setup, tmp_path_factory):
    """The same churn run twice on fresh pools: with nothing recording,
    and under a CPU ``jax.profiler`` capture (the tracer disabled)."""
    from repro.obs import trace
    model, params, _, _ = setup
    plain = _sched(model)
    comps_off = _churn(plain, params)
    sched = _sched(model)
    probe = _AdvanceProbe(sched)
    sched._advance = probe
    logdir = tmp_path_factory.mktemp("capture")
    jax.profiler.start_trace(str(logdir))
    try:
        comps_on = _churn(sched, params)
    finally:
        jax.profiler.stop_trace()
    return dict(sched=sched, plain=plain, probe=probe, logdir=logdir,
                spans=trace.captured(), comps_off=comps_off,
                comps_on=comps_on)


def test_capture_records_scheduler_spans_with_parents(captured_run):
    spans = captured_run["spans"]
    by = {}
    for sp in spans:
        by.setdefault(sp.name, []).append(sp)
    assert {"scheduler.tick", "scheduler.admit", "scheduler.alloc_pages",
            "scheduler.advance", "scheduler.harvest",
            "scheduler.harvest.sync", "scheduler.harvest.pull"} <= set(by)
    ticks = by["scheduler.tick"]
    assert all(sp.parent is None and sp.recording for sp in ticks)
    for name in ("scheduler.admit", "scheduler.alloc_pages",
                 "scheduler.advance", "scheduler.harvest"):
        assert all(any(sp.parent is t for t in ticks) for sp in by[name])
    for name in ("scheduler.harvest.sync", "scheduler.harvest.pull"):
        assert all(sp.parent.name == "scheduler.harvest"
                   for sp in by[name])
    for name in ("scheduler.prefill", "scheduler.prefill_suffix"):
        assert all(sp.parent.name == "scheduler.admit"
                   for sp in by.get(name, []))
    sched = captured_run["sched"]
    assert len(ticks) == len(by["scheduler.advance"]) == sched.stats.ticks
    admits = [sp for sp in by["scheduler.admit"]
              if sp.args["path"] != "deferred"]
    assert sorted(sp.args["uid"] for sp in admits) == list(range(6))
    assert {"cold", "full_hit"} <= {sp.args["path"] for sp in admits}
    assert all(sp.args["queue_s"] >= 0 for sp in admits)
    assert sum(t.args["admitted"] for t in ticks) == 6
    assert sum(t.args["evicted"] for t in ticks) == 6
    assert sum(t.args["alloc_pages"] for t in ticks) == sum(
        sp.args["pages"] for sp in by["scheduler.alloc_pages"])
    # children close inside their parent
    for sp in spans:
        if sp.parent is not None:
            assert sp.parent.t0 <= sp.t0 and sp.t1 <= sp.parent.t1


def test_capture_counts_match_the_host_mirror(captured_run):
    """Σ ctx_blocks is the live slots' committed blocks before each
    advance; walked_blocks the whole table of every slot; each tick's
    forwards_needed 1 + the most denoise steps a live row used."""
    sched, probe = captured_run["sched"], captured_run["probe"]
    adv = [sp for sp in captured_run["spans"]
           if sp.name == "scheduler.advance"]
    assert [sp.args["ctx_blocks"] for sp in adv] == probe.ctx
    assert all(sp.args["walked_blocks"] == 2 * sched.n_blocks_total
               and sp.args["forwards"] == 5 for sp in adv)
    hv = [sp for sp in captured_run["spans"]
          if sp.name == "scheduler.harvest"]
    assert [sp.args["forwards_needed"] for sp in hv] == probe.needed
    assert sched.stats.ctx_block_ticks == sum(probe.ctx)
    assert sched.stats.denoise_forwards == 5 * sched.stats.ticks


def test_capture_spans_sit_in_the_xplane_on_one_offset(captured_run):
    import glob
    from jax.profiler import ProfileData
    host, _ = _read_xplane(captured_run["logdir"])
    names = [n for _, _, n in host]
    for name in ("scheduler.tick", "scheduler.admit",
                 "scheduler.alloc_pages", "scheduler.advance",
                 "scheduler.harvest"):
        assert names.count(name) == sum(
            sp.name == name for sp in captured_run["spans"]), name
    (path,) = glob.glob(str(captured_run["logdir"] / "**" / "*.xplane.pb"),
                        recursive=True)
    offsets = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("scheduler."):
                    stats = dict(ev.stats)
                    offsets.append(stats["t_host_ns"] - int(ev.start_ns))
    assert len(offsets) == sum(sp.name.startswith("scheduler.")
                               for sp in captured_run["spans"])
    assert max(offsets) - min(offsets) < 1_000_000


def test_capture_leaves_tokens_and_traces_alone(captured_run):
    off, on = captured_run["comps_off"], captured_run["comps_on"]
    assert sorted(off) == sorted(on) == list(range(6))
    for uid, c in off.items():
        t = on[uid]
        hi = (c.prompt_blocks + c.gen_blocks) * BSZ
        assert c.gen_blocks == t.gen_blocks
        np.testing.assert_array_equal(c.tokens[:hi], t.tokens[:hi])
    assert captured_run["sched"].n_advance_traces == 1


def test_nothing_records_outside_a_capture(captured_run, setup,
                                           tmp_path):
    """With the tracer disabled and no capture a span times itself and
    records nothing; a new capture starts a new record."""
    from repro.obs import trace
    before = trace.captured()
    assert before                               # the fixture's capture
    plain = captured_run["plain"]
    _, params, _, _ = setup
    _churn(plain, params)
    assert len(plain.tracer) == 0
    tr = Tracer(enabled=False)
    with tr.span("outside") as sp:
        pass
    assert not sp.recording and sp.dur >= 0
    tr.instant("mark")
    tr.begin("k", "life")
    assert tr.end("k") is None and len(tr) == 0
    after = trace.captured()
    assert len(after) == len(before)
    assert all(a is b for a, b in zip(after, before))
    for name in ("first", "second"):
        jax.profiler.start_trace(str(tmp_path / name))
        try:
            with tr.span(name) as sp:
                pass
        finally:
            jax.profiler.stop_trace()
        assert sp.recording
        assert [s.name for s in trace.captured()] == [name]
    assert len(tr) == 0                         # the ring stays off


def test_sft_step_phases_nest_under_the_step(tmp_path):
    from repro.obs import trace
    from repro.optim import adamw
    from repro.sft.trainer import SFTTrainer
    model = BlockDiffLM(CFG)
    tr = SFTTrainer(model, adamw.AdamWConfig(lr=1e-3),
                    model.init(jax.random.PRNGKey(3)))
    B, L = 2, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (B, L),
                                          4, 100),
             "prompt_mask": np.arange(L)[None, :].repeat(B, 0) < 8,
             "valid": np.ones((B, L), bool)}
    tr.train_step(batch, jax.random.PRNGKey(5))          # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = tr.train_step(batch, jax.random.PRNGKey(6))
    finally:
        jax.profiler.stop_trace()
    spans = trace.captured()
    (step,) = [sp for sp in spans if sp.name == "sft.step"]
    phases = [sp for sp in spans if sp.parent is step]
    assert [sp.name for sp in sorted(phases, key=lambda s: s.t0)] == [
        "sft.batch_put", "sft.dispatch", "sft.sync", "sft.metric_pull",
        "sft.tile_stats"]
    assert step.parent is None and step.args["tokens"] == B * L
    assert tr.step_seconds[-1] <= step.dur
    assert np.isfinite(out["loss"]) and len(tr.tracer) == 0

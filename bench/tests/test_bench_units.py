"""Unit tests of the benchmark's own arithmetic: counts, traffic,
trace reduction, the BENCHMARK.json contract, and the chip check."""

from __future__ import annotations

import itertools
import json
import re

import numpy as np
import pytest

from bench.harness import common, traffic
from bench.harness.trace import Op, summarize

SMOKE = {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
         "d_ff": 256, "vocab_size": 512, "n_layers": 2, "block_size": 4}


def brute_pairs(L, bsz, window):
    """Visible pairs of the duplicated SFT layout, one pair at a time."""
    n = 0
    for copy_q, q in itertools.product((0, 1), range(L)):
        for copy_k, k in itertools.product((0, 1), range(L)):
            qb, kb = q // bsz, k // bsz
            if copy_q == 0:
                vis = copy_k == 0 and kb <= qb
            else:
                vis = (copy_k == 0 and kb < qb) or (copy_k == 1
                                                    and kb == qb)
            if window and q - k >= window:
                vis = False
            n += vis
    return n


@pytest.mark.parametrize("L,bsz,window", [(16, 4, 0), (16, 4, 6),
                                          (24, 8, 0), (32, 8, 12)])
def test_block_diff_pairs_match_a_hand_count(L, bsz, window):
    bd = common.counts("block_diff")
    assert bd.pairs(L, bsz, window) == brute_pairs(L, bsz, window)
    if not window:
        K = L // bsz
        assert bd.pairs(L, bsz) == bsz * bsz * K * (K + 1)


def test_block_diff_per_call_counts():
    bd = common.counts("block_diff")
    m = dict(SMOKE, block_size=8, sliding_window=0)
    w = bd.per_call(m, rows=2, L=64)
    pairs = 2 * 8 * 8 * 8 * 9
    assert w["fwd_flops"] == 4 * pairs * 32 * 4
    assert w["bwd_flops"] == 10 * pairs * 32 * 4
    T = 2 * 64 * 2
    assert w["fwd_bytes"] == 4 * (2 * T * 4 * 32 + 2 * T * 2 * 32)


def test_model_counts_by_hand():
    model = common.counts("model")
    per_layer = 128 * (4 + 2 * 2) * 32 + 4 * 32 * 128 + 3 * 128 * 256
    assert model.layer_weights(SMOKE) == per_layer == 147456
    assert model.matmul_flops(SMOKE, 10) == 2 * 10 * 2 * per_layer
    assert model.head_flops(SMOKE, 3) == 2 * 3 * 128 * 512
    assert model.attn_flops(SMOKE, 5) == 4 * 5 * 32 * 4 * 2


def test_paged_decode_counts_by_hand():
    pd = common.counts("paged_decode")
    # 2 live slots with 3 committed blocks between them, block 4
    flops, nbytes = pd.per_forward(SMOKE, live_slots=2, ctx_blocks=3)
    keys = (3 + 2) * 4
    assert flops == 4 * 4 * keys * 32 * 4 == 40960
    assert nbytes == 4 * (2 * keys * 2 * 32 + 2 * 2 * 4 * 4 * 32)
    f, n = pd.window(SMOKE, 2, 3, forwards_per_tick=5)
    assert (f, n) == (flops * 5 * 2, nbytes * 5 * 2)


def _rollout_spec():
    spec = json.loads((common.BENCH / "traffic" / "rollout_g8.json")
                      .read_text())
    m = json.loads((common.BENCH / "configs" / "sdar-8b.L4.json")
                   .read_text())["model"]
    return spec, m


def test_rollout_traffic_is_seeded_and_holds_the_same_work():
    spec, m = _rollout_spec()
    a = traffic.rollout_groups(spec, m, 2**40 + 3)
    b = traffic.rollout_groups(spec, m, 2**40 + 3)
    c = traffic.rollout_groups(spec, m, 17)
    assert all(np.array_equal(x.prompt, y.prompt) and x.budgets == y.budgets
               and np.array_equal(x.keys, y.keys) for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))

    def work(groups):
        return (sorted(len(g.prompt) for g in groups),
                sorted(itertools.chain.from_iterable(g.budgets
                                                     for g in groups)),
                sum(g.shared_prefix >= 0 for g in groups))
    assert work(a) == work(c)
    lv = traffic.budget_levels(spec["budget_tokens"], m["block_size"])
    assert min(lv) * 4 >= 128 and max(lv) * 4 == 2048   # the clip reached
    met_part_way = spec["n_slots"] // spec["group_size"]
    for g in a:
        assert len(g.prompt) % m["block_size"] == 0
        assert g.temperatures.count(0.0) == (
            spec["group_size"] if g.index < met_part_way
            else spec["greedy_members"])
        assert (g.prompt < m["vocab_size"] - 1).all()


def test_warm_prompts_cover_every_admission():
    spec, m = _rollout_spec()
    warm = traffic.warm_prompts(spec, m, 5)
    widths = [len(p) for p in warm]
    pre = spec["shared_prefix"]["tokens"]
    n_sfx = sum(w > pre for w in spec["prompt_tokens"])
    assert len(warm) == len(spec["prompt_tokens"]) + n_sfx + 1
    assert sorted(set(widths)) == sorted(spec["prompt_tokens"])
    assert np.array_equal(warm[-1], warm[0])   # the full hit


def test_sft_ring_is_seeded():
    t = {"ring": 2, "batch": 2, "seq_len": 32,
         "prompt_tokens": {"min": 4, "max": 8}}
    m = dict(SMOKE)
    a, ka = traffic.sft_ring(t, m, 2**35)
    b, kb = traffic.sft_ring(t, m, 2**35)
    c, _ = traffic.sft_ring(t, m, 1)
    assert np.array_equal(a[1]["tokens"], b[1]["tokens"])
    assert np.array_equal(ka, kb)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    plen = [np.asarray(x["prompt_mask"]).sum(-1) for x in (*a, *c)]
    assert all(((p >= 4) & (p <= 8)).all() for p in plen)
    # every seed: the same prompt lengths, in its own order
    assert sorted(np.concatenate(plen[:2])) == sorted(np.concatenate(plen[2:]))


def test_summarize_a_small_trace_by_hand():
    host = [(0, 100, "bench.window"), (0, 100, "bench.tick"),
            (35, 65, "sft_step")]
    kernel = ('%k.1 = f32[8] custom-call(s32[] %a, f32[8] %b), '
              'custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={s32[], f32[8]{0}}, x')
    dev = {0: [("jit_step/%while.1 = loop", "", 10, 30),
               ("jit_step/%fusion.1 = f", "", 10, 15),
               (f"jit_step/{kernel}", "", 25, 15),
               ("jit_step/%fusion.2 = f", "", 60, 10),
               ("jit_step/%fusion.1 = f", "", 90, 30),
               ("jit_step/%fusion.3 = f", "", -20, 10)]}
    s = summarize(host, dev)
    assert s.window == (0, 100) and s.n_chips == 1
    assert s.busy_ns == 30 + 10 + 10
    assert sorted(s.gaps) == sorted([("bench.tick", 10), ("sft_step", 20),
                                     ("bench.tick", 20)])
    assert s.idle_by_span()[0] == ["bench.tick", 30e-9]
    # the loop holds the first fusion and the kernel: not a leaf
    assert s.top_ops(1) == [["jit_step/%fusion.1", 25e-9]]
    k = [o for o in s.ops if o.is_kernel]
    assert len(k) == 1 and k[0].n_operands == 2 and k[0].leaf
    assert not [o for o in s.ops if "while" in o.name][0].leaf
    assert isinstance(s.ops[0], Op)


def test_reading_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from bench.harness.trace import capture, reduce_trace
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with capture(tmp_path):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(x).block_until_ready()
    s = reduce_trace(tmp_path)
    assert s.window_s > 0
    assert s.n_chips == 0          # no TPU plane on the CPU


def test_run_refuses_a_host_without_a_tpu(capsys):
    from bench.harness import cli
    rc = cli.main(["--workload", "danube3-4b.L2.sft_2k", "--seed",
                   str(2**34), "--seconds", "1", "--trace", "0"])
    assert rc == 3
    assert capsys.readouterr().out == ""


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_files_and_metrics():
    spec = common.load_spec()
    assert spec["paths"] == ["bench"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()
    for c in spec["configs"]:
        cfg = json.loads((common.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = common.find_cell(w["name"], 1, 1.0, False, 0.0, spec)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert (common.BENCH / "limits" / f"{w['name']}.json").exists()


def test_reveal_gaps_by_hand():
    """Block of 4, 4 steps, tau 0.5: a schedule that follows the dynamic
    rule reads 0, a near-tie reads its gap, and a whole block at step 0
    or a step that reveals nothing reads far above."""
    from bench.reference.dense_gqa import EMPTY_STEP, reveal_gaps
    conf = np.array([[[0.1, 0.3, 0.2, 0.05]] * 4])      # (1, s_max, bsz)
    conf[0, 1, 3] = 0.7                   # at step 1 one rises above tau
    lc = np.log(conf)
    ok = np.array([[3, 0, 2, 1]])
    assert reveal_gaps(lc, ok, 0.5, 4)[0] == 0.0
    # step 0 revealed position 2 (0.2) over position 1 (0.3)
    swap = np.array([[3, 2, 0, 1]])
    assert np.isclose(reveal_gaps(lc, swap, 0.5, 4)[0], np.log(0.3 / 0.2))
    whole = np.zeros((1, 4), np.int64)
    assert np.isclose(reveal_gaps(lc, whole, 0.5, 4)[0], np.log(0.5 / 0.05))
    # step 1 left position 3 (0.7) masked and revealed nothing
    late = np.array([[3, 0, 2, 3]])
    assert reveal_gaps(lc, late, 0.5, 4)[0] == EMPTY_STEP


def test_paged_decode_bytes_follow_the_dtype():
    pd = common.counts("paged_decode")
    f32 = pd.per_forward(dict(SMOKE, dtype="float32"), 2, 3)
    bf16 = pd.per_forward(dict(SMOKE, dtype="bfloat16"), 2, 3)
    assert bf16 == (f32[0], f32[1] // 2)


def test_rule_reveal_gaps_follow_the_control_confidences():
    """The rule run on the judge's own confidences reads 0; run on
    confidences that rank two positions the other way, it reads the
    judge's gap between them."""
    from bench.reference.dense_gqa import reveal_gaps, rule_reveal_gaps
    conf = np.array([[[0.1, 0.3, 0.2, 0.05]] * 4])
    lc = np.log(conf)
    steps = np.array([[2, 0, 1, 3]])
    assert reveal_gaps(lc, steps, 0.5, 4)[0] == 0.0
    assert rule_reveal_gaps(lc, lc, steps, 0.5, 4)[0] == 0.0
    swapped = lc[..., [0, 2, 1, 3]]
    assert np.isclose(rule_reveal_gaps(lc, swapped, steps, 0.5, 4)[0],
                      np.log(0.3 / 0.2))

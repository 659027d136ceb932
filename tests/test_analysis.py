"""HLO analysis utilities (roofline substrate)."""

import pytest

from repro.launch import hlo_analysis as hlo

SAMPLE_HLO = """
  %ag = bf16[8,128,256]{2,1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={1}
  %ar = f32[1024,1024]{1,0} all-reduce(%y), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ars = f32[64,64]{1,0} all-reduce-start(%z), replica_groups={{0,1}}
  %ard = f32[64,64]{1,0} all-reduce-done(%ars)
  %cp = bf16[32,32]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %tup = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%a, %b), replica_groups=[4,2]
"""


def test_collective_stats_parsing():
    st = hlo.collective_stats(SAMPLE_HLO)
    per = st["per_op"]
    assert per["all-gather"]["count"] == 1
    assert per["all-reduce"]["count"] == 2        # -start counted, -done not
    assert per["collective-permute"]["count"] == 1
    assert per["all-to-all"]["count"] == 1
    # all-gather: 8*128*256*2 bytes * (4-1)/4
    assert per["all-gather"]["bytes"] == int(8 * 128 * 256 * 2 * 3 / 4)
    # all-reduce big: 1024^2*4 * 2 * 7/8
    expect_ar = int(1024 * 1024 * 4 * 2 * 7 / 8) + int(64 * 64 * 4 * 2 / 2)
    assert per["all-reduce"]["bytes"] == expect_ar
    # tuple all-to-all sums both members, n=2 groups of size 2
    assert per["all-to-all"]["bytes"] == int(2 * 16 * 16 * 4 * 1 / 2)
    assert st["total_bytes"] == sum(v["bytes"] for v in per.values())


def test_roofline_terms_and_dominance():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    coll = {"total_bytes": 50e9 * 3}
    t = hlo.roofline_terms(cost, coll, 256)
    assert t["t_compute_s"] == pytest.approx(1.0)
    assert t["t_memory_s"] == pytest.approx(2.0)
    assert t["t_collective_s"] == pytest.approx(3.0)
    assert hlo.dominant_term(t) == "collective"


def test_active_params_moe():
    from repro import configs
    cfg = configs.get_config("mixtral-8x22b")
    total = 140_630_000_000
    act = hlo.active_params(cfg, total)
    # 8 experts top-2 -> roughly (2+overhead)/8 of expert params active
    assert act < 0.45 * total
    dense = configs.get_config("deepseek-7b")
    assert hlo.active_params(dense, 123) == 123


# ===========================================================================
# dirlint: the contract-checking static-analysis pass
# ===========================================================================

import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.analysis import run_all
from repro.analysis.astutils import Project
from repro.analysis import donation, trace_lint
from repro.analysis.guards import TraceGuard
from repro.analysis.kernel_contracts import (Launch, capture_launches,
                                             check_kernels, check_launch,
                                             check_parity_coverage)
from repro.analysis.rules import (Finding, RULES, apply_pragmas,
                                  scan_pragmas)


def _project(tmp_path, files: dict) -> Project:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return Project(tmp_path)


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------- rule registry


def test_rule_registry_complete():
    assert set(RULES) == {
        "trace-branch", "trace-host-pull", "hot-sync", "obs-in-trace",
        "post-donation-read", "kernel-oob-index", "kernel-scratch-tile",
        "kernel-block-shape", "kernel-plan-matrix",
        "kernel-parity-coverage"}
    for rule in RULES.values():
        assert rule.doc


# ------------------------------------------------------- trace hygiene


def test_trace_branch_and_host_pull_fire(tmp_path):
    project = _project(tmp_path, {"mod.py": """
        import jax

        def step(x):
            if x > 0:
                x = x + 1
            y = x.item()
            return x * y

        fast_step = jax.jit(step)
    """})
    findings = trace_lint.run(project)
    assert "trace-branch" in _rules(findings)
    assert "trace-host-pull" in _rules(findings)


def test_static_guards_do_not_fire(tmp_path):
    project = _project(tmp_path, {"mod.py": """
        import jax

        def sized(x, n, p):
            if n > 2:                    # static_argnames
                x = x + n
            if x.ndim == 3:              # shape metadata
                x = x[0]
            if "bias" in p:              # pytree structure
                x = x + p["bias"]
            return x

        jitted = jax.jit(sized, static_argnames=("n",))
    """})
    assert trace_lint.run(project) == []


def test_hot_sync_fires_in_hot_path(tmp_path):
    project = _project(tmp_path, {"serving/engine.py": """
        import jax

        class RolloutEngine:
            def stream(self, x):
                jax.block_until_ready(x)
                return x
    """})
    findings = trace_lint.run(project)
    assert _rules(findings) == {"hot-sync"}


# ------------------------------------------------------- obs-in-trace

_OBS_FIXTURE = {"obs/__init__.py": "", "obs/trace.py": """
    class Tracer:
        def span(self, name):
            pass

        def begin(self, key, name):
            pass
"""}


def test_obs_in_trace_fires(tmp_path):
    """Every detection route: `.tracer.<span-API>` chains, obs
    constructors, and method calls on a locally bound obs handle."""
    project = _project(tmp_path, {**_OBS_FIXTURE, "eng.py": """
        import jax
        from repro.obs.trace import Tracer

        class Eng:
            def hot(self, x):
                with self.tracer.span("step"):
                    return x * 2

            def hot2(self, x):
                t = Tracer()
                t.begin("k", "n")
                return x

            def drive(self, x):
                return jax.jit(self.hot)(x) + jax.jit(self.hot2)(x)
    """})
    findings = [f for f in trace_lint.run(project)
                if f.rule == "obs-in-trace"]
    assert len(findings) == 3
    msgs = " ".join(f.message for f in findings)
    assert "self.tracer.span" in msgs          # chain on conventional name
    assert "repro.obs.trace.Tracer" in msgs    # constructor via from-import
    assert "t.begin" in msgs                   # local obs handle


def test_obs_host_side_is_clean(tmp_path):
    """Obs calls *around* the dispatch — the scheduler pattern — stay
    unflagged: only jit-reachable bodies are walked."""
    project = _project(tmp_path, {**_OBS_FIXTURE, "sched.py": """
        import jax
        from repro.obs.trace import Tracer

        class Sched:
            def _kernel(self, x):
                return x + 1

            def step(self, x):
                with self.tracer.span("tick"):
                    return jax.jit(self._kernel)(x)
    """})
    assert "obs-in-trace" not in _rules(trace_lint.run(project))


def test_obs_in_trace_pragma_suppresses(tmp_path):
    src = textwrap.dedent("""
        import jax

        def hot(self, x):
            self.tracer.begin("k", "n")  # dirlint: ok(obs-in-trace)
            return x

        step = jax.jit(hot)
    """)
    project = _project(tmp_path, {"mod.py": src})
    findings = apply_pragmas(
        trace_lint.run(project),
        {str(tmp_path / "mod.py"): scan_pragmas(src)})
    obs = [f for f in findings if f.rule == "obs-in-trace"]
    assert len(obs) == 1 and obs[0].suppressed


# ------------------------------------------------------- donation safety


def test_post_donation_read_fires(tmp_path):
    project = _project(tmp_path, {"mod.py": """
        import jax

        def _adv(state, x):
            return state

        advance = jax.jit(_adv, donate_argnums=(0,))

        def drive(state, x):
            out = advance(state, x)
            return state.tokens
    """})
    findings = donation.run(project)
    assert _rules(findings) == {"post-donation-read"}
    (f,) = findings
    assert "state" in f.message and "advance" in f.message


def test_post_donation_rebind_is_safe(tmp_path):
    project = _project(tmp_path, {"mod.py": """
        import jax

        def _adv(state, x):
            return state

        advance = jax.jit(_adv, donate_argnums=(0,))

        def drive(state, x):
            state = advance(state, x)
            return state.tokens
    """})
    assert donation.run(project) == []


def test_post_donation_consumer_loop_wraparound_fires(tmp_path):
    """The async RL consumer hazard (rl/pipeline/loop.py): the fused
    DiPO step donates the param buffers the weight server still shares,
    so a loop body that pushes the step *output* but forgets to rebind
    its own ``params`` re-reads a dead buffer on the next iteration.
    This is the static face of the runtime guard
    ``ModelServer.params_at`` (StaleParamsError)."""
    project = _project(tmp_path, {"loop.py": """
        import jax

        def _step(params, opt_state, batch):
            return params, opt_state, {}

        step = jax.jit(_step, donate_argnums=(0, 1))

        def consume(server, params, opt_state, batches):
            for batch in batches:
                new_params, opt_state, m = step(params, opt_state, batch)
                server.update_weights(new_params)
            return new_params
    """})
    findings = donation.run(project)
    assert _rules(findings) == {"post-donation-read"}
    (f,) = findings
    assert "params" in f.message and "step" in f.message


def test_post_donation_consumer_rebind_and_push_is_safe(tmp_path):
    """The canonical consumer shape: rebind params from the step output
    in the call statement, push, and re-read live weights through the
    server's versioned surface — no dead-buffer read anywhere."""
    project = _project(tmp_path, {"loop.py": """
        import jax

        def _step(params, opt_state, batch):
            return params, opt_state, {}

        step = jax.jit(_step, donate_argnums=(0, 1))

        def consume(server, params, opt_state, batches):
            for batch in batches:
                params, opt_state, m = step(params, opt_state, batch)
                server.update_weights(params)
                version, live = server.params_versioned()
            return params
    """})
    assert donation.run(project) == []


# ------------------------------------------------------- kernel contracts


def _launch(**kw):
    base = dict(name="k", grid=(3,), num_scalar_prefetch=0,
                in_specs=[], out_specs=[], scratch=[], operands=[],
                out_shapes=[], interpret=True)
    base.update(kw)
    return Launch(**base)


def test_oob_index_map_fires():
    # grid point i=2 maps to rows [16, 24) of a 16-row operand
    bad = _launch(
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        operands=[np.zeros((16, 128), np.float32)])
    findings = check_launch(bad, require_tile=False, path="fix.py",
                            line=1, where="decode")
    assert _rules(findings) == {"kernel-oob-index"}

    ok = _launch(
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        operands=[np.zeros((24, 128), np.float32)])
    assert check_launch(ok, require_tile=False, path="fix.py",
                        line=1, where="decode") == []


def test_misaligned_scratch_fires_only_when_tiled():
    bad = _launch(scratch=[((16, 1), jnp.int32)])
    findings = check_launch(bad, require_tile=True, path="fix.py",
                            line=1, where="prefill")
    assert _rules(findings) == {"kernel-scratch-tile"}
    assert check_launch(bad, require_tile=False, path="fix.py",
                        line=1, where="prefill") == []


def test_misaligned_block_shape_fires_when_compiled():
    """The Mosaic BlockSpec rule, checked on CPU: a (1, 1, 1) block of
    a (2, 4, 4) tile map — the spec the TPU compiler refused in the
    training kernel — is flagged in a compiled launch; interpret mode
    does not enforce the rule, and aligned or full-extent blocks pass."""
    def tm_map(i):
        return (0, i, 0)

    bad = _launch(in_specs=[pl.BlockSpec((1, 1, 1), tm_map)],
                  operands=[np.zeros((2, 4, 4), np.int32)], interpret=False)
    findings = check_launch(bad, require_tile=False, path="fix.py",
                            line=1, where="fwd")
    assert _rules(findings) == {"kernel-block-shape"}
    assert check_launch(dataclasses.replace(bad, interpret=True),
                        require_tile=False, path="fix.py", line=1,
                        where="fwd") == []
    ok = _launch(in_specs=[pl.BlockSpec((1, 8, 4), lambda i: (0, 0, 0)),
                           pl.BlockSpec((1, 8, 128), lambda i: (0, i, 0))],
                 operands=[np.zeros((2, 8, 4), np.int32),
                           np.zeros((2, 24, 256), np.float32)],
                 interpret=False)
    assert check_launch(ok, require_tile=False, path="fix.py", line=1,
                        where="fwd") == []


def test_capture_launches_records_and_short_circuits():
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    with capture_launches() as launches:
        out = pl.pallas_call(
            body, grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        )(jnp.ones((16, 128), jnp.float32))
    assert out.shape == (16, 128)
    assert not out.any()                  # body never ran
    (launch,) = launches
    assert launch.grid == (2,) and launch.name == "body"
    # the patch is scoped: outside the context the real pallas_call is back
    assert "pallas_call" in repr(pl.pallas_call)


def test_kernel_plan_matrix_clean_on_cpu():
    """All four plan_exec combos of both paged kernels (plus
    block-diff) pass bounds/tiling/abstract-eval on a CPU host."""
    assert check_kernels() == []


def test_parity_coverage_clean_and_fires(tmp_path):
    assert check_parity_coverage() == []

    bad = tmp_path / "t.py"
    bad.write_text(textwrap.dedent("""
        def test_decode_only():
            out = paged_decode_attention(q, k, v, block_table=bt)
    """))
    findings = check_parity_coverage(tests_path=bad)
    rules = _rules(findings)
    assert rules == {"kernel-parity-coverage"}
    msgs = " ".join(f.message for f in findings)
    assert "paged_prefill_attention" in msgs     # prefill never exercised
    assert "window" in msgs or "softcap" in msgs  # decode features missing


# ------------------------------------------------------- pragmas


def test_pragma_suppression_same_line_and_above():
    src = ("x = compute()\n"
           "jax.block_until_ready(x)  # dirlint: ok(hot-sync)\n"
           "# dirlint: ok(trace-branch, trace-host-pull)\n"
           "y = float(x)\n")
    pragmas = {"f.py": scan_pragmas(src)}
    out = apply_pragmas(
        [Finding("hot-sync", "f.py", 2, "m"),
         Finding("trace-host-pull", "f.py", 4, "m"),
         Finding("hot-sync", "f.py", 4, "m")], pragmas)
    assert [f.suppressed for f in out] == [True, True, False]


# ------------------------------------------------------- whole repo


def test_repo_has_zero_unsuppressed_findings():
    findings = run_all()
    loud = [f for f in findings if not f.suppressed]
    assert loud == [], "\n".join(f.format() for f in loud)
    # the deliberate, pragma'd syncs are still visible to --verbose
    assert any(f.suppressed and f.rule == "hot-sync" for f in findings)


# ------------------------------------------------------- TraceGuard


def test_traceguard_counts_compiles_not_calls():
    def f(x, y):
        return x + y

    g = TraceGuard(f, name="g")
    a = jnp.ones((4,))
    g(a, a)
    g(a, a)                               # cache hit
    assert g.n_traces == 1
    g(jnp.ones((8,)), jnp.ones((8,)))     # new shape -> retrace
    assert g.n_traces == 2
    assert g.stats() == {"name": "g", "n_traces": 2}
    g.reset()
    assert g.n_traces == 0
    g(a, a)                               # cache survives reset()
    assert g.n_traces == 0


def test_traceguard_static_argnames_bind_positionally():
    def f(x, n):
        return x * n

    g = TraceGuard(f, static_argnames=("n",))
    out = g(jnp.ones((2,)), 3)            # n passed positionally
    assert float(out[0]) == 3.0
    assert g.n_traces == 1
    g(jnp.ones((2,)), 3)
    assert g.n_traces == 1
    g(jnp.ones((2,)), 4)                  # new static value -> retrace
    assert g.n_traces == 2


def test_guard_stats_surface_through_stats_dataclasses():
    from repro.serving.engine import EngineStats
    from repro.serving.scheduler import SchedulerStats
    assert "advance_traces" in {f.name
                                for f in dataclasses.fields(SchedulerStats)}
    assert "advance_traces" in {f.name
                                for f in dataclasses.fields(EngineStats)}

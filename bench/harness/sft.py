"""Driver of the ``sft`` traffic kind: ``SFTTrainer.train_step``.

Set-up builds one trainer from the seed's weights, drives its first
``checked_steps`` steps through the window's own call on ring batches
(reading the first gradient from Adam's state after step 1 and the
parameters' change before step 4 takes them), warms until the step time
settles, and hands that same trainer to the window.  The window counts
whole steps: real tokens of every step that ended in it over the time
from its start to the end of its last step.  After the window the
program's state is freed and the reference replays the checked steps.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from pathlib import Path

from . import common
from .common import log


@dataclasses.dataclass
class Readings:
    losses: list
    grad_norms: dict      # leaf -> norm of the first clipped gradient
    delta_norms: dict     # leaf -> norm of params after the checked steps
    #                       minus the initial ones


def build_model(model_dict: dict):
    from repro.models.config import ModelConfig
    from repro.models.model import BlockDiffLM
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    return BlockDiffLM(ModelConfig(**{k: v for k, v in model_dict.items()
                                      if k in fields}))


def _norm_fns(m: dict, seed: int, b1: float):
    import jax
    import jax.numpy as jnp
    from .weights import make_params

    def norms(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(a)))
                for p, a in flat}

    grad_norms = jax.jit(lambda mstate: norms(
        jax.tree.map(lambda a: a / (1.0 - b1), mstate)))

    def delta_norms(params):
        p0 = make_params(m, seed, jnp.float32)
        return norms(jax.tree.map(jnp.subtract, params, p0))
    return grad_norms, delta_norms


def _host(d: dict) -> dict:
    return {k: float(v) for k, v in d.items()}


def run(cell, timer, compiles, faults=None, control=False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.optim import adamw
    from repro.sft.trainer import SFTTrainer
    from .traffic import sft_ring
    from .weights import check_tree, make_params

    t, m, seed = cell.traffic, cell.model, cell.seed
    model = build_model(m)
    check_tree(m, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    params = make_params(m, seed)
    jax.block_until_ready(params)
    timer.mark("weights_init")
    opt = t["opt"]
    trainer = SFTTrainer(
        model, adamw.AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                                 eps=opt["eps"], clip_norm=opt["clip_norm"]),
        params)
    del params
    batches, keys = sft_ring(t, m, seed)
    keys = [keys[i] for i in range(256)]
    jax.block_until_ready((batches, keys))
    timer.mark("trainer_and_batches")
    if faults:
        faults(trainer)
    grad_norms, delta_norms = _norm_fns(m, seed, opt["b1"])
    R, n_chk = len(batches), t["checked_steps"]
    B, L = t["batch"], t["seq_len"]
    step_no = 0

    def step():
        nonlocal step_no
        out = trainer.train_step(batches[step_no % R], keys[step_no])
        step_no += 1
        return out

    losses, g1, dn = [], None, None
    for i in range(n_chk):
        out = step()
        losses.append(out["loss"])
        if i == 0:
            timer.mark("first_step_compile_or_load")
            g1 = _host(grad_norms(trainer.opt_state["m"]))
    dn = _host(delta_norms(trainer.params))
    timer.mark("checked_steps")
    # warm until three step times agree within ``settle``
    w = t["warm"]
    times = []
    while True:
        t0 = time.perf_counter()
        step()
        times.append(time.perf_counter() - t0)
        last = times[-3:]
        if len(times) >= w["min_steps"] and len(last) == 3 and \
                (max(last) - min(last)) <= w["settle"] * min(last):
            break
        if len(times) >= w["max_steps"]:
            break
    timer.mark(f"warm_{len(times)}_steps")
    traces0 = trainer._step.n_traces
    # set-up's objects leave the collector's generations, so a
    # collection inside the window scans only what the window made
    gc.collect()
    gc.freeze()
    setup_s = timer.total()

    # ---- the measured window -------------------------------------------
    n, nonfinite, step_times = 0, 0, []
    with compiles.window():
        t_start = time.perf_counter()
        t_prev = t_start
        while True:
            out = step()
            now = time.perf_counter()
            step_times.append(now - t_prev)
            t_prev = now
            n += 1
            nonfinite += 0 if common.finite(out["loss"]) else 1
            if now - t_start >= cell.seconds:
                break
        window_s = t_prev - t_start
    tok_s = n * B * L / window_s
    window_compiles = compiles.in_window + (trainer._step.n_traces
                                            - traces0)
    log(f"window: {n} steps in {window_s:.4f} s, {tok_s:.2f} tokens/s; "
        f"step ms min {min(step_times)*1e3:.2f} median "
        f"{sorted(step_times)[n // 2]*1e3:.2f} max "
        f"{max(step_times)*1e3:.2f}; compiles in window {window_compiles}")

    summary = None
    if cell.trace:
        from .common import Spans
        from .trace import capture, reduce_trace
        spans = Spans(True)
        spans.wrap(trainer, "_tile_stats", "bench.tile_stats")
        logdir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        with capture(logdir):
            for _ in range(t["trace_steps"]):
                with spans.span("bench.train_step"):
                    step()
        summary = reduce_trace(logdir)
        shutil.rmtree(logdir, ignore_errors=True)

    devs = jax.devices()
    peak = common.peak_memory(devs)
    del trainer, out
    gc.unfreeze()
    gc.collect()

    # ---- the reference, after the program's state is freed ------------
    from ..reference import dense_gqa as ref
    t_ref = time.perf_counter()
    p0 = make_params(m, seed, jnp.float32)
    rl, rg, rp = ref.sft_steps(p0, m, [batches[i % R] for i in range(n_chk)],
                               keys[:n_chk], opt)
    del p0
    p0 = make_params(m, seed, jnp.float32)
    rd = ref.leaf_norms(jax.tree.map(jnp.subtract, rp, p0))
    del rp, p0
    log(f"reference: {time.perf_counter() - t_ref:.2f} s")
    checks = compare(Readings(losses, g1, dn), Readings(rl, rg, rd))
    return {"e2e": {"sft_tok_s": tok_s, "setup_s": setup_s},
            "attempted": n, "failed": nonfinite,
            "window_compiles": window_compiles, "peak": peak,
            "checks": checks, "summary": summary,
            "counters": {"seq_len": L, "batch": B,
                         "trace_steps": t["trace_steps"]}}


def compare(prog: Readings, ref: Readings) -> dict:
    """The numbers compared, each by its worst case.

    loss: largest relative gap over the checked steps.  grad / delta:
    the worst leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam
    and are left out of the delta.
    """
    import statistics
    loss = max(abs(a - b) / max(abs(b), 1e-30)
               for a, b in zip(prog.losses, ref.losses))
    gmed = statistics.median(ref.grad_norms.values())
    grad = max(abs(prog.grad_norms[k] - v) / max(v, gmed)
               for k, v in ref.grad_norms.items())
    live = [k for k, v in ref.grad_norms.items() if v >= 1e-3 * gmed]
    dmed = statistics.median(ref.delta_norms[k] for k in live)
    delta = max(abs(prog.delta_norms[k] - ref.delta_norms[k])
                / max(ref.delta_norms[k], dmed) for k in live)
    return {"loss_rel_gap": loss, "grad_norm_gap": grad,
            "delta_norm_gap": delta}

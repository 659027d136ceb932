"""Driver of the ``rollout`` traffic kind: G-member rollout groups in a
closed loop through ``RolloutEngine`` (``scheduler.submit`` +
``stream_completions``, the path ``rl/pipeline/producer.py`` rides).

Set-up makes the weights, builds the engine, builds the whole request
list from the seed, compiles every admission the list uses (each cold
width, each (hit, suffix) pair, a full hit) with short warm requests and
runs the loop for ``warm_ticks`` ticks.  The scheduler's per-tick host
scatters compile once per length; the lengths the run will reach are
recorded from the program's own scheduler, run on the CPU over the same
requests with a stand-in model, and each is run once on the chip.  The
window starts at the end of a tick and ends at the end of the first tick
that closes at or after ``--seconds``: every tick in it is whole.

A tick is one ``SlotScheduler.step``: admissions, one pool advance (every
live slot commits one block), harvest.  The harvest reads ``done`` back,
so a tick ends when the device has finished it.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from . import common
from .common import log


@dataclasses.dataclass
class Tick:
    end: float          # perf_counter at the end of the tick
    active: int         # slots that committed a block
    admitted: int       # requests admitted in the tick
    ctx_blocks: int     # committed blocks visible to the live slots


@dataclasses.dataclass
class Done:
    """A harvested request as the client saw it."""
    uid: int
    group: int
    member: int
    temperature: float
    budget: int
    path: str
    received: float
    comp: object


def _gen_cfg(t: dict, m: dict, kernel: str):
    from repro.serving.engine import GenerationConfig
    from .traffic import budget_levels
    max_len = max(t["prompt_tokens"]) \
        + max(budget_levels(t["budget_tokens"], m["block_size"])) \
        * m["block_size"]
    return GenerationConfig(
        max_len=max_len, s_max=t["s_max"], mode=t["mode"], tau=t["tau"],
        temperature=t["temperature"], batching="continuous",
        n_slots=t["n_slots"], cache="paged", kernel=kernel,
        prefix_cache=True, eos_id=-1)


class Exhausted(RuntimeError):
    pass


class Client:
    """The closed-loop client: whole groups of G members, with
    ``queued_groups`` groups always waiting beyond the live slots."""

    def __init__(self, sched, groups, t: dict, bsz: int):
        self.sched, self.it, self.t, self.bsz = sched, iter(groups), t, bsz
        self.meta: dict[int, tuple] = {}

    def params(self, temperature: float, budget: int):
        from repro.serving.engine import SamplingParams
        t = self.t
        return SamplingParams(tau=t["tau"], temperature=temperature,
                              mode=t["mode"], max_new_blocks=budget,
                              eos_id=-1)

    def warm_admissions(self, engine, prompts) -> None:
        """One one-block request per warm prompt, run to the end."""
        zero_key = np.zeros((2,), np.uint32)
        for p in prompts:
            self.sched.submit(p, len(p) // self.bsz, zero_key,
                              params=self.params(1.0, 1))
        for _ in engine.stream_completions():
            pass

    def submit_group(self) -> None:
        g = next(self.it, None)
        if g is None:
            raise Exhausted("request list exhausted; raise n_groups")
        for i in range(self.t["group_size"]):
            uid = self.sched.submit(
                g.prompt, len(g.prompt) // self.bsz, g.keys[i],
                params=self.params(g.temperatures[i], g.budgets[i]))
            self.meta[uid] = (g.index, i, g.temperatures[i], g.budgets[i])

    def start(self) -> None:
        t = self.t
        for _ in range(t["n_slots"] // t["group_size"] + t["queued_groups"]):
            self.submit_group()

    def top_up(self) -> None:
        while self.sched.n_queued < self.t["group_size"]:
            self.submit_group()


def record_lengths(t: dict, model: dict, seed: int, emit) -> None:
    """Drive the program's own scheduler on the CPU, with a stand-in
    model of the cell's block size, through the same client over the
    same requests as a run with ``seed``, and call ``emit(lengths)``
    after each tick of the closed loop with the lengths of the eager
    host scatters seen in it: ``alloc`` (cursor pages given out in a tick),
    ``evict`` (slots freed in a tick), ``wipe`` (pages invalidated in one
    call).  With the stop token off, what the scheduler admits, evicts
    and pages does not depend on the model's numbers.  Ends at the end
    of the request list."""
    import jax
    from repro.serving.engine import RolloutEngine
    from repro.serving.server import ModelServer
    from .sft import build_model
    from .traffic import rollout_groups, warm_prompts
    bsz = model["block_size"]
    m = dict(model, n_layers=1, d_model=16, n_heads=2, n_kv_heads=1,
             head_dim=8, d_ff=32, vocab_size=512, dtype="float32",
             param_dtype="float32", remat=False)
    seen: dict = {"alloc": [], "evict": [], "wipe": []}
    dummy = build_model(m)
    engine = RolloutEngine(dummy,
                           ModelServer(dummy.init(jax.random.PRNGKey(0))),
                           _gen_cfg(t, m, "ref"))
    sched = engine.scheduler
    wipe, alloc, step = (sched._invalidate_pages, sched._alloc_cursor_pages,
                         sched.step)

    def rec_wipe(pages):
        seen["wipe"].append(len(pages))
        return wipe(pages)

    def rec_alloc():
        n0 = sched.stats.page_allocs
        alloc()
        if sched.stats.page_allocs > n0:
            seen["alloc"].append(sched.stats.page_allocs - n0)

    counting = [False]

    def rec_step(params, param_version=0):
        done = step(params, param_version)
        if done:
            seen["evict"].append(len(done))
        if counting[0]:
            emit(seen)
        for v in seen.values():
            v.clear()
        return done
    sched._invalidate_pages, sched._alloc_cursor_pages, sched.step = \
        rec_wipe, rec_alloc, rec_step
    client = Client(sched, rollout_groups(t, model, seed), t, bsz)
    try:
        client.warm_admissions(engine, warm_prompts(t, model, seed))
        counting[0] = True       # the run counts its ticks from here
        client.start()
        for _ in engine.stream_completions():
            client.top_up()
    except Exhausted:
        pass


class LengthRecorder:
    """``record_lengths`` in a child process on the CPU, started with
    set-up so that it runs beside it; ``until(horizon)`` gathers the
    lengths of the first ``horizon`` ticks, then stops the child."""

    def __init__(self, cell):
        import os
        import subprocess
        import sys
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        arg = json.dumps({"traffic": cell.traffic, "model": cell.model,
                          "seed": cell.seed})
        self.proc = subprocess.Popen(
            [sys.executable, str(Path(__file__).with_name("scatters.py")),
             arg], stdout=subprocess.PIPE, env=env, text=True)

    def until(self, horizon: int) -> dict:
        out: dict = {"alloc": set(), "evict": set(), "wipe": set()}
        n = 0
        try:
            while n < horizon:
                line = self.proc.stdout.readline()
                if not line:           # the list's end: no run gets past it
                    if self.proc.wait() != 0:
                        raise RuntimeError("recording the scatter lengths "
                                           "failed")
                    break
                for k, v in json.loads(line).items():
                    out[k].update(v)
                n += 1
        finally:
            self.stop()
        return {k: sorted(v) for k, v in out.items()}

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdout.close()


def _warm_scatters(sched, lengths: dict) -> int:
    """Run each eager scatter of the tick loop once at each length, on
    the live pool's arrays, leaving the scheduler's state as it was."""
    import jax
    import jax.numpy as jnp
    table = sched._state.table
    outs = []
    for n in lengths["alloc"]:
        ix = jnp.asarray(list(range(n)), jnp.int32)
        outs.append(table.at[ix, ix].set(ix))
    for n in lengths["evict"]:
        outs.append(table.at[jnp.asarray(list(range(n)),
                                         jnp.int32)].set(-1))
    kept = sched._state
    for n in lengths["wipe"]:
        sched._invalidate_pages(list(range(1, n + 1)))
        outs.append(sched._state.caches)
        sched._state = kept
    jax.block_until_ready(outs)
    return sum(len(v) for v in lengths.values())


def run(cell, timer, compiles, faults=None, control=False) -> dict:
    recorder = LengthRecorder(cell)
    try:
        return _run(cell, timer, compiles, faults, control, recorder)
    finally:
        recorder.stop()


def _run(cell, timer, compiles, faults, control, recorder) -> dict:
    import jax
    from repro.serving.engine import RolloutEngine
    from repro.serving.server import ModelServer
    from .sft import build_model
    from .traffic import rollout_groups, warm_prompts
    from .weights import check_tree, make_params

    t, m, seed = cell.traffic, cell.model, cell.seed
    bsz = m["block_size"]
    model = build_model(m)
    check_tree(m, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    params = make_params(m, seed)
    jax.block_until_ready(params)
    timer.mark("weights_init")
    server = ModelServer(params)
    del params
    groups = rollout_groups(t, m, seed)
    warm = warm_prompts(t, m, seed)
    engine = RolloutEngine(model, server, _gen_cfg(t, m, "pallas"))
    sched = engine.scheduler
    timer.mark("engine")
    if faults:
        faults(engine)
    client = Client(sched, groups, t, bsz)

    # every admission program this mix uses, and the pool advance
    client.warm_admissions(engine, warm)
    timer.mark("warm_admissions")

    # ---- instrumentation from the benchmark's side ----------------------
    spans = common.Spans(cell.trace)
    ticks: list[Tick] = []
    paths: dict[int, str] = {}
    ctx_now = [0]
    orig_advance = sched._advance
    guards = (orig_advance, sched._admit_jit, sched._admit_hit_jit,
              sched._admit_suffix_jit)

    def n_traces():
        return sum(g.n_traces for g in guards)

    def before_advance():
        ctx_now[0] = sum(sched._slot_blk[s] for s, r in
                         enumerate(sched._slot_req) if r is not None)
    sched._advance = common.GuardProxy(orig_advance, before_advance,
                                       spans.span, "bench.advance_dispatch")
    orig_admit = sched._admit_paged

    def admit(params, slot, req, budget):
        with spans.span("bench.admit"):
            ok = orig_admit(params, slot, req, budget)
        if ok:
            paths[req.uid] = sched._admit_info.get("path", "?")
        return ok
    sched._admit_paged = admit
    # what is left of bench.tick outside admit, alloc_pages and
    # advance_dispatch is the harvest: the done sync and completion pulls
    spans.wrap(sched, "_alloc_cursor_pages", "bench.alloc_pages")
    orig_step = sched.step
    state = {"phase": "warm", "t_start": None, "t_end": None,
             "trace_end": None}
    logdir = Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if cell.trace else None
    tracing: list = []

    def step(params, param_version=0):
        a0, ad0 = sched.stats.active_slot_ticks, sched.stats.admitted
        with spans.span("bench.tick"):
            out = orig_step(params, param_version)
        now = time.perf_counter()
        ticks.append(Tick(now, sched.stats.active_slot_ticks - a0,
                          sched.stats.admitted - ad0, ctx_now[0]))
        ph = state["phase"]
        if ph == "warm" and len(ticks) >= t["warm_ticks"]:
            # the ticks this run can still reach, at the warm ticks'
            # fastest pace, bound the scatter lengths to warm
            tick_s = min(b.end - a.end for a, b in zip(ticks[1:],
                                                        ticks[2:]))
            left = cell.seconds + t["trace_seconds"] \
                + t["check"]["wait_seconds"]
            horizon = len(ticks) + int(1.5 * left / tick_s) + 8
            timer.mark("warm_ticks")
            lengths = recorder.until(horizon)
            timer.mark(f"recorded_scatter_lengths_{horizon}_ticks")
            n = _warm_scatters(sched, lengths)
            timer.mark(f"warm_{n}_scatter_lengths")
            log(f"scatter lengths over {horizon} ticks: {lengths}")
            # set-up's objects leave the collector's generations, so a
            # collection inside the window scans only what it made
            gc.collect()
            gc.freeze()
            now = time.perf_counter()
            state["phase"], state["t_start"] = "window", now
            state["i_start"] = len(ticks)
            state["setup_s"] = timer.total()
            compiles.open()
            state["traces0"] = n_traces()
        elif ph == "window" and now - state["t_start"] >= cell.seconds:
            compiles.shut()
            state["phase"], state["t_end"] = "after", now
            state["i_end"] = len(ticks)
            state["traces1"] = n_traces()
            if cell.trace:
                jax.profiler.start_trace(str(logdir))
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                tracing.append(ann)
                state["phase"], state["trace_start"] = "tracing", now
                state["i_trace"] = len(ticks)
        elif ph == "tracing" and now - state["trace_start"] \
                >= t["trace_seconds"]:
            tracing[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
            state["phase"] = "after"
            state["i_trace_end"] = len(ticks)
            state["trace_end"] = now
        return out
    sched.step = step

    # ---- the closed loop --------------------------------------------------
    client.start()
    done: list[Done] = []
    t_wait = None
    need = t["check"]["min_tokens"]
    for comp in engine.stream_completions():
        now = time.perf_counter()
        with spans.span("bench.client"):
            gi, mi, temp, budget = client.meta[comp.uid]
            done.append(Done(comp.uid, gi, mi, temp, budget,
                             paths.get(comp.uid, "?"), now, comp))
            client.top_up()
        if state["phase"] == "after":
            t_wait = t_wait or now
            greedy = sum(d.comp.gen_blocks * bsz for d in done
                         if d.temperature == 0.0)
            if greedy >= need or now - t_wait > t["check"]["wait_seconds"]:
                break
    sched.step, sched._advance, sched._admit_paged = \
        orig_step, orig_advance, orig_admit

    win = ticks[state["i_start"]:state["i_end"]]
    window_s = state["t_end"] - state["t_start"]
    tokens = sum(k.active for k in win) * bsz
    gaps, prev = [], state["t_start"]
    for k in win:
        gaps.extend([(k.end - prev) * 1e3] * (k.active - k.admitted))
        prev = k.end
    window_compiles = compiles.in_window + state["traces1"] \
        - state["traces0"]
    in_win = [d for d in done if state["t_start"] < d.received
              <= state["t_end"]]
    failed = sum(1 for d in in_win if d.comp.gen_blocks != d.budget)
    attempted = len(in_win) + sched.n_active
    tick_ms = [(b.end - a.end) * 1e3 for a, b in zip(win[:-1], win[1:])]
    log("window ticks (ms, admitted): " + " ".join(
        f"{ms:.1f}/{k.admitted}" for ms, k in zip(tick_ms, win[1:])))
    by_path = collections.Counter(paths.get(d.uid, "?") for d in in_win)
    log(f"window: {len(win)} ticks in {window_s:.4f} s, {tokens} tokens, "
        f"{len(in_win)} requests done {dict(by_path)}; tick ms median "
        f"{np.median(tick_ms):.2f} p95 {np.percentile(tick_ms, 95):.2f} "
        f"max {max(tick_ms):.2f}; gap samples {len(gaps)}; compiles in "
        f"window {window_compiles}")
    summary = None
    counters = {"block_size": bsz, "s_max": t["s_max"], "n_slots":
                t["n_slots"]}
    if cell.trace:
        from .trace import reduce_trace
        tw = ticks[state["i_trace"]:state["i_trace_end"]]
        counters.update({
            "trace_ticks": len(tw),
            "trace_active": sum(k.active for k in tw),
            "trace_ctx_blocks": sum(k.ctx_blocks for k in tw),
            "trace_admitted": sum(k.admitted for k in tw)})
        summary = reduce_trace(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    counters.update({"window_ctx_blocks": sum(k.ctx_blocks for k in win),
                     "window_active": sum(k.active for k in win)})

    devs = jax.devices()
    peak = common.peak_memory(devs)
    sample = _pick_sample(done, need, seed, bsz)
    del engine, sched, server, orig_step, orig_advance, orig_admit
    gc.unfreeze()
    gc.collect()
    checks = _check(cell, sample, control)
    return {"e2e": {"rollout_tok_s": tokens / window_s,
                    "block_gap_p95_ms": common.quantile(gaps, 0.95),
                    "setup_s": state["setup_s"]},
            "attempted": attempted, "failed": failed,
            "window_compiles": window_compiles, "peak": peak,
            "checks": checks, "summary": summary, "counters": counters,
            "sample": [(d.uid, d.path) for d in sample]}


def _check(cell, sample, control: bool) -> dict:
    """The reference, once the program's state is freed, over the
    sampled greedy requests.  With ``control`` the reference with
    float8 matmul operands takes the program's place: ``logit_gap`` and
    ``reveal_gap`` are then its readings, the program's beside them."""
    import jax
    import jax.numpy as jnp
    from ..reference import dense_gqa as ref
    from .weights import make_params
    t, m, bsz = cell.traffic, cell.model, cell.model["block_size"]
    t_ref = time.perf_counter()
    # the served weights, in the configuration's type, computed on in
    # float32
    rparams = jax.tree.map(lambda a: a.astype(jnp.float32),
                           make_params(m, cell.seed))
    worst, reveal, served, flips = 0.0, 0.0, 0, 0
    worst_ctl, reveal_ctl = 0.0, 0.0
    for d in sample:
        c = d.comp
        n_gen = c.gen_blocks * bsz
        g, rv, ctl = ref.serve_check(
            rparams, m, c.tokens, c.prompt_blocks * bsz, c.steps, n_gen,
            s_max=t["s_max"], tau=t["tau"], control=control)
        worst = max(worst, float(np.max(g)))
        reveal = max(reveal, float(np.max(rv)))
        flips += int(np.sum(g > 0))
        if control:
            worst_ctl = max(worst_ctl, float(np.max(ctl[0])))
            reveal_ctl = max(reveal_ctl, float(np.max(ctl[1])))
        served += n_gen
        log(f"checked request {d.uid} (group {d.group} member {d.member}, "
            f"{d.path}, prompt {c.prompt_blocks * bsz}, {n_gen} tokens): "
            f"widest gap {float(np.max(g)):.6f}, reveal "
            f"{float(np.max(rv)):.6f}")
    del rparams
    log(f"reference: {len(sample)} requests, {served} served tokens, "
        f"{time.perf_counter() - t_ref:.2f} s")
    out = {"logit_gap": worst, "reveal_gap": reveal,
           "checked_tokens": served,
           "off_best_share": flips / max(served, 1)}
    if control:
        out.update(logit_gap=worst_ctl, reveal_gap=reveal_ctl,
                   program_logit_gap=worst, program_reveal_gap=reveal)
    return out


def _pick_sample(done, need: int, seed: int, bsz: int):
    """Greedy requests finished by the check (in the window or in the
    wait after it): the longest, one of each admission path, then
    others drawn from the seed, until ``need`` served tokens."""
    pool = sorted((d for d in done if d.temperature == 0.0),
                  key=lambda d: d.uid)
    if not pool:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 3])
    pick = [max(pool, key=lambda d: d.comp.gen_blocks)]
    for path in ("cold", "suffix_prefill", "full_hit"):
        cand = [d for d in pool if d.path == path and d not in pick]
        if cand and not any(d.path == path for d in pick):
            pick.append(cand[rng.integers(len(cand))])
    rest = [d for d in pool if d not in pick]
    rng.shuffle(rest)
    for d in rest:
        if sum(p.comp.gen_blocks for p in pick) * bsz >= need:
            break
        pick.append(d)
    return pick

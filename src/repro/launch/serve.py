"""Serving launcher: stand up a RolloutEngine on the selected mesh and
answer a request batch (or run a throughput loop).

  PYTHONPATH=src python -m repro.launch.serve --arch tiny
  PYTHONPATH=src python -m repro.launch.serve --arch tiny --ckpt ck.msgpack --tau 0.95

Mixed per-request traffic: ``--tau`` (and ``--temperature``) accept a
comma-separated list — requests round-robin over the values as
per-request ``SamplingParams`` on ONE slot pool, exercising the
request-granular decode path (no engine rebuild, no retrace per
config).  A single value behaves as before.

By default (``--cache paged --kernel pallas``) the pool is served
through the in-place page-aware kernels (``kernels.paged_attn`` —
decode and suffix prefill); the stats line then reports the per-tick
and admission-time transient KV copies (0 in place vs the gathered
fallback's dense-width bytes) plus the kernels' execution mode —
``compiled`` on TPU, ``interpret`` elsewhere — and why.  The persistent
compile cache is placed by ``launch.compile_cache``.
"""

from __future__ import annotations

import argparse


def _float_list(s: str) -> list[float]:
    return [float(v) for v in s.split(",") if v != ""]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--tau", type=_float_list, default=[0.9],
                    help="dynamic threshold; a comma list (e.g. "
                         "0.5,0.9,0.99) round-robins per-request "
                         "SamplingParams over one pool")
    ap.add_argument("--temperature", type=_float_list, default=[0.0],
                    help="sampling temperature; comma list round-robins "
                         "like --tau")
    ap.add_argument("--max-new-blocks", type=int, default=None,
                    help="per-request response budget in blocks")
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--s-max", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batching", choices=["continuous", "static"],
                    default="continuous")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot pool size (continuous batching)")
    ap.add_argument("--cache", choices=["dense", "paged"],
                    default="paged",
                    help="KV layout: shared page pool | per-slot regions")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged: pool size (default = dense-equivalent)")
    ap.add_argument("--kernel", choices=["ref", "pallas"],
                    default="pallas",
                    help="paged KV layout: read the page pool in place "
                         "(pallas; compiled on TPU, interpret mode "
                         "elsewhere) or gather pages into a dense-width "
                         "copy per step (ref)")
    ap.add_argument("--prefix-cache", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="paged: share committed prompt pages across "
                         "requests (default: on when --cache paged and "
                         "the backbone is pure-attention)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(request lifecycles + scheduler tick phases; "
                         "open in Perfetto / chrome://tracing). A "
                         ".jsonl path dumps raw spans instead")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write end-of-run metrics: .prom/.txt = "
                         "Prometheus text exposition, anything else = "
                         "the metrics JSON envelope")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a real XLA profiler trace of the run "
                         "into DIR (jax.profiler; open in TensorBoard "
                         "or Perfetto) — the honest device-time view")
    args = ap.parse_args()

    import jax

    from repro.launch import compile_cache
    compile_cache.configure()

    from repro import configs
    from repro.checkpoint.io import load_pytree
    from repro.data.math_tasks import sample_problem
    from repro.data.tokenizer import ByteTokenizer
    from repro.models.model import BlockDiffLM
    from repro.obs import export, profile
    from repro.serving.engine import (GenerationConfig, RolloutEngine,
                                      SamplingParams)
    from repro.serving.server import ModelServer

    import random
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = BlockDiffLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        params = load_pytree(args.ckpt, params)

    server = ModelServer(params)
    engine = RolloutEngine(model, server, GenerationConfig(
        max_len=args.max_len, s_max=args.s_max, mode="dynamic",
        tau=args.tau[0], temperature=args.temperature[0],
        batching=args.batching, n_slots=args.slots,
        cache=args.cache, n_pages=args.pages,
        prefix_cache=args.prefix_cache, kernel=args.kernel,
        trace=args.trace_out is not None))
    rng = random.Random(0)
    prompts = [sample_problem(rng, level=0).prompt
               for _ in range(args.requests)]
    # one SamplingParams per request, cycling over the CLI value lists
    sampling = [SamplingParams(
        tau=args.tau[i % len(args.tau)],
        temperature=args.temperature[i % len(args.temperature)],
        max_new_blocks=args.max_new_blocks,
        eos_id=ByteTokenizer().eos_id)
        for i in range(args.requests)]
    mixed = len(args.tau) > 1 or len(args.temperature) > 1
    # opt-in device profiling: a no-op context unless --profile-dir
    with profile.capture(args.profile_dir) as profiling:
        if args.batching == "continuous":
            # same per-request keys as generate_texts(rng=PRNGKey(1))
            # uses on the static path, so the printed completions match
            # the --batching static run byte-for-byte (parity check)
            keys = jax.random.split(jax.random.PRNGKey(1), args.requests)
            for p, sp, k in zip(prompts, sampling, keys):
                engine.submit(p, k, params=sp)
            outs = {out.uid: out for out in engine.stream()}
            for uid in sorted(outs):
                out = outs[uid]
                tag = f"tau={out.params.tau:g} " if mixed else ""
                print(f"{prompts[uid]!r} -> {out.text!r}")
                print(f"  [{uid}] {tag}finish={out.finish_reason} "
                      f"latency={out.latency_ticks} ticks "
                      f"v{out.param_version}")
        else:
            outs = engine.generate_texts(prompts, jax.random.PRNGKey(1),
                                         sampling=sampling)
            for p, o in zip(prompts, outs):
                print(f"{p!r} -> {o!r}")
    if profiling:
        print(f"[obs] XLA profiler trace -> {args.profile_dir}")
    if args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            n = export.write_jsonl(args.trace_out,
                                   engine.tracer.snapshot())
            print(f"[obs] {n} spans -> {args.trace_out}")
        else:
            export.write_chrome_trace(
                args.trace_out, engine.tracer.snapshot(),
                metadata={"tool": "repro.launch.serve"})
            print(f"[obs] Chrome trace ({len(engine.tracer)} spans, "
                  f"{engine.tracer.dropped} dropped) -> "
                  f"{args.trace_out}")
    if args.metrics_out:
        regs = [engine.stats.registry]
        if engine._sched is not None:
            regs.append(engine._sched.stats.registry)
        if args.metrics_out.endswith((".prom", ".txt")):
            export.write_prometheus(args.metrics_out, *regs)
        else:
            export.write_metrics_json(args.metrics_out, *regs)
        print(f"[obs] metrics -> {args.metrics_out}")
    s = engine.stats
    line = (f"[engine] {s.rollouts} rollouts | {s.total_tokens} tokens | "
            f"{s.tokens_per_step:.2f} tokens/denoise-step | "
            f"{s.total_tokens / max(s.wall_seconds, 1e-9):.0f} tok/s | "
            f"weights v{s.param_version}")
    if args.batching == "continuous":
        line += (f" | slot-util {s.utilization:.0%}"
                 f" | latency p50 {s.latency_p50:.0f}"
                 f"/p95 {s.latency_p95:.0f}"
                 f"/p99 {s.latency_p99:.0f} ticks")
        if args.cache == "paged" and engine.scheduler.prefix is not None:
            line += f" | prefix-hit {s.prefix_hit_rate:.0%}"
        if args.cache == "paged":
            line += (f" | kernel {args.kernel} "
                     f"(transient KV {s.transient_kv_bytes / 1024:.0f} "
                     f"KiB/tick, admit "
                     f"{s.admit_transient_kv_bytes / 1024:.0f} KiB)")
            plan = engine.scheduler.kernel_plan
            if plan is not None:
                line += f" | exec {plan.mode}: {plan.reason}"
        if mixed:
            line += (f" | {engine.scheduler.n_advance_traces} advance "
                     f"trace(s) across {args.requests} mixed requests")
    print(line)


if __name__ == "__main__":
    main()

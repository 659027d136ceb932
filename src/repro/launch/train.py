"""Distributed training launcher.

Builds the mesh, shards params/optimizer with the production partition
rules, and runs the blockwise-diffusion SFT loop.  On the CPU container it
runs a real (tiny) training job on the 1x1 host mesh; on a TPU slice the
same entry point takes --mesh single|multi and the full configs.

  PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch sdar-8b --mesh single --dry-run

With ``--rl-steps N`` the launcher continues into DiPO post-training on
the SFT'd weights (the paper's stage 2): a ModelServer + RolloutEngine
pair and the synchronous ``DiPOTrainer`` — or, with ``--async``, the
overlapped ``rl.pipeline`` producer/consumer loop whose staleness
window ``--staleness-k`` bounds how many updates a consumed rollout may
lag (K=0 reproduces the sync loop bitwise).

  PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 50 \\
      --rl-steps 10 --async --staleness-k 2
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=96)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--attn-impl", default="pallas",
                    choices=["ref", "structured", "chunked", "pallas"],
                    help="training attention backend; pallas runs the "
                         "differentiable tile-sparse kernels (compiled "
                         "on TPU, interpret mode elsewhere)")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile only (see repro.launch.dryrun for "
                         "the full sweep)")
    ap.add_argument("--save", default=None)
    # ---- DiPO post-training (stage 2) ----
    ap.add_argument("--rl-steps", type=int, default=0,
                    help="DiPO updates after SFT (0 = SFT only)")
    ap.add_argument("--async", dest="async_rl", action="store_true",
                    help="overlap rollout generation and DiPO updates "
                         "(rl.pipeline producer/consumer loop)")
    ap.add_argument("--staleness-k", type=int, default=1,
                    help="async: max updates a consumed rollout may lag "
                         "(0 = bitwise-equal to the sync loop)")
    ap.add_argument("--group-size", type=int, default=4,
                    help="DiPO rollouts per prompt (G)")
    ap.add_argument("--rl-prompts", type=int, default=4,
                    help="prompts per DiPO update (P)")
    ap.add_argument("--rl-lr", type=float, default=1e-4)
    args = ap.parse_args()

    import os
    if args.mesh != "host":
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import compile_cache
    compile_cache.configure()

    from repro import configs
    from repro.checkpoint.io import save_pytree
    from repro.data.pipeline import MathTaskDataset
    from repro.data.tokenizer import ByteTokenizer
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch.steps import make_train_step
    from repro.models.model import BlockDiffLM
    from repro.optim import adamw

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    cfg = cfg.replace(attn_impl=args.attn_impl)
    from repro.kernels.ops import train_exec_plan
    plan = train_exec_plan(cfg.attn_impl)
    print(f"[train] attn {plan.impl} | exec {plan.mode}: {plan.reason}")
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    model = BlockDiffLM(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, clip_norm=1.0)
    step_fn = make_train_step(model, opt_cfg)

    with jax.set_mesh(mesh):
        params_shape = jax.eval_shape(model.init,
                                      jax.ShapeDtypeStruct((2,), jnp.uint32))
        pspecs = shd.sanitize_specs(
            shd.param_specs(params_shape, cfg.n_experts), params_shape,
            mesh)
        ospecs = {"m": pspecs, "v": pspecs, "count": P()}
        bspecs = shd.train_batch_specs(mesh)
        ns = lambda s: shd.to_named(mesh, s)
        jstep = jax.jit(step_fn,
                        in_shardings=(ns(pspecs), ns(ospecs), ns(bspecs),
                                      NamedSharding(mesh, P())),
                        donate_argnums=(0, 1))

        if args.dry_run:
            from repro.launch.steps import input_specs
            si = input_specs(args.arch, "train_4k",
                             attn_impl=args.attn_impl)
            lowered = jstep.lower(si["params"], si["opt_state"],
                                  si["batch"], si["rng"])
            compiled = lowered.compile()
            print(compiled.memory_analysis())
            print({k: v for k, v in (compiled.cost_analysis() or {}).items()
                   if k in ("flops", "bytes accessed")})
            return

        tok = ByteTokenizer()
        ds = MathTaskDataset(tok, cfg.block_size, seq_len=args.seq_len)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = adamw.init_state(opt_cfg, params)
        print(f"[train] {cfg.name}: {model.param_count(params):,} params "
              f"on mesh {dict(mesh.shape)}")
        rng = jax.random.PRNGKey(1)
        it = ds.sft_batches(args.batch)
        for i in range(args.steps):
            rng, k = jax.random.split(rng)
            batch = {kk: jnp.asarray(v) for kk, v in
                     next(it).asdict().items()}
            t0 = time.perf_counter()
            params, opt_state, m = jstep(params, opt_state, batch, k)
            if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
                print(f"[{i:4d}] loss={float(m['loss']):.4f} "
                      f"gnorm={float(m['grad_norm']):.3f} "
                      f"({time.perf_counter() - t0:.2f}s)")
        if args.rl_steps:
            from repro.rl.pipeline import AsyncDiPOTrainer
            from repro.rl.trainer import DiPOConfig, DiPOTrainer
            from repro.serving.engine import (GenerationConfig,
                                              RolloutEngine)
            from repro.serving.server import ModelServer

            # the server holds its own copy: the DiPO step donates the
            # trainer's buffers and pushes fresh ones each update
            server = ModelServer(jax.tree.map(jnp.copy, params))
            engine = RolloutEngine(model, server, GenerationConfig(
                max_len=args.seq_len, s_max=4, mode="dynamic", tau=0.7,
                temperature=1.0, cache="paged", kernel="pallas",
                n_slots=max(args.rl_prompts * args.group_size // 2, 2)),
                tokenizer=tok)
            rl_cfg = DiPOConfig(group_size=args.group_size,
                                logprob_scheme="packed")
            rl_opt = adamw.AdamWConfig(lr=args.rl_lr)
            rng, kr = jax.random.split(rng)
            if args.async_rl:
                tr = AsyncDiPOTrainer(model, engine, rl_opt, rl_cfg,
                                      params,
                                      staleness_k=args.staleness_k)
                mode = f"async K={args.staleness_k}"
            else:
                tr = DiPOTrainer(model, engine, rl_opt, rl_cfg, params)
                mode = "sync"
            print(f"[rl] DiPO {mode}: {args.rl_steps} updates, "
                  f"P={args.rl_prompts} G={args.group_size}")
            hist = tr.run(ds.prompt_batches(args.rl_prompts),
                          args.rl_steps, kr)
            params = tr.params
            print(f"[rl] done: server v{server.version}, final "
                  f"acc={hist[-1]['acc']:.3f} "
                  f"reward={hist[-1]['reward_mean']:.3f}")

        if args.save:
            save_pytree(args.save, params)
            print(f"saved {args.save}")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) pair, ``jax.jit(step).lower(...)
.compile()`` must succeed on BOTH production meshes:

  * single pod : (16, 16)    ("data", "model")     = 256 chips
  * multi pod  : (2, 16, 16) ("pod", "data", "model") = 512 chips

and we record memory_analysis / cost_analysis / collective traffic into
``experiments/dryrun/<arch>__<shape>__<mesh>.json`` for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch rwkv6-1.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (input_specs, make_prefill_step,
                                make_serve_step, make_train_step)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _build(arch: str, shape: str, mesh, spec_overrides=None):
    """Returns (jitted_fn, example_args) for the combo on this mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    si = input_specs(arch, shape)
    cfg, model = si["cfg"], si["model"]
    pspecs = shd.sanitize_specs(
        shd.param_specs(si["params"], cfg.n_experts), si["params"], mesh)
    ns = lambda specs: shd.to_named(mesh, specs)
    dp = shd.batch_axes(mesh)

    if si["kind"] == "train":
        fn = make_train_step(model, si["opt_cfg"])
        ospecs = {"m": pspecs, "v": pspecs, "count": P()}
        bspecs = shd.train_batch_specs(mesh)
        if "memory" in si["batch"]:
            bspecs = dict(bspecs, memory=P(dp, None, None))
        in_sh = (ns(pspecs), ns(ospecs), ns(bspecs),
                 NamedSharding(mesh, P()))
        out_sh = (ns(pspecs), ns(ospecs), None)
        args = (si["params"], si["opt_state"], si["batch"], si["rng"])
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0, 1))
    elif si["kind"] == "prefill":
        fn = make_prefill_step(model)
        cspecs = shd.sanitize_specs(
            shd.cache_specs(si["caches"], mesh, shard_seq=False),
            si["caches"], mesh)
        in_sh = (ns(pspecs), NamedSharding(mesh, P(dp, None)),
                 NamedSharding(mesh, P(dp, None)), ns(cspecs))
        args = (si["params"], si["tokens"], si["valid"], si["caches"])
        if si["memory"] is not None:
            in_sh = in_sh + (NamedSharding(mesh, P(dp, None, None)),)
            args = args + (si["memory"],)
        jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(3,))
    else:  # decode
        fn = make_serve_step(model)
        shard_seq = configs.INPUT_SHAPES[shape].global_batch < 16
        cspecs = shd.sanitize_specs(
            shd.cache_specs(si["caches"], mesh, shard_seq=shard_seq),
            si["caches"], mesh)
        bspec = P(None, None) if shard_seq else P(dp, None)
        in_sh = (ns(pspecs), NamedSharding(mesh, bspec),
                 NamedSharding(mesh, bspec), ns(cspecs),
                 NamedSharding(mesh, P(bspec[0])))
        args = (si["params"], si["block_ids"], si["positions"],
                si["caches"], si["cache_limit"])
        if si["memory"] is not None:
            in_sh = in_sh + (NamedSharding(mesh, bspec + (None,)),)
            args = args + (si["memory"],)
        jfn = jax.jit(fn, in_shardings=in_sh, donate_argnums=(3,))
    return jfn, args, si


def run_combo(arch: str, shape: str, mesh_kind: str, *,
              save: bool = True, verbose: bool = True) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = 512 if multi else 256
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "n_chips": n_chips, "ok": False}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            jfn, args, si = _build(arch, shape, mesh)
            lowered = jfn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        try:
            hlo_text = compiled.as_text()
        except Exception:
            hlo_text = lowered.as_text()
        coll = hlo.collective_stats(hlo_text)
        terms = hlo.roofline_terms(cost or {}, coll, n_chips)

        cfg = si["cfg"]
        total_params = sum(
            x.size for x in jax.tree_util.tree_leaves(si["params"]))
        nact = hlo.active_params(cfg, total_params)
        shp = configs.INPUT_SHAPES[shape]
        batch_tokens = shp.global_batch * (
            shp.seq_len if si["kind"] != "decode" else cfg.block_size)
        mf = hlo.model_flops(cfg, nact, batch_tokens, si["kind"])

        from repro.models.config import layer_pattern
        pre, grp, ng = layer_pattern(cfg)
        rec.update(
            ok=True,
            # cost_analysis counts while-loop bodies ONCE (calibrated in
            # EXPERIMENTS.md §Methodology): in-loop flops/bytes/collective
            # contributions are to be scaled by ~layer_scan_trips when
            # absolute magnitudes (not before/after ratios) are needed.
            layer_scan_trips=ng,
            layers_per_trip=len(grp),
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory=_mem_dict(mem),
            cost={k: float(v) for k, v in (cost or {}).items()
                  if isinstance(v, (int, float))},
            collectives=coll,
            roofline=terms,
            dominant=hlo.dominant_term(terms),
            total_params=int(total_params),
            active_params=int(nact),
            model_flops_global=mf,
            model_flops_per_chip=mf / n_chips,
            useful_flop_ratio=(mf / n_chips) / max(terms["flops"], 1.0),
        )
    except Exception as e:  # noqa: BLE001 — record the failure
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 1)

    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_kind}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    if verbose:
        if rec["ok"]:
            t = rec["roofline"]
            print(f"[OK ] {arch:24s} {shape:12s} {mesh_kind:6s} "
                  f"dom={rec['dominant']:10s} "
                  f"tc={t['t_compute_s']:.3e} tm={t['t_memory_s']:.3e} "
                  f"tx={t['t_collective_s']:.3e} "
                  f"bytes/dev={rec['memory'].get('temp_mb', '?')}MB "
                  f"({rec['wall_s']}s)")
        else:
            print(f"[FAIL] {arch} {shape} {mesh_kind}: {rec['error']}")
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for attr in ("generated_code_size_in_bytes",
                 "argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if "temp_size_in_bytes" in out:
        out["temp_mb"] = out["temp_size_in_bytes"] // 2**20
    if "argument_size_in_bytes" in out:
        out["args_mb"] = out["argument_size_in_bytes"] // 2**20
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", type=str, default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        pairs = configs.arch_shape_pairs()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    n_fail = 0
    for arch, shape in pairs:
        for mk in meshes:
            path = os.path.join(OUT_DIR, f"{arch}__{shape}__{mk}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("ok"):
                        continue
            rec = run_combo(arch, shape, mk)
            n_fail += 0 if rec["ok"] else 1
    print(f"dry-run complete, failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

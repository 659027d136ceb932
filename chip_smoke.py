"""Drive the DiRL post-training loop once on one TPU chip.

    python3 chip_smoke.py            # on a machine with one TPU chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # CPU rehearsal

The model is h2o-danube-3-4b at its published widths (d_model 3840,
32 query / 8 kv heads, head_dim 120, d_ff 10240, vocab 32000, sliding
window 4096, block_size 32), cut to 2 layers; weights are random, made
from ``--seed``.  One process runs every phase — the chip belongs to
one process at a time:

  (a) device: platform, device_kind, device count; no TPU -> exit 2.
  (b) kernels: the compiled paged decode, paged prefill and
      block-diffusion forward + gradient at this model's shapes against
      the ``kernels/ref.py`` oracle (``attn_impl="ref"``, gathered
      pages) computed at ``highest`` matmul precision.
  (c) serve: RolloutEngine on the paged cache with the Pallas kernels
      and the prefix cache — two G=4 groups plus prompts that extend a
      cached prefix (suffix prefill); every request must finish.
  (d) SFT: a few SFTTrainer steps with attn_impl="pallas" under remat.
  (e) RL: one DiPOTrainer update that rolls out through the phase-(c)
      engine, then pushes its weights into the server.

Every kernel plan must be ``compiled``; any failure raises and the
script exits non-zero.  The per-phase wall times include compilation:
they are set-up figures, not speed.  The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--rehearse`` is the test-only path: it accepts a CPU backend, runs
every phase in interpret mode on the family's smoke-size config, and
prints no result line.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Phase (b) tolerances on outputs and gradients of order 1, kernel vs
# the f32 oracle at `highest` precision.  The kernels' f32 dots run at
# Mosaic's default contract precision, which may round operands to
# bf16 (2^-9 relative): that is ~5e-3 on a score and on an output, so
# 2e-2 leaves headroom.  A masking fault moves whole blocks of keys and
# shifts outputs by O(0.1) or more; each check also runs a control that
# must exceed the same tolerance.
ATOL = RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "memory: not reported by this backend"
    return (f"memory: peak {stats['peak_bytes_in_use']} B, in use "
            f"{stats['bytes_in_use']} B, limit "
            f"{stats.get('bytes_limit', 'n/a')} B")


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def close(name: str, got, want, control) -> None:
    """Assert ``got`` matches ``want`` within ATOL/RTOL, and that the
    masking-fault ``control`` does not.  Logs the max abs error and the
    worst element's share of its allowance ``ATOL + RTOL*|ref|``
    (<= 1 passes)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    control = np.asarray(control, np.float32)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL,
                               err_msg=name)
    assert not np.allclose(got, control, atol=ATOL, rtol=RTOL), \
        f"{name}: masking-fault control is within tolerance"

    def share(ref):
        return float(np.max(np.abs(got - ref) / (ATOL + RTOL * np.abs(ref))))
    log(f"[b] {name}: max|kernel-ref| {np.max(np.abs(got - want)):.3e}, "
        f"worst share of tolerance {share(want):.3f}; masking-fault "
        f"control max|diff| {np.max(np.abs(got - control)):.3e}, worst "
        f"share {share(control):.3f}")


def phase_kernels(cfg, key, *, interpret: bool) -> None:
    """(b) the three kernel launches at the model's shapes vs the oracle."""
    from repro.core.masks import SeqMeta, dirl_layout, sample_sft_noise
    from repro.kernels import ops
    from repro.kernels.paged_attn import (paged_decode_attention,
                                          paged_prefill_attention,
                                          plan_exec)
    from repro.models import attention as A

    H, Hkv, D, bsz = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                      cfg.block_size)
    plan = plan_exec(bsz, D, D)
    tplan = ops.train_exec_plan("pallas")
    log(f"[b] paged plan: {plan.mode} ({plan.reason}); "
        f"training plan: {tplan.mode} ({tplan.reason})")
    if not interpret:
        assert plan.mode == "compiled" and tplan.mode == "compiled"
    scale = D ** -0.5
    # a window that bites at these lengths, so window masking runs
    window = 3 * bsz + bsz // 2
    ks = iter(jax.random.split(key, 16))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.float32)

    # paged decode: 4 rows over a 8-block table with holes, per-row
    # committed limits (0 .. 6 blocks)
    B, K = 4, 8
    P = B * K + 1
    cache = A.PagedAttnCache(
        k=normal((P, Hkv, bsz, D)), v=normal((P, Hkv, bsz, D)),
        pos=jnp.asarray(np.arange(P * bsz).reshape(P, bsz) % (K * bsz),
                        jnp.int32))
    table = np.arange(1, P).reshape(B, K).astype(np.int32)
    table[1, 5:] = -1
    blk = np.array([0, 3, 6, 5], np.int32)
    table, limit = jnp.asarray(table), jnp.asarray(blk * bsz)
    positions = jnp.asarray(blk[:, None] * bsz + np.arange(bsz), jnp.int32)
    q, k_self, v_self = (normal((B, bsz, H, D)), normal((B, bsz, Hkv, D)),
                         normal((B, bsz, Hkv, D)))

    def decode_ref(w):
        return A.resolve_kv_layout(cache, "ref").attend(
            q, k_self, v_self, positions, cache, block_table=table,
            cache_limit=limit, scale=scale, softcap=None, window=w)

    got = jax.jit(functools.partial(
        paged_decode_attention, scale=scale, window=window))(
        q, cache.k, cache.v, cache.pos, table, k_self, v_self, positions,
        limit)
    with jax.default_matmul_precision("highest"):
        want, control = decode_ref(window), decode_ref(None)
    close("paged_decode", got, want, control)

    # paged prefill: 2 rows, 3 hit-prefix pages + a 2-block suffix
    Kp, Ts = 3, 2
    T = Ts * bsz
    pos = np.full((P, bsz), -1, np.int32)
    ctx = np.arange(1, 1 + B * Kp).reshape(B, Kp)[:2].astype(np.int32)
    for b in range(2):
        for j in range(Kp):
            pos[ctx[b, j]] = j * bsz + np.arange(bsz)
    pcache = cache._replace(pos=jnp.asarray(pos))
    spos = np.broadcast_to(Kp * bsz + np.arange(T), (2, T))
    meta = SeqMeta(copy=jnp.zeros((2, T), jnp.int32),
                   block=jnp.asarray(spos // bsz, jnp.int32),
                   step=jnp.zeros((2, T), jnp.int32),
                   pos=jnp.asarray(spos, jnp.int32),
                   valid=jnp.ones((2, T), bool))
    q, k_self, v_self = (normal((2, T, H, D)), normal((2, T, Hkv, D)),
                         normal((2, T, Hkv, D)))
    ctx = jnp.asarray(ctx)

    def prefill_ref(w):
        return A.resolve_kv_layout(pcache, "ref").prefill_attend(
            q, k_self, v_self, meta, pcache, context_table=ctx,
            block_size=bsz, impl="ref", scale=scale, softcap=None, window=w)

    got = jax.jit(functools.partial(
        paged_prefill_attention, scale=scale, window=window))(
        q, pcache.k, pcache.v, pcache.pos, ctx, k_self, v_self, meta.pos)
    with jax.default_matmul_precision("highest"):
        want, control = prefill_ref(window), prefill_ref(None)
    close("paged_prefill", got, want, control)

    # block-diffusion attention on the SFT duplicated layout, forward
    # and gradient w.r.t. q, k, v
    L = 8 * bsz
    tokens = jax.random.randint(next(ks), (1, L), 4, 100)
    valid = jnp.ones((1, L), bool)
    steps, _, _ = sample_sft_noise(next(ks), tokens,
                                   jnp.arange(L)[None] < bsz, valid,
                                   block_size=bsz)
    _, lmeta, _ = dirl_layout(tokens, steps, valid, block_size=bsz,
                              mask_token=cfg.resolved_mask_token,
                              noised=True)
    Tl = lmeta.length
    q, k, v = (normal((1, Tl, H, D)), normal((1, Tl, Hkv, D)),
               normal((1, Tl, Hkv, D)))
    cot = normal((1, Tl, H, D))

    def loss(impl, w):
        def f(q, k, v, cot):
            o = ops.attention(q, k, v, lmeta, lmeta, impl=impl, window=w)
            return jnp.sum(o * cot), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o_pal), g_pal = loss("pallas", window)(q, k, v, cot)
    with jax.default_matmul_precision("highest"):
        (_, o_ref), g_ref = loss("ref", window)(q, k, v, cot)
        (_, o_ctl), g_ctl = loss("ref", None)(q, k, v, cot)
    close("block_diff fwd", o_pal, o_ref, o_ctl)
    for name, a, b, c in zip("qkv", g_pal, g_ref, g_ctl):
        close(f"block_diff d{name}", a, b, c)


def phase_serve(model, server, tok, *, seed: int):
    """(c) paged + Pallas + prefix-cache serving of ~10 requests."""
    from repro.serving.engine import (GenerationConfig, RolloutEngine,
                                      SamplingParams)

    bsz = model.cfg.block_size
    # prompts of >= 2 blocks so their pages register in the prefix
    # index; the last two extend a cached prompt by a partial block, so
    # their admission is a suffix prefill against the shared pages
    base = ["Question: a farmer has {} sheep and buys {} more, then sells "
            "half of them at the market. How many sheep are left?"
            .format(3 + i, 5 + i) for i in range(2)]
    prompts = [base[0]] * 4 + [base[1]] * 4 + \
        [p + " Explain each step of the computation." for p in base]
    longest = max(len(tok.encode(p, bos=True)) for p in prompts)
    gen_cfg = GenerationConfig(
        max_len=(-(-longest // bsz) + 3) * bsz, s_max=4, mode="dynamic",
        tau=0.9, temperature=1.0, batching="continuous", n_slots=8,
        cache="paged", kernel="pallas", prefix_cache=True,
        eos_id=tok.eos_id)
    engine = RolloutEngine(model, server, gen_cfg, tokenizer=tok)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(prompts))
    sp = SamplingParams(tau=0.9, temperature=1.0, max_new_blocks=2,
                        eos_id=tok.eos_id)
    for p, k in zip(prompts, keys):
        engine.submit(p, k, params=sp)
    outs = list(engine.stream())
    sched = engine.scheduler
    st = sched.stats
    plan = sched.kernel_plan
    traces = sched.guard_stats()
    log(f"[c] kernel_plan: {plan.mode} ({plan.reason}); "
        f"n_advance_traces {sched.n_advance_traces}; traces per entry "
        f"point {traces}")
    log(f"[c] {len(outs)}/{len(prompts)} requests finished "
        f"({', '.join(sorted({o.finish_reason for o in outs}))}); "
        f"{engine.stats.total_tokens} tokens; prefix-hit blocks "
        f"{st.prefix_hit_blocks}, prefilled blocks {st.prefill_blocks}, "
        f"admit transient KV {st.admit_transient_kv_bytes} B")
    assert len(outs) == len(prompts)
    assert all(o.finish_reason in ("eos", "length") for o in outs)
    assert st.prefix_hit_blocks > 0
    assert traces["admit_hit"] >= 1 and traces["admit_suffix"] >= 1
    assert sched.n_advance_traces == 1
    return engine, plan


def phase_sft(model, params, tok, *, seed: int, steps: int, batch: int,
              seq_len: int):
    """(d) a few SFT steps on the fused duplicated-layout pass."""
    from repro.data.pipeline import MathTaskDataset
    from repro.kernels.ops import train_exec_plan
    from repro.optim import adamw
    from repro.sft.trainer import SFTTrainer

    plan = train_exec_plan(model.cfg.attn_impl)
    trainer = SFTTrainer(model, adamw.AdamWConfig(lr=1e-4, clip_norm=1.0),
                         params)
    ds = MathTaskDataset(tok, model.cfg.block_size, seq_len=seq_len,
                         seed=seed)
    batches = ds.sft_batches(batch)
    first = next(batches)
    # the step's device-memory plan, from the compiler, before it runs
    # (the same trace and executable the first step then uses)
    ma = trainer._step.lower(
        trainer.params, trainer.opt_state,
        {k: jnp.asarray(v) for k, v in first.asdict().items()},
        jax.random.PRNGKey(seed)).compile().memory_analysis()
    log(f"[d] SFT step memory plan: arguments "
        f"{ma.argument_size_in_bytes} B (donated params + Adam state), "
        f"outputs {ma.output_size_in_bytes} B, aliased "
        f"{ma.alias_size_in_bytes} B, temporaries "
        f"{ma.temp_size_in_bytes} B")
    hist = trainer.run(itertools.chain([first], batches), steps,
                       jax.random.PRNGKey(seed), verbose=False)
    losses = [h["loss"] for h in hist]
    log(f"[d] attn {plan.impl}: {plan.mode} ({plan.reason}); remat "
        f"{model.cfg.remat}; losses {losses}; step_traces "
        f"{hist[-1]['step_traces']}; batch {batch}x{seq_len}")
    assert all(np.isfinite(losses))
    assert hist[-1]["step_traces"] == 1
    return trainer, plan


def phase_rl(model, engine, params, tok, *, seed: int):
    """(e) one DiPO update through the serving engine + weight push."""
    from repro.data.pipeline import MathTaskDataset
    from repro.optim import adamw
    from repro.rl.trainer import DiPOConfig, DiPOTrainer

    server = engine.store
    trainer = DiPOTrainer(model, engine, adamw.AdamWConfig(lr=1e-5),
                          DiPOConfig(group_size=4, logprob_scheme="packed"),
                          params)
    ds = MathTaskDataset(tok, model.cfg.block_size,
                         seq_len=engine.gen_cfg.max_len, seed=seed + 1)
    v0 = server.version
    # one prompt group of 4: compiled for a described v5e, this step
    # plans 12.4 GB at 4 rollouts of 288 tokens, 14.3 GB at 8
    m = trainer.train_step(next(ds.prompt_batches(1)),
                           jax.random.PRNGKey(seed + 2))
    log(f"[e] DiPO update: loss {m['loss']}, reward {m['reward_mean']}, "
        f"step_traces {m['step_traces']}; server v{v0} -> v{server.version}")
    assert np.isfinite(m["loss"])
    assert m["step_traces"] == 1
    assert server.version == v0 + 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="test-only: run on CPU in interpret mode at the "
                         "smoke-size config; prints no result line")
    args = ap.parse_args(argv)

    # (a) device
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"[a] device: {json.dumps(device)}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 2

    from repro.configs import h2o_danube3_4b
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch import compile_cache
    from repro.models.model import BlockDiffLM
    from repro.serving.server import ModelServer

    log(f"[a] compile cache: {compile_cache.configure()}")
    make = h2o_danube3_4b.smoke_config if args.rehearse \
        else h2o_danube3_4b.config
    cfg = make().replace(n_layers=2, attn_impl="pallas", remat=True)
    bsz = cfg.block_size
    log(f"[a] model: {cfg.name} x{cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size},"
        f" window {cfg.sliding_window}, block {bsz}")
    log("[a] wall times below include compilation: set-up figures, not "
        "speed")

    t = time.perf_counter()
    phase_kernels(cfg, jax.random.PRNGKey(args.seed),
                  interpret=args.rehearse)
    log(f"[b] done in {time.perf_counter() - t:.1f} s; "
        f"{device_memory(dev)}")

    t = time.perf_counter()
    model = BlockDiffLM(cfg)
    # the server holds the only reference: its weight push releases them
    server = ModelServer(model.init(jax.random.PRNGKey(args.seed)))
    log(f"[c] params {model.param_count(server.params)} "
        f"({tree_bytes(server.params)} B f32)")
    tok = ByteTokenizer()
    engine, plan = phase_serve(model, server, tok, seed=args.seed)
    if not args.rehearse:
        assert plan.mode == "compiled"
    log(f"[c] done in {time.perf_counter() - t:.1f} s; "
        f"{device_memory(dev)}")

    # (d) trains its own copy: the SFT step donates its parameters
    t = time.perf_counter()
    sft, tplan = phase_sft(model, jax.tree.map(jnp.copy, server.params),
                           tok, seed=args.seed, steps=3, batch=2,
                           seq_len=8 * bsz)
    if not args.rehearse:
        assert tplan.mode == "compiled"
    log(f"[d] done in {time.perf_counter() - t:.1f} s; "
        f"{device_memory(dev)}")

    # (e) the SFT weights go to the server, then RL continues from them;
    # the SFT trainer (and its Adam state) is dropped first.  Server and
    # trainer share one copy, as DiPOTrainer's own push leaves them: the
    # update step donates it and pushes the new weights back
    t = time.perf_counter()
    tuned = sft.params
    del sft
    server.update_weights(tuned)
    phase_rl(model, engine, tuned, tok, seed=args.seed)
    log(f"[e] done in {time.perf_counter() - t:.1f} s; "
        f"{device_memory(dev)}")

    if args.rehearse:
        log("rehearsal ok (no result line: this is not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Microbench: the paged-attention kernel family — gathered vs in-place.

Raw-kernel counterpart of serve_bench §3/§5 (no model, no scheduler),
covering both passes the family serves:

* ``decode``  — one denoise step of current-block queries against a
  shared KV page pool (ragged per-row block counts, mid-run limits);
* ``prefill`` — one shared-prefix suffix prefill: plain-mode suffix
  queries against (hit-prefix pages ++ suffix self keys), the
  admission-time pass.

Three numbers per (pass, shape, kernel):

* ``us_per_call`` / ``tok_s`` — wall-clock of the jitted layout (CPU
  caveat: the Pallas path runs under ``interpret=True`` off-TPU, so its
  CPU time is a correctness harness, not the speed story — the ``mode``
  column says which path actually ran and why);
* ``transient_kv_bytes`` — the per-call K/V copy the layout
  materializes outside the resident pool.  This is the structurally
  meaningful column: the decode gather scales with slots x K*bsz and
  the prefill gather with the hit-prefix width, while the in-place
  kernels stay at 0 — the capacity headroom the page-aware family buys
  at serving scale.

Results flow through the shared ``common.write_bench_json`` path into
``benchmarks/BENCH_paged_attn.json`` (the cross-PR perf trajectory,
validated by CI's bench-smoke job); the returned CSV rows are the
human-readable view of the same entries.

Max-abs deviation between the two layouts is reported per shape (f32
flash-vs-plain-softmax rounding on decode and prefill alike; the
tolerances are pinned in tests/test_paged_attn.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masks import SeqMeta
from repro.kernels.paged_attn import plan_exec
from repro.models import attention as A

ENTRY_KEYS = ("pass", "kernel", "B", "K", "bsz", "Hkv", "Dk", "Dv",
              "us_per_call", "tok_s", "transient_kv_bytes", "mode",
              "mode_reason", "max_abs_dev")


def _decode_setup(key, *, B, K, Hkv, Dk, Dv, bsz):
    """Random pool + a ragged table (per-row mapped block counts drawn
    uniformly from [1, K], trailing blocks -1), limits mid-run."""
    P = B * K + 1
    ks = jax.random.split(key, 5)
    cache = A.PagedAttnCache(
        k=jax.random.normal(ks[0], (P, Hkv, bsz, Dk), jnp.float32),
        v=jax.random.normal(ks[1], (P, Hkv, bsz, Dv), jnp.float32),
        pos=jnp.asarray(
            np.arange(P * bsz).reshape(P, bsz) % (K * bsz), jnp.int32))
    rs = np.random.RandomState(0)
    table = np.full((B, K), -1, np.int64)
    perm = rs.permutation(P - 1) + 1          # never the null page
    t = 0
    for b in range(B):
        kb = rs.randint(1, K + 1)
        table[b, :kb] = perm[t:t + kb]
        t += kb
    blk = rs.randint(1, K, (B,))
    positions = blk[:, None] * bsz + np.arange(bsz)[None, :]
    limit = blk * bsz
    k_self = jax.random.normal(ks[2], (B, bsz, Hkv, Dk), jnp.float32)
    v_self = jax.random.normal(ks[3], (B, bsz, Hkv, Dv), jnp.float32)
    q = jax.random.normal(ks[4], (B, bsz, 4 * Hkv, Dk), jnp.float32)
    return (cache, jnp.asarray(table, jnp.int32), k_self, v_self,
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(limit, jnp.int32), q)


def _prefill_setup(key, *, B, K, Ts, Hkv, Dk, Dv, bsz):
    """Shared-prefix suffix prefill: every row has K fully-hit prefix
    pages (sequential positions) and a Ts-block suffix to commit."""
    P = B * K + 1
    ks = jax.random.split(key, 6)
    cache = A.PagedAttnCache(
        k=jax.random.normal(ks[0], (P, Hkv, bsz, Dk), jnp.float32),
        v=jax.random.normal(ks[1], (P, Hkv, bsz, Dv), jnp.float32),
        pos=jnp.zeros((P, bsz), jnp.int32))
    table = np.zeros((B, K), np.int32)
    pos = np.full((P, bsz), -1, np.int32)
    pg = 1
    for b in range(B):
        for j in range(K):
            table[b, j] = pg
            pos[pg] = j * bsz + np.arange(bsz)
            pg += 1
    cache = cache._replace(pos=jnp.asarray(pos))
    T = Ts * bsz
    positions = np.broadcast_to(K * bsz + np.arange(T), (B, T))
    q = jax.random.normal(ks[2], (B, T, 4 * Hkv, Dk), jnp.float32)
    k_self = jax.random.normal(ks[3], (B, T, Hkv, Dk), jnp.float32)
    v_self = jax.random.normal(ks[4], (B, T, Hkv, Dv), jnp.float32)
    meta = SeqMeta(copy=jnp.zeros((B, T), jnp.int32),
                   block=jnp.asarray(positions // bsz, jnp.int32),
                   step=jnp.zeros((B, T), jnp.int32),
                   pos=jnp.asarray(positions, jnp.int32),
                   valid=jnp.ones((B, T), bool))
    return cache, jnp.asarray(table), q, k_self, v_self, meta


def _entry(sh, pass_, kernel, us, tokens, tb, dev):
    plan = plan_exec(sh["bsz"], sh["Dk"], sh["Dv"]) \
        if kernel == "pallas" else None
    return {"pass": pass_, "kernel": kernel, "B": sh["B"], "K": sh["K"],
            "bsz": sh["bsz"], "Hkv": sh["Hkv"], "Dk": sh["Dk"],
            "Dv": sh["Dv"], "us_per_call": round(us * 1e6, 1),
            "tok_s": round(tokens / max(us, 1e-12), 1),
            "transient_kv_bytes": tb,
            "mode": plan.mode if plan else "",
            "mode_reason": plan.reason if plan else "",
            "max_abs_dev": dev}


def _bench_decode(shapes, iters) -> list[dict]:
    from .common import timed
    entries = []
    for sh in shapes:
        args = _decode_setup(jax.random.PRNGKey(0), **sh)
        cache, table, ksf, vsf, pos, lim, q = args
        kw = dict(scale=sh["Dk"] ** -0.5, softcap=None, window=None)
        outs = {}
        for kernel in ("ref", "pallas"):
            layout = A.resolve_kv_layout(cache, kernel)
            fn = jax.jit(lambda q, c, t, ksf, vsf, pos, lim, _l=layout:
                         _l.attend(q, ksf, vsf, pos, c, block_table=t,
                                   cache_limit=lim, **kw))
            t = timed(lambda: fn(q, cache, table, ksf, vsf, pos, lim),
                      warmup=1, iters=iters)
            outs[kernel] = fn(q, cache, table, ksf, vsf, pos, lim)
            tb = A.transient_kv_bytes(cache, sh["B"], sh["K"], kernel)
            dev = 0.0 if kernel == "ref" else float(
                jnp.abs(outs["pallas"] - outs["ref"]).max())
            entries.append(_entry(sh, "decode", kernel, t,
                                  sh["B"] * sh["bsz"], tb, dev))
    return entries


def _bench_prefill(shapes, iters) -> list[dict]:
    from .common import timed
    entries = []
    for sh in shapes:
        cache, table, q, ksf, vsf, meta = _prefill_setup(
            jax.random.PRNGKey(1), **sh)
        kw = dict(block_size=sh["bsz"], impl="chunked",
                  scale=sh["Dk"] ** -0.5, softcap=None, window=None)
        outs = {}
        for kernel in ("ref", "pallas"):
            layout = A.resolve_kv_layout(cache, kernel)
            fn = jax.jit(lambda q, c, t, ksf, vsf, m, _l=layout:
                         _l.prefill_attend(q, ksf, vsf, m, c,
                                           context_table=t, **kw))
            t = timed(lambda: fn(q, cache, table, ksf, vsf, meta),
                      warmup=1, iters=iters)
            outs[kernel] = fn(q, cache, table, ksf, vsf, meta)
            tb = A.prefill_transient_kv_bytes(cache, sh["B"], sh["K"],
                                              kernel)
            dev = 0.0 if kernel == "ref" else float(
                jnp.abs(outs["pallas"] - outs["ref"]).max())
            tokens = sh["B"] * sh["Ts"] * sh["bsz"]
            entries.append(_entry(sh, "prefill", kernel, t, tokens, tb,
                                  dev))
    return entries


def run(quick: bool = True, smoke: bool = False) -> list[str]:
    from .common import write_bench_json
    decode_shapes = [dict(B=8, K=8, Hkv=2, Dk=32, Dv=32, bsz=16)]
    prefill_shapes = [dict(B=4, K=4, Ts=2, Hkv=2, Dk=32, Dv=32, bsz=16)]
    if smoke:
        decode_shapes = [dict(B=2, K=2, Hkv=1, Dk=16, Dv=16, bsz=8)]
        prefill_shapes = [dict(B=1, K=2, Ts=1, Hkv=1, Dk=16, Dv=16,
                               bsz=8)]
    elif not quick:
        decode_shapes += [
            dict(B=16, K=16, Hkv=2, Dk=64, Dv=64, bsz=32),
            dict(B=8, K=16, Hkv=1, Dk=72, Dv=64, bsz=32)]   # MLA-ish
        prefill_shapes += [
            dict(B=4, K=8, Ts=4, Hkv=2, Dk=64, Dv=64, bsz=32),
            dict(B=2, K=8, Ts=2, Hkv=1, Dk=72, Dv=64, bsz=32)]
    iters = 1 if smoke else 3
    entries = _bench_decode(decode_shapes, iters) \
        + _bench_prefill(prefill_shapes, iters)
    path = write_bench_json("paged_attn", entries)
    rows = [",".join(ENTRY_KEYS)]
    rows += [",".join(str(e[k]) for k in ENTRY_KEYS) for e in entries]
    rows.append(f"# wrote {path}")
    return rows


if __name__ == "__main__":
    print("\n".join(run()))

"""BlockDiffLM — the unified block-diffusion language model.

Wraps any assigned backbone (dense / MoE / SSM / hybrid / enc-dec / VLM)
with the paper's block-diffusion post-training semantics.  Three entry
points (see context.LayerCtx):

* ``forward_masked``  — full-sequence masked pass; with ``dup_len`` set it
  is the paper's duplicated-sequence unbiased-logit pass (§4.1), without
  it a committed block-causal pass (prefill — optionally filling caches
  and emitting SSM boundary states for trajectory replay).
* ``decode_step``     — one denoise forward of the current block against
  the caches (serve_step; also the building block of trajectory replay).

Layers are applied in repeating pattern groups via ``lax.scan`` with
optional remat, so 72-layer configs lower with compact HLO.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.masks import SeqMeta
from repro.distributed.ctx import BATCH, shard_hint
from . import attention as attn
from . import ffn as ffn_mod
from . import ssm as ssm_mod
from .config import LayerSpec, ModelConfig, layer_pattern
from .context import LayerCtx
from .modules import (embed, fold_name, init_embedding, init_linear,
                      init_rmsnorm, linear, rmsnorm, softcap, split_like,
                      unembed)

Params = Any


# ---------------------------------------------------------------------------
# token-shift helpers (RWKV channel mix)
# ---------------------------------------------------------------------------


def _shift_plain(x: jax.Array, prev: jax.Array) -> jax.Array:
    return jnp.concatenate([prev[:, None, :].astype(x.dtype), x[:, :-1, :]],
                           axis=1)


def _shift_dup(x: jax.Array, L: int, bsz: int) -> jax.Array:
    """Token shift over the duplicated layout: the clean half shifts
    normally; each noisy block's first position shifts from the last clean
    hidden of the previous block."""
    B, T, d = x.shape
    K = L // bsz
    clean, noisy = x[:, :L], x[:, L:]
    zero = jnp.zeros((B, 1, d), x.dtype)
    sh_clean = jnp.concatenate([zero, clean[:, :-1]], axis=1)
    bounds = jnp.concatenate([zero, clean[:, bsz - 1:-1:bsz]], axis=1)
    noisy_b = noisy.reshape(B, K, bsz, d)
    sh_noisy = jnp.concatenate([bounds[:, :, None, :], noisy_b[:, :, :-1]],
                               axis=2).reshape(B, L, d)
    return jnp.concatenate([sh_clean, sh_noisy], axis=1)


def _fold_blocks(x, L, bsz):
    """(B, L, ...) -> (B*K, bsz, ...)"""
    B = x.shape[0]
    K = L // bsz
    return x.reshape(B, K, bsz, *x.shape[2:]).reshape(B * K, bsz,
                                                      *x.shape[2:])


def _unfold_blocks(x, B, L, bsz):
    return x.reshape(B, L // bsz, bsz, *x.shape[2:]).reshape(
        B, L, *x.shape[2:])


def _bounds_to_batch(bounds, B):
    """(K, B, ...) boundary pytree -> (B*K, ...) matching _fold_blocks."""
    return jax.tree.map(
        lambda a: a.swapaxes(0, 1).reshape(B * a.shape[0], *a.shape[2:]),
        bounds)


# ---------------------------------------------------------------------------
# single-layer application
# ---------------------------------------------------------------------------


def _ssm_forward_fn(spec: LayerSpec):
    return ssm_mod.rwkv6_forward if spec.mixer == "rwkv6" \
        else ssm_mod.mamba_forward


def _apply_ssm(cfg: ModelConfig, spec: LayerSpec, lp, h, ctx: LayerCtx,
               cache):
    fwd = _ssm_forward_fn(spec)
    key = "rwkv" if spec.mixer == "rwkv6" else "mamba"
    bsz = cfg.block_size
    if ctx.mode == "dup":
        B = h.shape[0]
        L = ctx.dup_len
        K = L // bsz
        zero = (ssm_mod.rwkv6_zero_state(cfg, B) if spec.mixer == "rwkv6"
                else ssm_mod.mamba_zero_state(cfg, B))
        zero = {k_: v for k_, v in zero.items() if k_ != "cm_shift"}
        y_clean, _, bounds = fwd(lp[key], h[:, :L], zero, cfg, n_blocks=K)
        binst = _bounds_to_batch(bounds, B)
        y_noisy, _, _ = fwd(lp[key], _fold_blocks(h[:, L:], L, bsz),
                            binst, cfg)
        y = jnp.concatenate([y_clean, _unfold_blocks(y_noisy, B, L, bsz)],
                            axis=1)
        return y, cache, None
    if ctx.mode == "plain":
        state = cache if cache is not None else _zero_ssm(cfg, spec,
                                                          h.shape[0])
        nb = h.shape[1] // bsz if ctx.want_boundaries else None
        state_in = {k_: v for k_, v in state.items() if k_ != "cm_shift"}
        y, new_state, bounds = fwd(lp[key], h, state_in, cfg, n_blocks=nb)
        if cache is not None and "cm_shift" in cache:
            new_state["cm_shift"] = cache["cm_shift"]
        return y, (new_state if cache is not None else cache), bounds
    # decode: run the block from the committed state
    state_in = {k_: v for k_, v in cache.items() if k_ != "cm_shift"}
    y, new_state, _ = fwd(lp[key], h, state_in, cfg)
    if ctx.write_cache:
        if "cm_shift" in cache:
            new_state["cm_shift"] = cache["cm_shift"]
        return y, new_state, None
    return y, cache, None


def _zero_ssm(cfg, spec, batch):
    return (ssm_mod.rwkv6_zero_state(cfg, batch) if spec.mixer == "rwkv6"
            else ssm_mod.mamba_zero_state(cfg, batch))


def _apply_mixer(cfg: ModelConfig, spec: LayerSpec, lp, h, ctx: LayerCtx,
                 cache):
    """Returns (y, new_cache, boundaries|None)."""
    if spec.mixer == "attn":
        masked_fn = attn.mla_masked if cfg.attn_kind == "mla" \
            else attn.gqa_masked
        decode_fn = attn.mla_decode if cfg.attn_kind == "mla" \
            else attn.gqa_decode
        if ctx.mode in ("dup", "plain"):
            if ctx.mode == "plain" and \
                    isinstance(cache, attn.PagedAttnCache):
                # shared-prefix suffix prefill: committed pass reading
                # the prefix through pages, committing into fresh pages
                paged_fn = attn.mla_plain_paged if cfg.attn_kind == "mla" \
                    else attn.gqa_plain_paged
                y, new_cache = paged_fn(
                    lp["attn"], h, ctx.meta, cache, cfg,
                    window=spec.window, context_table=ctx.context_table,
                    write_pages=ctx.write_pages, kernel=ctx.kv_kernel)
                return y, new_cache, None
            y, k, v = masked_fn(lp["attn"], h, ctx.meta, cfg,
                                window=spec.window, dup_len=ctx.dup_len,
                                strict=ctx.strict)
            new_cache = cache
            if cache is not None and ctx.mode == "plain":
                new_cache = attn.write_prefill_cache(cache, k, v,
                                                     ctx.meta.pos)
            return y, new_cache, None
        y, new_cache = decode_fn(lp["attn"], h, ctx.positions, cache, cfg,
                                 window=spec.window,
                                 write_cache=ctx.write_cache,
                                 cache_limit=ctx.cache_limit,
                                 block_table=ctx.block_table,
                                 kernel=ctx.kv_kernel)
        return y, new_cache, None
    if spec.mixer in ("rwkv6", "mamba"):
        return _apply_ssm(cfg, spec, lp, h, ctx, cache)
    if spec.mixer == "cross_attn":
        y = attn.cross_attn(lp["cross"], h, ctx.memory, cfg,
                            ctx.memory_valid)
        return y, cache, None
    raise ValueError(spec.mixer)


def _apply_ffn(cfg: ModelConfig, spec: LayerSpec, lp, h, ctx: LayerCtx,
               cache):
    """Returns (y, new_cache, aux_loss, boundaries|None)."""
    zero = jnp.zeros((), jnp.float32)
    if spec.ffn == "dense":
        return ffn_mod.swiglu(lp["ffn"], h, act=cfg.act), cache, zero, None
    if spec.ffn == "moe":
        y, aux = ffn_mod.moe(lp["moe"], h, cfg)
        return y, cache, aux["aux_loss"], None
    if spec.ffn == "rwkv_cm":
        if ctx.mode == "dup":
            shifted = _shift_dup(h, ctx.dup_len, cfg.block_size)
            y = ffn_mod.rwkv_cm(lp["cm"], h, shifted)
            return y, cache, zero, None
        prev = cache["cm_shift"] if (cache is not None and
                                     "cm_shift" in cache) \
            else jnp.zeros((h.shape[0], h.shape[-1]), h.dtype)
        shifted = _shift_plain(h, prev)
        y = ffn_mod.rwkv_cm(lp["cm"], h, shifted)
        new_cache = cache
        if cache is not None and (ctx.mode == "plain" or ctx.write_cache):
            new_cache = dict(cache)
            new_cache["cm_shift"] = h[:, -1, :].astype(jnp.float32)
        bounds = None
        if ctx.mode == "plain" and ctx.want_boundaries:
            bsz = cfg.block_size
            cm_b = jnp.concatenate(
                [prev[:, None, :].astype(jnp.float32),
                 h[:, bsz - 1:-1:bsz, :].astype(jnp.float32)], axis=1)
            bounds = {"cm_shift": cm_b.swapaxes(0, 1)}   # (K, B, d)
        return y, new_cache, zero, bounds
    raise ValueError(spec.ffn)


def _apply_layer(cfg: ModelConfig, spec: LayerSpec, lp, x, ctx: LayerCtx,
                 cache):
    """Pre-norm residual layer.  Returns (x, new_cache, aux, boundaries)."""
    h = rmsnorm(lp["attn_norm"], x, eps=cfg.norm_eps)
    y, new_cache, bounds = _apply_mixer(cfg, spec, lp, h, ctx, cache)
    if cfg.sandwich_norm:
        y = rmsnorm(lp["post_attn_norm"], y, eps=cfg.norm_eps)
    x = x + shard_hint(y, BATCH, None, None)

    if spec.cross and ctx.memory is not None:
        hc = rmsnorm(lp["cross_norm"], x, eps=cfg.norm_eps)
        x = x + attn.cross_attn(lp["cross"], hc, ctx.memory, cfg,
                                ctx.memory_valid)

    h = rmsnorm(lp["ffn_norm"], x, eps=cfg.norm_eps)
    y, new_cache, aux, ffn_bounds = _apply_ffn(cfg, spec, lp, h, ctx,
                                               new_cache)
    if cfg.sandwich_norm:
        y = rmsnorm(lp["post_ffn_norm"], y, eps=cfg.norm_eps)
    x = x + shard_hint(y, BATCH, None, None)
    # dict|None truthiness: pytree *structure*, static under jit
    if ffn_bounds:  # dirlint: ok(trace-branch)
        bounds = {**(bounds or {}), **ffn_bounds}
    return x, new_cache, aux, bounds


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = split_like(key, ["mixer", "cross", "ffn"])
    p: dict = {"attn_norm": init_rmsnorm(d, dtype=dt),
               "ffn_norm": init_rmsnorm(d, dtype=dt)}
    if cfg.sandwich_norm:
        p["post_attn_norm"] = init_rmsnorm(d, dtype=dt)
        p["post_ffn_norm"] = init_rmsnorm(d, dtype=dt)

    if spec.mixer == "attn":
        p["attn"] = attn.init_mla(ks["mixer"], cfg) \
            if cfg.attn_kind == "mla" else attn.init_gqa(ks["mixer"], cfg)
    elif spec.mixer == "rwkv6":
        p["rwkv"] = ssm_mod.init_rwkv6(ks["mixer"], cfg)
    elif spec.mixer == "mamba":
        p["mamba"] = ssm_mod.init_mamba(ks["mixer"], cfg)
    elif spec.mixer == "cross_attn":
        p["cross"] = attn.init_cross(ks["mixer"], cfg, gated=True)

    if spec.cross:
        p["cross_norm"] = init_rmsnorm(d, dtype=dt)
        p["cross"] = attn.init_cross(ks["cross"], cfg, gated=False)

    if spec.ffn == "dense":
        f = spec.d_ff or cfg.d_ff
        p["ffn"] = ffn_mod.init_swiglu(ks["ffn"], d, f, dtype=dt)
    elif spec.ffn == "moe":
        p["moe"] = ffn_mod.init_moe(ks["ffn"], cfg)
    elif spec.ffn == "rwkv_cm":
        p["cm"] = ffn_mod.init_rwkv_cm(ks["ffn"], d, cfg.d_ff, dtype=dt)
    return p


def _layer_cache_struct(cfg: ModelConfig, spec: LayerSpec, batch: int,
                        cache_len: int, ring: bool = True):
    dt = jnp.dtype(cfg.dtype)
    if spec.mixer == "attn":
        S = min(cache_len, spec.window) if (spec.window and ring) \
            else cache_len
        if cfg.attn_kind == "mla":
            return attn.make_attn_cache(
                batch, S, 1, cfg.kv_lora_rank + cfg.qk_rope_dim,
                cfg.kv_lora_rank, dt)
        return attn.make_attn_cache(batch, S, cfg.n_kv_heads,
                                    cfg.resolved_head_dim,
                                    cfg.resolved_head_dim, dt)
    if spec.mixer == "rwkv6":
        return ssm_mod.rwkv6_zero_state(cfg, batch)
    if spec.mixer == "mamba":
        st = ssm_mod.mamba_zero_state(cfg, batch)
        return st
    return None  # cross_attn layers keep no cache


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


class BlockDiffLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.prefix_specs, self.group_specs, self.n_groups = \
            layer_pattern(cfg)

    # ------------------------------------------------------------- init
    def init(self, key) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        ks = split_like(key, ["embed", "prefix", "groups", "head", "proj",
                              "enc"])
        params: dict = {
            "embed": init_embedding(ks["embed"], cfg.vocab_size, cfg.d_model,
                                    dtype=dt),
            "final_norm": init_rmsnorm(cfg.d_model, dtype=dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_linear(ks["head"], cfg.d_model,
                                            cfg.vocab_size, dtype=dt)
        if self.prefix_specs:
            pk = jax.random.split(ks["prefix"], len(self.prefix_specs))
            params["prefix"] = {
                f"l{i}": _init_layer(pk[i], cfg, s)
                for i, s in enumerate(self.prefix_specs)}

        def init_group(gkey):
            lk = jax.random.split(gkey, len(self.group_specs))
            return {f"l{j}": _init_layer(lk[j], cfg, s)
                    for j, s in enumerate(self.group_specs)}

        gkeys = jax.random.split(ks["groups"], self.n_groups)
        params["groups"] = jax.vmap(init_group)(gkeys)

        if cfg.n_extra_tokens:
            params["projector"] = init_linear(
                ks["proj"], cfg.extra_embed_dim or cfg.d_model, cfg.d_model,
                dtype=dt)
        if cfg.encoder_layers:
            enc_cfg = cfg.replace(arch_type="dense", n_layers=cfg.encoder_layers,
                                  n_experts=0, first_k_dense=0,
                                  sliding_window=0, local_global=False)
            enc_spec = enc_cfg.layer_spec(0)

            def init_enc(gkey):
                return {"l0": _init_layer(gkey, enc_cfg, enc_spec)}

            ekeys = jax.random.split(ks["enc"], cfg.encoder_layers)
            params["encoder"] = {
                "groups": jax.vmap(init_enc)(ekeys),
                "final_norm": init_rmsnorm(cfg.d_model, dtype=dt),
            }
        return params

    # --------------------------------------------------------- plumbing
    def _embed(self, params, ids):
        x = embed(params["embed"], ids, dtype=jnp.dtype(self.cfg.dtype))
        if self.cfg.embed_scale:
            x = x * jnp.asarray(self.cfg.d_model ** 0.5, x.dtype)
        return shard_hint(x, BATCH, None, None)

    def _logits(self, params, x):
        cfg = self.cfg
        x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = unembed(params["embed"], x)
        else:
            logits = linear(params["lm_head"], x,
                            dtype=jnp.float32)
        if cfg.final_logit_softcap:
            logits = softcap(logits, cfg.final_logit_softcap)
        return shard_hint(logits, BATCH, None, "model")

    def _run_stack(self, params, x, ctx: LayerCtx, caches):
        """prefix layers then scanned groups.

        caches: {"prefix": {...}|None, "groups": stacked-G pytree|None}.
        Returns (x, new_caches, aux_sum, boundaries).
        """
        cfg = self.cfg
        aux_sum = jnp.zeros((), jnp.float32)
        new_prefix = {}
        prefix_bounds = {}
        for i, spec in enumerate(self.prefix_specs):
            c = None if caches is None else caches["prefix"][f"l{i}"]
            x, nc, aux, bd = _apply_layer(cfg, spec,
                                          params["prefix"][f"l{i}"], x,
                                          ctx, c)
            new_prefix[f"l{i}"] = nc
            prefix_bounds[f"l{i}"] = bd
            aux_sum = aux_sum + aux

        gcaches = None if caches is None else caches["groups"]

        def body(carry, xs):
            x, aux_acc = carry
            x = shard_hint(x, BATCH, None, None)
            gp, gc = xs
            new_gc = {}
            bnds = {}
            for j, spec in enumerate(self.group_specs):
                c = None if gc is None else gc[f"l{j}"]
                x, nc, aux, bd = _apply_layer(cfg, spec, gp[f"l{j}"], x,
                                              ctx, c)
                new_gc[f"l{j}"] = nc
                bnds[f"l{j}"] = bd
                aux_acc = aux_acc + aux
            return (x, aux_acc), (new_gc, bnds)

        if cfg.remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        (x, aux_sum), (new_gcaches, gbounds) = jax.lax.scan(
            body, (x, aux_sum), (params["groups"], gcaches))

        new_caches = None
        if caches is not None:
            new_caches = {"prefix": new_prefix, "groups": new_gcaches}
        bounds = {"prefix": prefix_bounds, "groups": gbounds}
        return x, new_caches, aux_sum, bounds

    # ------------------------------------------------------ public API
    def compute_memory(self, params, extra_embeds, extra_valid=None):
        """Project (and for enc-dec, encode) modality-frontend embeddings."""
        cfg = self.cfg
        if extra_embeds is None:
            return None
        mem = linear(params["projector"],
                     extra_embeds.astype(jnp.dtype(cfg.dtype)))
        if cfg.encoder_layers:
            B, Ne, _ = mem.shape
            # bidirectional: all positions share block 0
            meta = SeqMeta(copy=jnp.zeros((B, Ne), jnp.int32),
                           block=jnp.zeros((B, Ne), jnp.int32),
                           step=jnp.zeros((B, Ne), jnp.int32),
                           pos=jnp.broadcast_to(
                               jnp.arange(Ne, dtype=jnp.int32), (B, Ne)),
                           valid=(extra_valid if extra_valid is not None
                                  else jnp.ones((B, Ne), bool)))
            ctx = LayerCtx(mode="plain", meta=meta)
            enc_cfg = cfg.replace(arch_type="dense",
                                  n_layers=cfg.encoder_layers, n_experts=0,
                                  first_k_dense=0, sliding_window=0,
                                  local_global=False)
            enc_spec = enc_cfg.layer_spec(0)

            def body(carry, gp):
                h, _ = carry
                h, _, _, _ = _apply_layer(enc_cfg, enc_spec, gp["l0"], h,
                                          ctx, None)
                return (h, 0.0), None

            (x, _), _ = jax.lax.scan(
                body, (mem, 0.0), params["encoder"]["groups"])
            mem = rmsnorm(params["encoder"]["final_norm"], x,
                          eps=cfg.norm_eps)
        return mem

    def forward_masked(self, params, input_ids, meta: SeqMeta, *,
                       dup_len: int | None = None, strict: bool = False,
                       memory=None, memory_valid=None, caches=None,
                       want_boundaries: bool = False,
                       logits_from: int | None = None):
        """Masked full-sequence forward.

        ``logits_from``: unembed only positions [logits_from:] — on
        duplicated layouts the clean copy never carries loss, and at a
        256k vocab skipping its logits halves the biggest activation of
        the train step.

        Returns (logits, {"aux_loss", "caches", "boundaries"}).
        """
        ctx = LayerCtx(mode="dup" if dup_len is not None else "plain",
                       meta=meta, dup_len=dup_len, strict=strict,
                       memory=memory, memory_valid=memory_valid,
                       want_boundaries=want_boundaries)
        x = self._embed(params, input_ids)
        x, new_caches, aux, bounds = self._run_stack(params, x, ctx, caches)
        if logits_from is not None:
            x = x[:, logits_from:]
        logits = self._logits(params, x)
        return logits, {"aux_loss": aux, "caches": new_caches,
                        "boundaries": bounds}

    def decode_step(self, params, block_ids, positions, caches, *,
                    cache_limit=None, block_table=None, memory=None,
                    memory_valid=None, write: bool = False,
                    kv_kernel: str = "ref"):
        """One denoise forward of the current block (serve_step).

        block_ids/positions: (B, block_size).  Returns (logits, caches).
        ``block_table`` (B, K) is required iff the attention caches are
        paged (``make_paged_caches``); dense caches ignore it.
        ``kv_kernel`` picks the decode KV layout (attention.
        resolve_kv_layout): ``"ref"`` = dense concat / gathered-paged
        fallback, ``"pallas"`` = the in-place page-aware kernel.
        """
        ctx = LayerCtx(mode="decode", positions=positions,
                       cache_limit=cache_limit, block_table=block_table,
                       write_cache=write, kv_kernel=kv_kernel,
                       memory=memory, memory_valid=memory_valid)
        x = self._embed(params, block_ids)
        x, new_caches, _, _ = self._run_stack(params, x, ctx, caches)
        logits = self._logits(params, x)
        return logits, new_caches

    def prefill_suffix(self, params, suffix_ids, meta: SeqMeta, caches, *,
                       context_table, write_pages,
                       kv_kernel: str = "ref"):
        """Committed pass over a prompt suffix through paged caches.

        ``suffix_ids`` (B, T) with ``meta`` carrying *absolute*
        positions; attention layers read the already-committed prefix
        through ``context_table`` (B, Kp) shared pages and commit the
        suffix blocks into ``write_pages`` (B, T // block_size).  Skips
        the logits (prefill only needs caches).  Attention-only stacks:
        recurrent layers carry per-slot state that pages cannot share
        (the scheduler gates prefix caching off for them).

        ``kv_kernel`` picks the prefill KV layout (attention.
        resolve_kv_layout): ``"ref"`` gathers the hit-prefix pages into
        a dense-width copy once per admission, ``"pallas"`` streams
        them in place (``kernels.paged_attn.paged_prefill_attention``),
        so admission pays zero transient KV bytes.  ``"ref"`` gives
        suffix KV bitwise identical to a full prefill, ``"pallas"``
        equal to f32 rounding.
        """
        ctx = LayerCtx(mode="plain", meta=meta,
                       context_table=context_table,
                       write_pages=write_pages, kv_kernel=kv_kernel)
        x = self._embed(params, suffix_ids)
        _, new_caches, _, _ = self._run_stack(params, x, ctx, caches)
        return new_caches

    def make_caches(self, batch: int, cache_len: int, *,
                    ring: bool = True):
        """Zero caches for ``batch`` sequences with ``cache_len`` capacity.

        ``ring=True`` bounds sliding-window layers' buffers to the window
        (correct for sequential serving, where only the last W committed
        keys are live).  Pass ``ring=False`` for replay-style random
        access over a fully prefilled sequence (every block revisited).
        """
        prefix = {f"l{i}": _layer_cache_struct(self.cfg, s, batch,
                                               cache_len, ring)
                  for i, s in enumerate(self.prefix_specs)}
        one = {f"l{j}": _layer_cache_struct(self.cfg, s, batch, cache_len,
                                            ring)
               for j, s in enumerate(self.group_specs)}
        return {"prefix": prefix, "groups": self._stack_groups(one)}

    def make_paged_caches(self, batch: int, n_pages: int):
        """Paged decode caches for ``batch`` slots over ``n_pages`` pages.

        Attention layers get a shared ``PagedAttnCache`` pool of
        block-size pages (page 0 is the null page — the allocator must
        never hand it out); recurrent/conv states are O(1) per sequence
        and stay per-slot exactly as in ``make_caches``.  Reads/writes go
        through the (batch, n_blocks) block table in ``GenState.table``.
        """
        prefix = {f"l{i}": self._paged_layer_cache_struct(s, batch, n_pages)
                  for i, s in enumerate(self.prefix_specs)}
        one = {f"l{j}": self._paged_layer_cache_struct(s, batch, n_pages)
               for j, s in enumerate(self.group_specs)}
        return {"prefix": prefix, "groups": self._stack_groups(one)}

    def _paged_layer_cache_struct(self, spec: LayerSpec, batch: int,
                                  n_pages: int):
        cfg = self.cfg
        if spec.mixer == "attn":
            dt = jnp.dtype(cfg.dtype)
            if cfg.attn_kind == "mla":
                return attn.make_paged_attn_cache(
                    n_pages, cfg.block_size, 1,
                    cfg.kv_lora_rank + cfg.qk_rope_dim, cfg.kv_lora_rank,
                    dt)
            return attn.make_paged_attn_cache(
                n_pages, cfg.block_size, cfg.n_kv_heads,
                cfg.resolved_head_dim, cfg.resolved_head_dim, dt)
        # recurrent / conv / no-cache layers: per-slot, unchanged
        return _layer_cache_struct(cfg, spec, batch, cfg.block_size)

    def _stack_groups(self, one):
        """Stack a single group's cache struct G times (pos sentinel
        preserved)."""
        groups = jax.tree.map(
            lambda a: jnp.zeros((self.n_groups,) + a.shape, a.dtype), one)
        # restore pos = -1 sentinel
        groups = jax.tree.map(
            lambda z, o: jnp.broadcast_to(o[None], z.shape).astype(z.dtype)
            if o.dtype == jnp.int32 else z, groups, one)
        return groups

    def param_count(self, params) -> int:
        return sum(p.size for p in jax.tree_util.tree_leaves(params))

"""Blockwise semi-autoregressive decoding (static & dynamic-threshold).

The generation loop is built from one reusable, jit-compatible primitive:
``advance_block`` advances every sequence of a ``GenState`` by exactly one
block — denoise (``denoise_block``), freeze finished rows, commit the
block into the caches, and move the per-sequence cursors.  The one-shot
``generate`` wraps it in a ``fori_loop``; the continuous-batching
``serving.scheduler.SlotScheduler`` calls the same primitive once per
scheduler tick with admissions in between.  Because every row of the
state advances independently (per-row caches, per-row rng streams), the
two drivers produce token-identical outputs and step maps for the same
per-sequence rng keys — the property the RL trainer relies on for
DiPO-exact rollouts.

Every revealed token's step index is recorded — that step map is exactly
what DiPO's unbiased logit computation consumes (trajectory.py).

Dynamic decoding (paper §4.4/§5.1): at each denoise step, reveal every
still-masked position whose top-1 probability exceeds tau (at least one —
the best-confidence position — is always revealed).  Static decoding:
reveal a fixed number of highest-confidence positions per step.

Per-row sampling parameters: every decode knob a request may set —
``tau``, ``temperature``, static-mode ``n_steps``, the dynamic/static
mode itself, and the stop token — lives in **per-sequence vectors on
``GenState``** and is read per row inside the jitted step (the two
reveal policies are computed side by side and selected with a per-row
``jnp.where``).  Nothing about a request's parameters is a jit static,
so one compiled ``advance_block`` serves arbitrarily mixed
configurations; the single remaining static is ``s_max``, the global
denoise-loop bound (it fixes compiled loop structure, not data — rows
whose policy finishes earlier just stop revealing).  A row decoded in a
mixed batch is bit-identical to the same row in a homogeneous batch:
every per-row branch selects between values computed from that row's
own parameters only.

RNG discipline: the state carries one rng key **per sequence** (shape
(B, 2)); each denoise step splits every row's key independently, so a
sequence's sample stream depends only on its own key — never on batch
composition.  ``generate`` accepts either a single key (split across the
batch) or a precomputed (B, 2) key array.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .masks import plain_layout


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GenState:
    tokens: jax.Array      # (B, L_max)
    steps: jax.Array       # (B, L_max) reveal-step map
    caches: dict
    blk: jax.Array         # (B,) next block index per sequence
    done: jax.Array        # (B,)
    rng: jax.Array         # (B, 2) per-sequence rng keys
    limit: jax.Array       # (B,) exclusive block cursor cap per sequence
    n_denoise: jax.Array   # (B,) cumulative denoise steps actually used
    # per-row sampling parameters (traced data, never jit statics — one
    # compiled advance serves mixed configurations without retracing)
    tau: jax.Array         # (B,) f32 dynamic-mode reveal threshold
    temperature: jax.Array  # (B,) f32; 0 = greedy argmax
    n_steps: jax.Array     # (B,) i32 static-mode denoise-step budget
    dynamic: jax.Array     # (B,) bool: dynamic vs static reveal policy
    eos: jax.Array         # (B,) i32 stop token (-1 disables EOS stop)
    # paged caches only: (B, L_max // block_size) block -> page id, -1 =
    # no page (None when the caches are dense per-sequence regions)
    table: jax.Array | None = None


def _per_seq_keys(rng, batch: int) -> jax.Array:
    """Accept a single key or a (B, 2) batch of keys."""
    rng = jnp.asarray(rng)
    if rng.ndim == 2:
        return rng
    return jax.random.split(rng, batch)


def sampling_vectors(batch: int, *, tau=0.9, temperature=0.0, n_steps=8,
                     mode="dynamic", eos_id=1) -> dict:
    """Broadcast scalar-or-per-row sampling fields to (B,) vectors.

    ``mode`` is either a string applied to every row or a (B,) bool
    array (True = dynamic); the numeric fields accept scalars or (B,)
    arrays.  Returns the ``GenState`` sampling-field dict.
    """
    if isinstance(mode, str):
        if mode not in ("dynamic", "static"):
            raise ValueError(f"mode must be dynamic|static, got {mode!r}")
        dynamic = jnp.full((batch,), mode == "dynamic")
    else:
        dynamic = jnp.broadcast_to(jnp.asarray(mode, bool), (batch,))
    return {
        "tau": jnp.broadcast_to(
            jnp.asarray(tau, jnp.float32), (batch,)),
        "temperature": jnp.broadcast_to(
            jnp.asarray(temperature, jnp.float32), (batch,)),
        "n_steps": jnp.broadcast_to(
            jnp.asarray(n_steps, jnp.int32), (batch,)),
        "dynamic": dynamic,
        "eos": jnp.broadcast_to(
            jnp.asarray(eos_id, jnp.int32), (batch,)),
    }


def _select_boundary(caches, bounds, prompt_blocks):
    """Per-sequence SSM state at each sequence's own prompt boundary."""
    B = prompt_blocks.shape[0]
    rows = jnp.arange(B)

    def merge_layer(cache, bd, grouped):
        if bd is None or cache is None:
            return cache
        new = dict(cache)
        for skey, arr in bd.items():
            if grouped:  # (G, K, B, ...)
                new[skey] = arr[:, prompt_blocks, rows]
            else:        # (K, B, ...)
                new[skey] = arr[prompt_blocks, rows]
        return new

    out = {"prefix": {}, "groups": {}}
    for lk, cache in caches["prefix"].items():
        out["prefix"][lk] = merge_layer(cache, bounds["prefix"].get(lk),
                                        grouped=False)
    for lk, cache in caches["groups"].items():
        out["groups"][lk] = merge_layer(cache, bounds["groups"].get(lk),
                                        grouped=True)
    return out


def prefill(model, params, prompt_tokens, prompt_blocks, max_len: int, *,
            ring: bool = True, memory=None, memory_valid=None):
    """Run the committed pass over (block-aligned, right-padded) prompts.

    prompt_tokens (B, Lp) with Lp a block multiple; prompt_blocks (B,) the
    per-sequence true prompt length in blocks.  Returns caches sized for
    ``max_len`` with every prompt position written (positions beyond a
    sequence's true prompt are masked at decode time via cache_limit and
    overwritten on commit).  ``ring=False`` keeps sliding-window layers'
    buffers full-length (needed when the rows are re-scattered into a
    paged pool block-by-block).
    """
    cfg = model.cfg
    B, Lp = prompt_tokens.shape
    valid = jnp.ones((B, Lp), bool)
    meta = plain_layout(prompt_tokens, valid, block_size=cfg.block_size)
    caches = model.make_caches(B, max_len, ring=ring)
    want_b = bool(cfg.ssm_kind)
    _, out = model.forward_masked(params, prompt_tokens, meta,
                                  caches=caches, want_boundaries=want_b,
                                  memory=memory, memory_valid=memory_valid)
    caches = out["caches"]
    if want_b:
        caches = _select_boundary(caches, out["boundaries"], prompt_blocks)
    return caches


def prefill_suffix(model, params, suffix_tokens, start_block: jax.Array,
                   caches, context_table, write_pages,
                   kv_kernel: str = "ref"):
    """Suffix-only prefill: commit prompt blocks [start_block, ...) while
    reading the shared-prefix KV through ``context_table`` pages.

    The shared-prefix admission path (``serving.prefix_cache``): when the
    first ``start_block`` blocks of a prompt are already cached, only the
    suffix needs a committed pass.  ``suffix_tokens`` (B, Ls) with Ls a
    block multiple; ``context_table`` (B, Kp) page ids of the cached
    prefix (Kp == start_block, no -1 padding); ``write_pages``
    (B, Ls // block_size) freshly allocated pages that receive the
    suffix KV.  Returns the updated (paged) caches.

    Bitwise contract: the combined key array (prefix pages ++ suffix
    self-KV) has exactly the full prompt's key layout, and the attention
    over it is row- and length-invariant, so the committed suffix KV is
    *byte-identical* to the same blocks of a full ``prefill`` — the
    property the scheduler's prefix-cache on/off token-parity guarantee
    rests on.  This holds for ``kv_kernel="ref"`` (the prefix pages are
    gathered into a dense-width copy) when the cache dtype equals the
    activation dtype (fp32 default); lower-precision caches would round
    the prefix context where the full pass attends pre-rounding.  With
    ``"pallas"`` (``paged_prefill_attention`` streams the pages in
    place, summing its online softmax page by page) the suffix KV
    agrees to f32 rounding, not bitwise.
    """
    cfg = model.cfg
    B, Ls = suffix_tokens.shape
    assert Ls % cfg.block_size == 0 and Ls > 0
    pos = jnp.asarray(start_block, jnp.int32) * cfg.block_size \
        + jnp.arange(Ls, dtype=jnp.int32)
    meta = plain_layout(suffix_tokens, jnp.ones((B, Ls), bool),
                        block_size=cfg.block_size)
    pos = jnp.broadcast_to(pos, (B, Ls))
    meta = dataclasses.replace(meta, pos=pos,
                               block=pos // cfg.block_size)
    return model.prefill_suffix(params, suffix_tokens, meta, caches,
                                context_table=context_table,
                                write_pages=write_pages,
                                kv_kernel=kv_kernel)


def denoise_block(model, params, caches, blk, rng, *, tau, temperature,
                  n_steps, dynamic, s_max: int, table=None,
                  kv_kernel: str = "ref",
                  memory=None, memory_valid=None):
    """Denoise one block for every sequence.

    ``rng`` is a (B, 2) batch of per-sequence keys; every row's stream is
    split independently so sampling is invariant to batch composition.
    ``tau`` / ``temperature`` / ``n_steps`` / ``dynamic`` are (B,)
    per-row vectors (see ``sampling_vectors``): both reveal policies are
    evaluated and a per-row ``jnp.where`` selects, so rows with
    different parameters share one compiled step.  Only ``s_max`` — the
    loop bound — is static.

    Returns (ids, step_map, pos, rng, steps_used) where ``steps_used``
    (B,) is the number of denoise steps that actually revealed tokens for
    each sequence (``step_map.max() + 1``) — in dynamic-threshold mode
    this is typically well below ``s_max`` and is what a production
    early-exit loop would execute; the engine's throughput stats consume
    it instead of assuming the worst-case budget.
    """
    cfg = model.cfg
    bsz = cfg.block_size
    MASK = cfg.resolved_mask_token
    B = blk.shape[0]
    pos = blk[:, None] * bsz + jnp.arange(bsz, dtype=jnp.int32)[None, :]
    cache_limit = blk * bsz
    # static mode reveals ceil(bsz / n_steps) positions per step
    ns = jnp.maximum(n_steps, 1)
    n_per_step = jnp.maximum(1, (bsz + ns - 1) // ns)        # (B,)
    sample = temperature > 0
    # rows with temperature 0 take the argmax branch; the divisor only
    # has to be finite for them, the sampled candidate is discarded
    safe_temp = jnp.where(sample, temperature, 1.0)

    def body(s, carry):
        ids, step_map, rng = carry
        logits, _ = model.decode_step(params, ids, pos, caches,
                                      cache_limit=cache_limit,
                                      block_table=table,
                                      kv_kernel=kv_kernel,
                                      memory=memory,
                                      memory_valid=memory_valid)
        lf = logits.astype(jnp.float32)
        # the [MASK] token is an input symbol, never an output
        lf = lf.at[..., MASK].set(-jnp.inf)
        ks = jax.vmap(jax.random.split)(rng)     # (B, 2, 2)
        rng, kr = ks[:, 0], ks[:, 1]
        # Gumbel-max categorical with the noise zeroed on greedy rows:
        # bit-identical to jax.random.categorical(kr, lf/temp) where
        # temperature > 0 (categorical IS argmax(logits + gumbel)) and
        # to argmax(lf) where not (safe_temp = 1, noise = 0), for the
        # cost of ONE vocab argmax instead of a per-policy pair
        noise = jax.vmap(
            lambda k: jax.random.gumbel(k, lf.shape[1:], lf.dtype))(kr)
        cand = jnp.argmax(
            lf / safe_temp[:, None, None]
            + jnp.where(sample[:, None, None], noise, 0.0), axis=-1)
        probs = jax.nn.softmax(lf, axis=-1)
        conf = jnp.take_along_axis(probs, cand[..., None], axis=-1)[..., 0]

        masked = ids == MASK
        score = jnp.where(masked, conf, -1.0)
        # dynamic: threshold reveal, and always at least the
        # best-confidence masked position
        rev_dyn = masked & (conf >= tau[:, None])
        best = jnp.argmax(score, axis=-1)
        force = jax.nn.one_hot(best, bsz, dtype=bool) & masked
        rev_dyn = rev_dyn | (force & ~rev_dyn.any(-1, keepdims=True))
        # static: the row's n_per_step highest-confidence positions
        thr = jnp.take_along_axis(jnp.sort(score, axis=-1),
                                  (bsz - n_per_step)[:, None], axis=-1)
        rev_st = masked & (score >= thr)
        reveal = jnp.where(dynamic[:, None], rev_dyn, rev_st)
        # last step: flush everything still masked
        reveal = jnp.where(s >= s_max - 1, masked, reveal)

        ids = jnp.where(reveal, cand.astype(ids.dtype), ids)
        step_map = jnp.where(reveal, s, step_map)
        return ids, step_map, rng

    ids0 = jnp.full((B, bsz), MASK, jnp.int32)
    steps0 = jnp.zeros((B, bsz), jnp.int32)
    ids, step_map, rng = jax.lax.fori_loop(0, s_max, body,
                                           (ids0, steps0, rng))
    steps_used = step_map.max(axis=-1) + 1
    return ids, step_map, pos, rng, steps_used


def advance_block(model, params, st: GenState, *, s_max: int,
                  kv_kernel: str = "ref",
                  memory=None, memory_valid=None) -> GenState:
    """Advance every sequence of ``st`` by exactly one block (jittable).

    The single-block step shared by the one-shot ``generate`` loop and
    the continuous-batching scheduler: denoise the block at each row's
    cursor, freeze rows already ``done`` (they re-commit their existing
    block — idempotent, so inactive scheduler slots are harmless),
    commit the block into the caches, scatter tokens/step-map, then
    update cursors / done flags / actual-denoise-step counters.  A row
    is done when its block hits its own stop token (``st.eos``) or its
    cursor reaches ``st.limit``.

    All sampling parameters come from the state's per-row vectors —
    ``s_max`` is the one static, so a single compiled instance serves
    every mix of request configurations a pool can hold.  ``kv_kernel``
    selects the decode KV layout (``"ref"`` = concat/gather fallback,
    ``"pallas"`` = in-place page-aware kernel on paged caches); it is a
    pool-level static like ``s_max``, never per-request data, so the
    zero-retrace mixed-``SamplingParams`` invariant is untouched.
    """
    bsz = model.cfg.block_size
    B, L = st.tokens.shape
    n_blocks_total = L // bsz
    rows = jnp.arange(B)[:, None]

    blk = jnp.minimum(st.blk, n_blocks_total - 1)
    ids, step_map, pos, rng, steps_used = denoise_block(
        model, params, st.caches, blk, st.rng, tau=st.tau,
        temperature=st.temperature, n_steps=st.n_steps,
        dynamic=st.dynamic, s_max=s_max,
        table=st.table, kv_kernel=kv_kernel,
        memory=memory, memory_valid=memory_valid)
    # frozen sequences re-commit their existing block (idempotent)
    old_ids = jnp.take_along_axis(st.tokens, pos, axis=1)
    old_steps = jnp.take_along_axis(st.steps, pos, axis=1)
    ids = jnp.where(st.done[:, None], old_ids, ids)
    step_map = jnp.where(st.done[:, None], old_steps, step_map)

    _, caches = model.decode_step(params, ids, pos, st.caches,
                                  cache_limit=blk * bsz,
                                  block_table=st.table, write=True,
                                  kv_kernel=kv_kernel,
                                  memory=memory,
                                  memory_valid=memory_valid)
    tokens = st.tokens.at[rows, pos].set(ids)
    steps = st.steps.at[rows, pos].set(step_map)
    hit_eos = (ids == st.eos[:, None]).any(axis=-1)
    done = st.done | hit_eos
    new_blk = jnp.where(st.done, st.blk,
                        jnp.minimum(st.blk + 1, st.limit))
    done = done | (new_blk >= st.limit)
    n_denoise = st.n_denoise + jnp.where(st.done, 0, steps_used)
    return GenState(tokens=tokens, steps=steps, caches=caches,
                    blk=new_blk, done=done, rng=rng, limit=st.limit,
                    n_denoise=n_denoise, tau=st.tau,
                    temperature=st.temperature, n_steps=st.n_steps,
                    dynamic=st.dynamic, eos=st.eos, table=st.table)


def init_state(model, params, prompt_tokens, prompt_blocks, rng, *,
               max_len: int, limit=None, mode="dynamic", tau=0.9,
               n_steps=8, temperature=0.0, eos_id=1,
               memory=None, memory_valid=None) -> GenState:
    """Prefill prompts and build the GenState ``advance_block`` consumes.

    ``limit``: per-sequence exclusive block cap (defaults to the full
    cache capacity ``max_len // block_size``).  The sampling fields
    accept scalars (applied to every row) or (B,) per-row arrays — see
    ``sampling_vectors``.
    """
    cfg = model.cfg
    bsz = cfg.block_size
    B, Lp = prompt_tokens.shape
    n_blocks_total = max_len // bsz
    MASK = cfg.resolved_mask_token
    caches = prefill(model, params, prompt_tokens, prompt_blocks, max_len,
                     memory=memory, memory_valid=memory_valid)
    tokens = jnp.concatenate(
        [prompt_tokens,
         jnp.full((B, max_len - Lp), MASK, prompt_tokens.dtype)], axis=1)
    if limit is None:
        limit = jnp.full((B,), n_blocks_total, jnp.int32)
    limit = jnp.asarray(limit, jnp.int32)
    blk = prompt_blocks.astype(jnp.int32)
    # rows with no block budget (prompt fills the cache / limit <=
    # prompt) start frozen: advance_block would otherwise denoise-commit
    # over their last prompt block
    return GenState(tokens=tokens.astype(jnp.int32),
                    steps=jnp.zeros((B, max_len), jnp.int32),
                    caches=caches, blk=blk,
                    done=blk >= limit,
                    rng=_per_seq_keys(rng, B),
                    limit=limit,
                    n_denoise=jnp.zeros((B,), jnp.int32),
                    **sampling_vectors(B, tau=tau,
                                       temperature=temperature,
                                       n_steps=n_steps, mode=mode,
                                       eos_id=eos_id))


def generate(model, params, prompt_tokens, prompt_blocks, rng, *,
             max_len: int, s_max: int, mode="dynamic",
             tau=0.9, n_steps=8, temperature=0.0, eos_id=1,
             limit=None, memory=None, memory_valid=None) -> dict:
    """Full blockwise generation (jit-compatible; all shapes static).

    Returns {"tokens" (B, L_max), "steps" (B, L_max), "gen_blocks" (B,),
    "prompt_blocks" (B,), "done" (B,), "denoise_steps" (B,)} — everything
    RolloutBatch and the engine stats need.

    Sampling parameters accept scalars or (B,) per-row arrays (``mode``:
    a string or a (B,) bool array, True = dynamic), so a mixed-config
    batch runs in one jitted call — the per-row contract the serving
    stack's ``SamplingParams`` rides on.  ``limit`` optionally caps each
    row's exclusive block cursor (None = cache capacity).

    The loop runs until every row is done (EOS or its own block budget),
    NOT for a trip count derived from the padded prompt width: in a
    ragged batch a row whose true prompt is shorter than the padding has
    more blocks of budget than ``(max_len - Lp) // bsz``, and cutting it
    off there silently truncated it without EOS (diverging from the
    continuous-batching scheduler, which runs each slot to its limit).
    """
    n_blocks_total = max_len // model.cfg.block_size

    st = init_state(model, params, prompt_tokens, prompt_blocks, rng,
                    max_len=max_len, limit=limit, mode=mode, tau=tau,
                    n_steps=n_steps, temperature=temperature,
                    eos_id=eos_id, memory=memory,
                    memory_valid=memory_valid)
    step = functools.partial(advance_block, model, params, s_max=s_max,
                             memory=memory, memory_valid=memory_valid)
    # every live row advances its cursor each trip, so n_blocks_total
    # trips is a hard ceiling; the counter is belt-and-braces
    _, st = jax.lax.while_loop(
        lambda c: (c[0] < n_blocks_total) & ~c[1].done.all(),
        lambda c: (c[0] + 1, step(st=c[1])),
        (jnp.int32(0), st))
    return {
        "tokens": st.tokens,
        "steps": st.steps,
        "gen_blocks": st.blk - prompt_blocks,
        "prompt_blocks": prompt_blocks,
        # zero-budget rows never decoded: report them not-done, matching
        # the scheduler's empty completions
        "done": st.done & (st.blk > prompt_blocks),
        "denoise_steps": st.n_denoise,
    }


def count_gen_tokens(tokens, prompt_blocks, gen_blocks, *, eos_id,
                     block_size: int) -> np.ndarray:
    """Per-sequence generated-token count, cut at the first EOS.

    Counts tokens in the generated region up to and *including* the
    first EOS (the whole region when no EOS landed) — the honest
    tokens/sec numerator: when EOS lands mid-block the rest of that
    block is padding the consumer trims, not served output.  ``eos_id``
    is a scalar or a (B,) per-row array (mixed ``SamplingParams``
    batches stop on per-request tokens; -1 disables).
    """
    tokens = np.asarray(tokens)
    pb = np.asarray(prompt_blocks).astype(np.int64)
    gb = np.asarray(gen_blocks).astype(np.int64)
    eos_id = np.broadcast_to(np.asarray(eos_id), (tokens.shape[0],))
    out = np.zeros((tokens.shape[0],), np.int64)
    for i in range(tokens.shape[0]):
        lo, hi = pb[i] * block_size, (pb[i] + gb[i]) * block_size
        region = tokens[i, lo:hi]
        eos = np.flatnonzero(region == eos_id[i])
        out[i] = eos[0] + 1 if eos.size else hi - lo
    return out


def rollout_to_batch(gen: dict, rewards, group, block_size: int):
    """Package a ``generate`` output dict into a RolloutBatch."""
    from .trajectory import RolloutBatch
    B, L = gen["tokens"].shape
    pos_blk = jnp.arange(L, dtype=jnp.int32)[None, :] // block_size
    prompt_mask = pos_blk < gen["prompt_blocks"][:, None]
    valid = pos_blk < (gen["prompt_blocks"] + gen["gen_blocks"])[:, None]
    if not isinstance(gen["gen_blocks"], jax.core.Tracer):
        gb = np.asarray(gen["gen_blocks"])
        assert (gb >= 0).all(), "negative gen_blocks in rollout"
        # an empty rollout must be explicitly all-prompt: a step map
        # claiming reveals on a gen_blocks == 0 row would relabel prompt
        # tokens as revealed-at-step-0 generation in the DiPO replay
        empty = gb == 0
        if empty.any() and not isinstance(gen["steps"], jax.core.Tracer):
            assert (np.asarray(gen["steps"])[empty] == 0).all(), \
                "gen_blocks == 0 row carries a nonzero reveal-step map"
    return RolloutBatch(tokens=gen["tokens"], steps=gen["steps"],
                        prompt_mask=prompt_mask, valid=valid,
                        rewards=rewards, group=group)

"""Plain reference of the dense GQA block-diffusion language model.

Straightforward ``jax.numpy`` following the published architecture
(pre-norm RMSNorm with a ``1 + scale`` gain, rotary positions in the
split-half convention, grouped-query attention, SwiGLU, final norm,
untied head) and the block-diffusion semantics of the DiRL paper:

* committed context is block-causal (bidirectional inside a block);
* a block being denoised at step ``s`` sees the committed blocks before
  it and its own partly revealed input (tokens revealed before ``s``,
  the mask token elsewhere);
* SFT (paper Eq. 3) noises each block at a level ``t ~ U(1e-3, 1)``,
  masks each output token with probability ``t`` and weighs its
  cross-entropy by ``1/t`` over the number of output tokens.

It imports nothing of the program.  Weights come from the benchmark's
own ``harness.weights``.  Matmuls run at ``highest`` precision in
float32; ``dtype=FP8`` gives the serving control, the same mathematics
with float8 matmul operands (the step below the bfloat16 that the
served configuration states).  Work is cut into blocks of
rows so that a full-width model fits beside its weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


FP8 = "fp8"


def _prec(dtype):
    return jax.lax.Precision.HIGHEST if dtype in (jnp.float32, FP8) \
        else None


def _act(dtype):
    """The type activations are kept in."""
    return jnp.float32 if dtype == FP8 else dtype


def _cast(a, dtype):
    """A matmul operand in ``dtype``.  ``FP8`` rounds it to float8
    (e4m3) with one scale for the whole tensor and computes on in
    float32: the step below the chip's default one-pass bfloat16."""
    if dtype != FP8:
        return a.astype(dtype)
    a = a.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _mm(x, w, dtype):
    return jnp.matmul(_cast(x, dtype), _cast(w, dtype),
                      precision=_prec(dtype),
                      preferred_element_type=jnp.float32).astype(_act(dtype))


def rmsnorm(scale, x, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, pos, theta):
    """x (..., T, H, D), pos (..., T); split-half rotation in float32."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def attend(q, k, v, mask, dtype):
    """q (Tq, H, D); k, v (Tk, Hkv, D); mask (Tq, Tk) bool."""
    H, D = q.shape[1], q.shape[2]
    g = H // k.shape[1]
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _cast(q, dtype), _cast(kk, dtype),
                   precision=_prec(dtype),
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(mask[None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None], p, 0.0)
    o = jnp.einsum("hqk,khd->qhd", _cast(p, dtype), _cast(vv, dtype),
                   precision=_prec(dtype),
                   preferred_element_type=jnp.float32)
    return o.astype(_act(dtype))


def layer(lp, m, x, pos, mask_fn, dtype, kv_extra=None):
    """One pre-norm layer over rows ``x`` (T, d) at positions ``pos``.

    ``mask_fn(q_idx_rows) -> (T, Tk)`` visibility over the keys, which
    are ``kv_extra`` (pre-computed K/V of other rows, or None) followed
    by these rows' own K/V.  Returns (x_out, k, v) of these rows.
    """
    H, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    T = x.shape[0]
    h = rmsnorm(lp["attn_norm"]["scale"], x, m["norm_eps"])
    q = _mm(h, lp["attn"]["wq"]["w"], dtype).reshape(T, H, Dh)
    k = _mm(h, lp["attn"]["wk"]["w"], dtype).reshape(T, Hkv, Dh)
    v = _mm(h, lp["attn"]["wv"]["w"], dtype).reshape(T, Hkv, Dh)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    if kv_extra is not None:
        ka = jnp.concatenate([kv_extra[0], k], 0)
        va = jnp.concatenate([kv_extra[1], v], 0)
    else:
        ka, va = k, v
    o = attend(q, ka, va, mask_fn(), dtype).reshape(T, H * Dh)
    x = x + _mm(o, lp["attn"]["wo"]["w"], dtype)
    h = rmsnorm(lp["ffn_norm"]["scale"], x, m["norm_eps"])
    f = jax.nn.silu(_mm(h, lp["ffn"]["w_gate"]["w"], dtype)) \
        * _mm(h, lp["ffn"]["w_up"]["w"], dtype)
    x = x + _mm(f, lp["ffn"]["w_down"]["w"], dtype)
    return x, k, v


def layer_params(params, i):
    return jax.tree.map(lambda a: a[i], params["groups"]["l0"])


def head(params, m, x, dtype):
    h = rmsnorm(params["final_norm"]["scale"], x, m["norm_eps"])
    return jnp.matmul(_cast(h, dtype), _cast(params["lm_head"]["w"], dtype),
                      precision=_prec(dtype),
                      preferred_element_type=jnp.float32)


def _window_ok(qp, kp, m):
    w = m.get("sliding_window") or 0
    return (qp[:, None] - kp[None, :]) < w if w else True


def mask_token(m):
    return m["vocab_size"] - 1


# ---------------------------------------------------------------------------
# serving: the served tokens and the reveal schedule of greedy requests
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m_items", "s_max", "dtype",
                                             "chunk"))
def _serve_hidden(params, tokens, n_valid, prompt_len, steps, *,
                  m_items, s_max, dtype, chunk):
    """Final hidden state of every generated block at every denoise step.

    tokens/steps (T,) padded; the first ``n_valid`` are real, the first
    ``prompt_len`` prompt.  For every generated block j and step s, the
    block's input at s (the tokens revealed before s, the mask token
    elsewhere) is rebuilt from the step map and run against the
    committed context.  Returns (T // bsz, s_max, bsz, d): [j, s, p] is
    position p of block j (counted from the first generated block) at
    step s; blocks past the generated ones are garbage.
    """
    m = dict(m_items)
    bsz, MASK = m["block_size"], mask_token(m)
    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    blk = pos // bsz
    kvalid = pos < n_valid
    emb = params["embed"]["table"]
    # committed pass: block-causal over the clean sequence
    x = jnp.take(emb, tokens, axis=0).astype(_act(dtype))
    clean_kv = []
    for i in range(m["n_layers"]):
        lp = layer_params(params, i)

        def mfn():
            return (blk[None, :] <= blk[:, None]) & kvalid[None, :] \
                & _window_ok(pos, pos, m)
        x, k, v = layer(lp, m, x, pos, mfn, dtype)
        clean_kv.append((k, v))
    # denoise inputs: copy (j, s) of generated block j at step s
    n_blk = T // bsz
    pb = prompt_len // bsz
    j = jnp.arange(n_blk)[:, None]                          # (n_blk, 1)
    s = jnp.arange(s_max)[None, :]                          # (1, s_max)
    blk_id = jnp.minimum(pb + j, n_blk - 1)                 # absolute
    cpos = blk_id[..., None] * bsz + jnp.arange(bsz)        # (n_blk,1,bsz)
    cpos = jnp.broadcast_to(cpos, (n_blk, s_max, bsz))
    ctok = tokens[cpos]
    cstep = steps[cpos]
    cids = jnp.where(cstep < s[..., None], ctok, MASK)
    n_c = n_blk * s_max
    n_pad = -n_c % chunk
    flat = lambda a: jnp.pad(a.reshape(n_c, bsz), ((0, n_pad), (0, 0)))
    cids, cpos_f = flat(cids), flat(cpos)

    def run_chunk(ids_pos):
        ids, cp = ids_pos                                   # (chunk, bsz)
        y = jnp.take(emb, ids.reshape(-1), axis=0).astype(_act(dtype))
        qp = cp.reshape(-1)
        qb = qp // bsz
        own = (qb[:, None] == qb[None, :]) & \
            (jnp.arange(qp.shape[0])[:, None] // bsz
             == jnp.arange(qp.shape[0])[None, :] // bsz)
        for i in range(m["n_layers"]):
            lp = layer_params(params, i)
            ck, cv = clean_kv[i]

            def mfn():
                ctx = (blk[None, :] < qb[:, None]) & kvalid[None, :] \
                    & _window_ok(qp, pos, m)
                return jnp.concatenate([ctx, own], axis=1)
            y, _, _ = layer(lp, m, y, qp, mfn, dtype, kv_extra=(ck, cv))
        return y.reshape(chunk, bsz, -1)

    ys = jax.lax.map(run_chunk, (cids.reshape(-1, chunk, bsz),
                                 cpos_f.reshape(-1, chunk, bsz)))
    return ys.reshape(-1, bsz, ys.shape[-1])[:n_c].reshape(n_blk, s_max, bsz,
                                                           -1)


@functools.partial(jax.jit, static_argnames=("m_items", "dtype"))
def _head_rows(params, hidden, tokens, *, m_items, dtype):
    """Per row: (best logit, logit of ``tokens``, argmax, log-sum-exp),
    the mask token excluded."""
    m = dict(m_items)
    lg = head(params, m, hidden, dtype)
    lg = lg.at[:, mask_token(m)].set(NEG)
    own = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
    return lg.max(-1), own, jnp.argmax(lg, -1), jax.nn.logsumexp(lg, -1)


def _head_blocks(params, m, hidden, tokens, dtype, rows=256):
    outs = []
    n = hidden.shape[0]
    pad = -n % rows
    hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
    tokens = jnp.pad(tokens, (0, pad))
    items = tuple(sorted(m.items()))
    for r in range(0, n + pad, rows):
        outs.append(_head_rows(params, hidden[r:r + rows],
                               tokens[r:r + rows], m_items=items,
                               dtype=dtype))
    return [np.concatenate([np.asarray(o[i]) for o in outs])[:n]
            for i in range(4)]


EMPTY_STEP = 100.0


def _step_gap(lc, masked, rev, lt):
    """Per block, how far the positions ``rev`` revealed at one step
    (before the last) depart from the dynamic rule over the ``masked``
    ones, given the reference's log-confidences ``lc`` (nb, bsz)."""
    v = np.where(masked & ~rev, lc - lt, 0.0).max(-1)
    hi = rev & (lc >= lt)
    lo = rev & ~hi
    lo_lc = np.where(lo, lc, NEG)
    pick = np.argmax(lo_lc, -1)
    forced = lo.any(-1) & ~hi.any(-1)
    best = np.where(masked, lc, NEG).max(-1)
    rows = np.arange(lc.shape[0])
    v_pick = np.where(forced, best - lo_lc[rows, pick], 0.0)
    others = lo.copy()
    others[rows[forced], pick[forced]] = False
    v_lo = np.where(others, lt - lc, 0.0).max(-1)
    empty = masked.any(-1) & ~rev.any(-1)
    return np.maximum.reduce([v, v_pick, v_lo,
                              np.where(empty, EMPTY_STEP, 0.0)])


def reveal_gaps(logconf, steps, tau: float, s_max: int):
    """Widest departure of each block's reveal schedule from the dynamic
    rule, in nats of confidence.

    ``logconf`` (nb, s_max, bsz): the log of the reference's top-1
    probability at each position of each block at each step; ``steps``
    (nb, bsz): the step at which the program revealed each position.
    Before the last step the rule reveals every masked position whose
    confidence reaches ``tau`` and, where none does, the one most
    confident masked position; the last step reveals what is left.  A
    position revealed below ``tau`` reads ``log tau - logconf`` (the
    forced one: how far it lies below the most confident masked
    position), one left masked above ``tau`` reads ``logconf - log
    tau``; a step that reveals nothing where the rule reveals one reads
    ``EMPTY_STEP``, and a step outside the loop's range too.  Near-ties
    read near 0.
    """
    lt = math.log(tau)
    worst = np.where((steps < 0) | (steps >= s_max), EMPTY_STEP, 0.0)
    worst = worst.max(-1)
    for s in range(s_max - 1):
        worst = np.maximum(worst, _step_gap(logconf[:, s, :], steps >= s,
                                            steps == s, lt))
    return np.maximum(worst, 0.0)


def rule_reveal_gaps(logconf, logconf_ctl, steps, tau: float, s_max: int):
    """``reveal_gaps`` of the positions that the dynamic rule reveals on
    ``logconf_ctl`` (the control's confidences), step by step over the
    masked positions of the program's schedule ``steps``, judged on
    ``logconf``."""
    lt = math.log(tau)
    worst = np.zeros(steps.shape[0])
    for s in range(s_max - 1):
        masked = steps >= s
        c = logconf_ctl[:, s, :]
        rev = masked & (c >= lt)
        best = np.argmax(np.where(masked, c, NEG), -1)
        top = np.zeros_like(masked)
        top[np.arange(len(best)), best] = True
        rev |= top & masked & ~rev.any(-1, keepdims=True)
        worst = np.maximum(worst, _step_gap(logconf[:, s, :], masked, rev,
                                            lt))
    return np.maximum(worst, 0.0)


def serve_check(params, m, tokens, prompt_len, steps, n_gen_tokens, *,
                s_max, tau, control=False, chunk=128):
    """One served greedy request against the float32 reference.

    Returns ``(gaps, reveal, control)``: per generated token, its logit's
    gap below the reference's best at the step that revealed it; per
    generated block, ``reveal_gaps`` of its schedule; and, with
    ``control``, the same two for the reference with float8 matmul
    operands (the step below the configuration's bfloat16) put in the
    program's place: the gap of the token it puts first at each of those
    positions, and ``rule_reveal_gaps`` of the positions its confidences
    reveal; else None.
    """
    T = tokens.shape[0]
    items = tuple(sorted(m.items()))
    bsz = m["block_size"]
    nb = n_gen_tokens // bsz
    n_valid = prompt_len + n_gen_tokens
    tok = jnp.asarray(tokens, jnp.int32)
    args = (params, tok, jnp.int32(n_valid), jnp.int32(prompt_len),
            jnp.asarray(steps, jnp.int32))
    gen = np.asarray(tokens[prompt_len:n_valid]).reshape(nb, bsz)
    st = np.asarray(steps[prompt_len:n_valid]).reshape(nb, bsz)
    every = np.broadcast_to(gen[:, None, :], (nb, s_max, bsz)).reshape(-1)
    j, p = np.arange(nb)[:, None], np.arange(bsz)[None, :]
    own_step = np.clip(st, 0, s_max - 1)
    with jax.default_matmul_precision("highest"):
        h32 = _serve_hidden(*args, m_items=items, s_max=s_max,
                            dtype=jnp.float32, chunk=chunk)
        h32 = h32[:nb]
        best, own, _, lse = _head_blocks(
            params, m, h32.reshape(nb * s_max * bsz, -1),
            jnp.asarray(every), jnp.float32)
    shape = (nb, s_max, bsz)
    best, own, lse = (a.reshape(shape) for a in (best, own, lse))
    gaps = (best - own)[j, own_step, p].reshape(-1)
    reveal = reveal_gaps(best - lse, st, tau, s_max)
    if not control:
        return gaps, reveal, None
    h8 = _serve_hidden(*args, m_items=items, s_max=s_max, dtype=FP8,
                       chunk=chunk)[:nb]
    b8, _, pick, lse8 = _head_blocks(params, m,
                                     h8.reshape(nb * s_max * bsz, -1),
                                     jnp.asarray(every), FP8)
    pick = pick.reshape(shape)[j, own_step, p].reshape(-1)
    h_own = h32[j, own_step, p].reshape(nb * bsz, -1)
    with jax.default_matmul_precision("highest"):
        b32, own_pick, _, _ = _head_blocks(params, m, h_own,
                                           jnp.asarray(pick, jnp.int32),
                                           jnp.float32)
    reveal8 = rule_reveal_gaps(best - lse, (b8 - lse8).reshape(shape), st,
                               tau, s_max)
    return gaps, reveal, (b32 - own_pick, reveal8)


# ---------------------------------------------------------------------------
# SFT: the NELBO step, its gradients and AdamW
# ---------------------------------------------------------------------------


def sft_noise(key, B, L, bsz):
    """The forward process: per-block level t and per-token uniforms."""
    K = L // bsz
    kt, km = jax.random.split(key)
    t_blk = jax.random.uniform(kt, (B, K), minval=1e-3, maxval=1.0)
    u = jax.random.uniform(km, (B, L))
    return jnp.repeat(t_blk, bsz, axis=-1), u


def _sft_row_loss(params, m, ids, masked, w, tokens, valid, dtype, chunk):
    """Weighted CE sum of one row of the duplicated layout.

    Copy A (clean, positions 0..L-1) is block-causal; copy B (the
    noised input) sees copy-A blocks strictly before its own and its
    own block of copy B.
    """
    L = tokens.shape[0]
    bsz = m["block_size"]
    pos = jnp.arange(L, dtype=jnp.int32)
    blk = pos // bsz
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(dtype)
    P2 = jnp.concatenate([pos, pos])
    B2 = jnp.concatenate([blk, blk])
    C2 = jnp.concatenate([jnp.zeros(L, jnp.int32), jnp.ones(L, jnp.int32)])
    V2 = jnp.concatenate([valid, valid])
    chunk = math.gcd(2 * L, chunk)
    n_q = 2 * L // chunk

    def mask_rows(qi):
        qc, qb, qp = C2[qi], B2[qi], P2[qi]
        a_q = (C2[None] == 0) & (B2[None] <= qb[:, None])
        b_q = ((C2[None] == 0) & (B2[None] < qb[:, None])) | \
              ((C2[None] == 1) & (B2[None] == qb[:, None]))
        vis = jnp.where(qc[:, None] == 0, a_q, b_q)
        return vis & V2[None] & V2[qi][:, None] & _window_ok(qp, P2, m)

    for i in range(m["n_layers"]):
        lp = layer_params(params, i)

        @jax.checkpoint
        def run_layer(lp, x):
            H, Hkv, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
            h = rmsnorm(lp["attn_norm"]["scale"], x, m["norm_eps"])
            q = _mm(h, lp["attn"]["wq"]["w"], dtype).reshape(-1, H, Dh)
            k = _mm(h, lp["attn"]["wk"]["w"], dtype).reshape(-1, Hkv, Dh)
            v = _mm(h, lp["attn"]["wv"]["w"], dtype).reshape(-1, Hkv, Dh)
            q, k = rope(q, P2, m["rope_theta"]), rope(k, P2, m["rope_theta"])

            def one(c):
                qi = c * chunk + jnp.arange(chunk)
                return attend(q[qi], k, v, mask_rows(qi), dtype)
            o = jax.lax.map(jax.checkpoint(one), jnp.arange(n_q))
            o = o.reshape(2 * L, H * Dh)
            x = x + _mm(o, lp["attn"]["wo"]["w"], dtype)
            h = rmsnorm(lp["ffn_norm"]["scale"], x, m["norm_eps"])
            f = jax.nn.silu(_mm(h, lp["ffn"]["w_gate"]["w"], dtype)) \
                * _mm(h, lp["ffn"]["w_up"]["w"], dtype)
            return x + _mm(f, lp["ffn"]["w_down"]["w"], dtype)
        x = run_layer(lp, x)
    lg = head(params, m, x[L:], dtype)
    logp = jax.nn.log_softmax(lg, axis=-1)
    ce = -jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * w)


@functools.partial(jax.jit, static_argnames=("m_items", "dtype", "chunk"))
def _sft_grad(params, ids, masked, w, tokens, valid, denom, *, m_items,
              dtype, chunk):
    """NELBO of the batch and its gradient; rows run one after another
    (``lax.map``), so activations are held for one row at a time."""
    m = dict(m_items)

    def f(p):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        per_row = jax.lax.map(
            lambda r: _sft_row_loss(p, m, *r, dtype, chunk),
            (ids, masked, w, tokens, valid))
        return jnp.sum(per_row) / denom
    return jax.value_and_grad(f)(params)


def sft_loss_and_grad(params, m, batch, key, *, dtype=jnp.float32,
                      chunk=512):
    """NELBO of one batch and its float32 gradient."""
    tokens, pmask, valid = (batch["tokens"], batch["prompt_mask"],
                            batch["valid"])
    B, L = tokens.shape
    t_tok, u = sft_noise(key, B, L, m["block_size"])
    masked = (u < t_tok) & valid & ~pmask
    w = jnp.where(masked, 1.0 / t_tok, 0.0)
    ids = jnp.concatenate([tokens, jnp.where(masked, mask_token(m),
                                             tokens)], axis=-1)
    denom = jnp.maximum(jnp.sum(valid & ~pmask), 1).astype(jnp.float32)
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        return _sft_grad(params, ids, masked, w, tokens, valid, denom,
                         m_items=tuple(sorted(m.items())), dtype=dtype,
                         chunk=chunk)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adamw(params, grads, m_state, v_state, count, hp):
    """One AdamW step with global-norm clipping (bias-corrected)."""
    lr, b1, b2, eps, clip = hp
    count = count + 1
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    b1c = 1.0 - b1 ** count.astype(jnp.float32)
    b2c = 1.0 - b2 ** count.astype(jnp.float32)
    g = jax.tree.map(lambda a: a * scale, grads)
    mn = jax.tree.map(lambda mm, a: mm * b1 + a * (1 - b1), m_state, g)
    vn = jax.tree.map(lambda vv, a: vv * b2 + a * a * (1 - b2), v_state, g)
    pn = jax.tree.map(lambda p, mm, vv: p - lr * (mm / b1c)
                      / (jnp.sqrt(vv / b2c) + eps), params, mn, vn)
    return pn, mn, vn, count, gnorm, scale


def sft_steps(params, m, batches, keys, opt, *, dtype=jnp.float32):
    """Run ``len(keys)`` SFT steps from ``params``.

    Returns (losses, per-leaf norms of the first clipped gradient,
    final params).  ``opt``: dict lr, b1, b2, eps, clip_norm.
    """
    hp = tuple(jnp.float32(opt[k]) for k in ("lr", "b1", "b2", "eps",
                                              "clip_norm"))
    zeros = jax.tree.map(jnp.zeros_like, params)
    m_s, v_s, count = zeros, jax.tree.map(jnp.zeros_like, params), \
        jnp.zeros((), jnp.int32)
    losses, g1 = [], None
    for i, key in enumerate(keys):
        loss, grad = sft_loss_and_grad(params, m, batches[i], key,
                                       dtype=dtype)
        if i == 0:
            g1 = leaf_norms(grad)
        params, m_s, v_s, count, gnorm, scale = _adamw(params, grad, m_s,
                                                       v_s, count, hp)
        losses.append(float(loss))
        if i == 0:
            g1 = {k: v * float(scale) for k, v in g1.items()}
    return losses, g1, params


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(a.astype(jnp.float32))))) for p, a in flat}

"""The one generator behind every traffic file of the two kinds.

Every seed gets the same multiset of sizes (prompt widths, prefixes,
output budgets), drawn in its own order, so the seed changes which
request meets which, not how much work a run holds.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Group:
    """One prompt and its G members (a DiPO rollout group)."""
    index: int
    prompt: np.ndarray          # (W,) int32, a whole number of blocks
    shared_prefix: int          # -1 unshared, else which system prefix
    budgets: list[int]          # per member, in blocks
    temperatures: list[float]   # per member; 0 = greedy
    keys: np.ndarray            # (G, 2) uint32 per-member rng keys


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def budget_levels(spec: dict, block: int) -> list[int]:
    """Quantiles (i + 1/2)/n of the clipped lognormal, in whole blocks."""
    from statistics import NormalDist
    n = spec["levels"]
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        tok = spec["median"] * math.exp(spec["sigma"] * z)
        tok = min(max(tok, spec["min"]), spec["max"])
        out.append(max(1, int(round(tok / block))))
    return out


def residual_levels(levels: list[int], n: int) -> list[int]:
    """Quantiles (i + 1/2)/n of the blocks still to go of a request met
    part-way: a slot holds a budget b with odds in proportion to b, and
    its request has 1..b blocks left with equal odds."""
    tot = sum(levels)
    out = []
    for i in range(n):
        q, lo, hi = (i + 0.5) / n, 0, max(levels)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if sum(min(mid, b) for b in levels) >= q * tot:
                hi = mid
            else:
                lo = mid
        out.append(hi)
    return out


def prompt_combos(t: dict) -> list[tuple[bool, int]]:
    """(shares a system prefix?, width) pairs, each used equally often."""
    pre = t["shared_prefix"]["tokens"]
    return [(False, w) for w in t["prompt_tokens"]] + \
        [(True, w) for w in t["prompt_tokens"] if w > pre]


def rollout_groups(t: dict, m: dict, seed: int) -> list[Group]:
    bsz, V = m["block_size"], m["vocab_size"]
    G, n = t["group_size"], t["n_groups"]
    combos = prompt_combos(t)
    if n % len(combos):
        raise ValueError(f"n_groups {n} is not a multiple of the "
                         f"{len(combos)} prompt combinations")
    levels = budget_levels(t["budget_tokens"], bsz)
    # the pool is met in its steady state: the requests that fill it
    # first are part-way through, with the residual budgets of a pool
    # that has run a long while
    n_first = t["n_slots"]
    res = residual_levels(levels, t["residual_levels"])
    if n_first % len(res) or n_first % G or \
            (n * G - n_first) % len(levels):
        raise ValueError("n_slots must be a multiple of residual_levels "
                         "and of group_size, and the other requests of "
                         "the budget levels")
    rng = _rng(seed, 1)
    order = rng.permutation(np.repeat(np.arange(len(combos)),
                                      n // len(combos)))
    budgets = np.concatenate([
        rng.permutation(np.repeat(res, n_first // len(res))),
        rng.permutation(np.repeat(levels,
                                  (n * G - n_first) // len(levels)))])
    pre_tok = t["shared_prefix"]["tokens"]
    n_pre = t["shared_prefix"]["n_prefixes"]
    prefixes = rng.integers(4, V - 1, size=(n_pre, pre_tok), dtype=np.int32)
    keys = rng.integers(0, 2**32, size=(n, G, 2), dtype=np.uint32)
    groups, shared_seen = [], 0
    for g in range(n):
        shared, width = combos[order[g]]
        assert width % bsz == 0
        prompt = rng.integers(4, V - 1, size=width, dtype=np.int32)
        which = -1
        if shared:
            which = shared_seen % n_pre
            shared_seen += 1
            prompt[:pre_tok] = prefixes[which]
        temps = [t["temperature"]] * G
        # greedy members, whose served tokens the reference can check:
        # every member of the groups met part-way (they finish first,
        # and their residual budgets are the same multiset on every
        # seed), and in the others the first (cold or suffix admission)
        # and full-hit members chosen round the group
        if g * G < n_first:
            temps = [0.0] * G
        for i in range(t["greedy_members"]):
            temps[0 if i == 0 else 1 + (g + i - 1) % (G - 1)] = 0.0
        groups.append(Group(index=g, prompt=prompt, shared_prefix=which,
                             budgets=[int(b) for b in
                                      budgets[g * G:(g + 1) * G]],
                             temperatures=temps, keys=keys[g]))
    return groups


def warm_prompts(t: dict, m: dict, seed: int) -> list[np.ndarray]:
    """Prompts that make the scheduler compile every admission this mix
    uses: each cold width, each (hit, suffix) pair, and a full hit.
    Drawn apart from the traffic, so they share no page with it."""
    bsz, V = m["block_size"], m["vocab_size"]
    rng = _rng(seed, 2)
    pre_tok = t["shared_prefix"]["tokens"]
    out = []
    for w in t["prompt_tokens"]:
        out.append(rng.integers(4, V - 1, size=w, dtype=np.int32))
    prefix = out[t["prompt_tokens"].index(pre_tok)] \
        if pre_tok in t["prompt_tokens"] else None
    if prefix is None:
        prefix = rng.integers(4, V - 1, size=pre_tok, dtype=np.int32)
        out.append(prefix)
    for w in t["prompt_tokens"]:
        if w > pre_tok:
            tail = rng.integers(4, V - 1, size=w - pre_tok, dtype=np.int32)
            out.append(np.concatenate([prefix, tail]))
    out.append(out[0].copy())      # a full hit
    assert all(len(p) % bsz == 0 for p in out)
    return out


def sft_ring(t: dict, m: dict, seed: int):
    """``ring`` batches and a long list of step keys, made on the device
    in one jitted call each.  Returns (list of batch dicts, key array)."""
    import jax
    import jax.numpy as jnp
    from .weights import seed_key
    R, B, L = t["ring"], t["batch"], t["seq_len"]
    V = m["vocab_size"]
    lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]

    # every seed gets the same prompt lengths, evenly spread over
    # [lo, hi], in its own order: the prompt's share of a row moves the
    # step's work
    n = R * B
    lens = np.round(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(
        np.int32)

    @jax.jit
    def make(key):
        kt, kp = jax.random.split(jax.random.fold_in(key, 7))
        tokens = jax.random.randint(kt, (R, B, L), 4, V - 1, jnp.int32)
        plen = jax.random.permutation(kp, jnp.asarray(lens)).reshape(R, B, 1)
        pos = jnp.arange(L, dtype=jnp.int32)
        return {"tokens": tokens, "prompt_mask": pos < plen,
                "valid": jnp.ones((R, B, L), bool)}

    ring = make(seed_key(seed))
    batches = [{k: v[i] for k, v in ring.items()} for i in range(R)]
    keys = jax.random.split(jax.random.fold_in(seed_key(seed), 11), 4096)
    return batches, keys

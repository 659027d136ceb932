"""First-class request API for the serving stack.

This module is the stable surface a front end programs against —
the LMDeploy-style request/response types of the paper's serving side
(§4.2), decoupled from both the engine and the scheduler so neither
has to be imported to *describe* work:

``SamplingParams``
    Every decode-time knob a single request may set — denoise threshold
    ``tau``, ``temperature``, reveal policy ``mode`` (dynamic vs
    static), static-mode step budget ``n_steps``, response length cap
    ``max_new_blocks``, stop token ``eos_id`` and an optional
    deterministic ``seed``.  The whole point of the type is that these
    are **per-request, per-row traced values** all the way down: the
    pool's jitted block-advance reads them out of per-sequence vectors
    in ``core.decoding.GenState``, so one ``SlotScheduler`` pool serves
    arbitrarily mixed configurations with zero retraces — changing τ is
    a field on a request, not an engine rebuild.  (DiFFPO makes the
    per-request threshold an RL lever; d1 sweeps decode budgets per
    task — both are plain ``SamplingParams`` traffic here.)

    Only ``s_max`` — the global denoise-loop bound — stays a pool
    static: it is the one value that fixes compiled loop *structure*
    rather than data.  Per-request ``n_steps`` above the pool's
    ``s_max`` is effectively clamped (the loop flushes all remaining
    masks at step ``s_max - 1``).

``Request`` / ``RequestOutput``
    The queue entry (prompt + rng + params) and the structured
    completion a streaming front end consumes: uid, decoded text,
    ``finish_reason`` ("eos" | "length") and admit→finish latency in
    scheduler ticks.

``GenerationConfig``
    Pool/engine construction config (slot count, cache layout, KV
    budget, ``s_max``) plus the *default* ``SamplingParams`` applied to
    requests that do not carry their own.  Kept flat for backwards
    compatibility; ``.sampling()`` derives the default params object.

Prefix-cache interaction: ``SamplingParams`` only shapes *decoding* —
prompt prefill (and therefore committed prompt KV) is parameter-free,
so requests with different params share prompt pages freely and a
params change can never invalidate a cached prefix.  The scheduler's
admission path relies on this (and tests/test_sampling_params.py pins
it): prefix keys are content hashes of prompt blocks only.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode parameters (all traced per row, never static).

    tau             dynamic mode: reveal positions whose top-1 prob
                    exceeds this threshold (at least one per step)
    temperature     0 = greedy argmax, > 0 = categorical sampling
    mode            "dynamic" (confidence threshold) | "static" (fixed
                    reveal count per step)
    n_steps         static mode: denoise steps per block (reveals
                    ceil(block_size / n_steps) positions per step)
    max_new_blocks  response budget in blocks (None = cache capacity)
    eos_id          stop token; -1 disables EOS stopping entirely
    seed            fallback rng source: used only when no explicit key
                    accompanies the request (an explicit key always
                    wins, preserving batch drivers' per-row streams)
    """
    tau: float = 0.9
    temperature: float = 0.0
    mode: str = "dynamic"
    n_steps: int = 8
    max_new_blocks: int | None = None
    eos_id: int = 1
    seed: int | None = None

    def __post_init__(self):
        if self.mode not in ("dynamic", "static"):
            raise ValueError(
                f"mode must be dynamic|static, got {self.mode!r}")
        if self.n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {self.n_steps}")
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.max_new_blocks is not None and self.max_new_blocks < 0:
            raise ValueError(
                f"max_new_blocks must be >= 0, got {self.max_new_blocks}")

    @property
    def dynamic(self) -> bool:
        return self.mode == "dynamic"

    def replace(self, **kw) -> "SamplingParams":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class Request:
    """One queued generation request (prompt already tokenised and
    trimmed to ``prompt_blocks`` block-aligned blocks)."""
    uid: int
    prompt: np.ndarray           # (Lp,) int32, Lp = prompt_blocks * bsz
    prompt_blocks: int           # true prompt length in blocks
    rng: "object"                # (2,) per-request rng key
    params: SamplingParams = SamplingParams()
    submitted: float = 0.0       # time.perf_counter() at submit()


@dataclasses.dataclass
class RequestOutput:
    """Structured streaming completion (what ``RolloutEngine.stream``
    yields): the decoded text plus everything a front end needs to
    report — why the request stopped and how long it decoded.

    ``latency_ticks`` spans admission → completion (the decode time in
    pool block-steps); queue wait before admission — e.g. page-pool
    backpressure deferrals — is *not* included (``admitted_tick`` is
    stamped when the request enters a slot, not when it was submitted).

    ``param_version`` is the model-weight version (``ModelServer.
    version``) live when the request was admitted.  Under the async RL
    loop weights are pushed between pool ticks, so a long response may
    finish on newer weights than it started on; the admission version is
    the request's staleness tag (the per-block record rides on the raw
    ``Completion``).
    """
    uid: int
    text: str                    # decoded, trimmed at the first EOS
    token_ids: np.ndarray        # generated ids, trimmed at first EOS
    finish_reason: str           # "eos" | "length"
    prompt_blocks: int
    gen_blocks: int
    gen_tokens: int              # generated tokens to first EOS incl.
    denoise_steps: int           # denoise steps that revealed tokens
    admitted_tick: int           # scheduler tick the request entered
    completed_tick: int          # scheduler tick it finished
    params: SamplingParams = SamplingParams()
    param_version: int = 0       # weight version live at admission

    @property
    def latency_ticks(self) -> int:
        """Admit -> finish latency in scheduler ticks (block steps)."""
        return self.completed_tick - self.admitted_tick


@dataclasses.dataclass
class GenerationConfig:
    """Pool/engine construction config + default ``SamplingParams``.

    The decode fields (``mode``/``tau``/``n_steps``/``temperature``/
    ``eos_id``) are only *defaults* — any request may override them via
    its own ``SamplingParams`` without retracing the pool.
    """
    max_len: int = 256
    s_max: int = 8               # max denoise steps per block (static:
    # the one compiled loop bound — per-request n_steps clamps to it)
    mode: str = "dynamic"        # default: dynamic | static
    tau: float = 0.9
    n_steps: int = 8             # default static denoise steps per block
    temperature: float = 0.0
    eos_id: int = 1
    batching: str = "continuous"  # continuous (slot pool) | static
    n_slots: int = 8             # continuous: decode-slot pool size
    cache: str = "dense"         # continuous: dense | paged KV layout
    n_pages: int | None = None   # paged: pool size (None = dense-equal)
    prefix_cache: bool | None = None  # paged: share prompt pages across
    # requests (None = auto: on for pure-attention backbones)
    kernel: str = "ref"          # paged decode KV layout: "ref" gathers
    # pages into a dense-width copy per step, "pallas" reads the page
    # pool in place (kernels.paged_attn; interpret-mode off-TPU)
    sync_each_tick: bool = False  # block on device results inside the
    # generate call for honest per-call latency stats; off by default —
    # the sync serializes dispatch (dirlint: hot-sync)
    trace: bool = False          # record obs.trace lifecycle/tick spans
    # (host wall-clock around dispatch; never syncs the device)
    trace_capacity: int = 65536  # span ring-buffer size (oldest evicted)

    def sampling(self, **overrides) -> SamplingParams:
        """The default per-request params this config implies."""
        base = SamplingParams(tau=self.tau, temperature=self.temperature,
                              mode=self.mode, n_steps=self.n_steps,
                              eos_id=self.eos_id)
        return base.replace(**overrides) if overrides else base

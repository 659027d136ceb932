# Compute hot-spot kernels (the paper's FlexAttention role on TPU):
#   block_diff_attn.py — flash attention under the block-diffusion
#       visibility predicate, *differentiable*: one forward kernel plus
#       a dQ/dKV backward kernel pair wired through jax.custom_vjp, all
#       three skipping provably-empty tiles via the same precomputed
#       ops.build_tile_map (the BlockMask analogue).  This is the
#       training hot path — SFT/DiPO run it under remat — as well as
#       the training-shaped forward.  Forward validated bitwise against
#       ref.mha_reference; gradients tolerance-checked against autodiff
#       through the structured/ref paths (tests/test_kernels.py).
#   paged_attn.py      — the paged-kernel family: decode attention and
#       plain-mode suffix prefill, both reading the serving KV page
#       pool in place through scalar-prefetched block tables (zero
#       transient gather); sub-tile shapes are zero-padded to the
#       (8, 128) tile so they stay compiled-eligible on TPU.  plan_exec
#       reports the chosen execution mode.  Validated against the
#       gathered fallback in models.attention (tests/test_paged_attn.py).
# Both run compiled on TPU and interpret=True elsewhere, so CPU CI
# exercises the real kernel bodies; default_interpret below is that one
# backend-driven choice.  ops.py dispatches the masked-pass
# implementations and reports the training execution mode via
# train_exec_plan.

import jax


def default_interpret() -> bool:
    """Run compiled on TPU, interpreted everywhere else (CPU CI)."""
    return jax.default_backend() != "tpu"

"""Pass 3 — Pallas kernel contracts, checked on CPU without a TPU.

The paged kernels' correctness rests on invariants no unit test states
directly: every BlockSpec index map must stay inside its operand at
every grid point (the block table's ``-1`` holes redirect to the null
page, never out of the pool), every block of a compiled launch must
satisfy Mosaic's shape rule (last two dims divisible by (8, 128) or
equal to the operand's — interpret mode never checks it, so a kernel
can pass every CPU test and still be refused by the chip's compiler),
scratch buffers must be (8, 128)-tile aligned whenever the plan
promises tile alignment, ``plan_exec`` must
resolve the full (interpret, pad) matrix to its documented modes, and
the masking contract (null pages, ``pos = -1`` holes, ``cache_limit``,
sliding window, MLA) must stay pinned by parity tests.

The differentiable training kernel gets the same treatment: the
``block_diff_attention`` matrix (aligned/subtile × compiled/interpret)
is driven *through ``jax.grad``*, so one capture records the
lse-emitting forward plus both backward launches (dQ, dKV) and their
BlockSpecs/scratch are bounds- and tile-checked like any other launch;
``kernel-parity-coverage`` additionally requires the gradient-parity
grid in ``tests/test_kernels.py`` to keep the VJP pinned vs autodiff.

None of this needs a TPU.  ``capture_launches`` monkeypatches
``pl.pallas_call`` on the shared pallas module (both kernel files bind
it via ``from jax.experimental import pallas as pl``, so the attribute
lookup happens at call time) to *record* each launch — grid, specs,
scratch, concrete operands — and return zeros of ``out_shape`` instead
of running.  Index maps are then evaluated over the whole grid with the
real scalar-prefetch operands (vmapped, so the table lookups inside the
maps run as one batched computation) and bounds-checked against the
operand shapes.  A separate ``jax.eval_shape`` of the *unpatched*
kernel traces the kernel body abstractly, catching in-body shape
mismatches that capture alone would miss.
"""

from __future__ import annotations

import ast
import contextlib
import dataclasses
import functools
import itertools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl_mod

from .rules import Finding

__all__ = ["capture_launches", "check_launch", "check_kernels",
           "check_parity_coverage", "run"]

_LANES = 128
_SUBLANES = 8

_DEFAULT_TESTS = Path(__file__).resolve().parents[3] / "tests" / \
    "test_paged_attn.py"


# ---------------------------------------------------------------------------
# launch capture
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Launch:
    """One recorded ``pl.pallas_call`` invocation."""
    name: str                      # kernel body function name
    grid: tuple
    num_scalar_prefetch: int
    in_specs: list                 # BlockSpec per non-prefetch operand
    out_specs: list
    scratch: list                  # [(shape tuple, dtype), ...]
    operands: list                 # concrete args (prefetch first)
    out_shapes: list               # [(shape, dtype), ...]
    interpret: bool


def _sds_list(out_shape) -> list:
    if isinstance(out_shape, (list, tuple)):
        return [(tuple(o.shape), o.dtype) for o in out_shape]
    return [(tuple(out_shape.shape), out_shape.dtype)]


@contextlib.contextmanager
def capture_launches():
    """Patch ``pallas_call`` to record launches and return zeros.

    Yields the list that accumulates ``Launch`` records.  The kernel
    body never runs and nothing is lowered, so this works on any
    backend — including "compiled"-mode plans on a CPU host.
    """
    launches: list[Launch] = []
    real = pl_mod.pallas_call

    def fake(kernel, *, grid_spec=None, grid=None, in_specs=None,
             out_specs=None, out_shape=None, scratch_shapes=(),
             interpret=False, **_kw):
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            npf = int(getattr(grid_spec, "num_scalar_prefetch", 0))
            ins = list(grid_spec.in_specs)
            outs = grid_spec.out_specs
            scr = grid_spec.scratch_shapes
        else:
            g = tuple(grid) if grid is not None else ()
            npf = 0
            ins = list(in_specs or [])
            outs = out_specs
            scr = scratch_shapes
        outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]
        scratch = [(tuple(s.shape), getattr(s, "dtype", None))
                   for s in (scr or [])]
        name = getattr(getattr(kernel, "func", kernel), "__name__",
                       "<kernel>")
        shapes = _sds_list(out_shape)

        def runner(*operands):
            launches.append(Launch(name, g, npf, ins, outs, scratch,
                                   list(operands), shapes,
                                   bool(interpret)))
            zeros = [jnp.zeros(s, d) for s, d in shapes]
            if isinstance(out_shape, (list, tuple)):
                return type(out_shape)(zeros)
            return zeros[0]

        return runner

    pl_mod.pallas_call = fake
    try:
        yield launches
    finally:
        pl_mod.pallas_call = real


# ---------------------------------------------------------------------------
# per-launch checks
# ---------------------------------------------------------------------------


def _eval_index_map(index_map, grid: tuple, prefetch: list):
    """Evaluate ``index_map`` at every grid point in one batched call.

    Returns an int array of shape (n_points, n_block_dims)."""
    points = np.array(list(itertools.product(*(range(g) for g in grid))),
                      dtype=np.int32)

    def at_point(pt):
        idx = index_map(*(pt[i] for i in range(len(grid))), *prefetch)
        # anchor constants to the batch so vmap output is uniform
        return tuple(jnp.asarray(x) + 0 * pt[0] for x in idx)

    cols = jax.vmap(at_point)(points)
    return np.stack([np.asarray(c) for c in cols], axis=1)


def _mosaic_block_ok(block: tuple, shape: tuple) -> bool:
    """Mosaic's BlockSpec rule: each of the last two block dims is a
    multiple of its tile dim (sublanes, lanes) or the operand's full
    dim.  A squeezed (``None``) dim counts as size 1."""
    tiles = (_SUBLANES, _LANES)[-len(block):] if block else ()
    for bs, dim, tile in zip(block[-2:], shape[-2:], tiles):
        bs = 1 if bs is None else bs
        if bs % tile and bs != dim:
            return False
    return True


def check_launch(launch: Launch, *, require_tile: bool, path: str,
                 line: int, where: str) -> list[Finding]:
    """Bounds-check every index map and (optionally) scratch tiling."""
    findings: list[Finding] = []
    prefetch = [jnp.asarray(x) for x in
                launch.operands[:launch.num_scalar_prefetch]]
    block_ops = launch.operands[launch.num_scalar_prefetch:]
    pairs = list(zip(launch.in_specs,
                     [tuple(o.shape) for o in block_ops])) + \
        list(zip(launch.out_specs, [s for s, _ in launch.out_shapes]))

    for spec_i, (spec, shape) in enumerate(pairs):
        block = tuple(spec.block_shape)
        if len(block) != len(shape):
            findings.append(Finding(
                "kernel-oob-index", path, line,
                f"{where}: spec #{spec_i} block rank {len(block)} != "
                f"operand rank {len(shape)} ({block} vs {shape})"))
            continue
        if not launch.interpret and not _mosaic_block_ok(block, shape):
            findings.append(Finding(
                "kernel-block-shape", path, line,
                f"{where}: spec #{spec_i} block {block} over operand "
                f"{shape}: the last two block dims must be divisible by "
                f"({_SUBLANES}, {_LANES}) or equal the operand's"))
        idx = _eval_index_map(spec.index_map, launch.grid, prefetch)
        for d, bs in enumerate(block):
            if bs is None:
                continue
            col = idx[:, d]
            bad = (col < 0) | ((col + 1) * bs > shape[d])
            if bad.any():
                pt = tuple(int(x) for x in
                           np.array(list(itertools.product(
                               *(range(g) for g in launch.grid))))
                           [int(np.argmax(bad))])
                findings.append(Finding(
                    "kernel-oob-index", path, line,
                    f"{where}: spec #{spec_i} dim {d} block index "
                    f"{int(col[int(np.argmax(bad))])} x block {bs} "
                    f"escapes operand dim {shape[d]} at grid point "
                    f"{pt}"))
                break

    if require_tile:
        for i, (shape, dtype) in enumerate(launch.scratch):
            if len(shape) < 2:
                continue
            if shape[-1] % _LANES or shape[-2] % _SUBLANES:
                findings.append(Finding(
                    "kernel-scratch-tile", path, line,
                    f"{where}: scratch #{i} shape {shape} "
                    f"({dtype}) is not ({_SUBLANES}, {_LANES})-tile "
                    "aligned but the plan promises tile alignment"))
    return findings


# ---------------------------------------------------------------------------
# kernel drivers: real shapes, full plan matrix
# ---------------------------------------------------------------------------


def _decode_args(*, aligned: bool):
    from ..kernels import paged_attn as pa
    if aligned:
        B, n, H, Hkv, Dk, Dv, P, K = 2, 8, 4, 2, 128, 128, 6, 3
    else:
        B, n, H, Hkv, Dk, Dv, P, K = 2, 4, 4, 2, 40, 40, 5, 3
    # table exercises -1 holes, the max page id, and an all-hole row
    table = np.full((B, K), -1, np.int32)
    table[0, 0] = P - 1
    table[0, 2] = 0
    args = (
        jnp.zeros((B, n, H, Dk), jnp.float32),
        jnp.zeros((P, Hkv, n, Dk), jnp.float32),
        jnp.zeros((P, Hkv, n, Dv), jnp.float32),
        jnp.zeros((P, n), jnp.int32),
        jnp.asarray(table),
        jnp.zeros((B, n, Hkv, Dk), jnp.float32),
        jnp.zeros((B, n, Hkv, Dv), jnp.float32),
        jnp.zeros((B, n), jnp.int32),
        jnp.zeros((B,), jnp.int32),
    )
    return pa.paged_decode_attention, args, (n, Dk, Dv), (B, n, H, Dv)


def _prefill_args(*, aligned: bool):
    from ..kernels import paged_attn as pa
    if aligned:
        B, bsz, Ts, H, Hkv, Dk, Dv, P, Kp = 2, 8, 2, 4, 2, 128, 128, 6, 2
    else:
        B, bsz, Ts, H, Hkv, Dk, Dv, P, Kp = 2, 4, 2, 4, 2, 40, 40, 5, 2
    T = Ts * bsz
    table = np.full((B, Kp), -1, np.int32)
    table[0, 0] = P - 1
    table[1, :] = [0, 1]
    args = (
        jnp.zeros((B, T, H, Dk), jnp.float32),
        jnp.zeros((P, Hkv, bsz, Dk), jnp.float32),
        jnp.zeros((P, Hkv, bsz, Dv), jnp.float32),
        jnp.zeros((P, bsz), jnp.int32),
        jnp.asarray(table),
        jnp.zeros((B, T, Hkv, Dk), jnp.float32),
        jnp.zeros((B, T, Hkv, Dv), jnp.float32),
        jnp.zeros((B, T), jnp.int32),
    )
    return pa.paged_prefill_attention, args, (bsz, Dk, Dv), (B, T, H, Dv)


# (shape, plan_exec kwargs, expected mode, expected padded)
_PLAN_MATRIX = [
    ("aligned", dict(interpret=True, pad=False), "interpret", False),
    ("subtile", dict(interpret=True, pad=True), "interpret", True),
    ("aligned", dict(interpret=False, pad=False), "compiled", False),
    ("subtile", dict(interpret=False, pad=None), "compiled", True),
]


def _check_paged_kernel(make_args, label: str) -> list[Finding]:
    from ..kernels import paged_attn as pa
    findings: list[Finding] = []
    path = str(Path(pa.__file__))
    for shape_kind, kw, want_mode, want_padded in _PLAN_MATRIX:
        fn, args, (bsz, dk, dv), out_shape = make_args(
            aligned=shape_kind == "aligned")
        line = fn.__code__.co_firstlineno
        where = f"{label}[{shape_kind}, interpret={kw['interpret']}, " \
            f"pad={kw['pad']}]"
        plan = pa.plan_exec(bsz, dk, dv, **kw)
        if (plan.mode, plan.padded) != (want_mode, want_padded):
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: plan_exec resolved to ({plan.mode}, "
                f"padded={plan.padded}), documented mode is "
                f"({want_mode}, padded={want_padded})"))
            continue
        call = functools.partial(fn, scale=1.0, **kw)
        with capture_launches() as launches:
            out = call(*args)
        if tuple(out.shape) != out_shape:
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: output shape {tuple(out.shape)} != expected "
                f"{out_shape}"))
        if not launches:
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: no pallas_call launch was captured"))
            continue
        require_tile = plan.padded or plan.mode == "compiled"
        for launch in launches:
            findings.extend(check_launch(
                launch, require_tile=require_tile, path=path, line=line,
                where=where))
        # abstract-eval the unpatched kernel: traces the real kernel
        # body with block-shaped avals, catching in-body mismatches
        try:
            jax.eval_shape(call, *args)
        except Exception as e:  # pragma: no cover - defect path
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: kernel failed abstract evaluation: "
                f"{type(e).__name__}: {e}"))
    # padding disabled + compiled + sub-tile: compiled unpadded, never
    # a silent fallback to interpret mode
    plan = pa.plan_exec(4, 40, 40, interpret=False, pad=False)
    if plan.mode != "compiled" or plan.padded:
        findings.append(Finding(
            "kernel-plan-matrix", path, 1,
            "plan_exec(subtile, interpret=False, pad=False) must compile "
            f"unpadded, got ({plan.mode}, padded={plan.padded})"))
    return findings


# (shape kind, interpret, require_tile): the aligned shape uses the
# production 128-tiles (scratch must hold the (8, 128) tile), the
# subtile shape exercises the clamped small-tile path trainers/tests
# run on CPU; both are checked compiled AND interpret — capture never
# lowers, so the compiled specs are checkable on a CPU host
_BLOCK_DIFF_MATRIX = [
    ("aligned", True, True),
    ("aligned", False, True),
    ("subtile", True, False),
    ("subtile", False, False),
]

# every kernel body the differentiable attention must launch: the
# (lse-emitting) forward plus the dQ / dKV backward pair
_BLOCK_DIFF_KERNELS = ("_kernel", "_dq_kernel", "_dkv_kernel")


def _block_diff_args(*, aligned: bool):
    if aligned:
        B, L, H, Hkv, D, Dv, t = 1, 256, 2, 1, 128, 128, 128
    else:
        B, L, H, Hkv, D, Dv, t = 1, 64, 4, 2, 32, 24, 16
    args = (
        jnp.zeros((B, L, H, D), jnp.float32),
        jnp.zeros((B, L, Hkv, D), jnp.float32),
        jnp.zeros((B, L, Hkv, Dv), jnp.float32),
        jnp.zeros((B, L, 4), jnp.int32),
        jnp.zeros((B, L, 4), jnp.int32),
        jnp.ones((B, L // t, L // t), jnp.int32),
    )
    return args, t, (B, L, H, Dv)


def _check_block_diff() -> list[Finding]:
    from ..kernels import block_diff_attn as bd
    findings: list[Finding] = []
    path = str(Path(bd.__file__))
    line = bd.block_diff_attention.__code__.co_firstlineno
    for shape_kind, interpret, require_tile in _BLOCK_DIFF_MATRIX:
        args, t, out_shape = _block_diff_args(
            aligned=shape_kind == "aligned")
        q, k, v, qm, km, tm = args
        where = f"block_diff_attention[{shape_kind}, " \
            f"interpret={interpret}]"
        call = functools.partial(bd.block_diff_attention, tq=t, tk=t,
                                 interpret=interpret)

        # differentiate through the kernel so ONE capture records the
        # lse-emitting forward plus both backward launches
        def grad_call(q, k, v):
            return jax.grad(
                lambda *a: jnp.sum(call(*a, qm, km, tm)
                                   .astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        with capture_launches() as launches:
            out = call(*args)          # inference forward (no lse)
            grad_call(q, k, v)         # training fwd + dQ + dKV
        if tuple(out.shape) != out_shape:
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: output shape {tuple(out.shape)} != expected "
                f"{out_shape}"))
        seen = {launch.name for launch in launches}
        for kern in _BLOCK_DIFF_KERNELS:
            if kern not in seen:
                findings.append(Finding(
                    "kernel-plan-matrix", path, line,
                    f"{where}: differentiating never launched {kern} "
                    f"(captured: {sorted(seen)})"))
        for launch in launches:
            findings.extend(check_launch(
                launch, require_tile=require_tile, path=path, line=line,
                where=f"{where}:{launch.name}"))
        # abstract-eval the unpatched forward AND backward bodies
        try:
            jax.eval_shape(call, *args)
            jax.eval_shape(grad_call, q, k, v)
        except Exception as e:  # pragma: no cover - defect path
            findings.append(Finding(
                "kernel-plan-matrix", path, line,
                f"{where}: failed abstract evaluation: "
                f"{type(e).__name__}: {e}"))
    return findings


def check_kernels() -> list[Finding]:
    """All capture/abstract-eval checks for the kernel family."""
    findings = _check_paged_kernel(_decode_args, "paged_decode_attention")
    findings += _check_paged_kernel(_prefill_args,
                                    "paged_prefill_attention")
    findings += _check_block_diff()
    return findings


# ---------------------------------------------------------------------------
# parity-test coverage of the masking contract
# ---------------------------------------------------------------------------

# feature -> regex over a test function's *effective* source (its own
# body + decorators + directly-called module-level helpers)
_DECODE_FEATURES = {
    "null page (table -1 holes)": r"table.{0,80}-\s*1|-\s*1.{0,80}table",
    "pos = -1 slot holes": r"pos.{0,60}-\s*1",
    "cache_limit edges": r"cache_limit",
    "sliding window": r"window.{0,80}\d",
    "MLA latent shape": r"\bmla\b",
}
_PREFILL_FEATURES = {
    "stale/unmapped pool rows": r"stale|poison",
    "pos = -1 slot holes": r"pos.{0,60}-\s*1",
    "sliding window": r"window.{0,80}\d",
    "MLA latent shape": r"\bmla\b",
}
_DECODE_USE = re.compile(r"block_table|paged_decode_attention")
_PREFILL_USE = re.compile(r"context_table|paged_prefill_attention")

# gradient-parity coverage of the differentiable training kernels
# (tests/test_kernels.py): the custom-VJP backward must stay pinned
# against autodiff across the mask-feature grid
_TRAIN_DEFAULT_TESTS = Path(__file__).resolve().parents[3] / "tests" / \
    "test_kernels.py"
_TRAIN_FEATURES = {
    "gradient parity (VJP vs autodiff)": r"jax\.grad|value_and_grad",
    "grouped heads (GQA/MQA/MLA)": r"\bHkv\b",
    "sliding window": r"window.{0,80}\d",
    "softcap tanh chain rule": r"softcap.{0,80}\d",
    "strict packed layout": r"packed|strict",
    "zero grads at INVALID_COPY padding": r"invalid|INVALID_COPY",
}
_TRAIN_USE = re.compile(
    r"[\"']pallas(_interpret)?[\"']|block_diff_attention")


def _effective_sources(source: str) -> dict[str, str]:
    """Test name -> its source expanded with called top-level helpers."""
    tree = ast.parse(source)
    helpers: dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            helpers[node.name] = ast.get_source_segment(source, node) or ""
    out: dict[str, str] = {}
    for node in tree.body:
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name.startswith("test")):
            continue
        parts = [ast.get_source_segment(source, d) or ""
                 for d in node.decorator_list]
        parts.append(helpers[node.name])
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in helpers and \
                    n.id != node.name:
                parts.append(helpers[n.id])
        out[node.name] = "\n".join(parts)
    return out


def _coverage_of(path: Path, kernels) -> list[Finding]:
    if not path.exists():
        return [Finding("kernel-parity-coverage", str(path), 1,
                        "parity test file is missing")]
    sources = _effective_sources(path.read_text())
    findings: list[Finding] = []
    for kernel, use_re, features in kernels:
        relevant = [s for s in sources.values() if use_re.search(s)]
        if not relevant:
            findings.append(Finding(
                "kernel-parity-coverage", str(path), 1,
                f"no parity test exercises {kernel} at all"))
            continue
        for feature, rx in features.items():
            if not any(re.search(rx, s, re.S) for s in relevant):
                findings.append(Finding(
                    "kernel-parity-coverage", str(path), 1,
                    f"masking-contract feature `{feature}` of {kernel} "
                    "is not exercised by any parity test"))
    return findings


def check_parity_coverage(tests_path=None,
                          train_tests_path=None) -> list[Finding]:
    serve = Path(tests_path) if tests_path else _DEFAULT_TESTS
    train = Path(train_tests_path) if train_tests_path \
        else _TRAIN_DEFAULT_TESTS
    findings = _coverage_of(serve, (
        ("paged_decode_attention", _DECODE_USE, _DECODE_FEATURES),
        ("paged_prefill_attention", _PREFILL_USE, _PREFILL_FEATURES)))
    findings += _coverage_of(train, (
        ("block_diff_attention (training VJP)", _TRAIN_USE,
         _TRAIN_FEATURES),))
    return findings


def run(project=None, tests_path=None) -> list[Finding]:
    return check_kernels() + check_parity_coverage(tests_path)

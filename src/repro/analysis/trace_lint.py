"""Pass 1 — trace hygiene over everything reachable from a jit site.

Finds every ``jax.jit`` / ``TraceGuard`` call site in the tree, resolves
the jitted callable (plain function, ``self.method``,
``functools.partial`` target, or a factory-returned nested def like
``make_train_step``), seeds its non-static parameters as *tainted*
(traced values), and walks the call graph propagating taint
interprocedurally.  Inside tainted code it flags:

``trace-branch``     Python ``if``/``while``/``for``/``assert`` whose
                     condition (or iterable) is a traced value — the
                     classic retrace-per-value / leaked-tracer bug.
                     ``x is None`` / ``isinstance(x, T)`` tests are
                     exempt (they are static under tracing), as is
                     iterating a ``.items()``-style call (dict pytree
                     structure is static).
``trace-host-pull``  ``float()``/``int()``/``bool()``, ``.item()``/
                     ``.tolist()``, or ``np.asarray``/``np.array`` on a
                     traced value — a host round-trip that fails (or
                     silently constant-folds) under tracing.
``hot-sync``         ``jax.block_until_ready`` / ``jax.device_get``
                     inside a registered per-tick/per-step hot path
                     (scheduler tick, engine drain/stream, trainer
                     step) — host syncs that serialize dispatch.
``obs-in-trace``     any ``obs.metrics`` / ``obs.trace`` call inside
                     jit-reachable code — instrumentation is host-side
                     bookkeeping *between* dispatches; inside a trace
                     it runs at trace time (recording garbage once per
                     compilation) or leaks a tracer into a span or
                     metric.  Detected via import-alias calls
                     (``trace.Tracer(...)``, ``obs.MetricsRegistry``),
                     resolved callees living in an obs module, locally
                     constructed obs handles, and ``*.tracer.<span-
                     API>()`` method chains.

Taint is deliberately shape-transparent: ``x.shape`` / ``x.ndim`` /
``x.dtype`` / ``len(x)`` of a tracer are static, so branching on them
is fine and stays unflagged.
"""

from __future__ import annotations

import ast
from collections import deque

from .astutils import FunctionInfo, Project, attr_path
from .rules import Finding

__all__ = ["run", "HOT_PATHS", "EXTRA_ROOTS"]

# per-tick / per-step host-side hot paths: block_until_ready/device_get
# anywhere in their (repo-local) call graph is a dispatch stall
HOT_PATHS = [
    ("repro.serving.scheduler", "SlotScheduler.step"),
    ("repro.serving.engine", "RolloutEngine.generate_ids"),
    ("repro.serving.engine", "RolloutEngine._generate_ids_continuous"),
    ("repro.serving.engine", "RolloutEngine.stream"),
    ("repro.rl.trainer", "DiPOTrainer.train_step"),
    ("repro.sft.trainer", "SFTTrainer.train_step"),
]

# always-traced entry points reached through dynamic dispatch the
# resolver cannot follow (KVLayout.attend -> Pallas wrappers): lint
# them with every non-defaulted parameter tainted
EXTRA_ROOTS = [
    ("repro.kernels.paged_attn", "paged_decode_attention"),
    ("repro.kernels.paged_attn", "paged_prefill_attention"),
    ("repro.kernels.block_diff_attn", "block_diff_attention"),
    ("repro.kernels.ops", "chunked_masked_attention"),
]

# duck-typed method calls on a hinted parameter name: "model" is always
# the BlockDiffLM, so model.decode_step(...) resolves statically
PARAM_TYPE_HINTS = {
    "model": ("repro.models.model", "BlockDiffLM"),
}

_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "aval",
                "weak_type", "sharding"}
_STATIC_CALLS = {"isinstance", "len", "type", "hasattr", "callable",
                 "getattr", "issubclass", "id", "repr"}
_HOST_PULL_NAMES = {"float", "int", "bool"}
_HOST_PULL_METHODS = {"item", "tolist"}
_SYNC_ATTRS = {"block_until_ready", "device_get"}

# obs modules whose calls must never be jit-reachable (profile/export
# are not listed: capture() and the exporters are cold paths no jit
# site can reach)
_OBS_MODULES = {"repro.obs", "repro.obs.metrics", "repro.obs.trace"}
# Tracer's recording API: a `<anything>.tracer.<one of these>()` chain
# is an obs call even when the receiver cannot be resolved statically
# (e.g. `self.tracer.span(...)`).  Deliberately excludes generic names
# like `set`/`add` that jnp's `.at[...]` API shares.
_TRACER_METHODS = {"span", "begin", "end", "instant", "amend",
                   "snapshot"}

_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


def _is_jax_attr(module, node: ast.expr, attr: str) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == attr
            and isinstance(node.value, ast.Name)
            and module.import_aliases.get(node.value.id) == "jax")


def _is_jit_site(module, call: ast.Call) -> bool:
    f = call.func
    if _is_jax_attr(module, f, "jit"):
        return True
    if isinstance(f, ast.Name) and f.id == "TraceGuard":
        return True
    if isinstance(f, ast.Attribute) and f.attr == "TraceGuard":
        return True
    return False


def _is_partial(module, call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Name) and f.id == "partial":
        return True
    return (isinstance(f, ast.Attribute) and f.attr == "partial"
            and isinstance(f.value, ast.Name)
            and module.import_aliases.get(f.value.id) == "functools")


def _const_strs(node) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str)]
    return []


def _const_ints(node) -> list[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, int)]
    return []


def _scope_stmts(body):
    """Every statement in this scope, recursing into compound
    statements but never into nested function/class definitions."""
    for stmt in body:
        if isinstance(stmt, _DEFS):
            continue
        yield stmt
        for _, val in ast.iter_fields(stmt):
            if isinstance(val, list):
                yield from _scope_stmts(
                    [s for s in val if isinstance(s, ast.stmt)])
        for h in getattr(stmt, "handlers", []):
            yield from _scope_stmts(h.body)


def _expr_calls(stmt):
    """Call nodes among this statement's own expressions (nested
    statements, lambdas and defs excluded)."""
    stack = [stmt]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Call):
            yield n
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.stmt, ast.Lambda)) or \
                    isinstance(c, _DEFS):
                continue
            stack.append(c)


def _scope_calls(body):
    for stmt in _scope_stmts(body):
        yield from _expr_calls(stmt)


class _Resolver:
    """Project resolution + PARAM_TYPE_HINTS method dispatch."""

    def __init__(self, project: Project):
        self.project = project

    def resolve(self, module, scope, cls, func_expr):
        fi = self.project.resolve_callable(module, scope, cls, func_expr)
        if fi is not None:
            return fi
        if isinstance(func_expr, ast.Attribute) and \
                isinstance(func_expr.value, ast.Name):
            hint = PARAM_TYPE_HINTS.get(func_expr.value.id)
            if hint and hint[0] in self.project.modules:
                return self.project.modules[hint[0]].functions.get(
                    f"{hint[1]}.{func_expr.attr}")
        return None


# --------------------------------------------------------------------------
# jit-site discovery
# --------------------------------------------------------------------------


def find_jit_sites(project: Project, resolver: _Resolver):
    """Yield (target FunctionInfo, seed-tainted param frozenset)."""
    for module in project.modules.values():
        scopes = [("", None, module.tree.body)]
        scopes += [(fi.qualname, fi.cls_name, fi.node.body)
                   for fi in module.functions.values()]
        for scope, cls, body in scopes:
            for call in _scope_calls(body):
                if not _is_jit_site(module, call) or not call.args:
                    continue
                yield from _resolve_site(project, resolver, module,
                                         scope, cls, body, call)


def _resolve_site(project, resolver, module, scope, cls, body, call):
    target = call.args[0]
    bound_pos, bound_kw = 0, set()
    if isinstance(target, ast.Call) and _is_partial(module, target) \
            and target.args:
        bound_pos = len(target.args) - 1
        bound_kw = {kw.arg for kw in target.keywords if kw.arg}
        target = target.args[0]
    fi = resolver.resolve(module, scope, cls, target)
    if fi is None and isinstance(target, ast.Name):
        # local `step_fn = make_train_step(...)` factory pattern
        for stmt in _scope_stmts(body):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and stmt.targets[0].id == target.id \
                    and isinstance(stmt.value, ast.Call):
                factory = resolver.resolve(module, scope, cls,
                                           stmt.value.func)
                if factory is not None:
                    fi = project.resolve_factory_return(factory)
    if fi is None:
        return
    statics = set()
    params = fi.params
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            statics |= set(_const_strs(kw.value))
        elif kw.arg == "static_argnums":
            statics |= {params[i] for i in _const_ints(kw.value)
                        if i < len(params)}
    tainted = frozenset(p for i, p in enumerate(params)
                        if i >= bound_pos and p not in statics
                        and p not in bound_kw)
    if tainted:
        yield fi, tainted


def _no_default_params(fi: FunctionInfo) -> frozenset:
    a = fi.node.args
    pos = [p.arg for p in a.posonlyargs + a.args]
    if pos and pos[0] in ("self", "cls"):
        pos = pos[1:]
    n_def = len(a.defaults)
    out = set(pos[:len(pos) - n_def] if n_def else pos)
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is None:
            out.add(p.arg)
    return frozenset(out)


# --------------------------------------------------------------------------
# intraprocedural taint walk
# --------------------------------------------------------------------------


class _FnTaint:
    def __init__(self, resolver: _Resolver, fi: FunctionInfo,
                 tainted: frozenset, findings: list, enqueue):
        self.r = resolver
        self.fi = fi
        self.module = fi.module
        self.path = str(fi.module.path)
        self.tainted: set[str] = set(tainted)
        self.findings = findings
        self.enqueue = enqueue
        self._flagged: set[tuple] = set()
        # local names bound to obs objects (`t = Tracer(...)`): later
        # method calls on them are obs calls even without resolution
        self._obs_handles: set[str] = set()

    def run(self):
        for _ in range(2):        # fixpoint for loop-carried taint
            for stmt in self.fi.node.body:
                self.stmt(stmt)

    def flag(self, rule: str, node, msg: str):
        key = (rule, node.lineno)
        if key in self._flagged:
            return
        self._flagged.add(key)
        self.findings.append(Finding(rule, self.path, node.lineno, msg))

    # ------------------------------------------------------ expressions
    def is_tainted(self, e) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.tainted
        if isinstance(e, ast.Attribute):
            if e.attr in _SHAPE_ATTRS:
                return False
            # fields declared static via register_dataclass metadata
            # (LayerCtx.mode, .write_cache, ...) are host values even
            # when the carrying pytree is traced
            if e.attr in self.r.project.static_fields:
                return False
            return self.is_tainted(e.value)
        if isinstance(e, ast.Subscript):
            return self.is_tainted(e.value) or self.is_tainted(e.slice)
        if isinstance(e, ast.Call):
            if isinstance(e.func, ast.Name) and \
                    e.func.id in _STATIC_CALLS:
                return False
            if isinstance(e.func, ast.Attribute) and \
                    e.func.attr in ("keys", "items", "values"):
                # dict *structure* is static even for tracer pytrees;
                # the yielded values re-taint through loop targets
                return self.is_tainted(e.func.value)
            args = list(e.args) + [kw.value for kw in e.keywords]
            return self.is_tainted(e.func) or \
                any(self.is_tainted(a) for a in args)
        if isinstance(e, ast.BoolOp):
            return any(self.is_tainted(v) for v in e.values)
        if isinstance(e, ast.BinOp):
            return self.is_tainted(e.left) or self.is_tainted(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.is_tainted(e.operand)
        if isinstance(e, ast.Compare):
            return self.is_tainted(e.left) or \
                any(self.is_tainted(c) for c in e.comparators)
        if isinstance(e, ast.IfExp):
            return any(self.is_tainted(x)
                       for x in (e.test, e.body, e.orelse))
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(x) for x in e.elts)
        if isinstance(e, ast.Dict):
            return any(self.is_tainted(x)
                       for x in list(e.keys) + list(e.values)
                       if x is not None)
        if isinstance(e, ast.Starred):
            return self.is_tainted(e.value)
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                          ast.DictComp)):
            return any(self.is_tainted(g.iter) for g in e.generators)
        if isinstance(e, ast.NamedExpr):
            return self.is_tainted(e.value)
        return False

    def _is_static_guard(self, t) -> bool:
        """Tests that are Python-static even over tracers."""
        if isinstance(t, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in t.ops):
            return True
        if isinstance(t, ast.Compare) and t.ops and all(
                isinstance(op, (ast.In, ast.NotIn)) for op in t.ops):
            # `"key" in pytree` / `x in ("a", "b")`: dict *structure*
            # and literal membership are static; membership in a traced
            # array (`x in arr`) is not, and stays flagged
            if isinstance(t.left, ast.Constant) and \
                    isinstance(t.left.value, str):
                return True
            if all(isinstance(c, (ast.Tuple, ast.List, ast.Set))
                   for c in t.comparators):
                return True
        if isinstance(t, ast.Call) and isinstance(t.func, ast.Name) \
                and t.func.id in _STATIC_CALLS:
            return True
        if isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not):
            return self._is_static_guard(t.operand)
        if isinstance(t, ast.BoolOp):
            return all(self._is_static_guard(v) or not self.is_tainted(v)
                       for v in t.values)
        return False

    # ------------------------------------------------------- statements
    def assign_target(self, tgt, value_tainted: bool):
        if isinstance(tgt, ast.Name):
            if value_tainted:
                self.tainted.add(tgt.id)
            else:
                self.tainted.discard(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for e in tgt.elts:
                self.assign_target(e, value_tainted)
        elif isinstance(tgt, ast.Starred):
            self.assign_target(tgt.value, value_tainted)
        # attribute/subscript stores: untracked

    def stmt(self, s):
        if isinstance(s, _DEFS):
            return
        self.scan_calls(s)
        if isinstance(s, ast.Assign):
            if isinstance(s.value, ast.Call) and \
                    self._obs_call_kind(s.value) is not None:
                for tgt in s.targets:
                    if isinstance(tgt, ast.Name):
                        self._obs_handles.add(tgt.id)
            t = self.is_tainted(s.value)
            if isinstance(s.value, ast.Tuple) and len(s.targets) == 1 \
                    and isinstance(s.targets[0], ast.Tuple) \
                    and len(s.targets[0].elts) == len(s.value.elts):
                for tgt, v in zip(s.targets[0].elts, s.value.elts):
                    self.assign_target(tgt, self.is_tainted(v))
            else:
                for tgt in s.targets:
                    self.assign_target(tgt, t)
        elif isinstance(s, ast.AugAssign):
            if self.is_tainted(s.value):
                self.assign_target(s.target, True)
        elif isinstance(s, ast.AnnAssign) and s.value is not None:
            self.assign_target(s.target, self.is_tainted(s.value))
        elif isinstance(s, (ast.If, ast.While)):
            if self.is_tainted(s.test) and \
                    not self._is_static_guard(s.test):
                kind = "while" if isinstance(s, ast.While) else "if"
                self.flag("trace-branch", s,
                          f"Python `{kind}` on a traced value in "
                          f"{self.fi.qualname} (retraces per value or "
                          "leaks the tracer); use jnp.where/lax.cond")
            narrowed = self._narrow_names(s.test)
            saved = {n for n in narrowed if n in self.tainted}
            self.tainted -= saved
            for sub in s.body:
                self.stmt(sub)
            self.tainted |= saved
            for sub in s.orelse:
                self.stmt(sub)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            it_tainted = self.is_tainted(s.iter)
            if it_tainted and not isinstance(s.iter, ast.Call):
                self.flag("trace-branch", s,
                          f"Python `for` over a traced value in "
                          f"{self.fi.qualname} (statically unrolls / "
                          "leaks the tracer); use lax.fori_loop/scan")
            self.assign_target(s.target, it_tainted)
            for sub in s.body + s.orelse:
                self.stmt(sub)
        elif isinstance(s, ast.Assert):
            if self.is_tainted(s.test) and \
                    not self._is_static_guard(s.test):
                self.flag("trace-branch", s,
                          f"assert on a traced value in "
                          f"{self.fi.qualname} (forces concretization); "
                          "assert on .shape/.dtype or use checkify")
        elif isinstance(s, ast.With):
            for sub in s.body:
                self.stmt(sub)
        elif isinstance(s, ast.Try):
            for sub in s.body + s.orelse + s.finalbody:
                self.stmt(sub)
            for h in s.handlers:
                for sub in h.body:
                    self.stmt(sub)

    def _narrow_names(self, test) -> set[str]:
        """Names an isinstance/is-None guard makes host-static in the
        body (approximate flow-sensitivity)."""
        out = set()
        if isinstance(test, ast.Call) and \
                isinstance(test.func, ast.Name) and \
                test.func.id == "isinstance" and test.args and \
                isinstance(test.args[0], ast.Name):
            out.add(test.args[0].id)
        if isinstance(test, ast.Compare) and \
                isinstance(test.left, ast.Name) and all(
                    isinstance(op, (ast.Is, ast.IsNot))
                    for op in test.ops):
            out.add(test.left.id)
        if isinstance(test, ast.BoolOp):
            for v in test.values:
                out |= self._narrow_names(v)
        return out

    # ---------------------------------------------------- calls / edges
    def scan_calls(self, stmt):
        for node in _expr_calls(stmt):
            if _is_jit_site(self.module, node):
                continue
            self._check_sinks(node)
            if self._check_obs(node):
                continue        # don't chase taint into obs internals
            self._edges(node)

    def _obs_call_kind(self, call: ast.Call) -> str | None:
        """How this call lands in repro.obs (a display string), or None."""
        f = call.func
        if isinstance(f, ast.Attribute):
            root = f.value
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                m = self.r.project.module_of_alias(self.module, root.id)
                if m is not None and m.name in _OBS_MODULES:
                    return f"{m.name}.{f.attr}"
                if root.id in self._obs_handles:
                    return f"{root.id}.{f.attr}"
            if f.attr in _TRACER_METHODS:
                p = attr_path(f)
                # `<anything>.tracer.span(...)` — the conventional
                # handle name makes the receiver recognizable even when
                # its type cannot be resolved (self.tracer, eng.tracer)
                if p is not None and "tracer" in p.split(".")[:-1]:
                    return p
        elif isinstance(f, ast.Name):
            src = self.module.from_imports.get(f.id)
            if src is not None and src[0] in _OBS_MODULES:
                return f"{src[0]}.{src[1]}"
        callee = self.r.resolve(self.module, self.fi.qualname,
                                self.fi.cls_name, f)
        if callee is not None and callee.module.name in _OBS_MODULES:
            return f"{callee.module.name}:{callee.qualname}"
        return None

    def _check_obs(self, call: ast.Call) -> bool:
        kind = self._obs_call_kind(call)
        if kind is None:
            return False
        self.flag("obs-in-trace", call,
                  f"obs call {kind}() reachable in jitted body "
                  f"{self.fi.qualname}; metrics/spans are host-side "
                  "bookkeeping — record them between dispatches, not "
                  "inside the trace")
        return True

    def _check_sinks(self, call: ast.Call):
        f = call.func
        if isinstance(f, ast.Name) and f.id in _HOST_PULL_NAMES and \
                len(call.args) == 1 and self.is_tainted(call.args[0]):
            self.flag("trace-host-pull", call,
                      f"{f.id}() on a traced value in "
                      f"{self.fi.qualname} (host pull fails under "
                      "tracing)")
        elif isinstance(f, ast.Attribute):
            if f.attr in _HOST_PULL_METHODS and self.is_tainted(f.value):
                self.flag("trace-host-pull", call,
                          f".{f.attr}() on a traced value in "
                          f"{self.fi.qualname}")
            elif f.attr in ("asarray", "array") and \
                    isinstance(f.value, ast.Name) and \
                    self.module.import_aliases.get(f.value.id) == \
                    "numpy" and call.args and \
                    self.is_tainted(call.args[0]):
                self.flag("trace-host-pull", call,
                          f"np.{f.attr}() on a traced value in "
                          f"{self.fi.qualname} (device->host copy "
                          "fails under tracing); use jnp")

    def _edges(self, call: ast.Call):
        callee = self.r.resolve(self.module, self.fi.qualname,
                                self.fi.cls_name, call.func)
        if callee is not None:
            self._call_edge(call, callee)
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            self._fn_value_edge(arg)

    def _call_edge(self, call: ast.Call, callee: FunctionInfo):
        names = callee.all_params
        offset = 0
        if names and names[0] in ("self", "cls") and \
                isinstance(call.func, ast.Attribute):
            offset = 1
        tainted = set()
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred):
                continue
            j = i + offset
            if j < len(names) and self.is_tainted(a):
                tainted.add(names[j])
        for kw in call.keywords:
            if kw.arg and kw.arg in names and self.is_tainted(kw.value):
                tainted.add(kw.arg)
        if tainted:
            self.enqueue(callee, frozenset(tainted))

    def _fn_value_edge(self, arg):
        """A function *value* passed into a call inside traced code is
        assumed traced with every parameter a tracer (lax control flow,
        vmap, grad, tree.map bodies)."""
        if isinstance(arg, ast.Lambda):
            params = [p.arg for p in arg.args.posonlyargs
                      + arg.args.args + arg.args.kwonlyargs]
            saved = set(self.tainted)
            self.tainted |= set(params)
            for node in ast.walk(arg.body):
                if isinstance(node, ast.Call):
                    self._check_sinks(node)
            self.tainted = saved
            return
        if isinstance(arg, ast.Call) and _is_partial(self.module, arg) \
                and arg.args:
            inner = self.r.resolve(self.module, self.fi.qualname,
                                   self.fi.cls_name, arg.args[0])
            if inner is not None:
                bound_pos = len(arg.args) - 1
                bound_kw = {kw.arg for kw in arg.keywords if kw.arg}
                ps = inner.params
                tset = frozenset(p for i, p in enumerate(ps)
                                 if i >= bound_pos and p not in bound_kw)
                if tset:
                    self.enqueue(inner, tset)
            return
        if isinstance(arg, ast.Name):
            fi = self.r.resolve(self.module, self.fi.qualname,
                                self.fi.cls_name, arg)
            if fi is not None and fi.params:
                self.enqueue(fi, frozenset(fi.params))


# --------------------------------------------------------------------------
# hot-path sync scan (no taint needed)
# --------------------------------------------------------------------------


def _hot_sync_scan(project: Project, resolver: _Resolver,
                   findings: list):
    queue = deque()
    seen = set()
    for mod_name, qual in HOT_PATHS:
        mod = project.modules.get(mod_name)
        if mod and qual in mod.functions:
            queue.append((mod.functions[qual], f"{mod_name}:{qual}"))
    flagged = set()
    while queue:
        fi, root = queue.popleft()
        key = (id(fi.module), fi.qualname, root)
        if key in seen:
            continue
        seen.add(key)
        for call in _scope_calls(fi.node.body):
            f = call.func
            is_sync = any(_is_jax_attr(fi.module, f, a)
                          for a in _SYNC_ATTRS)
            if isinstance(f, ast.Attribute) and \
                    f.attr == "block_until_ready" and not call.args:
                is_sync = True              # arr.block_until_ready()
            if is_sync:
                fkey = (str(fi.module.path), call.lineno)
                if fkey not in flagged:
                    flagged.add(fkey)
                    findings.append(Finding(
                        "hot-sync", str(fi.module.path), call.lineno,
                        f"host sync in per-tick hot path {root} "
                        f"(via {fi.qualname}); gate it behind an "
                        "opt-in latency-stats flag"))
                continue
            callee = resolver.resolve(fi.module, fi.qualname,
                                      fi.cls_name, f)
            if callee is not None:
                queue.append((callee, root))


# --------------------------------------------------------------------------
# pass driver
# --------------------------------------------------------------------------


def run(project: Project) -> list[Finding]:
    findings: list[Finding] = []
    resolver = _Resolver(project)

    seen: set[tuple] = set()
    queue: deque = deque()

    def enqueue(fi: FunctionInfo, tainted: frozenset):
        key = (id(fi.module), fi.qualname, tainted)
        if key not in seen:
            seen.add(key)
            queue.append((fi, tainted))

    for fi, tainted in find_jit_sites(project, resolver):
        enqueue(fi, tainted)
    for mod_name, fname in EXTRA_ROOTS:
        mod = project.modules.get(mod_name)
        if mod and fname in mod.functions:
            fi = mod.functions[fname]
            seeds = _no_default_params(fi)
            if seeds:
                enqueue(fi, seeds)

    budget = 4000                      # worklist backstop
    while queue and budget:
        budget -= 1
        fi, tainted = queue.popleft()
        _FnTaint(resolver, fi, tainted, findings, enqueue).run()

    _hot_sync_scan(project, resolver, findings)
    return findings

"""dirlint rule registry, findings, and suppression pragmas.

Every contract the analyzer enforces is one ``Rule`` subclass with a
stable ``id`` — the string that appears in reports, in suppression
pragmas, and in ROADMAP's "standing contracts" table.  Passes emit
``Finding`` records tagged with a rule id; the registry is the single
place a new contract is declared, so adding one is: subclass ``Rule``
(anywhere that gets imported), emit findings with its id.

Suppression: a comment ``# dirlint: ok(rule-id)`` — on the flagged line
or the line directly above it — marks a finding as deliberate.  Several
ids may be listed: ``# dirlint: ok(hot-sync, trace-host-pull)``.
Suppressed findings are still collected (``--verbose`` shows them) but
never fail ``--strict``.
"""

from __future__ import annotations

import dataclasses
import re

__all__ = ["Finding", "Rule", "RULES", "register", "scan_pragmas",
           "apply_pragmas"]


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation (or deliberate, pragma'd exception)."""
    rule: str
    path: str
    line: int
    message: str
    suppressed: bool = False

    def format(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.rule}: {self.message}{tag}"


RULES: dict[str, type["Rule"]] = {}


def register(cls: type["Rule"]) -> type["Rule"]:
    assert cls.id and cls.id not in RULES, cls
    RULES[cls.id] = cls
    return cls


class Rule:
    """Base class: one enforced contract.  Subclasses set ``id`` (the
    stable kebab-case identifier) and ``doc`` (one-line contract
    statement shown by ``--list-rules``)."""
    id: str = ""
    doc: str = ""


# --------------------------------------------------------------------------
# pass 1: trace hygiene (analysis.trace_lint)
# --------------------------------------------------------------------------


@register
class TraceBranchRule(Rule):
    id = "trace-branch"
    doc = ("no Python-level if/while/for/assert on a traced value inside "
           "jit-reachable code (retraces per value, or leaks a tracer)")


@register
class TraceHostPullRule(Rule):
    id = "trace-host-pull"
    doc = ("no .item()/.tolist()/float()/int()/bool()/np.asarray on a "
           "traced value inside jit-reachable code (host round-trip "
           "breaks tracing)")


@register
class HotSyncRule(Rule):
    id = "hot-sync"
    doc = ("no jax.block_until_ready/jax.device_get in per-tick serving "
           "or per-step training hot paths (serializes dispatch)")


@register
class ObsInTraceRule(Rule):
    id = "obs-in-trace"
    doc = ("no obs.metrics / obs.trace call reachable inside a jitted "
           "body — instrumentation is host-side bookkeeping between "
           "dispatches; inside a trace it records trace-time garbage "
           "(or leaks a tracer into the span/metric)")


# --------------------------------------------------------------------------
# pass 2: donation safety (analysis.donation)
# --------------------------------------------------------------------------


@register
class PostDonationReadRule(Rule):
    id = "post-donation-read"
    doc = ("an argument donated to a jit call (donate_argnums) must not "
           "be read afterwards in the enclosing scope unless the call "
           "statement rebinds it")


# --------------------------------------------------------------------------
# pass 3: Pallas kernel contracts (analysis.kernel_contracts)
# --------------------------------------------------------------------------


@register
class KernelOOBIndexRule(Rule):
    id = "kernel-oob-index"
    doc = ("every BlockSpec index map must stay within the operand's "
           "bounds at every grid point (block tables included: -1 holes "
           "redirect to the null page, never out of the pool)")


@register
class KernelScratchTileRule(Rule):
    id = "kernel-scratch-tile"
    doc = ("kernel scratch shapes must be (8, 128)-tile-aligned exactly "
           "when KernelPlan.padded promises tile alignment (and always "
           "in compiled mode)")


@register
class KernelBlockShapeRule(Rule):
    id = "kernel-block-shape"
    doc = ("in a compiled launch, the last two dims of every BlockSpec "
           "block must be divisible by (8, 128) or equal to the "
           "operand's own (the rule Mosaic enforces before lowering)")


@register
class KernelPlanMatrixRule(Rule):
    id = "kernel-plan-matrix"
    doc = ("plan_exec must resolve every (interpret, pad) combination to "
           "the documented mode, and the kernel must abstract-eval "
           "cleanly under each")


@register
class KernelParityCoverageRule(Rule):
    id = "kernel-parity-coverage"
    doc = ("each masking-contract feature (null page, pos=-1 holes, "
           "cache_limit, SWA window, MLA) must be exercised by >= 1 "
           "parity test per kernel in tests/test_paged_attn.py")


# --------------------------------------------------------------------------
# pragmas
# --------------------------------------------------------------------------

_PRAGMA_RE = re.compile(r"#\s*dirlint:\s*ok\(([^)]*)\)")


def scan_pragmas(source: str) -> dict[int, set[str]]:
    """Line number (1-based) -> set of rule ids suppressed there."""
    out: dict[int, set[str]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(text)
        if m:
            ids = {s.strip() for s in m.group(1).split(",") if s.strip()}
            out[i] = ids
    return out


def apply_pragmas(findings: list[Finding],
                  pragmas: dict[str, dict[int, set[str]]]) -> list[Finding]:
    """Mark findings suppressed when a matching pragma sits on the
    flagged line or the line directly above it."""
    out = []
    for f in findings:
        per_file = pragmas.get(f.path, {})
        ids = per_file.get(f.line, set()) | per_file.get(f.line - 1, set())
        if f.rule in ids:
            f = dataclasses.replace(f, suppressed=True)
        out.append(f)
    return out

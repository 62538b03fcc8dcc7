"""Span-phase rule (migrated from ``tools/check_span_phases.py``).

The span ring's phase vocabulary (``runtime/telemetry.PHASES``) is an
operator contract: every SpanTracer call site emits a CONSTANT phase
from the vocabulary, every member is emitted somewhere, and both the
telemetry docstring and TELEMETRY.md document it. The router tier's span
ring (``serve/router.py RouterSpanRing.emit_span``) carries the same
contract against ``telemetry.ROUTER_PHASES``.
"""

from __future__ import annotations

import ast
import sys

from .core import REPO, Finding, Project, rule

PKG = "dllama_tpu"


def _load_phases():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import PHASES, ROUTER_PHASES
    finally:
        sys.path.pop(0)
    return PHASES, ROUTER_PHASES


def _is_tracer_emit(node: ast.Call) -> bool:
    f = node.func
    if not (isinstance(f, ast.Attribute) and f.attr == "emit"
            and isinstance(f.value, ast.Call)):
        return False
    inner = f.value.func
    return (isinstance(inner, ast.Name) and inner.id == "tracer") or \
        (isinstance(inner, ast.Attribute) and inner.attr == "tracer")


def _is_router_emit(node: ast.Call) -> bool:
    """``<anything>.emit_span(...)`` — the RouterSpanRing method name is
    unique in the tree, so matching the attribute is enough."""
    return isinstance(node.func, ast.Attribute) \
        and node.func.attr == "emit_span"


def check(project: Project, phases=None) -> tuple[list[Finding], str]:
    phases, router_phases = (phases if phases is not None
                             else _load_phases())
    findings: list[Finding] = []
    sites: dict[str, list[tuple[str, int]]] = {}
    r_sites: dict[str, list[tuple[str, int]]] = {}

    for sf in project.walk(PKG):
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_tracer_emit(node):
                into, what = sites, "tracer().emit"
            elif _is_router_emit(node):
                into, what = r_sites, "emit_span"
            else:
                continue
            if len(node.args) < 2 or not (
                    isinstance(node.args[1], ast.Constant)
                    and isinstance(node.args[1].value, str)):
                findings.append(Finding(
                    "span-phases", sf.rel, node.lineno,
                    f"{what} phase argument is not a string "
                    f"constant — the closed-world vocabulary cannot be "
                    f"checked"))
                continue
            into.setdefault(node.args[1].value, []).append(
                (sf.rel, node.lineno))

    T = f"{PKG}/runtime/telemetry.py"
    for vocab_name, vocab, found in (
            ("telemetry.PHASES", phases, sites),
            ("telemetry.ROUTER_PHASES", router_phases, r_sites)):
        for phase, where in sorted(found.items()):
            if phase not in vocab:
                findings.append(Finding(
                    "span-phases", where[0][0], where[0][1],
                    f"emits span phase {phase!r} which is not in "
                    f"{vocab_name} (typo, or add it to the documented "
                    f"vocabulary)"))
        for phase in vocab:
            if phase not in found:
                findings.append(Finding(
                    "span-phases", T, 0,
                    f"{vocab_name} documents {phase!r} but no call "
                    f"site emits it (dead vocabulary)"))

    tsf = project.file(T)
    telemetry_src = tsf.text if tsf is not None else ""
    psf = project.file("dllama_tpu/runtime/TELEMETRY.md")
    perf = psf.text if psf is not None else ""
    for phase in (*phases, *router_phases):
        if f"``{phase}``" not in telemetry_src:
            findings.append(Finding(
                "span-phases", T, 0,
                f"phase {phase!r} is not described in the telemetry.py "
                f"vocabulary docstring"))
        if phase not in perf:
            findings.append(Finding(
                "span-phases", "dllama_tpu/runtime/TELEMETRY.md", 0,
                f"phase {phase!r} is not documented in TELEMETRY.md"))

    n_sites = sum(len(w) for w in sites.values()) \
        + sum(len(w) for w in r_sites.values())
    return findings, (f"{len(phases)} span + {len(router_phases)} router "
                      f"phases: {n_sites} call sites, vocabulary + "
                      f"telemetry docstring + TELEMETRY.md all consistent")


rule("span-phases",
     "every SpanTracer phase literal is in telemetry.PHASES; the "
     "vocabulary is emitted and documented")(check)

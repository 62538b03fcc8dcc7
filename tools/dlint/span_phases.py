"""Span-phase rule.

The span ring's phase vocabulary (``runtime/telemetry.PHASES``) is an
operator contract: every SpanTracer call site emits a CONSTANT phase
from the vocabulary, every member is emitted somewhere, and both the
telemetry docstring and TELEMETRY.md document it. The router tier's span
ring (``serve/router.py RouterSpanRing.emit_span``) carries the same
contract against ``telemetry.ROUTER_PHASES``, and the scheduler's tick
phases (``FlightRecorder.tick_phase`` / ``next_phase`` in
``runtime/flightrec.py``) against ``telemetry.TICK_PHASES``: what a
profile, a flight dump and ``dllama_tick_phase_ms_total`` call one part
of a tick is one closed set of names. What lies outside every phase
(``telemetry.LOOP_GAPS``: between two ticks, between two phases) and why
an interval stalled (``telemetry.STALL_CAUSES``) are held the same way:
a gap's name is emitted where another module reads its constant off
``telemetry``, a cause where ``stall_cause`` returns its literal.
"""

from __future__ import annotations

import ast
import sys

from .core import REPO, Finding, Project, rule

PKG = "dllama_tpu"


def _load_phases():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import (LOOP_GAPS, PHASES,
                                                  ROUTER_PHASES,
                                                  STALL_CAUSES, TICK_PHASES)
    finally:
        sys.path.pop(0)
    return PHASES, ROUTER_PHASES, TICK_PHASES, LOOP_GAPS, STALL_CAUSES


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


def _is_tick_phase(node: ast.Call) -> bool:
    """``<anything>.tick_phase(...)`` / ``<phase>.next_phase(...)`` — both
    method names are unique to the flight recorder's tick phases."""
    return isinstance(node.func, ast.Attribute) \
        and node.func.attr in ("tick_phase", "next_phase")


def _gap_constants(tsf, gaps) -> dict[str, str]:
    """``{constant's name: gap name}`` for telemetry.py's module-level
    ``NAME = "<gap>"`` assignments."""
    out: dict[str, str] = {}
    if tsf is None or tsf.tree is None:
        return out
    for node in tsf.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and node.value.value in gaps:
            out[node.targets[0].id] = node.value.value
    return out


def _returned_literals(fn: ast.FunctionDef):
    """``(literal or None, lineno)`` for every value a ``return`` of ``fn``
    can hand back (both arms of a conditional expression)."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Return) or node.value is None:
            continue
        arms = [node.value]
        while arms:
            v = arms.pop()
            if isinstance(v, ast.IfExp):
                arms += [v.body, v.orelse]
            elif isinstance(v, ast.Constant) and isinstance(v.value, str):
                yield v.value, node.lineno
            else:
                yield None, node.lineno


def check(project: Project, phases=None) -> tuple[list[Finding], str]:
    """``phases``: ``(span, router[, tick[, gaps, causes]])`` vocabularies
    (fixtures); the live ones are read from telemetry."""
    phases, router_phases, *rest = (phases if phases is not None
                                    else _load_phases())
    tick_phases = rest[0] if rest else ()
    gaps, causes = (rest[1], rest[2]) if len(rest) >= 3 else ((), ())
    findings: list[Finding] = []
    sites: dict[str, list[tuple[str, int]]] = {}
    r_sites: dict[str, list[tuple[str, int]]] = {}
    t_sites: dict[str, list[tuple[str, int]]] = {}
    g_sites: dict[str, list[tuple[str, int]]] = {}
    c_sites: dict[str, list[tuple[str, int]]] = {}
    T = f"{PKG}/runtime/telemetry.py"
    gap_consts = _gap_constants(project.file(T), gaps)

    for sf in project.walk(PKG):
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Attribute) and node.attr in gap_consts \
                    and sf.rel != T:
                g_sites.setdefault(gap_consts[node.attr], []).append(
                    (sf.rel, node.lineno))
            if isinstance(node, ast.FunctionDef) and causes \
                    and node.name == "stall_cause":
                for lit, lineno in _returned_literals(node):
                    if lit is None:
                        findings.append(Finding(
                            "span-phases", sf.rel, lineno,
                            "stall_cause returns something that is not a "
                            "string constant — the closed-world "
                            "vocabulary cannot be checked"))
                    else:
                        c_sites.setdefault(lit, []).append((sf.rel, lineno))
            if not isinstance(node, ast.Call):
                continue
            if _is_tracer_emit(node):
                into, what = sites, "tracer().emit"
            elif _is_router_emit(node):
                into, what = r_sites, "emit_span"
            elif _is_tick_phase(node):
                into, what = t_sites, node.func.attr
            else:
                continue
            at = 0 if into is t_sites else 1    # the phase's position
            if len(node.args) <= at or not (
                    isinstance(node.args[at], ast.Constant)
                    and isinstance(node.args[at].value, str)):
                findings.append(Finding(
                    "span-phases", sf.rel, node.lineno,
                    f"{what} phase argument is not a string "
                    f"constant — the closed-world vocabulary cannot be "
                    f"checked"))
                continue
            into.setdefault(node.args[at].value, []).append(
                (sf.rel, node.lineno))

    for vocab_name, vocab, found in (
            ("telemetry.PHASES", phases, sites),
            ("telemetry.ROUTER_PHASES", router_phases, r_sites),
            ("telemetry.TICK_PHASES", tick_phases, t_sites),
            ("telemetry.LOOP_GAPS", gaps, g_sites),
            ("telemetry.STALL_CAUSES", causes, c_sites)):
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
    for phase in (*phases, *router_phases, *tick_phases, *gaps, *causes):
        if f"``{phase}``" not in telemetry_src:
            findings.append(Finding(
                "span-phases", T, 0,
                f"phase {phase!r} is not described in the telemetry.py "
                f"vocabulary docstring"))
        if phase not in perf:
            findings.append(Finding(
                "span-phases", "dllama_tpu/runtime/TELEMETRY.md", 0,
                f"phase {phase!r} is not documented in TELEMETRY.md"))

    n_sites = sum(len(w) for found in (sites, r_sites, t_sites, g_sites,
                                       c_sites)
                  for w in found.values())
    return findings, (f"{len(phases)} span + {len(router_phases)} router "
                      f"+ {len(tick_phases)} tick "
                      f"phases + {len(gaps)} loop gaps + {len(causes)} "
                      f"stall causes: {n_sites} call sites, vocabulary + "
                      f"telemetry docstring + TELEMETRY.md all consistent")


rule("span-phases",
     "every SpanTracer phase literal is in telemetry.PHASES (router: "
     "ROUTER_PHASES; scheduler tick: TICK_PHASES, LOOP_GAPS, "
     "STALL_CAUSES); the vocabulary is emitted and documented")(check)

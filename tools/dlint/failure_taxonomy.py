"""Failure-taxonomy rule.

Three closed vocabularies name how requests end when something goes
wrong, one per tier: ``finish_reason`` on every terminal SSE chunk
(``serve/api.py FINISH_REASONS``), the mid-stream failover outcome on
``dllama_router_stream_resumes_total`` (``serve/router.py
RESUME_OUTCOMES``), and the KV-migration fallback reason on
``dllama_kvwire_fallback_total`` (``runtime/kvwire.py
FALLBACK_REASONS``). Each is the same three-way contract slo-names
enforces for objectives: the DECLARED tuple, the CALL SITES that emit
members, and the OPERATOR DOCS (telemetry label help + TELEMETRY.md's
"Failure taxonomy" section) must agree in both directions — a literal
outside its tuple is a typo that silently forks the vocabulary, a
declared member nothing emits is dead taxonomy, and an undocumented
member is an alert nobody can interpret.

The vocabularies are AST-extracted, never imported: ``serve/api.py``
pulls the engine (jax) at import time, and dlint must run on bare CI
runners before the native build. Only ``runtime/telemetry`` (jax-free
by design) is imported, for the metric help strings.
"""

from __future__ import annotations

import ast
import sys

from .core import REPO, Finding, Project, rule

# (tuple name, declaring file) — the three declarations
VOCABS = (
    ("FINISH_REASONS", "dllama_tpu/serve/api.py"),
    ("RESUME_OUTCOMES", "dllama_tpu/serve/router.py"),
    ("FALLBACK_REASONS", "dllama_tpu/runtime/kvwire.py"),
)
PERF = "dllama_tpu/runtime/TELEMETRY.md"
PERF_SECTION = "Failure taxonomy"


def _tuple_const(sf, name: str) -> tuple | None:
    """The module-level ``NAME = ("a", "b", ...)`` assignment's value,
    extracted from the AST (no import)."""
    if sf is None or sf.tree is None:
        return None
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id == name):
            continue
        if isinstance(node.value, ast.Tuple) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, str)
                for e in node.value.elts):
            return tuple(e.value for e in node.value.elts)
    return None


def _str_const(node) -> str | None:
    return (node.value if isinstance(node, ast.Constant)
            and isinstance(node.value, str) else None)


def _finish_reason_sites(sf) -> list[tuple[int, str]]:
    """Every ``finish_reason`` literal the api server can emit:
    ``finish_reason = "x"`` assignments, ``finish_reason ==/in ...``
    comparisons, and ``stream_abort("x")`` terminal events."""
    out: list[tuple[int, str]] = []
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "finish_reason":
            v = _str_const(node.value)
            if v is not None:
                out.append((node.lineno, v))
        elif isinstance(node, ast.Compare) \
                and isinstance(node.left, ast.Name) \
                and node.left.id == "finish_reason":
            for cmp in node.comparators:
                elts = cmp.elts if isinstance(cmp, ast.Tuple) else [cmp]
                for e in elts:
                    v = _str_const(e)
                    if v is not None:
                        out.append((e.lineno, v))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Name) \
                and node.func.id == "stream_abort" and node.args:
            v = _str_const(node.args[0])
            if v is not None:
                out.append((node.lineno, v))
    return out


def _resume_outcome_sites(sf) -> list[tuple[int, str]]:
    """Every resume-outcome literal the router can count: ``outcome =
    "x"`` assignments (the terminal-abort classification) and literal
    ``c_resumes.inc(outcome="x")`` keywords."""
    out: list[tuple[int, str]] = []
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "outcome":
            v = _str_const(node.value)
            if v is not None:
                out.append((node.lineno, v))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "inc" \
                and isinstance(node.func.value, ast.Attribute) \
                and node.func.value.attr == "c_resumes":
            for kw in node.keywords:
                if kw.arg == "outcome":
                    v = _str_const(kw.value)
                    if v is not None:
                        out.append((node.lineno, v))
    return out


def _fallback_reason_sites(sf_kvwire, sf_serving) -> list[tuple[str, int, str]]:
    """Every fallback-reason literal: ``classify_failure``'s returns
    (kvwire.py) plus ``reason = "x"`` assignments inside the scheduler's
    ``_service_migrations`` (the import-side ``exhaustion`` case)."""
    out: list[tuple[str, int, str]] = []
    for node in ast.walk(sf_kvwire.tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "classify_failure":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Return):
                    v = _str_const(sub.value)
                    if v is not None:
                        out.append((sf_kvwire.rel, sub.lineno, v))
    for node in ast.walk(sf_serving.tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "_service_migrations":
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) \
                        and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name) \
                        and sub.targets[0].id == "reason":
                    v = _str_const(sub.value)
                    if v is not None:
                        out.append((sf_serving.rel, sub.lineno, v))
    return out


def _metric_help(metric: str) -> str:
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import SPECS
    finally:
        sys.path.pop(0)
    spec = SPECS.get(metric)
    return spec.help if spec is not None else ""


def check(project: Project) -> tuple[list[Finding], str]:
    findings: list[Finding] = []

    def f(path, msg, lineno=0):
        findings.append(Finding("failure-taxonomy", path, lineno, msg))

    vocabs: dict[str, tuple] = {}
    for name, rel in VOCABS:
        sf = project.file(rel)
        vals = _tuple_const(sf, name)
        if vals is None:
            f(rel, f"expected a module-level {name} = (...) tuple of "
                   f"string literals (the declared failure vocabulary)")
            vals = ()
        elif len(set(vals)) != len(vals):
            f(rel, f"{name} has duplicate members: {vals}")
        vocabs[name] = vals

    # forward, docs: every member spelled in TELEMETRY.md's taxonomy section
    perf = project.file(PERF)
    perf_text = perf.text if perf is not None else ""
    if PERF_SECTION not in perf_text:
        f(PERF, f"TELEMETRY.md needs a {PERF_SECTION!r} section documenting "
                f"the three failure vocabularies")
    for name, rel in VOCABS:
        for member in vocabs[name]:
            if f'"{member}"' not in perf_text \
                    and f"`{member}`" not in perf_text:
                f(PERF, f"{name} member {member!r} ({rel}) is not "
                        f"documented in TELEMETRY.md")

    # forward, telemetry: the label-bearing metrics' help strings must
    # name every member (the operator reads the /metrics exposition)
    for name, metric in (("RESUME_OUTCOMES",
                          "dllama_router_stream_resumes_total"),
                         ("FALLBACK_REASONS",
                          "dllama_kvwire_fallback_total")):
        help_text = _metric_help(metric)
        if not help_text:
            f("dllama_tpu/runtime/telemetry.py",
              f"{metric} is not registered in telemetry.SPECS")
            continue
        for member in vocabs[name]:
            if member not in help_text:
                f("dllama_tpu/runtime/telemetry.py",
                  f"{metric} help does not document the {name} "
                  f"member {member!r}")

    # reverse: every emitted literal is declared, every declared member
    # is emitted somewhere (closed world in both directions)
    api = project.file("dllama_tpu/serve/api.py")
    router = project.file("dllama_tpu/serve/router.py")
    kvwire = project.file("dllama_tpu/runtime/kvwire.py")
    serving = project.file("dllama_tpu/runtime/serving.py")
    sites = {
        "FINISH_REASONS": [(api.rel, ln, v)
                           for ln, v in _finish_reason_sites(api)],
        "RESUME_OUTCOMES": [(router.rel, ln, v)
                            for ln, v in _resume_outcome_sites(router)],
        "FALLBACK_REASONS": _fallback_reason_sites(kvwire, serving),
    }
    for name, _ in VOCABS:
        emitted = set()
        for rel, lineno, val in sites[name]:
            emitted.add(val)
            if vocabs[name] and val not in vocabs[name]:
                f(rel, f"literal {val!r} is outside the declared "
                       f"{name} vocabulary {vocabs[name]} (typo, or "
                       f"extend the tuple)", lineno)
        for member in vocabs[name]:
            if member not in emitted:
                f(dict(VOCABS)[name],
                  f"{name} member {member!r} is declared but no call "
                  f"site emits it (dead taxonomy)")

    n = sum(len(v) for v in vocabs.values())
    n_sites = sum(len(s) for s in sites.values())
    return findings, (f"3 failure vocabularies ({n} members, {n_sites} "
                      f"emit sites): declarations, call sites, "
                      f"telemetry label docs, and TELEMETRY.md all agree")


rule("failure-taxonomy",
     "finish_reason / resume-outcome / kvwire-fallback vocabularies are "
     "closed-world: declared tuples, emitting call sites, telemetry "
     "label docs, and TELEMETRY.md's Failure taxonomy section agree in both "
     "directions")(check)

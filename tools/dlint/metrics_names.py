"""Metric-name rule.

Closed-world in BOTH directions against the single declaration point
(``dllama_tpu.runtime.telemetry.SPECS``): naming convention, TELEMETRY.md
documentation, no orphaned source literals, no stale doc mentions.
Importing only the telemetry module keeps this runnable without jax.
"""

from __future__ import annotations

import re
import sys

from .core import REPO, Finding, Project, rule

NAME_RE = re.compile(r"^dllama_[a-z0-9_]+$")
LITERAL_RE = re.compile(r"""["'](dllama_[a-z0-9_]+)["']""")
TOKEN_RE = re.compile(r"\b(dllama_[a-z0-9_]+)")
NOT_METRICS = {"dllama_tpu"}
NOT_METRIC_PREFIXES = ("dllama_model_",)


def _not_a_metric(lit: str) -> bool:
    return lit in NOT_METRICS or lit.startswith(NOT_METRIC_PREFIXES)


def _load_specs():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import SPECS
    finally:
        sys.path.pop(0)
    return SPECS


def check(project: Project, specs=None) -> tuple[list[Finding], str]:
    """``specs`` injectable for fixture self-tests; defaults to the
    repo's live telemetry registry."""
    specs = specs if specs is not None else _load_specs()
    findings: list[Finding] = []
    T = "dllama_tpu/runtime/telemetry.py"

    def f(path, msg, lineno=0):
        findings.append(Finding("metrics-names", path, lineno, msg))

    for name, spec in specs.items():
        if not NAME_RE.match(name):
            f(T, f"registered metric {name!r} violates "
                 f"dllama_[a-z0-9_]+ naming")
        if spec.kind not in ("counter", "gauge", "histogram"):
            f(T, f"{name}: unknown kind {spec.kind!r}")
        if spec.kind == "counter" and not name.endswith("_total"):
            f(T, f"counter {name} must end in _total "
                 f"(Prometheus convention)")
        if not spec.help:
            f(T, f"{name}: empty help text")

    perf_sf = project.file("dllama_tpu/runtime/TELEMETRY.md")
    perf = perf_sf.text if perf_sf is not None else ""
    for name in specs:
        if name not in perf:
            f("dllama_tpu/runtime/TELEMETRY.md", f"metric {name} is not documented in TELEMETRY.md")

    derived = {base + suffix for base, spec in specs.items()
               if spec.kind == "histogram"
               for suffix in ("_bucket", "_sum", "_count")}
    for name in sorted(set(LITERAL_RE.findall(perf))
                       | set(TOKEN_RE.findall(perf))):
        if _not_a_metric(name) or name in specs or name in derived:
            continue
        f("dllama_tpu/runtime/TELEMETRY.md", f"TELEMETRY.md mentions {name!r} but no such metric "
                     f"family is registered in telemetry.SPECS "
                     f"(stale doc or typo)")

    for sf in project.walk("dllama_tpu"):
        for lineno, line in enumerate(sf.lines, 1):
            for lit in LITERAL_RE.findall(line):
                if _not_a_metric(lit) or lit in specs:
                    continue
                f(sf.rel, f"literal {lit!r} looks like a metric name "
                          f"but is not registered in telemetry.SPECS",
                  lineno)

    return findings, (f"{len(specs)} metric names: convention + TELEMETRY.md "
                      f"docs + source literals all consistent")


rule("metrics-names",
     "every telemetry metric name is convention-clean, documented in "
     "TELEMETRY.md, and closed-world vs source literals")(check)

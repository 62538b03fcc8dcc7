"""SLO objective-name rule.

The SLO observatory's objective vocabulary
(``dllama_tpu.runtime.slo.OBJECTIVES``) names the same thing in four
places: the ``--slo`` cli grammar, the ``/debug/slo`` body, the
``dllama_slo_*`` gauge labels, and the TELEMETRY.md / README.md docs. This rule keeps the vocabulary closed in
BOTH directions: every declared objective follows the grammar and is
documented everywhere, and every objective-shaped token anywhere in the
tree names a declared objective — a typo'd SLO name must fail lint, not
silently never alarm. Importing only the slo module keeps this runnable
without jax.
"""

from __future__ import annotations

import re
import sys

from .core import REPO, Finding, Project, rule

# the grammar each OBJECTIVES member must satisfy
GRAMMAR_RE = re.compile(r"^(?:(?:ttft|itl)_p\d{2}_ms|shed_rate)$")
# objective-shaped tokens in docs/source: the lookaround keeps composed
# identifiers (resume_ttft_p95_ms, ttft_ms_p95) from false-positiving
TOKEN_RE = re.compile(r"(?<![a-z0-9_])((?:ttft|itl)_p\d{2}_ms)(?!_)")

# where every objective must be spelled (the operator-facing contract)
DOC_FILES = ("dllama_tpu/runtime/TELEMETRY.md", "README.md",
             "dllama_tpu/serve/cli.py")
# where objective-shaped tokens are hunted for the reverse direction
SCAN_DIRS = ("dllama_tpu",)
SCAN_FILES = ("dllama_tpu/runtime/TELEMETRY.md", "README.md")


def _load_objectives():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.slo import OBJECTIVES
    finally:
        sys.path.pop(0)
    return OBJECTIVES


def check(project: Project, objectives=None) -> tuple[list[Finding], str]:
    """``objectives`` injectable for fixture self-tests; defaults to the
    repo's live vocabulary."""
    objectives = (objectives if objectives is not None
                  else _load_objectives())
    findings: list[Finding] = []
    S = "dllama_tpu/runtime/slo.py"

    def f(path, msg, lineno=0):
        findings.append(Finding("slo-names", path, lineno, msg))

    for name in objectives:
        if not GRAMMAR_RE.match(name):
            f(S, f"objective {name!r} violates the SLO grammar "
                 f"((ttft|itl)_pNN_ms or shed_rate)")

    # forward: every objective spelled in each operator-facing file
    for rel in DOC_FILES:
        sf = project.file(rel)
        text = sf.text if sf is not None else ""
        for name in objectives:
            if name not in text:
                f(rel, f"SLO objective {name!r} is not mentioned in "
                       f"{rel} (grammar/docs drift)")

    # reverse: every objective-shaped token names a declared objective
    sources = [sf for sf in project.walk(*SCAN_DIRS)]
    for rel in SCAN_FILES:
        sf = project.file(rel)
        if sf is not None:
            sources.append(sf)
    for sf in sources:
        for lineno, line in enumerate(sf.lines, 1):
            for tok in TOKEN_RE.findall(line):
                if tok not in objectives:
                    f(sf.rel, f"token {tok!r} looks like an SLO "
                              f"objective but is not in slo.OBJECTIVES "
                              f"(typo, or extend the vocabulary)",
                      lineno)

    # the gauges the observatory publishes must be registered metrics
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import SPECS
    finally:
        sys.path.pop(0)
    for metric in ("dllama_slo_compliance", "dllama_slo_burn_rate"):
        if metric not in SPECS:
            f("dllama_tpu/runtime/telemetry.py",
              f"SLO gauge {metric!r} is not registered in "
              f"telemetry.SPECS")

    return findings, (f"{len(objectives)} SLO objectives: grammar + "
                      f"docs + source tokens + gauges all consistent")


rule("slo-names",
     "every SLO objective name is grammar-clean, documented in the cli "
     "grammar / TELEMETRY.md / README.md, and closed-world vs "
     "objective-shaped tokens")(check)

"""Tenant decision-reason rule.

The tenant observatory's admission decisions (defer / shed / requeue /
preempt flight notes in ``runtime/serving.py``, ``note_shed`` calls in
``runtime/serving.py`` and ``serve/router.py``) are only queryable if
every decision names a reason from ONE closed vocabulary
(``dllama_tpu.runtime.tenancy.ADMIT_REASONS``). This rule keeps that
vocabulary closed in BOTH directions — every emit site names a declared
reason, every declared reason has a live emit site and a doc line — and
holds the ``dllama_tenant_*`` metric family closed-world between
``telemetry.SPECS`` and TELEMETRY.md (the tenant-scoped twin of the
metrics-names rule, so a renamed tenant metric cannot strand its docs).
A misspelled reason must fail lint, not silently never match a
postmortem query. Importing only tenancy/telemetry keeps this runnable
without jax.
"""

from __future__ import annotations

import re
import sys

from .core import REPO, Finding, Project, rule

# the grammar each ADMIT_REASONS member must satisfy
GRAMMAR_RE = re.compile(r"^[a-z][a-z0-9_]{0,31}$")

TENANCY = "dllama_tpu/runtime/tenancy.py"
T = "dllama_tpu/runtime/telemetry.py"
# the files allowed (and required) to emit admission decisions
EMIT_FILES = ("dllama_tpu/runtime/serving.py",
              "dllama_tpu/serve/router.py")
DOC_FILES = ("dllama_tpu/runtime/TELEMETRY.md",)

# an admission-decision flight note: the event name is one of the four
# decision verbs and a reason= kwarg follows inside the same call (the
# gap excludes ')' so the match cannot leak into a neighboring call).
# timeout/cancel notes carry their own lifecycle reasons (queued /
# admitting / in_flight) and are deliberately out of scope.
NOTE_RE = re.compile(
    r'\.note\(\s*"(?:defer|shed|requeue|preempt)"[^)]{0,200}?'
    r'reason="([a-z_]+)"', re.DOTALL)
# a per-tenant shed attribution (TenantRegistry.note_shed): the second
# positional argument is the reason literal
SHED_RE = re.compile(r'\.note_shed\(\s*[^,()]+,\s*"([a-z_]+)"')

TENANT_METRIC_RE = re.compile(r"\b(dllama_tenant_[a-z0-9_]+)")


def _load_vocab():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import SPECS
        from dllama_tpu.runtime.tenancy import ADMIT_REASONS
    finally:
        sys.path.pop(0)
    return ADMIT_REASONS, SPECS


def check(project: Project, vocab=None) -> tuple[list[Finding], str]:
    """``vocab`` — an ``(ADMIT_REASONS, SPECS)`` pair — is injectable
    for fixture self-tests; defaults to the repo's live vocabulary."""
    reasons, specs = vocab if vocab is not None else _load_vocab()
    findings: list[Finding] = []

    def f(path, msg, lineno=0):
        findings.append(Finding("tenant-reasons", path, lineno, msg))

    for name in reasons:
        if not GRAMMAR_RE.match(name):
            f(TENANCY, f"admission reason {name!r} violates the grammar "
                       f"([a-z][a-z0-9_]*)")

    # every reason carries its own doc line in the ADMIT_REASONS comment
    # block (the ``* ``reason`` — ...`` convention): a reason with no
    # prose is a label nobody can interpret in a postmortem
    sf = project.file(TENANCY)
    tenancy_text = sf.text if sf is not None else ""
    for name in reasons:
        if f"``{name}``" not in tenancy_text:
            f(TENANCY, f"admission reason {name!r} has no doc line in "
                       f"the ADMIT_REASONS comment block")

    # emit sites: both directions against the declared vocabulary
    emitted: dict[str, int] = {}
    for rel in EMIT_FILES:
        sf = project.file(rel)
        text = sf.text if sf is not None else ""
        for m in list(NOTE_RE.finditer(text)) + list(SHED_RE.finditer(text)):
            reason = m.group(1)
            lineno = text.count("\n", 0, m.start()) + 1
            emitted[reason] = emitted.get(reason, 0) + 1
            if reason not in reasons:
                f(rel, f"admission decision names reason {reason!r}, "
                       f"which is not in tenancy.ADMIT_REASONS",
                  lineno)
    for name in reasons:
        if name not in emitted:
            f(TENANCY, f"admission reason {name!r} has no emit site in "
                       f"{' or '.join(EMIT_FILES)} (dead vocabulary "
                       f"entry — remove it or wire the decision)")

    # the dllama_tenant_* metric family: registered names documented,
    # documented names registered, and reasons spelled out in TELEMETRY.md
    tenant_metrics = sorted(n for n in specs
                            if n.startswith("dllama_tenant_"))
    if not tenant_metrics:
        f(T, "no dllama_tenant_* metrics registered in telemetry.SPECS "
             "(the tenant observatory family is missing)")
    for rel in DOC_FILES:
        sf = project.file(rel)
        text = sf.text if sf is not None else ""
        for name in tenant_metrics:
            if name not in text:
                f(rel, f"tenant metric {name} is not documented in {rel}")
        for name in sorted(set(TENANT_METRIC_RE.findall(text))):
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            if name not in specs and base not in specs:
                f(rel, f"{rel} mentions {name!r} but no such metric is "
                       f"registered in telemetry.SPECS (stale doc or "
                       f"typo)")
        for name in reasons:
            if name not in text:
                f(rel, f"admission reason {name!r} is not documented "
                       f"in {rel} (the shed/defer taxonomy must be "
                       f"operator-visible)")

    return findings, (f"{len(reasons)} admission reasons across "
                      f"{sum(emitted.values())} emit sites + "
                      f"{len(tenant_metrics)} dllama_tenant_* metrics: "
                      f"vocabulary, emit sites, and docs all consistent")


rule("tenant-reasons",
     "every tenant admission decision (defer/shed/requeue/preempt) "
     "names a reason from tenancy.ADMIT_REASONS, every reason has a "
     "live emit site and docs, and the dllama_tenant_* family is "
     "closed-world vs telemetry.SPECS and TELEMETRY.md")(check)

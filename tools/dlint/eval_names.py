"""Eval config-name rule.

The quality observatory's config vocabulary
(``dllama_tpu.runtime.telemetry.EVAL_CONFIGS``) names the same thing in
four places: the eval CLI's ``--compare`` grammar, the ``config`` label
on the ``dllama_eval_*`` metric family, the parity map inside the
committed ``QUALITY_BASELINE.json``, and the README docs. This rule keeps the vocabulary
closed in BOTH directions: every declared config is grammar-clean,
derived (not hand-copied) into the CLI grammar, recorded in the
committed baseline, and documented — and every config-shaped consumer
(the parity pairs, the baseline's keys) names a declared config. A
typo'd config name must fail lint, not silently never gate. Importing
only the telemetry module keeps this runnable without jax.
"""

from __future__ import annotations

import json
import re
import sys

from .core import REPO, Finding, Project, rule

# the grammar each EVAL_CONFIGS member must satisfy
GRAMMAR_RE = re.compile(r"^[a-z][a-z0-9_]{0,31}$")

T = "dllama_tpu/runtime/telemetry.py"
BASELINE = "QUALITY_BASELINE.json"
# files that must DERIVE the vocabulary from telemetry.EVAL_CONFIGS
# instead of hand-spelling it (a hand-copied list is how grammars drift)
DERIVING_FILES = ("dllama_tpu/serve/cli.py",
                  "dllama_tpu/runtime/evalharness.py",
                  "tools/quality_baseline.py")
# operator-facing docs where every config must be spelled out
DOC_FILES = ("README.md",)


def _load_vocab():
    sys.path.insert(0, str(REPO))
    try:
        from dllama_tpu.runtime.telemetry import (EVAL_CONFIGS, EVAL_PARITY,
                                                  SPECS)
    finally:
        sys.path.pop(0)
    return EVAL_CONFIGS, EVAL_PARITY, SPECS


def check(project: Project, vocab=None) -> tuple[list[Finding], str]:
    """``vocab`` — an ``(EVAL_CONFIGS, EVAL_PARITY, SPECS)`` triple —
    is injectable for fixture self-tests; defaults to the repo's live
    vocabulary."""
    configs, parity, specs = vocab if vocab is not None else _load_vocab()
    findings: list[Finding] = []

    def f(path, msg, lineno=0):
        findings.append(Finding("eval-names", path, lineno, msg))

    for name in configs:
        if not GRAMMAR_RE.match(name):
            f(T, f"eval config {name!r} violates the grammar "
                 f"([a-z][a-z0-9_]*)")

    # the parity contract only ranges over declared configs, and a pair
    # must relate two DIFFERENT configs (a reflexive pair gates nothing)
    for a, b in parity:
        for side in (a, b):
            if side not in configs:
                f(T, f"EVAL_PARITY references {side!r}, which is not in "
                     f"EVAL_CONFIGS")
        if a == b:
            f(T, f"EVAL_PARITY pair ({a!r}, {b!r}) is reflexive")

    # the dllama_eval_* family the configs label must be registered
    for metric in ("dllama_eval_tokens_total", "dllama_eval_nll_total",
                   "dllama_eval_perplexity"):
        if metric not in specs:
            f(T, f"eval metric {metric!r} is not registered in "
                 f"telemetry.SPECS")

    # consumers must derive the vocabulary, not hand-copy it: the token
    # EVAL_CONFIGS (or EVAL_PARITY for the gates) must appear in each
    for rel in DERIVING_FILES:
        sf = project.file(rel)
        text = sf.text if sf is not None else ""
        if "EVAL_CONFIGS" not in text and "EVAL_PARITY" not in text:
            f(rel, "does not reference telemetry.EVAL_CONFIGS/"
                   "EVAL_PARITY — the eval config grammar must be "
                   "derived from the closed vocabulary, not hand-spelled")

    # forward docs: every config spelled in the operator-facing files
    for rel in DOC_FILES:
        sf = project.file(rel)
        text = sf.text if sf is not None else ""
        for name in configs:
            if name not in text:
                f(rel, f"eval config {name!r} is not mentioned in {rel} "
                       f"(grammar/docs drift)")

    # the committed quality baseline's parity keys are the vocabulary's
    # on-disk mirror: both directions — no undeclared key, no missing
    # config (the builtin recorder scores every config)
    sf = project.file(BASELINE)
    if sf is None:
        f(BASELINE, "committed quality baseline is missing (rerun "
                    "`python tools/quality_baseline.py record`)")
    else:
        try:
            doc = json.loads(sf.text)
        except json.JSONDecodeError as e:
            doc = None
            f(BASELINE, f"not JSON: {e}")
        if isinstance(doc, dict):
            for dataset, hexes in sorted((doc.get("parity") or {}).items()):
                for key in hexes:
                    if key not in configs:
                        f(BASELINE, f"parity key {key!r} (dataset "
                                    f"{dataset!r}) is not in "
                                    f"telemetry.EVAL_CONFIGS")
                for name in configs:
                    if name not in hexes:
                        f(BASELINE, f"config {name!r} has no recorded "
                                    f"parity hex for dataset {dataset!r} "
                                    f"(re-record the baseline)")

    return findings, (f"{len(configs)} eval configs: grammar + parity "
                      f"pairs + derived grammars + docs + committed "
                      f"baseline all consistent")


rule("eval-names",
     "every eval config name is grammar-clean, derived from "
     "telemetry.EVAL_CONFIGS by its consumers (cli/--compare, harness, "
     "quality ledger), documented in README, and closed-world vs "
     "the committed QUALITY_BASELINE.json parity keys")(check)

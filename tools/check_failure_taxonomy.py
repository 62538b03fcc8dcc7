#!/usr/bin/env python
"""Failure-taxonomy lint: the finish_reason / resume-outcome /
kvwire-fallback vocabularies are closed-world — declared tuples,
emitting call sites, telemetry label docs, and TELEMETRY.md's "Failure
taxonomy" section agree in both directions.

Thin wrapper (Makefile ``lint`` compatibility): the scanner itself
lives on the shared dlint framework as the ``failure-taxonomy`` rule —
``python -m tools.dlint --only failure-taxonomy`` is the canonical
entry point; this script exists so direct CLI invocations keep working.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from tools.dlint import Project, run_rules  # noqa: E402


def main() -> int:
    return run_rules(Project(), only=["failure-taxonomy"])


if __name__ == "__main__":
    sys.exit(main())

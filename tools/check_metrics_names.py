#!/usr/bin/env python
"""Metric-name lint: telemetry.SPECS naming convention + TELEMETRY.md docs + source literals, closed-world in both directions.

Thin wrapper (Makefile ``lint`` compatibility): the scanner itself now
lives on the shared dlint framework as the ``metrics-names`` rule —
``python -m tools.dlint --only metrics-names`` is the canonical entry point;
this script exists so historical CLI invocations keep working.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from tools.dlint import Project, run_rules  # noqa: E402


def main() -> int:
    return run_rules(Project(), only=["metrics-names"])


if __name__ == "__main__":
    sys.exit(main())

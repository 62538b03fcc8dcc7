#!/usr/bin/env python
"""SLO objective-name lint: every objective in slo.OBJECTIVES is
grammar-clean, documented (cli grammar, TELEMETRY.md, README.md, bench), and
closed-world vs objective-shaped tokens anywhere in the tree.

Thin wrapper (Makefile ``lint`` compatibility): the scanner itself
lives on the shared dlint framework as the ``slo-names`` rule —
``python -m tools.dlint --only slo-names`` is the canonical entry point;
this script exists so direct CLI invocations keep working.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from tools.dlint import Project, run_rules  # noqa: E402


def main() -> int:
    return run_rules(Project(), only=["slo-names"])


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Tenant decision-reason lint: every admission decision in
runtime/serving.py and serve/router.py names a reason from
tenancy.ADMIT_REASONS, every reason has a live emit site + docs, and
the dllama_tenant_* metric family is closed-world vs telemetry.SPECS
and TELEMETRY.md.

Thin wrapper (Makefile ``lint`` compatibility): the scanner itself
lives on the shared dlint framework as the ``tenant-reasons`` rule —
``python -m tools.dlint --only tenant-reasons`` is the canonical entry
point; this script exists so direct CLI invocations keep working.
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from tools.dlint import Project, run_rules  # noqa: E402


def main() -> int:
    return run_rules(Project(), only=["tenant-reasons"])


if __name__ == "__main__":
    sys.exit(main())

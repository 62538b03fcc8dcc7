"""dlint — the repo's unified AST static-analysis framework.

One file walker, one comment/docstring-aware source model, one visitor
registry, one ``file:line`` finding reporter with ``# dlint: disable=RULE``
suppressions — and every repo invariant as a rule module on top:

* :mod:`tools.dlint.trace_safety` — closed-world jit entry, tracer-hazard
  detection inside traced function bodies, guarded-twin completeness.
* :mod:`tools.dlint.thread_ownership` — declared thread ownership
  (``# dlint: owner=...``), monitor-vs-loop call-graph checking,
  lock-discipline (``# dlint: guarded-by=...``) and lock-order cycles.
* the name and registry rules (:mod:`tools.dlint.metrics_names`,
  ``exception_hygiene``, ``route_labels``, ``failpoint_sites``,
  ``span_phases``, and ``shard-map-shim`` in ``trace_safety``).
* :mod:`tools.dlint.slo_names` — the SLO observatory's objective
  vocabulary (``runtime/slo.OBJECTIVES``) closed-world across the cli
  grammar, gauges and docs.

Run everything: ``python -m tools.dlint`` (repo-clean exit 0); one rule:
``--only RULE``; machine-readable: ``--json``. The invariant catalog
(what each rule enforces, the review finding that motivated it, how to
suppress) lives in ``LINTS.md``.
"""

from .core import (  # noqa: F401
    Finding,
    Project,
    Rule,
    all_rules,
    get_rule,
    load_rule_modules,
    rule,
    run_rules,
)

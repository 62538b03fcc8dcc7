#!/usr/bin/env python3
"""Where a judged percentile of the gaps may stand: ``python3 benchmark/ladder.py
[--workload <cell>] [--markdown <set's name>] <run.dump.json> ...`` over the
``--dump`` files of a set of runs of ONE cell (``run.py --dump`` writes every gap
as ``itl_ms`` beside the run's result line), three runs or more.

A window's gaps fall into classes of tick (a step; a step that carries a chunk;
two chunks), and a percentile repeats from run to run only while it lies inside
a class: on the cliff between two, a few gaps more or less in the upper class
move it by the whole height of the cliff (PERF.md section 2). So the rule reads
the ladder, not the one rung. It prints

- per run the rungs p50, p75 and every point from p80 to p99, and the count of gaps;
- for each candidate q, the q's of the ``end_to_end/itl_p<q>_ms.json`` files that
  exist, highest first (a cell whose class none of them meets is one data file
  away from a candidate that does): the set's median, its spread two ways
  (quartile distance over the median, as ``statistics.quantiles(n=4)`` gives the
  quartiles: the measure a bound is SET by; and the runs' range leaving out the
  run farthest from the median, over the median: the measure the driver REFUSES
  by, in its notes), the count of gaps beyond q in the run that has fewest, and
  the flank test: in EVERY run p(q-2) and p(q+2) each within ``FLANK`` of p(q);
- the verdict: ``steady`` where both spreads are under ``HALF`` the bound the
  manifest gives ``itl_p<q>_ms``, both flanks hold in every run and
  ``MIN_BEYOND`` gaps or more lie beyond q; else every reason it is not:
  ``too few beyond``, ``cliff below``, ``cliff above``, ``spreads``;
- every q of ``SWEEP`` that would read ``steady`` under ``TAIL_BOUND``: where a
  class's middle lies, for the file that a cell with no steady candidate needs;
- for ``itl_mean_ms``, ``out_tok_s`` and ``setup_s``, read from the runs' result
  lines where they carry them: the same two spreads against half their bounds;
- with ``--workload``: the tail the manifest judges the cell by, and the one the
  rule gives it (``choose``);
- with ``--markdown``: the set as one row of each of PERF.md's two tables (the
  ladder with its verdicts; the cell's judged metrics with their spreads),
  under the tables' heads.

``FLANK`` and ``HALF`` are constants of this file, not arguments: a PR that moves
a percentile argues with the ladder, not with the rule.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

FLANK = 0.10             # p(q-2) and p(q+2) each within this share of p(q), in every run
HALF = 0.5               # both spreads under this share of the metric's bound: the driver's "too tight" line
TAIL_BOUND = 0.0225      # the bound a percentile with no metric of its own is swept under
MIN_BEYOND = 10          # gaps beyond q in the run that has fewest
RUNGS = (50, 75) + tuple(range(80, 100))
SWEEP = tuple(range(82, 98))
TABLE_RUNGS = (50, 80, 83, 85, 87, 88, 90, 92, 93, 95, 97, 99)
LINE_METRICS = ("itl_mean_ms", "out_tok_s", "setup_s")


def tail_name(q: int) -> str:
    return f"itl_p{q}_ms"


def candidates() -> tuple:
    """The q's a tail metric exists for (a file in ``end_to_end/``), highest first."""
    names = (re.fullmatch(r"itl_p(\d+)_ms\.json", os.path.basename(p)) for p in glob.glob(os.path.join(HERE, "end_to_end", "*")))
    return tuple(sorted((int(m.group(1)) for m in names if m), reverse=True))


def quartile_spread(values) -> float:
    """The distance between the first and third quartile over the median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def drivers_spread(values) -> float:
    """The runs' range, the run farthest from the median left out, over the median."""
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return (max(kept) - min(kept)) / mid


def spreads(values, bound: float) -> dict:
    out = {"median": statistics.median(values), "quartile": quartile_spread(values),
           "drivers": drivers_spread(values), "bound": bound}
    out["within"] = max(out["quartile"], out["drivers"]) < HALF * bound
    return out


def rungs(gaps, points=RUNGS) -> dict:
    return dict(zip(points, np.percentile(np.asarray(gaps, dtype=np.float64), points).tolist()))


def judge(runs: list, q: int, bound: float) -> dict:
    """One percentile over a set's runs (each a list of gaps, ms)."""
    at = [rungs(g, (q - 2, q, q + 2)) for g in runs]
    out = spreads([r[q] for r in at], bound)
    out["beyond"] = min(int(np.sum(np.asarray(g) > r[q])) for g, r in zip(runs, at))
    out["below"] = max(abs(r[q - 2] - r[q]) / r[q] for r in at)      # the worst run's flank, as a share of p(q)
    out["above"] = max(abs(r[q + 2] - r[q]) / r[q] for r in at)
    reasons = [why for why, bad in (("too few beyond", out["beyond"] < MIN_BEYOND), ("cliff below", out["below"] > FLANK),
                                    ("cliff above", out["above"] > FLANK), ("spreads", not out["within"])) if bad]
    out["verdict"] = ", ".join(reasons) or "steady"
    return out


def choose(found: dict, judged: int | None) -> int | None:
    """The rule's tail for a cell: the judged one while it is steady, else the
    highest candidate that is. Where none is, a tail that only ``spreads``
    stays: every rung of the cell spreads then (a slow host lengthens every
    tick) and no move cures that; a tail on a cliff, or with too few gaps beyond
    it, goes to the candidate that has neither fault and the smallest driver's
    spread, never onto another cliff for a smaller one. Where every candidate
    has a fault the cell stays (None if it had no tail) and needs a percentile
    of its own: ``steady_at`` says which."""
    steady = [q for q in found if found[q]["verdict"] == "steady"]
    sound = [q for q in found if found[q]["verdict"] in ("steady", "spreads")]      # no cliff, gaps enough beyond
    if judged in steady or not sound:
        return judged
    if steady:
        return steady[0]
    return judged if judged in sound else min(sound, key=lambda q: found[q]["drivers"])


def read(runs: list, manifest: dict, workload: str | None = None, qs: tuple | None = None) -> dict:
    """``runs``: one ``{"itl_ms": [...], "metrics": {name: value}}`` a run of the
    set; ``qs``: the candidates, highest first (``candidates()`` unless given)."""
    if len(runs) < 3:
        raise ValueError(f"a set is three runs or more, not {len(runs)}: neither spread means anything under that")
    qs = candidates() if qs is None else qs
    bounds = {m["name"]: m["bound"] for m in manifest["end_to_end"]}
    gaps = [r["itl_ms"] for r in runs]
    out = {"runs": [dict(rungs(g), n=len(g)) for g in gaps],
           "candidates": {q: judge(gaps, q, bounds.get(tail_name(q), TAIL_BOUND)) for q in qs},
           "steady_at": [q for q in SWEEP if judge(gaps, q, TAIL_BOUND)["verdict"] == "steady"],
           "line": {name: spreads([r["metrics"][name] for r in runs], bounds[name])
                    for name in LINE_METRICS if name in bounds and all(name in r["metrics"] for r in runs)}}
    if workload is not None:
        on = [q for q in qs for m in manifest["end_to_end"] if m["name"] == tail_name(q) and workload in m.get("workloads", ())]
        out["judged"] = on[0] if len(on) == 1 else None
        out["chosen"] = choose(out["candidates"], out["judged"])
    return out


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        dump = json.load(f)
    metrics = {name: m["value"] for name, m in dump["result"]["metrics"].items()}
    metrics.setdefault("setup_s", dump["setup_s"])
    return {"itl_ms": dump["itl_ms"], "metrics": metrics}


def _pct(x: float) -> str:
    return f"{100 * x:.2f}"


def _name(q: int | None) -> str:
    return tail_name(q) if q else "no one tail"


def render(report: dict, names: list[str]) -> str:
    lines = ["run".ljust(max(map(len, names))) + "      n " + " ".join(f"{'p%d' % q:>6}" for q in RUNGS)]
    for name, run in zip(names, report["runs"]):
        lines.append(name.ljust(max(map(len, names))) + f" {run['n']:6d} " + " ".join(f"{run[q]:6.2f}" for q in RUNGS))
    lines.append("")
    for q, c in report["candidates"].items():
        lines.append(f"{tail_name(q)}: median {c['median']:.3f} ms, spread {_pct(c['quartile'])}% by quartiles and "
                     f"{_pct(c['drivers'])}% the driver's way (half the bound: {_pct(HALF * c['bound'])}%), {c['beyond']} gaps "
                     f"beyond, p{q - 2} at most {_pct(c['below'])}% and p{q + 2} at most {_pct(c['above'])}% from it in a run "
                     f"({_pct(FLANK)}% allowed): {c['verdict']}")
    lines.append(f"steady under a bound of {TAIL_BOUND}, of p{SWEEP[0]} to p{SWEEP[-1]}: "
                 + (" ".join(f"p{q}" for q in report["steady_at"]) or "none"))
    for name, c in report["line"].items():
        lines.append(f"{name}: median {c['median']:.3f}, spread {_pct(c['quartile'])}% by quartiles and {_pct(c['drivers'])}% the "
                     f"driver's way (half the bound: {_pct(HALF * c['bound'])}%): " + ("steady" if c["within"] else "spreads"))
    if "chosen" in report:
        lines.append(f"judged by {_name(report['judged'])}; the rule gives {_name(report['chosen'])}")
    return "\n".join(lines)


def markdown(report: dict, cell: str, label: str) -> str:
    """The set as a row of each of PERF.md's two tables, under their heads:
    spreads in % as quartiles / the driver's way, **over** past half the bound."""
    runs, found = report["runs"], report["candidates"]
    span = lambda q: f"{min(r[q] for r in runs):.1f}-{max(r[q] for r in runs):.1f}"  # noqa: E731
    said = "; ".join(f"p{q} {c['median']:.2f}: {c['verdict']} ({_pct(c['quartile'])} / {_pct(c['drivers'])}; flanks "
                     f"{_pct(c['below'])} / {_pct(c['above'])})" for q, c in found.items())
    ladder_row = [f"`{cell}`", label, f"{len(runs)} x {min(r['n'] for r in runs):,}-{max(r['n'] for r in runs):,}",
                  *(span(q) for q in TABLE_RUNGS), said, " ".join(f"p{q}" for q in report["steady_at"]) or "none",
                  f"{_name(report.get('judged'))} -> {_name(report.get('chosen'))}"]
    judged = {**({tail_name(report["judged"]): found[report["judged"]]} if report.get("judged") else {}), **report["line"]}
    judged.pop("setup_s", None)          # judged by its median alone: PERF.md section 5 has its own table
    judged_row = [f"`{cell}`", label] + [f"`{name}` (half {_pct(HALF * c['bound'])}): {c['median']:.2f}: {_pct(c['quartile'])} / "
                                         f"{_pct(c['drivers'])}" + ("" if c["within"] else " **over**") for name, c in judged.items()]
    head = ["cell", "set", "runs x gaps", *(f"p{q}" for q in TABLE_RUNGS),
            "each candidate: median: verdict (spreads; flanks below / above, %)", "steady at", "judged -> the rule's"]
    table = lambda *rows: "\n".join("| " + " | ".join(r) + " |" for r in rows)  # noqa: E731
    return (table(head, ["---"] * len(head), ladder_row) + "\n\n"
            + table(["cell", "set", *judged], ["---"] * len(judged_row), judged_row))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("dumps", nargs="+", help="the --dump files of a set of runs of one cell")
    p.add_argument("--workload", default=None, help="the cell, to name its judged tail and the rule's")
    p.add_argument("--markdown", default=None, metavar="SET", help="print the set, under this name, as PERF.md's rows (needs --workload)")
    args = p.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    try:
        report = read([load(path) for path in args.dumps], manifest, args.workload)
    except ValueError as e:
        print(f"ladder: {e}", file=sys.stderr)
        return 2
    if args.markdown is not None:
        print(markdown(report, args.workload or "?", args.markdown))
    else:
        print(render(report, [os.path.basename(path).removesuffix(".dump.json") for path in args.dumps]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

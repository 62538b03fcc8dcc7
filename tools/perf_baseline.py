#!/usr/bin/env python
"""Perf-regression sentinel: record a noise-aware baseline from bench.py
output and check later runs against it.

The BENCH trajectory had no enforced floor — a PR could silently give back
the optimization ledger's wins and nothing would go red until a human
re-read the numbers. This tool closes that loop:

    python tools/perf_baseline.py record BENCH.json --name r05
    python tools/perf_baseline.py check  BENCH.json

``record`` writes ``PERF_BASELINE.json`` (repo root; ``--baseline-file``
overrides): per-metric value + a noise threshold. ``check`` compares a
bench result against it and exits 1 naming every regressed metric.
``bench.py --baseline {check,update}`` wraps the same functions around a
live bench run (``make perf-check``).

Noise model (RTT-floor-aware — PERF.md "Methodology" rule 2): every bench
region is fetch-forced and pays one host↔device round-trip (~67 ms in the
2026-07-31 capture), so a decode region of N steps cannot resolve a change
smaller than ``rtt / (N × ms_per_step)`` of itself. The per-metric
threshold is ``max(10%, that floor)`` — on the 1b preset (5.5 ms steps)
the RTT floor (~19%) dominates; on the 8b preset (29 ms steps) the flat
10% does. A difference inside the threshold is noise, not a verdict.

Skip semantics are first-class: a side that never measured (backend down
→ ``skipped: true``; a stage that errored; a metric absent from the
current run) is **no evidence** — reported as such, never a pass and
never a fail. A check where nothing overlaps exits 0 with an explicit
``no_evidence`` verdict, so CI stays green on hardware-less runners
without pretending it verified anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")

# higher-is-better rates and lower-is-better latencies the sentinel guards
# (stage-scoped: the key is "<stage>.<field>")
RATE_FIELDS = ("decode_tok_per_s", "prefill_tok_per_s",
               "sampled_decode_tok_per_s", "chunked_decode_tok_per_s",
               "paged_decode_tok_per_s", "agg_tok_per_s",
               "accepted_tok_per_s", "decode_tok_per_s_q80",
               "sessions_per_chip", "slo_compliance_min",
               "eval_tok_per_s", "jain_index")
LATENCY_FIELDS = ("decode_ms_per_step", "verify_k4_ms",
                  "ttft_ms_p50", "ttft_ms_p95", "resume_ttft_p95_ms",
                  "comm_exposed_ms", "slo_worst_burn")
# decode-region fields whose RTT floor scales with the region length
_DECODE_REGION_FIELDS = ("decode_tok_per_s", "decode_ms_per_step",
                         "sampled_decode_tok_per_s",
                         "chunked_decode_tok_per_s")

DEFAULT_NOISE_FRAC = 0.10
MAX_NOISE_FRAC = 0.50  # a region THIS close to the RTT floor is reported
# null by bench.py anyway; cap so a borderline one can't excuse anything
REGION_STEPS = 64      # bench.py's decode_steps default per measured region
REGION_STEPS_BATCHED = 32  # the @b16 stages run half the steps (bench.py
# stage_child's b16 kwargs) — their RTT floor is twice as tall
# A zero-valued lower-is-better baseline (e.g. fully-overlapped exposed
# comm) has no relative scale: any value below this absolute band is
# timer/union jitter beneath the honest-timing resolution, not a move.
ZERO_LATENCY_TOL_MS = 0.5


def last_json_line(text: str) -> dict | None:
    """The last parseable JSON-object line in ``text`` (bench emits
    exactly one; logs/wrappers may surround it), or None."""
    for line in str(text).splitlines()[::-1]:
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                return obj
    return None


def load_bench_json(path: str) -> dict:
    """A bench result from any of its on-disk shapes: the one-line emit,
    a capture's BENCH_live.json, or the driver's BENCH_rN.json wrapper
    ({n, cmd, rc, tail, parsed})."""
    if os.path.isdir(path):
        path = os.path.join(path, "BENCH_live.json")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        whole = json.loads(text)
        if isinstance(whole, dict):
            if "stages" in whole or "value" in whole:
                return whole
            if isinstance(whole.get("parsed"), dict):
                return whole["parsed"]
            if "tail" in whole:
                found = last_json_line(whole["tail"])
                if found is not None:
                    return found
    except json.JSONDecodeError:
        pass
    found = last_json_line(text)
    if found is not None:
        return found
    raise ValueError(f"no bench JSON found in {path}")


def write_baseline(doc: dict, path: str) -> None:
    """THE baseline writer — `tools/perf_baseline.py record` and
    `bench.py --baseline update` both come through here, so the two can
    never drift in formatting (a byte-stable committed file diffs
    cleanly across either writer)."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"✅ baseline '{doc['name']}' → {path} "
          f"({len(doc['metrics'])} metrics)")


def _noise_frac(stage: dict, field: str, stage_name: str = "") -> float:
    """Per-metric threshold: the flat noise floor, raised to the RTT
    floor's share of the measured region when that is larger."""
    frac = DEFAULT_NOISE_FRAC
    rtt = stage.get("fetch_rtt_ms")
    ms_step = stage.get("decode_ms_per_step")
    if rtt and ms_step and field in _DECODE_REGION_FIELDS:
        steps = (REGION_STEPS_BATCHED if stage_name.endswith("@b16")
                 else REGION_STEPS)
        region_ms = ms_step * steps
        if region_ms > 0:
            frac = max(frac, min(MAX_NOISE_FRAC, rtt / region_ms))
    return round(frac, 4)


def extract_metrics(bench: dict) -> dict:
    """Flatten a bench result into the sentinel's comparable metrics:
    ``{"<stage>.<field>": {value, higher_better, noise_frac}}`` plus the
    headline roofline fraction when present. Skipped results and errored
    stages contribute NOTHING (no evidence is not a zero)."""
    out: dict = {}
    if bench.get("skipped"):
        return out
    for stage, rec in (bench.get("stages") or {}).items():
        if not isinstance(rec, dict) or rec.get("skipped") \
                or rec.get("error"):
            continue
        # `is not None`, not truthiness: a measured 0.0 (e.g. a fully
        # overlapped comm_exposed_ms) is evidence — dropping it would let
        # a later 0 → 50 ms regression pass unnamed. bench.py reports an
        # unmeasured region as null, which IS excluded here.
        for field in RATE_FIELDS:
            v = rec.get(field)
            if v is not None:
                out[f"{stage}.{field}"] = {
                    "value": float(v), "higher_better": True,
                    "noise_frac": _noise_frac(rec, field, stage)}
        for field in LATENCY_FIELDS:
            v = rec.get(field)
            if v is not None:
                out[f"{stage}.{field}"] = {
                    "value": float(v), "higher_better": False,
                    "noise_frac": _noise_frac(rec, field, stage)}
    roof = bench.get("roofline") or {}
    if roof.get("roofline_fraction") is not None:
        out["headline.roofline_fraction"] = {
            "value": float(roof["roofline_fraction"]),
            "higher_better": True, "noise_frac": DEFAULT_NOISE_FRAC}
    # per program-family fractions (decode vs prefill vs paged): lock each
    # family's distance-to-ceiling in independently, so a paged-path
    # regression can't hide behind a steady headline decode number (a
    # family with no_evidence contributes nothing, same as a stage)
    for fam, rec in (roof.get("families") or {}).items():
        frac = (rec or {}).get("roofline_fraction")
        if frac is not None:
            out[f"family.{fam}.roofline_fraction"] = {
                "value": float(frac), "higher_better": True,
                "noise_frac": DEFAULT_NOISE_FRAC}
    return out


def make_baseline(bench: dict, name: str, source: str = "") -> dict:
    metrics = extract_metrics(bench)
    if not metrics:
        raise ValueError(
            "bench result carries no measured metrics to baseline "
            + ("(skipped: " + str(bench.get("skip_reason")) + ")"
               if bench.get("skipped") else "(every stage errored?)"))
    return {
        "name": name,
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "source": source,
        "git": bench.get("git"),
        "device_kind": bench.get("device_kind"),
        "bench_metric": bench.get("metric"),
        "metrics": metrics,
    }


def compare(bench: dict, baseline: dict) -> dict:
    """One check: every baseline metric against the current result.

    Verdict grammar — ``regressions`` (worse beyond the threshold),
    ``improvements`` (better beyond it), ``within_noise``, and
    ``no_evidence`` (the current side did not measure that metric: a
    skipped run, an errored stage, different hardware tier). Only
    ``regressions`` can fail a check; ``no_evidence`` never passes or
    fails it."""
    current = extract_metrics(bench)
    out: dict = {"baseline_name": baseline.get("name"),
                 "regressions": [], "improvements": [],
                 "within_noise": [], "no_evidence": []}
    if bench.get("skipped"):
        out["skipped"] = True
        out["skip_reason"] = bench.get("skip_reason")
    for key, base in sorted((baseline.get("metrics") or {}).items()):
        cur = current.get(key)
        if cur is None:
            out["no_evidence"].append({
                "metric": key, "baseline": base["value"],
                "reason": ("run skipped (no hardware)" if bench.get("skipped")
                           else "metric not measured in this run")})
            continue
        bv, cv = base["value"], cur["value"]
        thresh = max(base.get("noise_frac", DEFAULT_NOISE_FRAC),
                     cur.get("noise_frac", DEFAULT_NOISE_FRAC))
        if bv == 0:
            # a zero baseline (e.g. fully-overlapped exposed comm) has no
            # relative scale: staying zero is a perfect hold, sub-resolution
            # jitter on a latency metric is NOISE (a 0.4 µs union sliver
            # must not hard-fail CI as a "-100% regression"), and anything
            # past the band is a full-size move in the metric's direction
            if cv == 0:
                delta = 0.0
            elif base.get("higher_better", True):
                delta = 1.0  # grew from zero: improvement-positive
            elif cv <= ZERO_LATENCY_TOL_MS:
                delta = 0.0
            else:
                delta = -1.0
        elif base.get("higher_better", True):
            delta = (cv - bv) / bv
        else:
            delta = (bv - cv) / bv  # improvement-positive either way
        # the absolute sub-resolution band applies to EVERY latency
        # metric, not only exact-zero baselines: 0.15 ms → 0.35 ms of
        # union sliver is the same timer jitter as 0 → 0.2
        if not base.get("higher_better", True) \
                and abs(cv - bv) <= ZERO_LATENCY_TOL_MS:
            delta = 0.0
        rec = {"metric": key, "baseline": bv, "current": cv,
               "delta_frac": round(delta, 4), "threshold_frac": thresh}
        if delta < -thresh:
            out["regressions"].append(rec)
        elif delta > thresh:
            out["improvements"].append(rec)
        else:
            out["within_noise"].append(rec)
    out["verdict"] = ("regression" if out["regressions"]
                      else "no_evidence" if not (out["within_noise"]
                                                 or out["improvements"])
                      else "ok")
    return out


def format_report(cmp: dict) -> str:
    lines = [f"perf-baseline check vs '{cmp.get('baseline_name')}': "
             f"{cmp['verdict'].upper()}"]
    for r in cmp["regressions"]:
        lines.append(f"  ❌ REGRESSED {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.1f}%, "
                     f"threshold ±{100 * r['threshold_frac']:.0f}%)")
    for r in cmp["improvements"]:
        lines.append(f"  ✅ improved {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.1f}%)")
    for r in cmp["within_noise"]:
        lines.append(f"  · within noise {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.1f}% of "
                     f"±{100 * r['threshold_frac']:.0f}%)")
    for r in cmp["no_evidence"]:
        lines.append(f"  ∅ no evidence {r['metric']} "
                     f"(baseline {r['baseline']}): {r['reason']}")
    if cmp["verdict"] == "no_evidence":
        lines.append("  (nothing measured overlaps the baseline — not a "
                     "pass, not a fail; run on hardware for a verdict)")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("record", "check"))
    ap.add_argument("result", help="bench JSON (one-line emit, capture "
                                   "dir, or BENCH_rN.json wrapper)")
    ap.add_argument("--name", default=None,
                    help="baseline name (record mode; default: result "
                         "file stem)")
    ap.add_argument("--baseline-file", default=DEFAULT_BASELINE)
    args = ap.parse_args()

    try:
        bench = load_bench_json(args.result)
    except (OSError, ValueError) as e:
        # a missing/corrupt RESULT file is a filesystem error, not a perf
        # verdict: named rc 2, never the regression exit code
        print(f"❌ result file unusable: {e}", file=sys.stderr)
        return 2
    if args.mode == "record":
        name = args.name or os.path.splitext(
            os.path.basename(args.result))[0]
        doc = make_baseline(bench, name, source=args.result)
        write_baseline(doc, args.baseline_file)
        return 0

    try:
        with open(args.baseline_file, encoding="utf-8") as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        # unreadable OR corrupt: a named rc-2, never a traceback that a
        # CI gate misreads as a perf regression
        print(f"❌ baseline file unusable: {e}", file=sys.stderr)
        return 2
    cmp = compare(bench, baseline)
    print(format_report(cmp))
    return 1 if cmp["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())

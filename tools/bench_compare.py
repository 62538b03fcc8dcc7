"""Diff two bench JSON results (or two captures) stage by stage.

Round-5 helper: quantify what a change bought —

    python tools/bench_compare.py BENCH_r04_manual.json \\
        bench_results/capture_<ts>/BENCH_live.json

Accepts bench JSON files (the one-line emit), capture directories
(reads BENCH_live.json inside), or a ``PERF_BASELINE.json`` artifact
from the perf-regression sentinel (``bench.py --baseline update`` /
``tools/perf_baseline.py record``) — a baseline side is expanded back
into per-stage fields so "current run vs enforced baseline" diffs the
same way as "capture vs capture". Prints per-stage deltas for every
rate field present in both, most-improved first; the roofline fraction
ranks higher-is-better and the exposed-comm wall lower-is-better.
"""

from __future__ import annotations

import json
import os
import sys

_RATES = ("decode_tok_per_s", "prefill_tok_per_s", "sampled_decode_tok_per_s",
          "chunked_decode_tok_per_s", "paged_decode_tok_per_s",
          "agg_tok_per_s", "accepted_tok_per_s", "decode_tok_per_s_q80",
          "sessions_per_chip", "slo_compliance_min", "eval_tok_per_s",
          "jain_index")
# lower-is-better latencies (--scenario continuous/fleet TTFT + the
# tiered wave's resume TTFT; --scenario multichip exposed collective
# wall; the fleet scenario's worst SLO error-budget burn; --scenario
# eval teacher-forced perplexity): the printed pct is still
# "improvement-positive", so the sign is flipped before ranking
_LATENCIES = ("ttft_ms_p50", "ttft_ms_p95", "resume_ttft_p95_ms",
              "comm_exposed_ms", "comm_exposed_ms_off", "slo_worst_burn",
              "perplexity")
# context-only scenario fields: printed for both sides, never ranked (a
# higher occupancy or sharing count is workload-dependent, not a win/loss
# — and the fleet scenario's churn counters describe the kill/restart
# schedule, not a performance delta)
_GAUGES = ("block_occupancy_peak", "block_occupancy_mean",
           "kv_blocks_shared_peak", "prefix_reuse_tokens",
           "spec_accept_rate", "itl_p50_ms_delta",
           "wire_q80_shrink", "exposed_overlap_lower",
           "f32_tokens_identical",
           "router_retries", "router_ejects", "router_shed",
           "n_midstream_error", "readmitted",
           "total_nll_hex", "parity_drift")


def _from_baseline(doc: dict) -> dict:
    """Expand a PERF_BASELINE.json artifact (the sentinel's recorded
    side: flat ``{"<stage>.<field>": {value, ...}}`` metrics) into the
    bench-result shape this tool diffs."""
    stages: dict = {}
    out: dict = {"metric": f"baseline:{doc.get('name')}",
                 "git": doc.get("git"),
                 "device_kind": doc.get("device_kind"),
                 "stages": stages}
    for key, rec in (doc.get("metrics") or {}).items():
        scope, _, field = key.partition(".")
        if scope == "headline" and field == "roofline_fraction":
            out.setdefault("roofline", {})["roofline_fraction"] = rec["value"]
        elif scope == "family":
            fam, _, ffield = field.partition(".")
            if ffield == "roofline_fraction":
                out.setdefault("roofline", {}).setdefault(
                    "families", {})[fam] = {"roofline_fraction": rec["value"]}
        else:
            stages.setdefault(scope, {})[field] = rec["value"]
    return out


def _from_gemv_sweep(doc: dict) -> dict:
    """Expand a ``tools/gemv_sweep.py --json`` line into the bench-result
    shape: one stage per GEMV shape, one ``gbps:<variant>`` rate per swept
    kernel variant — so two sweeps diff (and rank by effective GB/s) the
    same way two bench captures do."""
    stages: dict = {}
    for row in doc.get("rows") or ():
        if row.get("gbps") is None:
            continue
        stages.setdefault(row["shape"], {})[f"gbps:{row['label']}"] = \
            row["gbps"]
    return {"metric": "gemv_sweep", "git": doc.get("git"),
            "device_kind": doc.get("device_kind"), "stages": stages}


def _load(path: str) -> dict:
    if os.path.isdir(path):
        path = os.path.join(path, "BENCH_live.json")
    with open(path) as f:
        text = f.read()
    try:
        whole = json.loads(text)
        if isinstance(whole, dict) and whole.get("tool") == "gemv_sweep":
            return _from_gemv_sweep(whole)
        if "metrics" in whole and "stages" not in whole \
                and "value" not in whole:
            return _from_baseline(whole)
        if "stages" in whole or "value" in whole:
            return whole
        # the driver's BENCH_rN.json wrapper: {n, cmd, rc, tail, parsed}
        if isinstance(whole.get("parsed"), dict):
            return whole["parsed"]
        if "tail" in whole:  # tail holds the emitted line (may be truncated)
            for line in str(whole["tail"]).splitlines()[::-1]:
                if line.startswith("{"):
                    try:
                        return json.loads(line)
                    except json.JSONDecodeError:
                        continue
    except json.JSONDecodeError:
        pass
    for line in text.splitlines()[::-1]:
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    raise SystemExit(f"no bench JSON in {path}")


def main() -> None:
    if len(sys.argv) != 3:
        raise SystemExit(__doc__)
    a, b = _load(sys.argv[1]), _load(sys.argv[2])
    print(f"A: {sys.argv[1]}  (git {a.get('git')}, {a.get('device_kind')})")
    print(f"B: {sys.argv[2]}  (git {b.get('git')}, {b.get('device_kind')})")
    # a skipped run never measured live hardware (bench.py emits
    # `skipped: true` + the reason when the backend was down, possibly
    # re-emitting an older banked capture): say so loudly — its deltas
    # are "no hardware", not a regression signal
    for tag, d in (("A", a), ("B", b)):
        if d.get("skipped"):
            print(f"⚠️ {tag} SKIPPED (no live measurement): "
                  f"{d.get('skip_reason') or d.get('error') or 'backend unavailable'}")
    if a.get("skipped") or b.get("skipped"):
        print("⚠️ deltas below compare non-live data — not a regression "
              "signal\n")
    # the eval scenario's bit-parity verdict: a side whose exact-parity
    # configs (telemetry.EVAL_PARITY — paged vs dense vs the single-seq
    # oracle, spec-on vs spec-off) disagree on total NLL is numerically
    # broken; its perplexity/eval_tok_per_s deltas describe a bug, not a
    # quality tradeoff
    for tag, d in (("A", a), ("B", b)):
        for stage, rec in sorted((d.get("stages") or {}).items()):
            if isinstance(rec, dict) and rec.get("parity_drift"):
                print(f"❌ {tag} stage '{stage}': PARITY DRIFT — "
                      f"exact-parity eval configs disagree bit-for-bit "
                      f"on total NLL; treat this side's quality numbers "
                      f"as a numerics bug, not a quality tradeoff")
    hv_a, hv_b = a.get("value") or 0, b.get("value") or 0
    if hv_a and hv_b:
        print(f"headline {a.get('metric')}: {hv_a} -> {hv_b} "
              f"({100 * (hv_b - hv_a) / hv_a:+.1f}%)\n")

    rows = []
    sa, sb = a.get("stages") or {}, b.get("stages") or {}
    for stage in sorted(set(sa) & set(sb)):
        # gbps:<variant> fields come from gemv-sweep expansion (effective
        # GB/s per kernel variant — higher is better, ranked like rates)
        sweep = sorted(k for k in set(sa[stage]) & set(sb[stage])
                       if k.startswith("gbps:"))
        for k in _RATES + tuple(sweep):
            va, vb = sa[stage].get(k), sb[stage].get(k)
            if va and vb:
                rows.append((100 * (vb - va) / va, stage, k, va, vb))
        for k in _LATENCIES:  # lower is better: +% means B got FASTER
            va, vb = sa[stage].get(k), sb[stage].get(k)
            if va and vb:
                rows.append((100 * (va - vb) / va, stage, k, va, vb))
    # roofline observatory section (higher fraction = closer to the chip
    # ceiling = better); ceiling source printed as context below when the
    # two sides measured against different ceilings
    ra, rb = a.get("roofline") or {}, b.get("roofline") or {}
    va, vb = ra.get("roofline_fraction"), rb.get("roofline_fraction")
    if va and vb:
        rows.append((100 * (vb - va) / va, "headline",
                     "roofline_fraction", va, vb))
    # per-family fractions (decode vs prefill vs paged — the paged family
    # is where the PR6 gather cost shows up; a no_evidence family has no
    # fraction and drops out of the ranking by construction)
    fa, fb = ra.get("families") or {}, rb.get("families") or {}
    for fam in sorted(set(fa) & set(fb)):
        va = (fa[fam] or {}).get("roofline_fraction")
        vb = (fb[fam] or {}).get("roofline_fraction")
        if va and vb:
            rows.append((100 * (vb - va) / va, f"family:{fam}",
                         "roofline_fraction", va, vb))
    if not rows:
        print("no overlapping measured rates")
        return
    for pct, stage, k, va, vb in sorted(rows, reverse=True):
        print(f"  {stage:10s} {k:28s} {va:>10} -> {vb:>10}  ({pct:+.1f}%)")
    gauges = []
    for stage in sorted(set(sa) & set(sb)):
        for k in _GAUGES:
            va, vb = sa[stage].get(k), sb[stage].get(k)
            if va is not None and vb is not None:
                gauges.append((stage, k, va, vb))
    if (ra.get("ceiling_source") or rb.get("ceiling_source")) \
            and ra.get("ceiling_source") != rb.get("ceiling_source"):
        gauges.append(("headline", "roofline_ceiling_source",
                       ra.get("ceiling_source"), rb.get("ceiling_source")))
    if gauges:
        print("  -- context (not ranked) --")
        for stage, k, va, vb in gauges:
            print(f"  {stage:10s} {k:28s} {va!s:>10} -> {vb!s:>10}")


if __name__ == "__main__":
    main()

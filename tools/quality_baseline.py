#!/usr/bin/env python
"""Quality-regression sentinel: record a perplexity baseline from the
eval harness's JSON output and gate later runs against it.

``benchmark/run.py`` and the ledger measure speed; they do not say
whether a build still *predicts well* — a quant or kernel change could
trade perplexity for throughput and stay green. This tool is the
quality half of that record:

    python -m dllama_tpu eval --model m.m --data d.jsonl --json > R.json
    python tools/quality_baseline.py record R.json --name r01
    python tools/quality_baseline.py check  R.json

``record`` writes ``QUALITY_BASELINE.json`` (repo root;
``--baseline-file`` overrides): per-dataset perplexity + the documented
tolerance, plus the recorded per-config total-NLL hexes for reference.
``check`` exits 1 naming every metric whose perplexity regressed beyond
the tolerance — and, independently, whenever two exact-parity configs
in the CURRENT run (telemetry.EVAL_PARITY: paged vs dense-vs-single,
spec-on vs spec-off) disagree bit-for-bit on total NLL. Parity is
gated within one run, never across runs: a kernel change may move NLL
bits while staying inside the perplexity tolerance, but two configs of
the SAME build must agree exactly or something is numerically wrong.

With no result file, ``check``/``record`` run the built-in fixture
eval: a deterministically-seeded tiny model scored on
``tests/goldens/eval_tiny.jsonl`` under every config in
telemetry.EVAL_CONFIGS — the hermetic CI gate behind ``make
quality-check`` (no model download, no hardware assumption).

Same verdict grammar as the perf sentinel: ``regressions`` /
``improvements`` / ``within_noise`` / ``no_evidence``. A skipped or
absent measurement is **no evidence** — never a pass, never a fail —
and a corrupt baseline or result file is rc 2, never a quality verdict.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # the parity check reads telemetry.EVAL_PARITY
DEFAULT_BASELINE = os.path.join(REPO, "QUALITY_BASELINE.json")
FIXTURE = os.path.join(REPO, "tests", "goldens", "eval_tiny.jsonl")

# the documented tolerance: per-dataset perplexity may move this much
# (relative) before the gate goes red. Teacher-forced NLL on a fixed
# dataset is far less noisy than a wall-clock benchmark — float-math
# reassociation across jax/XLA versions and backends is the only
# legitimate wiggle, and it is well under 2%.
QUALITY_TOL = 0.02

BUILTIN_SEED = 0x5EED  # the built-in fixture eval's tiny-model RNG seed


def last_json_line(text: str) -> dict | None:
    """The last parseable JSON-object line in ``text`` (the eval CLI
    emits exactly one with ``--json``; logs may surround it), or None."""
    for line in str(text).splitlines()[::-1]:
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                return obj
    return None


def load_eval_json(path: str) -> dict:
    """An eval result from disk: the ``--json`` one-line emit (a single
    run summary, optionally carrying a ``compare`` sub-run) or this
    tool's own multi-run shape (``{"runs": [...]}``)."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    try:
        whole = json.loads(text)
        if isinstance(whole, dict) and ("runs" in whole
                                        or "dataset" in whole):
            return whole
    except json.JSONDecodeError:
        pass
    found = last_json_line(text)
    if found is not None:
        return found
    raise ValueError(f"no eval JSON found in {path}")


def iter_runs(result: dict):
    """Every complete run summary in a result doc, compare sub-runs
    included. Partial (aborted) runs contribute NOTHING — a truncated
    perplexity is no evidence, not a number."""
    runs = result.get("runs") if isinstance(result.get("runs"), list) \
        else [result]
    for run in runs:
        if not isinstance(run, dict) or run.get("partial"):
            continue
        if "dataset" in run and "config" in run:
            yield run
        sub = run.get("compare")
        if isinstance(sub, dict) and not sub.get("partial"):
            yield sub


def extract_metrics(result: dict) -> dict:
    """Flatten a result into the sentinel's comparable metrics:
    ``{"<dataset>.perplexity": {value, higher_better, noise_frac}}``.
    One perplexity per dataset — configs are exact-parity by contract,
    so any complete run's number stands for all of them (the parity
    gate, not this one, catches disagreement)."""
    out: dict = {}
    for run in iter_runs(result):
        key = f"{run['dataset']}.perplexity"
        v = run.get("perplexity")
        if v is None or key in out:
            continue
        v = float(v)
        if math.isfinite(v):
            out[key] = {"value": v, "higher_better": False,
                        "noise_frac": QUALITY_TOL}
    return out


def extract_parity(result: dict) -> dict:
    """Per-dataset map of config → total-NLL hex from every complete
    run in the result: ``{"eval_tiny": {"single": "0x1...", ...}}``."""
    out: dict = {}
    for run in iter_runs(result):
        hexes = out.setdefault(run["dataset"], {})
        if run.get("total_nll_hex"):
            hexes[run["config"]] = run["total_nll_hex"]
    return out


def check_parity(result: dict) -> list[dict]:
    """Within-run bit-parity over telemetry.EVAL_PARITY: every pair of
    exact-parity configs present in the CURRENT result must agree on
    total NLL to the bit. Returns one drift record per violated pair."""
    from dllama_tpu.runtime import telemetry

    drifts = []
    for dataset, hexes in sorted(extract_parity(result).items()):
        for a, b in telemetry.EVAL_PARITY:
            ha, hb = hexes.get(a), hexes.get(b)
            if ha is not None and hb is not None and ha != hb:
                drifts.append({"dataset": dataset, "configs": (a, b),
                               "hex": (ha, hb)})
    return drifts


def write_baseline(doc: dict, path: str) -> None:
    """THE baseline writer (byte-stable: sorted keys, one-space indent, so
    committed files diff cleanly)."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"✅ baseline '{doc['name']}' → {path} "
          f"({len(doc['metrics'])} metrics)")


def make_baseline(result: dict, name: str, source: str = "") -> dict:
    metrics = extract_metrics(result)
    if not metrics:
        raise ValueError("eval result carries no complete runs to "
                         "baseline (aborted/partial runs are no "
                         "evidence)")
    return {
        "name": name,
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "source": source,
        "tolerance_frac": QUALITY_TOL,
        "metrics": metrics,
        # recorded per-config total-NLL hexes: documentation of the
        # bit-exact state at record time (parity is GATED within each
        # check run, not against these — a legitimate kernel change may
        # move the bits while staying inside the tolerance)
        "parity": extract_parity(result),
    }


def compare(result: dict, baseline: dict) -> dict:
    """Every baseline metric against the current result. Only
    ``regressions`` can fail a check; ``no_evidence`` never passes or
    fails one."""
    current = extract_metrics(result)
    out: dict = {"baseline_name": baseline.get("name"),
                 "regressions": [], "improvements": [],
                 "within_noise": [], "no_evidence": [],
                 "parity_drift": []}
    for key, base in sorted((baseline.get("metrics") or {}).items()):
        cur = current.get(key)
        if cur is None:
            out["no_evidence"].append({
                "metric": key, "baseline": base["value"],
                "reason": "metric not measured in this run"})
            continue
        bv, cv = base["value"], cur["value"]
        thresh = max(base.get("noise_frac", QUALITY_TOL),
                     cur.get("noise_frac", QUALITY_TOL))
        # perplexity is lower-is-better and never legitimately zero;
        # improvement-positive delta like the perf sentinel's
        delta = (bv - cv) / bv if bv else 0.0
        rec = {"metric": key, "baseline": bv, "current": cv,
               "delta_frac": round(delta, 4), "threshold_frac": thresh}
        if delta < -thresh:
            out["regressions"].append(rec)
        elif delta > thresh:
            out["improvements"].append(rec)
        else:
            out["within_noise"].append(rec)
    out["parity_drift"] = check_parity(result)
    out["verdict"] = ("parity_drift" if out["parity_drift"]
                      else "regression" if out["regressions"]
                      else "no_evidence" if not (out["within_noise"]
                                                 or out["improvements"])
                      else "ok")
    return out


def format_report(cmp: dict) -> str:
    lines = [f"quality-baseline check vs '{cmp.get('baseline_name')}': "
             f"{cmp['verdict'].upper()}"]
    for d in cmp["parity_drift"]:
        a, b = d["configs"]
        ha, hb = d["hex"]
        lines.append(f"  ❌ PARITY DRIFT {d['dataset']}: {a} ({ha}) != "
                     f"{b} ({hb}) — exact-parity configs disagree "
                     f"bit-for-bit; this is a numerics bug, not a "
                     f"quality tradeoff")
    for r in cmp["regressions"]:
        lines.append(f"  ❌ REGRESSED {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.2f}%, "
                     f"threshold ±{100 * r['threshold_frac']:.0f}%)")
    for r in cmp["improvements"]:
        lines.append(f"  ✅ improved {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.2f}%)")
    for r in cmp["within_noise"]:
        lines.append(f"  · within noise {r['metric']}: {r['baseline']} -> "
                     f"{r['current']} ({100 * r['delta_frac']:+.2f}% of "
                     f"±{100 * r['threshold_frac']:.0f}%)")
    for r in cmp["no_evidence"]:
        lines.append(f"  ∅ no evidence {r['metric']} "
                     f"(baseline {r['baseline']}): {r['reason']}")
    if cmp["verdict"] == "no_evidence":
        lines.append("  (nothing measured overlaps the baseline — not a "
                     "pass, not a fail)")
    return "\n".join(lines)


def run_builtin() -> dict:
    """The hermetic fixture eval behind ``make quality-check``: a
    deterministically-seeded tiny model (tests/helpers) scored on the
    committed fixture under EVERY config in telemetry.EVAL_CONFIGS, so
    one invocation produces both the perplexity evidence and all the
    parity hexes. CPU-safe and model-download-free by construction."""
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tests"))
    sys.path.insert(0, REPO)
    from helpers import (byte_vocab_tokenizer, tiny_header_params,
                         write_tiny_model)

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime import evalharness, telemetry
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    seqs = evalharness.load_dataset(FIXTURE)
    runs = []
    with tempfile.TemporaryDirectory() as d:
        mpath = os.path.join(d, "m.m")
        tpath = os.path.join(d, "t.t")
        write_tiny_model(mpath, tiny_header_params(seq_len=64),
                         np.random.RandomState(BUILTIN_SEED))
        tfile.write_tfile(tpath, byte_vocab_tokenizer())
        for config in telemetry.EVAL_CONFIGS:
            kw = {}
            if config in ("paged", "paged_spec"):
                kw["kv_block_size"] = 8
            if config == "paged_spec":
                kw["spec_lookup"] = 4
            eng = InferenceEngine(mpath, tpath, tp=1, **kw)
            sched = None
            try:
                if config == "single":
                    run = evalharness.run_eval(seqs, dataset="eval_tiny",
                                               config=config, engine=eng)
                else:
                    sched = BatchScheduler(eng, n_slots=4)
                    run = evalharness.run_eval(seqs, dataset="eval_tiny",
                                               config=config, sched=sched)
            finally:
                if sched is not None:
                    sched.close()
                eng.close()
            print(f"· builtin eval [{config}]: perplexity "
                  f"{run['perplexity']:.4f} ({run['total_nll_hex']})",
                  file=sys.stderr)
            runs.append(run)
    return {"runs": runs, "builtin_seed": BUILTIN_SEED}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("record", "check"))
    ap.add_argument("result", nargs="?", default=None,
                    help="eval --json output (default: run the built-in "
                         "fixture eval across every config)")
    ap.add_argument("--name", default=None,
                    help="baseline name (record mode; default: result "
                         "file stem, or 'builtin')")
    ap.add_argument("--baseline-file", default=DEFAULT_BASELINE)
    args = ap.parse_args()

    if args.result is None:
        result = run_builtin()
        source = "builtin fixture eval (tests/goldens/eval_tiny.jsonl)"
    else:
        try:
            result = load_eval_json(args.result)
        except (OSError, ValueError) as e:
            # missing/corrupt RESULT is a filesystem error, not a
            # quality verdict: named rc 2, never the regression exit
            print(f"❌ result file unusable: {e}", file=sys.stderr)
            return 2
        source = args.result
    if args.mode == "record":
        name = args.name or (os.path.splitext(
            os.path.basename(args.result))[0] if args.result else "builtin")
        try:
            doc = make_baseline(result, name, source=source)
        except ValueError as e:
            print(f"❌ result file unusable: {e}", file=sys.stderr)
            return 2
        write_baseline(doc, args.baseline_file)
        return 0

    try:
        with open(args.baseline_file, encoding="utf-8") as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        # unreadable OR corrupt: a named rc-2, never a traceback a CI
        # gate misreads as a quality regression
        print(f"❌ baseline file unusable: {e}", file=sys.stderr)
        return 2
    cmp = compare(result, baseline)
    print(format_report(cmp))
    return 1 if (cmp["regressions"] or cmp["parity_drift"]) else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Kernel-choice perf sweep: one command turns a live-chip window into a
comparison table instead of a single point.

Runs ``bench.py --stage <preset>`` once per knob combo — each in its own
subprocess (wedge-isolated, same as the bench) — and prints a JSON line
per combo plus a final summary. The knobs:

  DLLAMA_TPU_QUANT_KERNEL  pallas | xla   (ops/linear.py dispatch)
  DLLAMA_BENCH_ATTN        flash  | xla   (ModelConfig.attn_impl)
  DLLAMA_BENCH_KV          bf16 | f8 | f32  (KV cache storage dtype)
  DLLAMA_TPU_QUANT_MODE    fast | exact | turbo | turbo16  (ops/linear.py)
  DLLAMA_TPU_DENSE_LOGITS  on | off      (resident bf16 head vs Q40)
  DLLAMA_TPU_SCAN_UNROLL   N             (layer-scan unroll, models/llama.py)
  DLLAMA_BENCH_WEIGHTS     q40 | bf16    (dense planes: the no-dequant
                                          streaming ceiling; 1b-only — the
                                          8b dense stack exceeds HBM and the
                                          budget check refuses it cleanly)

Usage:
  python tools/perf_matrix.py [preset] [per-stage-budget-s]
  # defaults: preset=1b (safe shape), budget=420

The reference's analogue is its Eval-ms/Sync-ms per-token table
(/root/reference/src/dllama.cpp:59-67); this sweep answers the TPU-side
question the reference never had: which of XLA-fused dequant vs the Pallas
kernel, and XLA attention vs the flash kernel, wins at each shape.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402 — the bench parent module is deliberately jax-free

# the bench presets run bf16 compute, so un-pinned rows resolve to FAST
# numerics (auto): the production config. Each other row isolates one knob
# against it. (Round-4 finding: fast quant dispatch is always the XLA fused
# dequant — the gemv sweep measured it 3-5x over the Pallas kernel — so the
# old pallas-vs-xla fast rows collapsed into one "pallas" comparison row.)
# DECISION-VALUE order, not taxonomy order: a truncated chip window (the
# round-4/5 failure mode is a wedge or a window opening minutes before the
# round ends) banks combos front-to-back, and the round's verdict rides on
# auto-vs-turbo — so those three run FIRST.
COMBOS = [
    # (label, quant_kernel, attn_impl, kv_dtype, quant_mode, dense_logits,
    #  scan_unroll, weights)
    ("auto", None, None, None, None, None, None, None),          # production
    # integer-dot turbo modes (ops/turbo.py): per-column int8 planes,
    # scales in the epilogue; a8 = s8xs8 MXU dots, a16 = bf16 activations
    ("turbo16", None, None, None, "turbo16", None, None, None),
    ("turbo", None, None, None, "turbo", None, None, None),
    ("unroll4", None, None, None, None, None, "4", None),        # layer-scan unroll
    # dense bf16 planes: the no-dequant streaming ceiling (fits HBM on the
    # 1b preset only; the 8b row fails its budget check with a clean error)
    ("bf16-dense", None, None, None, None, None, None, "bf16"),
    ("auto+f8kv", None, None, "f8", None, None, None, None),     # fp8 KV storage
    ("q40-logits", None, None, None, None, "off", None, None),   # quantized head
    ("xla-attn", None, "xla", None, None, None, None, None),     # oracle attention
    ("exact", None, None, None, "exact", None, None, None),      # parity numerics
    ("pallas", "pallas", "flash", None, None, None, None, None), # Pallas kernel
    # decode-shaped fused dequant-GEMV (one full-K pass per N stripe;
    # also turns the ragged paged attention kernel on via the shared gate)
    ("fused", "fused", None, None, None, None, None, None),
]


def run_combo(preset: str, budget: float, quant: str | None,
              attn: str | None, kv: str | None = None,
              qmode: str | None = None,
              dense_logits: str | None = None,
              scan_unroll: str | None = None,
              weights: str | None = None) -> dict:
    """Set the combo's knobs in this process's env and delegate to
    bench.run_stage (subprocess isolation, live phase tracking, stderr tail,
    kill+reap — no second implementation to drift)."""
    for var, val in (("DLLAMA_TPU_QUANT_KERNEL", quant),
                     ("DLLAMA_BENCH_ATTN", attn),
                     ("DLLAMA_BENCH_KV", kv),
                     ("DLLAMA_TPU_QUANT_MODE", qmode),
                     ("DLLAMA_TPU_DENSE_LOGITS", dense_logits),
                     ("DLLAMA_TPU_SCAN_UNROLL", scan_unroll),
                     ("DLLAMA_BENCH_WEIGHTS", weights)):
        if val:
            os.environ[var] = val
        else:
            os.environ.pop(var, None)
    bench._stage_cache_env()
    return bench.run_stage(preset, budget)


def main() -> None:
    preset = sys.argv[1] if len(sys.argv) > 1 else "1b"
    budget = float(sys.argv[2]) if len(sys.argv) > 2 else 420.0
    rows: dict = {}
    for label, quant, attn, kv, qmode, dense, unroll, weights in COMBOS:
        t0 = time.monotonic()
        res = run_combo(preset, budget, quant, attn, kv, qmode, dense,
                        unroll, weights)
        res["combo_s"] = round(time.monotonic() - t0, 1)
        rows[label] = res
        print(json.dumps({label: res}), flush=True)
    print(json.dumps({"preset": preset, "matrix": rows}))
    keys = ("decode_tok_per_s", "prefill_tok_per_s", "sampled_decode_tok_per_s",
            "chunked_decode_tok_per_s")
    print(f"\n{'combo':14s}" + "".join(f"{k.split('_tok')[0]:>18s}" for k in keys))
    for label, res in rows.items():
        cells = "".join(f"{res.get(k, '-'):>18}" for k in keys)
        err = f"   ({res['error'][:40]})" if res.get("error") else ""
        print(f"{label:14s}{cells}{err}")


if __name__ == "__main__":
    main()

"""Roofline attribution — achieved vs ceiling, per compiled program.

A join over three sources the serving stack already records:

* **per-program bytes + FLOPs** — the compile ledger's AOT analysis
  (``runtime/introspection.py``: ``memory_analysis()`` argument/temp/
  output bytes and ``cost_analysis()`` FLOPs of each compiled program —
  measured from the executable, not estimated from shapes);
* **per-dispatch walls** — the telemetry step histograms
  (``dllama_decode_step_ms`` / ``dllama_batch_step_ms`` /
  ``dllama_prefill_chunk_ms``), with the ledger's compile walls
  subtracted so warm-up dispatches don't dilute the steady-state mean
  (the first dispatch of every program rode a trace+compile and its
  recorded wall is mostly compiler, not hardware);
* **chip ceilings** — the nameplate table below, by device kind. A kind
  the table does not hold is an error (:class:`UnknownDeviceKind`),
  never a default row. The ceiling source (``nameplate:<kind>``) is
  named in the output.

Per program it yields achieved HBM GB/s, achieved TFLOP/s, the roofline
fraction (max of the bandwidth and compute fractions, clamped to (0, 1]
— a raw value above 1 means the byte/FLOP accounting over-counted, e.g.
aliased arguments, and is kept in ``raw_fraction``), and a memory-bound
vs compute-bound classification. Surfaces: ``GET /debug/roofline``,
``dllama_roofline_fraction{scope,program}`` /
``dllama_achieved_hbm_gbps`` / ``dllama_achieved_tflops`` gauges and a
``roofline=…%`` fragment in ``--stats``. This is the serving process's
own view of itself; the repository's measured record is
``benchmark/run.py``'s per-layer metrics (PERF.md section 3), which
reduce a profiler trace and do not read this module.

The walls it consumes end in a ``jax.device_get`` of a value that
data-depends on the dispatch, and the first dispatch after a compile is
subtracted as warm-up (the ledger's compile walls).
"""

from __future__ import annotations

from dataclasses import asdict, dataclass

# nameplate peak dense-bf16 TFLOP/s and HBM GB/s by device-kind substring
# (first match wins). "v5 lite" is what jax reports as ``device_kind`` for
# a v5e chip ("TPU v5 lite"); both v5e rows are Google Cloud's "TPU v5e"
# page: 197 TFLOP/s bf16, 819 GB/s HBM. The CPU line is a nominal
# DDR-class figure so fractions stay finite on the test mesh, not a
# measured claim. A kind that matches no row is an error.
NAMEPLATE_SPECS = (
    ("v5 lite", 197.0, 819.0),
    ("v5e", 197.0, 819.0),
    ("v5p", 459.0, 2765.0),
    ("v4", 275.0, 1228.0),
    ("v6", 918.0, 1640.0),  # trillium
    ("cpu", 1.0, 50.0),
)


class UnknownDeviceKind(ValueError):
    """No nameplate row for this ``device_kind`` — add the row with its
    source to :data:`NAMEPLATE_SPECS` rather than price a run against
    another chip's peaks."""


@dataclass(frozen=True)
class Ceilings:
    """One chip's roofline ceilings and where they came from.

    ``source`` is ``nameplate:<kind>``: the row of
    :data:`NAMEPLATE_SPECS` the fractions are taken against."""

    hbm_gbps: float
    tflops: float
    source: str
    device_kind: str = ""


def nameplate_ceilings(device_kind: str) -> Ceilings:
    """Nameplate ceilings by device-kind substring; raises
    :class:`UnknownDeviceKind` for a kind the table does not hold."""
    dk = (device_kind or "").lower()
    for key, tflops, gbps in NAMEPLATE_SPECS:
        if key in dk:
            return Ceilings(hbm_gbps=gbps, tflops=tflops,
                            source=f"nameplate:{key}", device_kind=device_kind)
    raise UnknownDeviceKind(
        f"no nameplate ceilings for device kind {device_kind!r} "
        f"(known: {', '.join(k for k, _, _ in NAMEPLATE_SPECS)})")


def load_ceilings() -> Ceilings:
    """The running backend's ceilings, from the nameplate table."""
    import jax

    return nameplate_ceilings(jax.devices()[0].device_kind)


# -- the per-program math ------------------------------------------------------


def attribute(hbm_bytes: float, flops: float, wall_ms: float | None,
              ceilings: Ceilings) -> dict:
    """THE roofline formula for one program: achieved bandwidth/compute
    from measured bytes/FLOPs over a measured steady-state dispatch
    wall, fractions against the ceilings, and the bound classification.

    Returns a dict with ``roofline_fraction`` in (0, 1] (raw value above
    1 preserved in ``raw_fraction`` — over-unity means the byte/FLOP
    accounting over-counted, not that the chip beat physics), or a
    ``no_evidence`` reason when a side is missing. A zero-FLOP program
    is legitimate (pure gather/copy): it classifies memory-bound on its
    bandwidth fraction alone."""
    if wall_ms is None or wall_ms <= 0:
        return {"no_evidence": "no steady-state dispatch wall measured"}
    if hbm_bytes <= 0 and flops <= 0:
        return {"no_evidence": "no measured bytes or FLOPs "
                               "(compile-ledger analysis missing)"}
    wall_s = wall_ms / 1e3
    achieved_gbps = hbm_bytes / wall_s / 1e9
    achieved_tflops = flops / wall_s / 1e12
    bw_frac = achieved_gbps / ceilings.hbm_gbps if ceilings.hbm_gbps else 0.0
    comp_frac = achieved_tflops / ceilings.tflops if ceilings.tflops else 0.0
    raw = max(bw_frac, comp_frac)

    def _frac(f: float) -> float:
        # 6 decimals, floored at 1e-6 for positive values: a CPU-mesh toy
        # model against real silicon ceilings is genuinely ~1e-5, and the
        # (0, 1] contract must survive the rounding
        return max(round(min(1.0, f), 6), 1e-6 if f > 0 else 0.0)

    out = {
        "wall_ms": round(wall_ms, 4),
        "hbm_bytes": int(hbm_bytes),
        "flops": float(flops),
        "achieved_hbm_gbps": round(achieved_gbps, 6),
        "achieved_tflops": round(achieved_tflops, 6),
        "bw_fraction": _frac(bw_frac),
        "compute_fraction": _frac(comp_frac),
        "roofline_fraction": _frac(raw),
        "bound": "memory" if bw_frac >= comp_frac else "compute",
    }
    if raw > 1.0:
        out["raw_fraction"] = round(raw, 4)
    if flops > 0 and hbm_bytes > 0:
        # operational intensity vs the machine's ridge point — the classic
        # roofline x-axis, kept for plotting
        out["flops_per_byte"] = round(flops / hbm_bytes, 4)
        out["ridge_flops_per_byte"] = round(
            ceilings.tflops * 1e12 / (ceilings.hbm_gbps * 1e9), 4)
    if raw <= 0:
        return {"no_evidence": "achieved rate computed as zero"}
    return out


# program → wall family: every engine/serving program is either a
# prefill-regime forward (variable token width per dispatch) or a
# decode-regime step (the histograms below time exactly these dispatches)
_PREFILL_PROGRAMS = ("forward", "replicated_forward", "forward_with_taps")


def _wall_family(program: str) -> str:
    if program in _PREFILL_PROGRAMS or "prefill" in program:
        return "prefill"
    return "decode"


def _family_walls(reg, led_snap: dict) -> dict:
    """Steady-state mean dispatch wall per family, compile-corrected:
    the histograms record EVERY dispatch, including the one that rode
    each trace+compile — subtract the ledger's compile walls and counts
    so a cold server's means aren't mostly compiler time. Walls are
    process-global (the histograms are unlabeled), which is the honest
    grain: two engines' dispatches interleave on one chip."""
    from . import telemetry

    comp_ms = {"decode": 0.0, "prefill": 0.0}
    comp_n = {"decode": 0, "prefill": 0}
    for p in led_snap.get("programs", ()):
        fam = _wall_family(p["program"])
        comp_ms[fam] += p.get("total_compile_s", 0.0) * 1e3
        comp_n[fam] += p.get("compiles", 0)

    fams = {}
    hists = {"decode": (telemetry.DECODE_STEP_MS, telemetry.BATCH_STEP_MS),
             "prefill": (telemetry.PREFILL_CHUNK_MS,)}
    for fam, names in hists.items():
        s = sum(reg.histogram(n).sum() for n in names)
        c = sum(reg.histogram(n).count() for n in names)
        n_adj, s_adj = c - comp_n[fam], s - comp_ms[fam]
        if n_adj >= 1 and s_adj > 0:
            fams[fam] = {"wall_ms": s_adj / n_adj, "n_dispatches": n_adj,
                         "source": "+".join(names) + " (compile-corrected)"}
        elif c >= 1:
            fams[fam] = {"wall_ms": s / c, "n_dispatches": c,
                         "source": "+".join(names) + " (raw — too few "
                                   "dispatches to subtract compiles)"}
        else:
            fams[fam] = {"wall_ms": None, "n_dispatches": 0,
                         "source": "+".join(names)}
    if fams["prefill"]["wall_ms"] is None:
        # batched serving prefills through the generator's own chunk
        # dispatch (no engine-histogram record) but every chunk leaves a
        # `prefill_chunk` span in the always-on ring — the MEDIAN duration
        # is robust to the compile-inflated first chunk
        durs = sorted((sp["end_ns"] - sp["start_ns"]) / 1e6
                      for sp in telemetry.tracer().raw_spans()
                      if sp["phase"] == "prefill_chunk")
        if durs:
            fams["prefill"] = {"wall_ms": durs[len(durs) // 2],
                               "n_dispatches": len(durs),
                               "source": "prefill_chunk spans (median)"}
    return fams


def snapshot(*, ceilings: Ceilings | None = None, scope: str | None = None,
             publish: bool = True) -> dict:
    """The roofline observatory's one computation: join the compile
    ledger's per-program measured bytes/FLOPs with the step-histogram
    walls against the chip ceilings. Pure host-side reads — touches no
    jitted program, so it is trace-invisible (zero post-steady compiles;
    test-asserted). ``publish`` also updates the three gauges so a
    ``/metrics`` scrape after any snapshot carries the same numbers."""
    from . import introspection, telemetry

    reg = telemetry.registry()
    ceil = ceilings or load_ceilings()
    led_snap = introspection.ledger().snapshot()
    walls = _family_walls(reg, led_snap)

    programs = []
    g_frac = reg.gauge(telemetry.ROOFLINE_FRACTION)
    g_bw = reg.gauge(telemetry.ACHIEVED_HBM_GBPS)
    g_fl = reg.gauge(telemetry.ACHIEVED_TFLOPS)
    best = None  # decode-family program with the largest measured bytes
    for p in led_snap.get("programs", ()):
        if scope is not None and p["scope"] != scope:
            continue
        analysis = p.get("analysis") or {}
        fam = _wall_family(p["program"])
        wall = walls[fam]
        entry = {"scope": p["scope"], "program": p["program"],
                 "family": fam, "wall_source": wall["source"],
                 "n_dispatches": wall["n_dispatches"]}
        if not analysis or "hbm_total_bytes" not in analysis:
            entry["no_evidence"] = ("compile-ledger analysis missing "
                                    "(analyze off, or the backend has no "
                                    "memory_analysis)")
            programs.append(entry)
            continue
        entry.update(attribute(analysis.get("hbm_total_bytes", 0),
                               analysis.get("flops", 0.0) or 0.0,
                               wall["wall_ms"], ceil))
        programs.append(entry)
        if "roofline_fraction" not in entry:
            continue
        if publish:
            labels = dict(scope=p["scope"], program=p["program"])
            g_frac.set(entry["roofline_fraction"], **labels)
            g_bw.set(entry["achieved_hbm_gbps"], **labels)
            g_fl.set(entry["achieved_tflops"], **labels)
        if fam == "decode" and (best is None
                                or entry["hbm_bytes"] > best["hbm_bytes"]):
            best = entry
    out = {"ceilings": asdict(ceil), "programs": programs}
    if best is not None:
        out["summary"] = {
            "program": best["program"], "scope": best["scope"],
            "roofline_fraction": best["roofline_fraction"],
            "achieved_hbm_gbps": best["achieved_hbm_gbps"],
            "achieved_tflops": best["achieved_tflops"],
            "bound": best["bound"],
        }
    return out


def stats_fraction() -> float | None:
    """The ``--stats`` fragment: the decode-program roofline fraction of
    the dominant (largest measured bytes) decode program, refreshing the
    gauges as a side effect. None while there is no evidence."""
    try:
        summary = snapshot(publish=True).get("summary")
    except Exception:  # noqa: BLE001 — the stats line must never die on this
        return None
    return summary["roofline_fraction"] if summary else None


def rate_roofline(tok_per_s: float, weight_gb: float,
                  ceilings: Ceilings) -> dict:
    """Bench-parent helper: the classic decode roofline from a measured
    token rate and the weight bytes streamed per token (no jax, no
    ledger — the parent process stays jax-free by design). The HBM
    roofline rate for a decode step that must stream every weight byte
    is ``ceiling_GBps / weight_GB`` tok/s; the fraction is the measured
    rate against it (clamped like :func:`attribute`)."""
    roof = ceilings.hbm_gbps / weight_gb if weight_gb > 0 else 0.0
    raw = tok_per_s / roof if roof > 0 else 0.0
    out = {
        "roofline_tok_per_s": round(roof, 1),
        "achieved_hbm_gbps": round(tok_per_s * weight_gb, 1),
        "roofline_fraction": round(min(1.0, raw), 4),
        "bound": "memory",
        "ceiling_source": ceilings.source,
        "ceiling_hbm_gbps": ceilings.hbm_gbps,
        "ceiling_tflops": ceilings.tflops,
    }
    if raw > 1.0:
        out["raw_fraction"] = round(raw, 4)
    return out


def rate_roofline_families(stage: dict, weight_gb: float, n_params: int,
                           ceilings: Ceilings) -> dict:
    """Bench-parent helper: ``roofline_fraction`` per program FAMILY
    (decode vs prefill vs paged) from one measured stage's rates — the
    same jax-free shape algebra as :func:`rate_roofline`, with first-class
    ``no_evidence`` for any family the stage never measured.

    * **decode** — memory-bound against the weight stream (the headline
      formula).
    * **prefill** — compute-bound: achieved TFLOP/s from ``2 * n_params``
      FLOPs per token against the MXU ceiling (the classic MFU).
    * **paged** — the SAME weight-stream pricing as decode, applied to the
      block-table step: both families must stream every weight byte, so
      the paged fraction sitting below decode's is exactly the
      gather/kernel overhead of the paged path — previously invisible in
      the ranked metrics (the PR6 gather materializes the dense logical
      cache per layer per step; the ragged paged attention kernel exists
      to close this gap)."""
    fams: dict = {}
    v = stage.get("decode_tok_per_s")
    fams["decode"] = (rate_roofline(v, weight_gb, ceilings) if v
                      else {"no_evidence": "decode never measured"})
    v = stage.get("prefill_tok_per_s")
    if v:
        ach = v * 2.0 * n_params / 1e12
        raw = ach / ceilings.tflops if ceilings.tflops else 0.0
        rec = {"achieved_tflops": round(ach, 3),
               "roofline_fraction": round(min(1.0, raw), 4),
               "bound": "compute",
               "ceiling_source": ceilings.source,
               "ceiling_tflops": ceilings.tflops}
        if raw > 1.0:
            rec["raw_fraction"] = round(raw, 4)
        fams["prefill"] = rec
    else:
        fams["prefill"] = {"no_evidence": "prefill never measured"}
    v = stage.get("paged_decode_tok_per_s")
    fams["paged"] = (rate_roofline(v, weight_gb, ceilings) if v
                     else {"no_evidence": "paged decode never measured"})
    return fams

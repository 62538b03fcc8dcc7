"""The program's own spans in a traced slice: what the scheduler's loop was
doing, on the device trace's clock.

``BatchScheduler._tick`` opens a ``dllama.tick`` span around every tick (it
carries ``tick=<flight recorder's tick number>``) and a ``dllama.tick.<phase>``
span around each phase of it (``runtime/telemetry.TICK_PHASES``). They are
``jax.profiler.TraceAnnotation``s, so they lie in the profiler's own
``.xplane.pb`` on the loop thread's host line, beside the device lanes. This
module groups them into ticks and splits device 0's idle time by the phase
that overlaps it. A program without the spans (a parent commit) gives
``None``, and every reader built on this returns ``None`` with it.

``python3 benchmark/program_spans.py <file.xplane.pb> [window_s]`` prints the
per-phase table of one trace (ms per tick, idle under each phase, how much of
the median tick its children cover).
"""

from __future__ import annotations

import glob
import os
import statistics

from trace_reduce import _lanes, total, union    # the reduction's own lanes and union: not copied

ROOT_SPAN = "dllama.tick"
NO_WORK = "idle_wait"             # the loop asleep with nothing to do
DEVICE_WAIT = "step_wait"         # the loop waiting for the device


def _tick_events(pd) -> list[list[tuple]]:
    """Per host line that holds any: its ``dllama.tick*`` events as
    ``(name, start_s, end_s, stats)``, sorted by start."""
    out = []
    for plane in pd.planes:
        if "/device:" in plane.name:
            continue
        for ln in plane.lines:
            evs = [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, dict(ev.stats))
                   for ev in ln.events if ev.name == ROOT_SPAN or ev.name.startswith(ROOT_SPAN + ".")]
            if evs:
                out.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return out


def group_ticks(lines: list[list[tuple]]) -> list[dict]:
    """Ticks with their children: ``{"tick", "n_active", "start", "end",
    "children": [(phase, start, end, stats)]}``. A child belongs to the root
    on its own line that contains its start; one whose root the slice cut off
    is dropped."""
    ticks = []
    for evs in lines:
        cur = None
        for name, s, e, stats in evs:
            if name == ROOT_SPAN:
                cur = {"tick": stats.get("tick"), "n_active": stats.get("n_active"),
                       "start": s, "end": e, "children": []}
                ticks.append(cur)
            elif cur is not None and cur["start"] <= s <= cur["end"]:
                cur["children"].append((name[len(ROOT_SPAN) + 1:], s, min(e, cur["end"]), stats))
    return sorted(ticks, key=lambda t: t["start"])


def idle_by_phase(busy: list[tuple[float, float]], ticks: list[dict]) -> dict:
    """Device idle time under each phase. ``busy`` is one lane's merged op
    intervals. The window is the hull of the ops and the ticks; idle is the
    window less ``busy``; each idle stretch is given to the phase spans that
    overlap it, and what no phase span covers (between phases, or outside
    every tick) is ``unspanned``."""
    spans = sorted((s, e, name) for t in ticks for name, s, e, _st in t["children"])
    lo = min([b[0] for b in busy[:1]] + [t["start"] for t in ticks[:1]])
    hi = max([b[1] for b in busy[-1:]] + [t["end"] for t in ticks[-1:]])
    idle, at = [], lo
    for a, b in busy:
        if a > at:
            idle.append((at, a))
        at = max(at, b)
    if hi > at:
        idle.append((at, hi))
    by_phase: dict[str, float] = {}
    covered, i = 0.0, 0
    for a, b in idle:
        while i < len(spans) and spans[i][1] <= a:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < b:
            ov = min(b, spans[j][1]) - max(a, spans[j][0])
            if ov > 0:
                by_phase[spans[j][2]] = by_phase.get(spans[j][2], 0.0) + ov
                covered += ov
            j += 1
    idle_s = total(idle)
    return {"window": (lo, hi), "idle_s": idle_s, "by_phase": by_phase, "unspanned_s": idle_s - covered}


def load(path: str) -> dict | None:
    """Ticks and the idle split of one trace file; ``None`` where the program
    wrote no ``dllama.tick`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ticks = group_ticks(_tick_events(pd))
    if not ticks:
        return None
    lanes = _lanes(pd)
    busy = union([(s, e) for _n, s, e in lanes[0][1]]) if lanes else []
    return {"path": path, "ticks": ticks, "idle": idle_by_phase(busy, ticks) if busy else None}


def newest_trace(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def of_run(ctx) -> dict | None:
    """The traced slice of this run, parsed once and kept in ``ctx``. The file
    is the newest under ``.bench_work/trace/<cell name>/``, where ``run.py``
    traces into."""
    if "program_spans" not in ctx:
        spans = None
        if ctx.get("trace") is not None:
            here = os.path.dirname(os.path.abspath(__file__))
            path = newest_trace(os.path.join(os.path.dirname(here), ".bench_work", "trace",
                                             ctx["cell"]["name"]))
            spans = load(path) if path else None
        ctx["program_spans"] = spans
    return ctx["program_spans"]


# -- per-tick quantities the readers take medians of ------------------------------


def phase_ms(tick: dict, phase: str) -> float:
    return 1e3 * sum(e - s for name, s, e, _st in tick["children"] if name == phase)


def has(tick: dict, phase: str) -> bool:
    return any(name == phase for name, _s, _e, _st in tick["children"])


def work_ticks(ticks: list[dict]) -> list[dict]:
    """Ticks that carried work: every tick but those that slept in
    ``idle_wait``."""
    return [t for t in ticks if t["children"] and not has(t, NO_WORK)]


def host_ms(tick: dict) -> float:
    """The tick's wall less the time it waited for the device (or slept):
    host time a synchronous loop cannot overlap."""
    return 1e3 * (tick["end"] - tick["start"]) - phase_ms(tick, DEVICE_WAIT) - phase_ms(tick, NO_WORK)


def coverage(tick: dict) -> float:
    """Share of the tick its children cover."""
    wall = tick["end"] - tick["start"]
    return sum(e - s for _n, s, e, _st in tick["children"]) / wall if wall > 0 else 0.0


def table(spans: dict, window_s: float | None = None) -> dict:
    """The per-phase table PERF.md keeps: per phase the median ms in the
    work-carrying ticks that have it, the share of those ticks, and the device
    idle under it as a share of the window."""
    work = work_ticks(spans["ticks"])
    idle = spans["idle"]
    if window_s is None and idle is not None:
        window_s = idle["window"][1] - idle["window"][0]
    names = sorted({n for t in spans["ticks"] for n, _s, _e, _st in t["children"]})
    rows = {}
    for n in names:
        vals = [phase_ms(t, n) for t in work if has(t, n)]
        rows[n] = {"ticks_with_it": len(vals), "ms_p50": statistics.median(vals) if vals else None,
                   "ms_total": sum(phase_ms(t, n) for t in spans["ticks"]),
                   "idle_share_pct": (100.0 * idle["by_phase"].get(n, 0.0) / window_s) if idle else None}
    return {"n_ticks": len(spans["ticks"]), "n_work_ticks": len(work),
            "tick_ms_p50": statistics.median(1e3 * (t["end"] - t["start"]) for t in work) if work else None,
            "tick_host_ms_p50": statistics.median(host_ms(t) for t in work) if work else None,
            "children_cover_p50": statistics.median(coverage(t) for t in work) if work else None,
            "window_s": window_s, "hull_s": (idle["window"][1] - idle["window"][0]) if idle else None,
            "idle_s": idle["idle_s"] if idle else None,
            "idle_unspanned_pct": (100.0 * idle["unspanned_s"] / window_s) if idle else None,
            "phases": rows}


if __name__ == "__main__":
    import json
    import sys

    found = load(sys.argv[1])
    if found is None:
        print(json.dumps(None))
    else:
        print(json.dumps(table(found, float(sys.argv[2]) if len(sys.argv) > 2 else None), indent=1))

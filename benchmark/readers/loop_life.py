"""What the traced slice holds of the scheduler loop's life OUTSIDE the tick's
phases: the ``dllama.loop.between_ticks`` span the program opens from one
tick's end to the next one's start, and ``cpu_us`` on every root ``dllama.tick``
span, the loop thread's CPU time over the tick (``runtime/flightrec.py``).
``program_spans.py`` keeps neither (the gap is deliberately not named
``dllama.tick*``), so the slice's file is read once more here, with the
reduction's own lanes and union. ``what``:

* ``idle_between_ticks``: device 0's idle time under the between-ticks spans
  as a share of the hull of ops and ticks (``program_spans``' window: one
  clock), in percent: the part of ``idle_unspanned_share`` that lay between
  two ticks; the rest lay between phases or outside the loop's life;
* ``tick_cpu``: the loop thread's CPU time a work-carrying tick, in ms, from the
  root spans' ``cpu_us``: the median over runs of ``RUN_TICKS`` consecutive work
  ticks of the run's CPU time over its ticks. Not the median tick's: on the
  machine that holds the chip a thread's CPU clock ticks at 10 ms, so one tick
  reads 0 or 10,000 us and only a run of them reads a level (50 ticks: to 0.2
  ms); a median over runs still leaves a stalled run out. Beside
  ``tick_host_ms_p50`` (wall less waits): CPU level and wall up says the loop
  was kept off its CPU, both up that it did more.

0.0 where no idle lay under a gap. A program without the spans gives nothing."""

import statistics

import program_spans   # run.py puts benchmark/ on sys.path
from trace_reduce import _lanes, union    # the reduction's own lanes and union: not copied

GAP_SPAN = "dllama.loop.between_ticks"
RUN_TICKS = 50


def load(path: str) -> dict | None:
    """``{"gaps": [(start_s, end_s)], "cpu_us": {tick: us}, "busy": device 0's
    merged op intervals}`` of one trace file; ``None`` where the program wrote
    neither a between-ticks span nor ``cpu_us``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    gaps, cpu_us = [], {}
    for plane in pd.planes:
        if "/device:" in plane.name:
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name == GAP_SPAN:
                    gaps.append((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
                elif ev.name == program_spans.ROOT_SPAN:
                    stats = dict(ev.stats)
                    if "cpu_us" in stats:
                        cpu_us[stats.get("tick")] = float(stats["cpu_us"])
    if not gaps and not cpu_us:
        return None
    lanes = _lanes(pd)
    return {"gaps": union(gaps), "cpu_us": cpu_us,
            "busy": union([(s, e) for _n, s, e in lanes[0][1]]) if lanes else []}


def of_run(ctx) -> dict | None:
    """This run's slice, read once and kept in ``ctx``."""
    if "loop_life" not in ctx:
        spans = program_spans.of_run(ctx)
        ctx["loop_life"] = load(spans["path"]) if spans is not None else None
    return ctx["loop_life"]


def idle_under(gaps, busy, window) -> float:
    """Seconds of ``window`` in which a span of ``gaps`` lay and no op of
    ``busy`` ran (both merged and sorted: one pass over each)."""
    lo, hi = window
    idle, i = 0.0, 0
    for a, b in gaps:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while i < len(busy) and busy[i][1] <= a:
            i += 1
        j, covered = i, 0.0
        while j < len(busy) and busy[j][0] < b:
            covered += min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
        idle += (b - a) - covered
    return idle


def read(ctx, what: str):
    life, spans = of_run(ctx), program_spans.of_run(ctx)
    if life is None:
        return None
    if what == "idle_between_ticks":
        if spans["idle"] is None:
            return None
        lo, hi = spans["idle"]["window"]
        return 100.0 * idle_under(life["gaps"], life["busy"], (lo, hi)) / (hi - lo)
    if what == "tick_cpu":
        vals = [life["cpu_us"][t["tick"]] / 1e3 for t in program_spans.work_ticks(spans["ticks"])
                if t["tick"] in life["cpu_us"]]
        runs = [vals[i:i + RUN_TICKS] for i in range(0, len(vals) - len(vals) % RUN_TICKS, RUN_TICKS)] or [vals]
        return statistics.median(sum(run) / len(run) for run in runs) if vals else None
    raise ValueError(f"loop_life reads 'idle_between_ticks' or 'tick_cpu', not {what!r}")

"""A decode step's account: the tick's ``step_*`` spans on the loop thread's
line joined to the step's program on the device, per tick, in ms.

For every work-carrying tick that has a ``step_wait`` and no
``prefill_dispatch`` (the ticks ``step_wait_ms_p50`` takes) the step's module
event M is the one on device 0's "XLA Modules" line whose name contains
``match`` and which ends inside the tick's ``step_wait``, and the ops are those
of "XLA Ops" that start inside M (``trace_reduce._lanes``: the lanes, the noise
filter and the container ops are the reduction's own, so a gap here is a gap of
``busy_s``). Per tick:

* ``lag``    = M's start less the end of ``step_dispatch`` (signed: negative
  where the device starts before the call returns). The end of ``step_dispatch``
  is taken where ``step_wait`` starts: ``next_phase`` closes one annotation and
  opens the next, under a microsecond apart in a trace, and with the boundary
  there the three parts add up to ``step_wait`` exactly;
* ``module`` = M's length;
* ``tail``   = the end of ``step_wait`` less M's end;
* ``inner``  = M's length less the union of the ops inside it: device time the
  program holds with no op running;
* ``copy``   = the length of the tick's ``dllama.step.fetch`` spans that START
  after M's end: a fetch of an array that is ready, so the copy and the call
  alone (none where one ``device_get`` brings every output);
* ``outside`` = the union of the device ops inside the tick's wall that lie
  outside M (printed by the command line, no metric).

Beside them the command line prints three checks of the account itself:
``since_call`` (M's start less the START of ``step_dispatch``: a program cannot
start before it is called, so a negative reading is the device lane's clock
running early against the host line's by at least as much, and ``lag`` and
``tail`` then carry that offset with opposite signs while their sum does not),
``first`` (the end of the tick's first fetch less M's end: what lies between the
program's last op and its tokens on the host) and ``inner_leaf`` (M less the
union of its ops that are no containers: ``inner`` counts a ``while`` as busy
from end to end, as ``busy_s`` does, so the gaps between the ops of a layer scan
show only here); and the slice's device time by program.

``lag + module + tail`` is the tick's ``step_wait`` to the nanosecond, asserted
on whole nanoseconds. A trace with no ``dllama.tick.step_upload`` span (a
program from before PR 40, whose ``step_dispatch`` holds the uploads) gives
``None``.

``python3 benchmark/readers/step_account.py <file.xplane.pb> [match]`` prints the
median account of such a tick: every phase, then lag, module, inner, tail, copy
and the ops outside M, and what programs those ops belong to.
"""

import bisect
import os
import statistics
import sys

if __name__ == "__main__":          # as a command: benchmark/ on the path, as run.py puts it
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import program_spans
import trace_reduce

FETCH_SPAN = "dllama.step.fetch"
SPLIT = "step_upload"
PARTS = ("lag", "module", "inner", "tail", "copy", "outside")
CHECKS = ("since_call", "first", "inner_leaf")          # the command line's, no metric


def _ns(seconds: float) -> int:
    return round(seconds * 1e9)


def _host_lines(pd) -> list[tuple[list, list]]:
    """Per host line that holds ticks: (its ``dllama.tick*`` events as
    ``program_spans`` takes them, its fetch spans as (start_s, end_s))."""
    out = []
    for plane in pd.planes:
        if "/device:" in plane.name:
            continue
        for ln in plane.lines:
            ticks, fetches = [], []
            for ev in ln.events:
                s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if ev.name == program_spans.ROOT_SPAN or ev.name.startswith(program_spans.ROOT_SPAN + "."):
                    ticks.append((ev.name, s, e, dict(ev.stats)))
                elif ev.name == FETCH_SPAN:
                    fetches.append((s, e))
            if ticks:
                out.append((sorted(ticks, key=lambda ev: (ev[1], -ev[2])), sorted(fetches)))
    return out


def _container(name: str) -> bool:
    return trace_reduce.op_label(name).endswith(trace_reduce._CONTAINERS)


def _union_ns(intervals) -> int:
    return sum(_ns(b) - _ns(a) for a, b in trace_reduce.union(intervals))


def _account(tick: dict, fetches, mods, mod_ends, ops, op_starts, match: str) -> dict | None:
    """One tick's parts in whole nanoseconds, or ``None`` where no program
    named ``match`` ends inside its ``step_wait``."""
    w0, w1 = next((s, e) for name, s, e, _st in tick["children"] if name == "step_wait")
    at = bisect.bisect_right(mod_ends, w1) - 1
    while at >= 0 and mods[at][2] > w0 and match not in mods[at][0]:
        at -= 1
    if at < 0 or mods[at][2] <= w0:
        return None
    _name, m0, m1 = mods[at]
    inside = [(n, s, min(e, m1)) for n, s, e in ops[bisect.bisect_left(op_starts, m0):bisect.bisect_left(op_starts, m1)]]
    t0, t1 = tick["start"], tick["end"]
    around = [(s, min(e, t1)) for _n, s, e in ops[bisect.bisect_left(op_starts, t0):bisect.bisect_left(op_starts, t1)]
              if not m0 <= s < m1]
    mine = [(s, e) for s, e in fetches if w0 <= s <= w1]
    late = [(s, e) for s, e in mine if s >= m1]
    called = next((s for name, s, _e, _st in tick["children"] if name == "step_dispatch"), None)
    got = {"lag": _ns(m0) - _ns(w0), "module": _ns(m1) - _ns(m0), "tail": _ns(w1) - _ns(m1),
           "inner": (_ns(m1) - _ns(m0)) - _union_ns([(s, e) for _n, s, e in inside]), "outside": _union_ns(around),
           "copy": sum(_ns(e) - _ns(s) for s, e in late) if late else None,
           "since_call": None if called is None else _ns(m0) - _ns(called),
           "first": _ns(mine[0][1]) - _ns(m1) if mine else None,
           "inner_leaf": (_ns(m1) - _ns(m0)) - _union_ns([(s, e) for n, s, e in inside if not _container(n)])}
    assert got["lag"] + got["module"] + got["tail"] == _ns(w1) - _ns(w0), (tick["tick"], got)
    got["tick"], got["program"], got["spans"] = tick["tick"], (m0, m1), tick
    return got


def load(path: str, match: str = "paged_sampled_step") -> dict | None:
    """Every chunk-free step tick's account; ``None`` where the program wrote
    no ``step_upload`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines = _host_lines(pd)
    if not any(name == f"{program_spans.ROOT_SPAN}.{SPLIT}" for evs, _f in lines for name, *_ in evs):
        return None
    lanes = trace_reduce._lanes(pd)
    ops = sorted(lanes[0][1], key=lambda ev: ev[1]) if lanes else []
    mods = sorted(lanes[0][2], key=lambda ev: ev[2]) if lanes else []
    mod_ends, op_starts = [m[2] for m in mods], [o[1] for o in ops]
    ticks, skipped = [], 0
    for evs, fetches in lines:
        for t in program_spans.work_ticks(program_spans.group_ticks([evs])):
            if not program_spans.has(t, "step_wait") or program_spans.has(t, "prefill_dispatch"):
                continue
            got = _account(t, fetches, mods, mod_ends, ops, op_starts, match)
            if got is None:
                skipped += 1
            else:
                ticks.append(got)
    return {"path": path, "ticks": ticks, "skipped": skipped, "ops": ops, "mods": lanes[0][2] if lanes else []}


def median_ms(ticks: list[dict], what: str) -> float | None:
    vals = [t[what] for t in ticks if t[what] is not None]
    return statistics.median(vals) * 1e-6 if vals else None


def of_run(ctx, match: str) -> dict | None:
    """The traced slice of this run (the file ``program_spans.of_run`` found),
    parsed once and kept in ``ctx``."""
    if "step_account" not in ctx:
        spans = program_spans.of_run(ctx)
        ctx["step_account"] = None if spans is None else load(spans["path"], match)
    return ctx["step_account"]


def read(ctx, what: str, match: str = "paged_sampled_step"):
    if what not in PARTS:
        raise ValueError(what)
    found = of_run(ctx, match)
    return None if found is None else median_ms(found["ticks"], what)


def by_program(found: dict, outside_only: bool) -> dict[str, float]:
    """Seconds of device ops (containers left out) by the program (XLA module)
    each belongs to, ``"no program"`` for an op under no module event: over the
    whole slice, or ``outside_only`` what lies outside M in the accounted ticks."""
    module_at = trace_reduce._module_of(found["mods"])
    ops, out = found["ops"], {}
    if outside_only:
        starts = [o[1] for o in ops]
        ops = [(n, s, min(e, t["spans"]["end"])) for t in found["ticks"]
               for n, s, e in ops[bisect.bisect_left(starts, t["spans"]["start"]):
                                  bisect.bisect_left(starts, t["spans"]["end"])]
               if not t["program"][0] <= s < t["program"][1]]
    for n, s, e in ops:
        if not _container(n):
            key = module_at(s) or "no program"
            out[key] = out.get(key, 0.0) + (e - s)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(found: dict) -> dict:
    """What the command line prints: the median tick's account."""
    ticks = found["ticks"]
    names = sorted({n for t in ticks for n, _s, _e, _st in t["spans"]["children"]},
                   key=lambda n: min(s - t["spans"]["start"] for t in ticks
                                     for name, s, _e, _st in t["spans"]["children"] if name == n))
    phases = {n: statistics.median(program_spans.phase_ms(t["spans"], n) for t in ticks
                                   if program_spans.has(t["spans"], n)) for n in names}
    return {"ticks": len(ticks), "ticks_without_a_program": found["skipped"],
            "tick_ms_p50": statistics.median(1e3 * (t["spans"]["end"] - t["spans"]["start"]) for t in ticks)
            if ticks else None,
            "phase_ms_p50": phases, **{f"{p}_ms_p50": median_ms(ticks, p) for p in PARTS + CHECKS},
            "lag_ms_min": min((t["lag"] for t in ticks), default=0) * 1e-6,
            "since_call_ms_min": min((t["since_call"] for t in ticks if t["since_call"] is not None), default=0) * 1e-6,
            "outside_s_by_program": by_program(found, outside_only=True),
            "slice_s_by_program": by_program(found, outside_only=False)}


if __name__ == "__main__":
    import json

    loaded = load(sys.argv[1], *sys.argv[2:3])
    print(json.dumps(None if loaded is None else report(loaded), indent=1))

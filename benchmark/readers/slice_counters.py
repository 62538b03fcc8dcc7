"""The program's counters over the TRACED SLICE, not since the process started
(the warm-up's dummy rows and the probe are in a registry's totals, and they
are not the window's traffic): while a profiler listens, the program puts its
counters' running totals on every decode step's ``dllama.tick.step_wait`` span
(``runtime/serving.py::PagedGenerator._note_moe``), and what the slice added is
the last span's total less the first's. ``what``:

* ``ratio``: ``scale * sum(over) / sum(under)`` of what the slice added to each
  named total;
* ``spread``: largest over mean of what the slice added to each entry of
  ``series``, a total a label joined by ``/``; 1.0 is a perfectly even load.

Returns None where the spans carry no such total (a parent commit), the slice
holds fewer than two steps, or nothing was added below the line."""

import program_spans   # run.py puts benchmark/ on sys.path


def _first_and_last(ctx, stat: str):
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    seen = [st[stat] for t in spans["ticks"] for name, _s, _e, st in t["children"]
            if name == "step_wait" and stat in st]
    return (seen[0], seen[-1]) if len(seen) >= 2 else None


def read(ctx, what: str, over=(), under=(), scale: float = 1.0, series: str = ""):
    if what == "ratio":
        added = {}
        for stat in {*over, *under}:
            ends = _first_and_last(ctx, stat)
            if ends is None:
                return None
            added[stat] = float(ends[1]) - float(ends[0])
        bottom = sum(added[s] for s in under)
        return scale * sum(added[s] for s in over) / bottom if bottom else None
    if what == "spread":
        ends = _first_and_last(ctx, series)
        if ends is None:
            return None
        first, last = ([int(x) for x in str(v).split("/")] for v in ends)
        added = [b - a for a, b in zip(first, last)]
        return max(added) * len(added) / sum(added) if sum(added) else None
    raise ValueError(what)

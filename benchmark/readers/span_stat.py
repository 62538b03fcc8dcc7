"""A number the program put on one kind of ``dllama.tick.<phase>`` span, over
the traced slice (``program_spans.py``): every span of ``phase`` that carries
``stat`` (and ``under``, where one is named) gives ``scale * stat`` or ``scale *
stat / under``; ``what`` is their ``median`` or their ``max`` (a gauge's peak
in the slice: what parked blocks cost is what they hold at the most). Spans
whose ``stat`` is 0 give nothing to a median (an admission that took no
column has no column's bytes). Returns None where no span carries the
number: a parent commit, or a configuration without it."""

import statistics

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, phase: str, stat: str, what: str, under: str = "", scale: float = 1.0):
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    vals = []
    for tick in spans["ticks"]:
        for name, _s, _e, st in tick["children"]:
            if name != phase or stat not in st or (under and not float(st.get(under, 0))):
                continue
            vals.append(scale * float(st[stat]) / (float(st[under]) if under else 1.0))
    if what == "max":
        return max(vals) if vals else None
    if what == "median":
        vals = [v for v in vals if v > 0]
        return statistics.median(vals) if vals else None
    raise ValueError(what)

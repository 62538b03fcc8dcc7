"""Median over the traced slice's ticks of a quantity built from the program's
``dllama.tick`` spans (``program_spans.py``), in ms. ``what``:

* ``host``: per work-carrying tick, the tick's wall less ``step_wait`` and
  ``idle_wait``: host time the synchronous loop cannot overlap;
* ``admit_begin``: the ``admit_begin`` spans that admitted something (the
  span carries ``admitted=<n>``): prefix match, block allocation and the
  gather's dispatch, in front of every live row's next token;
* ``step_wait``: the ``step_wait`` of ticks with no ``prefill_dispatch``: the
  device-inclusive wait for one step, chunk-free.

A program without the spans gives nothing."""

import statistics

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, what: str):
    spans = program_spans.of_run(ctx)
    if spans is None:
        return None
    work = program_spans.work_ticks(spans["ticks"])
    if what == "host":
        vals = [program_spans.host_ms(t) for t in work]
    elif what == "admit_begin":
        vals = [1e3 * (e - s) for t in work for name, s, e, st in t["children"]
                if name == "admit_begin" and int(st.get("admitted", 0)) > 0]
    elif what == "step_wait":
        vals = [program_spans.phase_ms(t, "step_wait") for t in work
                if program_spans.has(t, "step_wait") and not program_spans.has(t, "prefill_dispatch")]
    else:
        raise ValueError(what)
    return statistics.median(vals) if vals else None

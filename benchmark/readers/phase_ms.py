"""Median length of one named phase of the scheduler's tick, in ms: over the
traced slice's work-carrying ticks that have a ``dllama.tick.<phase>`` span
(``program_spans.py``; the population of its table's ``ms_p50``).

Since PR 40 the step's head is two phases: ``step_upload`` (the host arrays made
device arguments) and ``step_dispatch`` (the jitted call alone). A trace with no
``step_upload`` span is a program from before the split, whose ``step_dispatch``
holds the uploads as well: it gives nothing, so that no line pairs the two
meanings of one name."""

import statistics

import program_spans   # run.py puts benchmark/ on sys.path

SPLIT = "step_upload"


def read(ctx, phase: str):
    spans = program_spans.of_run(ctx)
    if spans is None or not any(program_spans.has(t, SPLIT) for t in spans["ticks"]):
        return None
    vals = [program_spans.phase_ms(t, phase) for t in program_spans.work_ticks(spans["ticks"])
            if program_spans.has(t, phase)]
    return statistics.median(vals) if vals else None

"""A grouped routed kernel against the HBM roof, in percent, over the planes a
NAMED span field counted: ``expert_planes_roofline.py`` with the field an
argument, so that the kernel inside a PREFILL program can be read beside the one
inside the step. The bytes of ONE held expert's planes (the configuration's
``counts.kernel_counts``) times the planes the programs of the traced slice
really had to fetch, over the chip's published bandwidth, divided by the summed
device time of the ops whose name holds ``kernel`` under ``program/``.

The planes are the program's own count: a prefill chunk's routing counters
(the distinct held experts each routed layer's rows chose, over its layers) are
added to the totals' chunk row when the admission commits, and that row's
running total rides every step's ``dllama.tick.step_wait`` span as ``field``
(``moe_chunk_planes``) while a profiler listens; what the slice added is the
last span's total less the first's. A chunk's planes are counted when its
admission COMMITS and its kernel time where it RAN, so an admission that
straddles an edge of the slice is over- or under-read by its part outside: of a
dozen admissions in an 8 s slice, one at each edge. Returns None where the
trace holds no such op, the configuration has no such kernel, or the spans
carry no such field (a parent commit)."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, kernel: str, program: str, field: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    kernel_s = sum(secs for label, secs in trace["device_ops"]
                   if label.startswith(program + "/") and kernel in label.split("/", 1)[-1])
    counts = getattr(ctx["counts"], "kernel_counts", None)
    one = counts(ctx["model"], kernel, rows=1) if counts else None
    spans = program_spans.of_run(ctx)
    if kernel_s <= 0.0 or one is None or spans is None:
        return None
    seen = [int(st[field]) for t in spans["ticks"] for name, _s, _e, st in t["children"]
            if name == "step_wait" and field in st]
    if len(seen) < 2 or seen[-1] <= seen[0]:
        return None
    return 100.0 * one["bytes"] * (seen[-1] - seen[0]) / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s

"""The grouped routed kernel inside the decode step against the HBM roof, in
percent: the bytes of ONE held expert's three planes (the configuration's
``counts.kernel_counts``) times the PLANES the step programs of the traced slice
really had to fetch, over the chip's published bandwidth, divided by the summed
device time of the ops whose name holds ``kernel`` under ``program/``.

The planes are the program's own count: a step's routing counters come back
from the device with its tokens, among them the distinct held experts each
routed layer's rows chose, and their running total rides every step's
``dllama.tick.step_wait`` span as ``moe_planes`` while a profiler listens; what
the slice added is the last span's total less the first's (the first step's own
planes are left out: an under-reading of one step in some hundred). A kernel
that fetches a plane once a RUN of pairs moves these bytes; one that fetches it
once a pair moves ``moe_pairs_per_plane`` times as many and reads so much lower
here. Returns None where the trace holds no such op, the configuration has no
such kernel, or the spans carry no such total (a parent commit)."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, kernel: str, program: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    kernel_s = sum(secs for label, secs in trace["device_ops"]
                   if label.startswith(program) and kernel in label.split("/", 1)[-1])
    counts = getattr(ctx["counts"], "kernel_counts", None)
    one = counts(ctx["model"], kernel, rows=1) if counts else None
    spans = program_spans.of_run(ctx)
    if kernel_s <= 0.0 or one is None or spans is None:
        return None
    seen = [int(st["moe_planes"]) for t in spans["ticks"] for name, _s, _e, st in t["children"]
            if name == "step_wait" and "moe_planes" in st]
    if len(seen) < 2 or seen[-1] <= seen[0]:
        return None
    return 100.0 * one["bytes"] * (seen[-1] - seen[0]) / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s

"""The routed decode kernel against the HBM roof, in percent: the bytes of ONE
(row, expert) pair (the configuration's ``counts.kernel_counts``: its three
planes as held) times the pairs the step programs of the traced slice REALLY
ran, over the chip's published bandwidth, divided by the summed device time of
the ops whose name holds ``kernel`` under ``program/``.

The pairs really run are the program's own count: each decode step's routing
counters come back from the device with its tokens, and the step's held pairs
ride its ``dllama.tick.step_wait`` span as ``moe_pairs`` (``program_spans.py``
reads the spans of the same trace the kernel's time comes from). Never ``slots x
k``: that would count pairs that were not run, and neither the window's mean
rows a step (a slice anchored on one burst is not the window). The kernel reads
a plane once a pair, also where two pairs share an expert, so the bytes it
moves are these. Returns None where the trace holds no such op, the
configuration has no such kernel, or the program's spans carry no such count (a
parent commit)."""

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
    pairs = [int(st["moe_pairs"]) for t in spans["ticks"] for name, _s, _e, st in t["children"]
             if name == "step_wait" and "moe_pairs" in st]
    if not pairs:
        return None
    return 100.0 * one["bytes"] * sum(pairs) / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s

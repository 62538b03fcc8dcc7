"""A named kernel inside one program, from the device trace. ``share``
``hbm``: the bytes its calls must move (the configuration's
``counts.kernel_counts``: bytes of one call over the rows a dispatch carries,
times the calls one program run makes, times the runs of ``program`` in the
slice) over the chip's published bandwidth, divided by the summed device time
of the ops whose name holds ``kernel`` under ``program/``, in percent: the
kernel's share of the HBM roof. ``share`` ``time``: those ops' time over the
program's whole device time, in percent. A share over 105% is a wrong count,
not a fast kernel. Returns None where the trace holds no such op or the
configuration's counts module knows no such kernel (a configuration, or a
parent commit, without it)."""


def read(ctx, kernel: str, program: str, share: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    kernel_s = sum(secs for label, secs in trace["device_ops"]
                   if label.startswith(program) and kernel in label.split("/", 1)[-1])
    runs = [d for name, durations in trace["modules"].items() if program in name for d in durations]
    if kernel_s <= 0.0 or not runs:
        return None
    if share == "time":
        return 100.0 * kernel_s / sum(runs)
    counts = getattr(ctx["counts"], "kernel_counts", None)
    one = counts(ctx["model"], kernel, rows=int(ctx["conf"]["engine"]["slots"])) if counts else None
    if one is None:
        return None
    need = one["bytes"] * one["calls_per_program"] * len(runs)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s

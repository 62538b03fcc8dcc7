"""What one program spends OUTSIDE its kernels, from the device trace, in
percent: the summed device time of the ops under ``program/`` that are not
custom calls (a Pallas kernel is one; ``trace_reduce`` has already left the
containers out: a ``while``, a ``conditional``, a ``call``, whose bodies' ops are
events of their own) over the program's whole device time.

This is how an XLA form with no kernel of its own is read: a fusion's name in
the trace is ``fusion.N``, whatever ``jax.named_scope`` it was traced under (the
scope is in the instruction's metadata, not in its name), so a reader that
matches names (``kernel_roofline.py``) cannot find it. What the share holds for
a program is said where its metric is (PERF.md section 3): for ``forward`` of a
state-space stack, the SSD mixer's chunk form and beside it the norms, the
router, the sort of the pairs, the convolution and the gates. Returns None
where the trace holds no such program."""


def read(ctx, program: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    xla_s = sum(secs for label, secs in trace["device_ops"]
                if label.startswith(program + "/") and not label.endswith(" custom-call"))
    runs = [d for name, durations in trace["modules"].items() if program in name for d in durations]
    if xla_s <= 0.0 or not runs:
        return None
    return 100.0 * xla_s / sum(runs)

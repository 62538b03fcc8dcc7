"""The scheduler loop's stalls inside the run's window, from the flight
recorder's stall ring (``dllama_tpu/runtime/flightrec.py``: every ONE interval
of the loop's life, a phase, a gap between two phases or between two ticks,
that lasted a quarter second or more leaves a record there, always on, traced
or not). The recorder is the process's own, read as ``engine_build.py`` reads
the engine; its stamps are ``time.monotonic_ns()``, the clock of the load
generator's ``time.monotonic()``.

The window is ``[first submit, last token]`` over what the load generator
sent, so warm-up, the probe and the reference check lie outside it. ``what``:

* ``share``: the records' ``ms`` summed over the window's length, in percent
  (2.58 s in 45 s is 5.7);
* ``max``: the longest record's ``ms``.

Both are 0.0 where nothing stalled. A program without the ring gives nothing."""


def records_in_window(ctx):
    """``(records whose start lies in the window, the window's seconds)``, or
    ``None`` where the program keeps no stall ring or the window is empty."""
    from dllama_tpu.runtime import flightrec

    stalls = flightrec.recorder().snapshot().get("stalls")
    ends = [s.token_times[-1] for s in ctx["sent"] if s.token_times]
    if stalls is None or not ends:
        return None
    lo, hi = min(s.t_submit for s in ctx["sent"]), max(ends)
    if hi <= lo:
        return None
    return [r for r in stalls if lo <= r["t_start_ns"] * 1e-9 <= hi], hi - lo


def read(ctx, what: str):
    found = records_in_window(ctx)
    if found is None:
        return None
    records, window_s = found
    if what == "share":
        return 100.0 * sum(r["ms"] for r in records) * 1e-3 / window_s
    if what == "max":
        return max((r["ms"] for r in records), default=0.0)
    raise ValueError(f"loop_stalls reads 'share' or 'max', not {what!r}")

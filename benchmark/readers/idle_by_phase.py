"""Device idle time split by what the scheduler's loop was doing, as a share
of the traced window in percent (``program_spans.py``: the program's
``dllama.tick.<phase>`` spans against device 0's idle stretches).

``what``: ``host`` = idle under any phase but ``idle_wait`` (the host holding
the chip back while there is work); ``no_work`` = idle under ``idle_wait``
(the loop asleep: headroom at the cell's offered rate); ``unspanned`` = the
rest of the idle time, so that the three add up to ``device_idle_share``
exactly: stretches no phase span covers, and whatever the trace's own extent
differs from the window on the host's clock. A program without the spans
gives nothing."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, what: str):
    spans = program_spans.of_run(ctx)
    if spans is None or spans["idle"] is None:
        return None
    t, by = ctx["trace"], spans["idle"]["by_phase"]
    no_work = by.get(program_spans.NO_WORK, 0.0)
    host = sum(by.values()) - no_work
    if what == "host":
        part = host
    elif what == "no_work":
        part = no_work
    elif what == "unspanned":
        part = (t["window_s"] - t["busy_s"]) - host - no_work
    else:
        raise ValueError(what)
    return 100.0 * part / t["window_s"]

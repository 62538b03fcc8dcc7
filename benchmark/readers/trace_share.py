"""A share of the traced window, in percent, from the trace reduction:
``idle`` (1 - union of device op intervals over the window, mean over chips)
or ``sync`` (collective ops' time over device busy time)."""


def read(ctx, what: str):
    t = ctx["trace"]
    if t is None:
        return None
    if what == "idle":
        return 100.0 * t["idle_share"]
    if what == "sync":
        return 100.0 * t["collective_s"] / t["busy_s"] if t["busy_s"] else None
    raise ValueError(what)

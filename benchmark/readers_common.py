"""What more than one reader needs."""

import statistics


def module_seconds(ctx, match: str):
    """Median device seconds of the program whose module name contains
    ``match``; of several such programs, the one with the longest median."""
    if ctx["trace"] is None:
        return None
    groups = {n: d for n, d in ctx["trace"]["modules"].items() if match in n}
    if not groups:
        return None
    return max(statistics.median(d) for d in groups.values())

"""Median over the window's requests of a quantity built from the program's own
``Request`` stamps (``runtime/serving.py``): monotonic host stamps taken at the
scheduler's boundaries. ``what``: ``queue_wait`` (t_admit - t_submit) or
``decode_step`` (ms_decode_steps per emitted token: the paged step fetches its
tokens, so this wall includes the device). ``ms_prefill`` is not read: the
paged prefill dispatch is not fetch-forced, so it is the time to enqueue."""

import statistics


def read(ctx, what: str):
    vals = []
    for s in ctx["sent"]:
        r = s.req
        if r is None or not r.t_admit:
            continue
        if what == "queue_wait":
            vals.append((r.t_admit - r.t_submit) / 1e6)
        elif what == "decode_step" and r.tokens and r.ms_decode_steps > 0:
            vals.append(r.ms_decode_steps / len(r.tokens))
    return statistics.median(vals) if vals else None

"""The mean of what the load generator timed at the ``on_token`` callback:
``itl_ms`` (every gap between a request's consecutive tokens, of every request
the window sent, those its end cut included: all the decode time of the window
over all the tokens after a first) or ``ttft_ms``. A mean moves with every
tick's length and with the share of ticks that carry a prefill chunk, and a
stall counts at its whole length; it does not see a tail get longer while most
gaps get shorter, so it stands beside a judged percentile
(``loadgen_percentile``), never in place of one (PERF.md section 2)."""

import numpy as np


def read(ctx, what: str):
    xs = ctx["summary"][what]
    return float(np.mean(np.asarray(xs, dtype=np.float64))) if len(xs) else None

"""A percentile of what the load generator timed at the ``on_token`` callback:
``ttft_ms`` (from due, open loop, or submit, closed loop, to the first token) or
``itl_ms`` (every gap between a request's consecutive tokens)."""

import numpy as np


def read(ctx, what: str, q: float):
    xs = ctx["summary"][what]
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) if len(xs) else None

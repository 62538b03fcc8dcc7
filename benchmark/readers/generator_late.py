"""How late the load generator sent requests against their due times: the 95th
percentile, in ms. A starved generator must not read as a fast server."""

import numpy as np


def read(ctx):
    late = ctx["summary"]["lateness_ms"]
    return float(np.percentile(late, 95)) if late else None

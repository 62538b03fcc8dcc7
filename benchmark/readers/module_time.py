"""Device time of one program (an XLA module in the trace), in ms: the median
duration of the module events whose name contains ``match``. Where several
programs share the name (the prefill buckets are all ``forward``), the one
with the longest median is taken: the widest bucket."""

from readers_common import module_seconds   # run.py puts benchmark/ on sys.path


def read(ctx, match: str):
    t = module_seconds(ctx, match)
    return None if t is None else t * 1e3

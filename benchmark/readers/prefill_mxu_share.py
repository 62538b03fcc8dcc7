"""The widest prefill chunk against the MXU roof, in percent: the FLOPs one chip
needs for a chunk of the widest bucket the traffic reaches, at the mean depth
of the window's prompts (the configuration's ``counts.prefill_chunk_flops``),
over the published bf16 peak, divided by that program's device time."""

import statistics

from readers_common import module_seconds   # run.py puts benchmark/ on sys.path


def read(ctx, match: str):
    chunk_s = module_seconds(ctx, match)
    prompts = [len(s.prompt) for s in ctx["sent"] if s.req is not None]
    if chunk_s is None or not prompts:
        return None
    widest = max(b for b in ctx["engine"].prefill_buckets if b <= max(prompts) - 1 or
                 b == min(ctx["engine"].prefill_buckets))
    depth = max(0.0, statistics.mean(prompts) / 2.0 - widest / 2.0)
    need = ctx["counts"].prefill_chunk_flops(ctx["model"], chunk=widest, context_before=depth, chips=ctx["chips"])
    return 100.0 * need / ctx["peaks"]["bf16_flops"] / chunk_s

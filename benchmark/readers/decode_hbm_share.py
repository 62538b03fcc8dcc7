"""The decode step against the HBM roof, in percent: the bytes one chip must
read for one step (the configuration's ``counts.decode_step_bytes``: weights as
held, the head, the cache rows of the contexts that were live, by the mean of
the sampled ``dllama_kv_blocks_used``) over the chip's published bandwidth,
divided by the step program's device time. A decode step at 16 rows is
memory-bound on this chip, so this is its roofline share;
``peaks.roofline_seconds`` says so."""

from readers_common import module_seconds   # run.py puts benchmark/ on sys.path


def read(ctx, match: str):
    step_s = module_seconds(ctx, match)
    c, s = ctx["counters"], ctx["samples"]
    if step_s is None or not c["steps"] or not s:
        return None
    rows = c["tokens"] / c["steps"]
    context = s["kv_used_mean"] * int(ctx["conf"]["engine"]["kv_block_size"])
    need = ctx["counts"].decode_step_bytes(ctx["model"], rows=rows, context_tokens=context, chips=ctx["chips"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / step_s

"""Mean active slots per decode step: tokens the batch emitted over decode
steps dispatched inside the window (``dllama_batch_tokens_total`` over the
count of ``dllama_batch_step_ms``)."""


def read(ctx):
    c = ctx["counters"]
    return c["tokens"] / c["steps"] if c["steps"] else None

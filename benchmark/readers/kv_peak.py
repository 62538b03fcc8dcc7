"""Peak of ``dllama_kv_blocks_used`` over ``dllama_kv_blocks_total``, in percent,
as the traced run's sampler saw it (polled every 20 ms)."""


def read(ctx):
    s = ctx["samples"]
    if not s or not s.get("kv_blocks_total"):
        return None
    return 100.0 * s["kv_used_peak"] / s["kv_blocks_total"]

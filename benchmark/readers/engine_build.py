"""Seconds ``InferenceEngine.__init__`` and the serving generator's
construction took, from the engine's own start-up stamps
(``engine.startup_s``: header, mesh plan, HBM budget, weight load, cache and
programs, pool fit, generator). An engine without the stamps gives nothing."""


def read(ctx):
    stamps = getattr(ctx["engine"], "startup_s", None)
    return sum(stamps.values()) if stamps else None

"""How the process came by its programs, from the registry's totals
(``dllama_programs_loaded_total`` / ``dllama_programs_traced_total`` /
``dllama_program_trace_seconds_total``: every program built through
``plan_scoped_jit`` counts once, loaded from the program store or traced,
lowered and compiled here). Set-up is over when a reader runs, so the totals
are warm-up's account. A program without the counters gives nothing."""


def read(ctx, what):
    from dllama_tpu.runtime import telemetry

    names = [getattr(telemetry, n, None)
             for n in ("PROGRAMS_LOADED", "PROGRAMS_TRACED", "PROGRAM_TRACE_SECONDS")]
    if None in names:
        return None
    reg = telemetry.registry()
    loaded, traced, trace_s = (reg.counter(n).total() for n in names)
    if what == "trace_s":
        return trace_s
    if what == "loaded_share":
        return 100.0 * loaded / (loaded + traced) if loaded + traced else None
    raise ValueError(f"program_store reads 'loaded_share' or 'trace_s', not {what!r}")

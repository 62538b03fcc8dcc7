"""The latent-attention chunk kernel against the MXU's roof, in percent. Its
work is what the chunks of the traced slice REALLY attended: while a profiler
listens each ``dllama.tick.prefill_dispatch`` span carries the chunk's padded
width (``bucket``) and the position it starts at (``start``), so a chunk's
queries see ``bucket * start + bucket (bucket + 1) / 2`` cached tokens between
them, in every layer; one query token on one cached token in one layer is the
configuration's ``counts.kernel_counts(model, <step kernel>)["flops"]`` (every
head's score over the row's useful lanes and value over the latent's: the chunk
form is absorbed as the step is). Those FLOPs over the published bf16 peak,
divided by the summed device time of the ops whose name holds ``kernel`` under
``program/``. The kernel computes whole tiles (the masked half of a diagonal
block, a row's padding lanes): charged to its time, not credited. Returns None
where the trace holds no such op, the configuration has no such count, or the
spans carry no ``start`` (a parent commit)."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, kernel: str, program: str, token_counts: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    kernel_s = sum(secs for label, secs in trace["device_ops"]
                   if label.startswith(program) and kernel in label.split("/", 1)[-1])
    counts = getattr(ctx["counts"], "kernel_counts", None)
    one = counts(ctx["model"], token_counts, rows=1) if counts else None
    spans = program_spans.of_run(ctx)
    if kernel_s <= 0.0 or one is None or spans is None:
        return None
    chunks = [(int(st["bucket"]), int(st["start"])) for t in spans["ticks"] for name, _s, _e, st in t["children"]
              if name == "prefill_dispatch" and "start" in st and int(st.get("bucket", 0))]
    if not chunks:
        return None
    attended = sum(b * s + b * (b + 1) / 2.0 for b, s in chunks)
    return 100.0 * attended * one["layers"] * one["flops"] / ctx["peaks"]["bf16_flops"] / kernel_s

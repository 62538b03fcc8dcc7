"""The latent-attention step kernel against one of the chip's roofs, in
percent. Its work is what the steps of the traced slice REALLY walked: each
decode step's ``dllama.tick.step_wait`` span carries ``mla_walk_blocks``, the
cache blocks its rows' walks read (``ceil((pos + 1) / block)`` a live row), so
the cached tokens read are those times the block size, in every layer; one
cached token one row reads in one layer is the configuration's
``counts.kernel_counts`` (``bytes``: the useful lanes of its row; ``flops``:
every head's score over them and value over the latent's). ``roof`` ``hbm``:
those bytes over the published bandwidth; ``mxu``: those FLOPs over the
published bf16 peak; either divided by the summed device time of the ops whose
name holds ``kernel`` under ``program/``. Summed from what each step's span
carries, never from slots x context: a count of what was not walked could pass
100%. The kernel fetches whole groups of blocks and pads a row to whole lane
tiles; both are charged to its time and not credited. Returns None where the
trace holds no such op, the configuration has no such kernel, or the spans
carry no such count (a parent commit)."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, kernel: str, program: str, roof: str):
    trace = ctx["trace"]
    if trace is None:
        return None
    kernel_s = sum(secs for label, secs in trace["device_ops"]
                   if label.startswith(program) and kernel in label.split("/", 1)[-1])
    counts = getattr(ctx["counts"], "kernel_counts", None)
    one = counts(ctx["model"], kernel, rows=1) if counts else None
    spans = program_spans.of_run(ctx)
    if kernel_s <= 0.0 or one is None or spans is None:
        return None
    blocks = [int(st["mla_walk_blocks"]) for t in spans["ticks"] for name, _s, _e, st in t["children"]
              if name == "step_wait" and "mla_walk_blocks" in st]
    if not blocks:
        return None
    tokens = sum(blocks) * int(ctx["conf"]["engine"]["kv_block_size"]) * one["layers"]
    need_s = (tokens * one["bytes"] / ctx["peaks"]["hbm_bytes_per_s"] if roof == "hbm"
              else tokens * one["flops"] / ctx["peaks"]["bf16_flops"])
    return 100.0 * need_s / kernel_s

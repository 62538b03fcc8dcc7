"""The paged-attention step kernel against the HBM roof, in percent. Its work
is what the steps of the traced slice REALLY walked: each decode step's
``dllama.tick.step_wait`` span carries ``kv_walk_blocks``, the cache blocks its
live rows' walks read (``ceil((pos + 1) / block)`` a row), so the cached tokens
read are those times the block size, in every layer that caches K and V; one
cached token one row reads in one layer is the configuration's
``counts.kernel_counts`` (``bytes``: the USEFUL lanes of its K and V rows). Those
bytes over the published bandwidth, divided by the summed device time of the
ops whose name holds ``kernel`` under ``program/``. Summed from what each step's
span carries, never from slots x context: a count of what was not walked
could pass 100%. Where the pool pads a head to whole lane tiles (64 lanes held
in 128) the kernel moves twice the useful bytes; the padding, the whole groups
of blocks it fetches and a dead row's skipped walk are charged to its time and
not credited. Returns None where the trace holds no such op, the configuration
has no such kernel, or the spans carry no such count (a parent commit)."""

import program_spans   # run.py puts benchmark/ on sys.path


def read(ctx, kernel: str, program: str):
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
    blocks = [int(st["kv_walk_blocks"]) for t in spans["ticks"] for name, _s, _e, st in t["children"]
              if name == "step_wait" and "kv_walk_blocks" in st]
    if not blocks:
        return None
    tokens = sum(blocks) * int(ctx["conf"]["engine"]["kv_block_size"]) * one["layers"]
    return 100.0 * tokens * one["bytes"] / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s

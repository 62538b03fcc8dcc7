"""On-chip timing of the two recurrent-state step kernels alone, the pool in
place, over how many heads one grid step moves.

Table 1: ``ops/ssd.ssd_step`` at the three cells' geometries (falcon-h1-34b's
pool ``[12, 17, 32, 128, 256]`` in 2 groups, nemotron-3-super's ``[10, 33,
128, 64, 128]`` in 8, granite-4.0-h-small's ``[9, 17, 128, 64, 128]`` in 1)
at every admissible count of heads a grid step (each answer
``ssd.heads_per_step`` gives as its budget grows a head at a time; the count
the module's budget picks is marked ``*``). Table 2:
``ops/gated_delta.gated_delta_step`` at olmo-hybrid-7b's ``[24, 5, 30, 96,
192]`` (one decay a head) and solar-open2's ``[6, 17, 64, 128, 128]`` (a decay
a key channel) over the counts of ITS list that divide the heads. Neither
kernel has such a knob: the tool sets the module's constant around a fresh
trace of the function under the jitted entry.

One line a geometry and width: the state block's bytes, the grid steps a
call, microseconds a CALL (the kernel and the layout ops around it: the
slope of wall time over two iteration counts of one ``lax.fori_loop`` whose
carry is the pool, donated, the layer index walking the layers as a step
program's does: ``tools/gemv_sweep.py``'s method), microseconds the KERNEL
alone (the Mosaic op's device time a call in a profiler trace of that loop:
what the benchmark's ``<kernel>_hbm_share`` divides by), and for both GB/s
over the bytes ``benchmark/<family>/counts.py::kernel_counts`` reckons for
the call and the share of a v5e's 819 GB/s. A width the chip's compiler
refuses (four blocks over the scoped VMEM) prints ``refused``.

Then parity of the module's own width against the XLA twin, a dead slot
beside the live ones (1e-5 of the largest value asserted: the exit code is 1
where it fails).

Usage: python tools/state_step_sweep.py [--iters 20,100] [--only falcon,...]
           [--json-out FILE] [--rehearse]

Off a TPU nothing is timed: ``--rehearse`` walks the same control flow at toy
shapes in interpret mode and prints ``not measured`` where a time would stand.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

# name: (configuration, pool [layers, rows, H, P or dk, N or dv], groups or
# None, a decay a key channel)
SSD = {
    "falcon": ("falcon-h1-34b", (12, 17, 32, 128, 256), 2),
    "nemotron": ("nemotron-3-super-120b-a12b", (10, 33, 128, 64, 128), 8),
    "granite": ("granite-4.0-h-small", (9, 17, 128, 64, 128), 1),
}
DELTA = {
    "olmo": ("olmo-hybrid-7b", (24, 5, 30, 96, 192), False),
    "solar": ("solar-open2-250b", (6, 17, 64, 128, 128), True),
}
TOY_SSD = {"toy": (None, (2, 5, 16, 8, 128), 4)}
TOY_DELTA = {"toy": (None, (2, 3, 6, 8, 128), False),
             "toy-kda": (None, (2, 3, 6, 8, 128), True)}
HBM_GBPS = 819.0  # one v5e (benchmark/peaks.py)


def counted_bytes(config: str | None, kernel: str, fallback: float) -> float:
    """The bytes ``kernel_counts`` reckons for one call over the cell's slots
    (what ``readers/kernel_roofline.py`` hands it); the state alone, twice,
    for a toy shape."""
    if config is None:
        return fallback
    import run  # benchmark/run.py: the configuration as its modules see it

    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json"),
              encoding="utf-8") as f:
        conf = json.load(f)
    counts = run.load_modules(conf)["counts"]
    return counts.kernel_counts(run.model_view(conf), kernel,
                                rows=int(conf["engine"]["slots"]))["bytes"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", default="20,100")
    ap.add_argument("--only", default="")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import gated_delta as gd
    from dllama_tpu.ops import ssd

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU here: --rehearse walks the tool at toy shapes")
        return 2
    n_lo, n_hi = (int(n) for n in args.iters.split(","))
    if not on_chip:
        n_lo, n_hi = 1, 2
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    only = set(filter(None, args.only.split(",")))
    pick = lambda table: {k: v for k, v in table.items() if not only or k in only}
    f32 = jnp.float32
    results: list[dict] = []
    failed = False

    def looped(step):
        """``step(pool, layer, rows, *vectors) -> (y, pool)`` as one program
        of ``n`` calls: the pool is the carry (in place, donated), the layer
        walks the pool's layers, and ``y``'s mean feeds the first vector so
        that no call can be dropped."""
        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(n, pool, rows, first, *rest):
            def body(i, carry):
                pool, first = carry
                y, pool = step(pool, i % pool.shape[0], rows, first, *rest)
                return pool, first + 1e-6 * jnp.mean(y)

            return jax.lax.fori_loop(0, n, body, (pool, first))[0]

        return run

    def kernel_us(run, n, pool, operands, kernel: str):
        """The Mosaic op's device microseconds a call, from a profiler trace
        of one loop of ``n`` calls; the pool comes back for the next loop."""
        from jax.profiler import ProfileData
        from trace_reduce import op_label  # benchmark/: an op's own name, not its operands'

        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                pool = jax.block_until_ready(run(n, pool, *operands))
            spent = [ev.duration_ns
                     for path in glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
                     for plane in ProfileData.from_file(path).planes
                     if plane.name.startswith("/device:TPU:0")
                     for line in plane.lines if line.name == "XLA Ops"
                     for ev in line.events if kernel in op_label(ev.name).split(" ")[0]]
        return (sum(spent) / len(spent) / 1e3 if spent else None), pool

    def measure(step, pool, operands, kernel: str):
        """(microseconds a call by the slope, microseconds the kernel by the
        trace, the pool); (None, None, pool) off a TPU, where the loop runs
        once for its control flow alone."""
        run = looped(step)
        if not on_chip:
            return None, None, jax.block_until_ready(run(n_lo, pool, *operands))
        wall = {}
        pool = jax.block_until_ready(run(n_lo, pool, *operands))  # compile, warm
        for n in (n_lo, n_hi):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                pool = jax.block_until_ready(run(n, pool, *operands))
                best = min(best, time.perf_counter() - t0)
            wall[n] = best
        k_us, pool = kernel_us(run, n_hi, pool, operands, kernel)
        return (wall[n_hi] - wall[n_lo]) / (n_hi - n_lo) * 1e6, k_us, pool

    def line(table, gname, hb, block_bytes, grid, call_us, k_us, nbytes, mark):
        def cell(us):
            if us is None:
                return "not measured".rjust(32)
            return f"{us:8.1f} us {nbytes / us / 1e3:6.1f} GB/s {100 * nbytes / us / 1e3 / HBM_GBPS:5.1f}%"

        print(f"  {gname:<9} hb {hb:3d}{mark}  block {block_bytes / 1024:7.0f} KB  "
              f"{grid:4d} grid steps  call {cell(call_us)}  kernel {cell(k_us)}", flush=True)
        results.append({"table": table, "geometry": gname, "heads_per_step": hb,
                        "module_choice": mark == "*", "block_bytes": block_bytes,
                        "grid_steps": grid, "call_us": call_us, "kernel_us": k_us,
                        "counted_bytes": nbytes})

    def row(kernel, gname, hb, block_bytes, grid, step, pool, operands, nbytes, chosen):
        """Time one width and print its line; a width the chip's compiler
        refuses is a line too. Returns the pool for the next width."""
        try:
            call_us, k_us, pool = measure(step, pool, operands, kernel)
        except Exception as e:  # noqa: BLE001 — whatever the compiler says is the row
            print(f"  {gname:<9} hb {hb:3d}   block {block_bytes / 1024:7.0f} KB  refused: "
                  f"{str(e).strip().splitlines()[-1][:120]}", flush=True)
            results.append({"table": kernel, "geometry": gname, "heads_per_step": hb,
                            "block_bytes": block_bytes, "refused": True})
            return rnd(*pool.shape, scale=0.1)  # the refused call may have taken the donated one
        line(kernel, gname, hb, block_bytes, grid, call_us, k_us, nbytes, "*" if hb == chosen else " ")
        return pool

    rng = np.random.default_rng(59)
    rnd = lambda *shape, scale=1.0: jnp.asarray(rng.standard_normal(shape) * scale, f32)

    # -- table 1: ssd_step -------------------------------------------------------
    print(f"\nssd_step (state block budget {ssd._STATE_BLOCK_BYTES} bytes; * the module's choice)")
    for gname, (config, shape, G) in pick(SSD if on_chip else TOY_SSD).items():
        L, R, H, P, N = shape
        B = R - 1
        nbytes = counted_bytes(config, "ssd_step", 2.0 * B * H * P * N * 4)
        print(f" {gname}: pool {list(shape)} in {G} groups, {B} rows, "
              f"{nbytes / 1e6:.1f} MB a call ({nbytes / HBM_GBPS / 1e3:.1f} us at the roof)")
        pool = rnd(*shape, scale=0.1)
        rows = jnp.arange(1, R, dtype=jnp.int32)  # row 0 is the null row
        dt = jax.nn.softplus(rnd(B, H))
        operands = (rows, rnd(B, H, P), dt, jnp.exp(-dt * jnp.exp(rnd(H, scale=0.5))),
                    rnd(B, G, N), rnd(B, G, N))
        chosen = ssd.heads_per_step(H, G, P, N)
        was, rule, traced = ssd._STATE_BLOCK_BYTES, ssd.heads_per_step, []
        # what each trace asked the rule for: a loop that reused another width's trace says so
        ssd.heads_per_step = lambda *a: traced.append(rule(*a)) or traced[-1]
        for heads in range(1, H + 1):
            ssd._STATE_BLOCK_BYTES = heads * P * N * 4
            hb = rule(H, G, P, N)
            if hb != heads:  # not admissible: the rule's own answer under this budget is a smaller one
                continue
            del traced[:]
            # the function itself, traced anew inside this width's loop: the jitted entry
            # (and any ``jax.jit`` of the same function) keeps its first trace
            pool = row("ssd_step", gname, hb, ssd._STATE_BLOCK_BYTES, B * (H // hb),
                       functools.partial(ssd.ssd_step.__wrapped__, interpret=not on_chip),
                       pool, operands, nbytes, chosen)
            assert set(traced) == {hb}, (hb, traced)
        ssd._STATE_BLOCK_BYTES = was
        ssd.heads_per_step = rule
        # parity at the module's own width, a dead slot (the null row) beside live ones,
        # on a fresh pool (the loops' has grown by hundreds of tokens)
        pool = rnd(*shape, scale=0.1)
        rows_p = rows.at[B // 2].set(0)
        y_k, pool_k = ssd.ssd_step(pool, jnp.int32(L - 1), rows_p, *operands[1:], interpret=not on_chip)
        y_x, pool_x = jax.jit(ssd.ssd_step_xla)(pool, jnp.int32(L - 1), rows_p, *operands[1:])
        live = np.asarray(rows_p) != 0
        # against the largest value: a readout is a sum of N products
        err = max(float(jnp.abs(y_k - y_x)[live].max() / jnp.abs(y_x)[live].max()),
                  float(jnp.abs(pool_k[:, 1:] - pool_x[:, 1:]).max() / jnp.abs(pool_x[:, 1:]).max()))
        ok = err < 1e-5 and bool(jnp.all(pool_k[:L - 1] == pool[:L - 1]))  # and the other layers' bits
        failed |= not ok
        print(f"  parity {gname} hb {chosen}: max |diff| of readout and state against the XLA twin, "
              f"over the largest value, {err:.3e} {'PASS' if ok else 'FAIL'}")
        results.append({"geometry": gname, "parity": "ssd_step", "max_abs": err, "ok": ok})
        del pool, pool_k, pool_x

    # -- table 2: gated_delta_step (read only: its list as it stands) -------------
    print(f"\ngated_delta_step (the module's list {gd._HEADS_PER_STEP}; * its choice)")
    for gname, (config, shape, per_channel) in pick(DELTA if on_chip else TOY_DELTA).items():
        L, R, H, dk, dv = shape
        B = R - 1
        nbytes = counted_bytes(config, "gated_delta_step", 2.0 * B * H * dk * dv * 4)
        print(f" {gname}: pool {list(shape)}, a decay {'a key channel' if per_channel else 'a head'}, "
              f"{B} rows, {nbytes / 1e6:.1f} MB a call ({nbytes / HBM_GBPS / 1e3:.1f} us at the roof)")
        pool = rnd(*shape, scale=0.1)
        rows = jnp.arange(1, R, dtype=jnp.int32)
        alpha = jax.nn.sigmoid(rnd(*((B, H, dk) if per_channel else (B, H))) + 3.0)
        operands = (rows, gd.l2norm(rnd(B, H, dk)), gd.l2norm(rnd(B, H, dk)), rnd(B, H, dv),
                    alpha, jax.nn.sigmoid(rnd(B, H)))
        was = gd._HEADS_PER_STEP
        chosen = next(c for c in was if H % c == 0)
        for hb in sorted(c for c in was if H % c == 0):
            gd._HEADS_PER_STEP = (hb,)
            pool = row("gated_delta_step", gname, hb, hb * dk * dv * 4, B * (H // hb),
                       functools.partial(gd.gated_delta_step.__wrapped__, interpret=not on_chip),
                       pool, operands, nbytes, chosen)
        gd._HEADS_PER_STEP = was
        del pool

    if args.json_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.json_out)), exist_ok=True)
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump({"tool": "state_step_sweep", "platform": dev.platform,
                       "device_kind": dev.device_kind, "iters": [n_lo, n_hi], "rows": results}, f)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

"""On-chip timing of the gated delta rule's CHUNK form alone
(``ops/gated_delta.py``): the XLA form against the Pallas kernel, at
olmo-hybrid-7b's geometry (``H 30, dk 96, dv 192``, one decay a head) and
solar-open2-250b's (``H 64, dk 128, dv 128``, a decay a key channel), one
sequence, over chunks of T = 32, 64, 128, 256 tokens and, for the kernel,
over the head groups a grid step may take (every divisor of ``H`` whose block
the module's budget rule would give as the budget grows; ``*`` marks the
module's own choice).

One line a geometry, T and form: microseconds a CALL (the form and the
layout ops around it: the slope of wall time over two iteration counts of
one ``lax.fori_loop`` whose carry is the state, ``tools/state_step_sweep.py``'s
method), the device's busy microseconds a call (the union of the ``XLA Ops``
events of a profiler trace of that loop, over its calls) and, for the kernel,
the Mosaic op's own device microseconds a call. A width the chip's compiler
refuses prints ``refused``.

Then parity of the kernel at the module's own width against the XLA twin (1e-5
of the largest value asserted) and against the per-token recurrence (5e-5: on
the chip the twin itself stands 1.0-1.4e-5 from it), with a nonzero state: the
exit code is 1 where it fails.

Usage: python tools/delta_chunk_sweep.py [--iters 10,40] [--only olmo,solar]
           [--tokens 32,64,128,256] [--json-out FILE] [--rehearse]

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

# name: (H, dk, dv, a decay a key channel)
GEOMETRY = {"olmo": (30, 96, 192, False), "solar": (64, 128, 128, True)}
TOY = {"toy": (6, 8, 128, False), "toy-kda": (4, 8, 128, True)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", default="10,40")
    ap.add_argument("--only", default="")
    ap.add_argument("--tokens", default="32,64,128,256")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import gated_delta as gd

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU here: --rehearse walks the tool at toy shapes")
        return 2
    n_lo, n_hi = (int(n) for n in args.iters.split(","))
    tokens = [int(t) for t in args.tokens.split(",")]
    if not on_chip:
        n_lo, n_hi, tokens = 1, 2, [32, 128]
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")
    only = set(filter(None, args.only.split(",")))
    f32 = jnp.float32
    results: list[dict] = []
    failed = False
    rng = np.random.default_rng(61)
    rnd = lambda *shape, scale=1.0: jnp.asarray(rng.standard_normal(shape) * scale, f32)

    def operands(T, H, dk, dv, per_channel):
        """One sequence's chunk as the mixer hands it: keys behind a SiLU
        (nearly parallel), unit length; a log decay of -0.05 a token on
        average; a write strength in [0, 2]."""
        k = gd.l2norm(jax.nn.silu(rnd(1, T, H, dk) + 1.0))
        q = gd.l2norm(rnd(1, T, H, dk)) * dk ** -0.5
        g = -jax.nn.sigmoid(rnd(*((1, T, H, dk) if per_channel else (1, T, H)))) * 0.1
        return q, k, rnd(1, T, H, dv), g, jax.nn.sigmoid(rnd(1, T, H)) * 2.0

    def looped(form):
        """``form(q, k, v, g, beta, S) -> (o, S)`` as one program of ``n``
        calls: the state is the carry (donated), and ``o``'s mean, a millionth
        of it, moves EVERY operand of the next call, so that no call can be
        dropped and nothing of a call (the pairs, the solve: all but ``v``'s
        part is a function of q, k, g and beta alone) can be lifted out of
        the loop."""
        @functools.partial(jax.jit, donate_argnums=(1,))
        def run(n, S, q, k, v, g, beta):
            def body(_i, carry):
                S, eps = carry
                o, S = form(q + eps, k + eps, v + eps, g - jnp.abs(eps), beta + eps, S)
                return S, 1e-6 * jnp.mean(o)

            return jax.lax.fori_loop(0, n, body, (S, jnp.float32(0.0)))[0]

        return run

    def traced_us(run, n, S, ops):
        """(the device's busy microseconds a call, the Mosaic op's
        microseconds a call or None, the state) from a profiler trace of one
        loop of ``n`` calls."""
        from jax.profiler import ProfileData
        from trace_reduce import op_label  # benchmark/: an op's own name, not its operands'

        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                S = jax.block_until_ready(run(n, S, *ops))
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_label(ev.name).split(" ")[0])
                      for path in glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
                      for plane in ProfileData.from_file(path).planes
                      if plane.name.startswith("/device:TPU:0")
                      for line in plane.lines if line.name == "XLA Ops"
                      for ev in line.events]
        busy, end = 0.0, 0.0
        for s, e, _name in sorted(events):   # the union: a loop's own event spans its body's
            if e > end:
                busy += e - max(s, end)
                end = e
        kernel = [e - s for s, e, name in events if "gated_delta_chunk" in name]
        return busy / n / 1e3, (sum(kernel) / len(kernel) / 1e3 if kernel else None), S

    def measure(form, S, ops):
        """(microseconds a call by the slope, busy microseconds a call and
        the kernel's by the trace, the state); Nones off a TPU, where the
        loop runs once for its control flow alone."""
        run = looped(form)
        if not on_chip:
            return None, None, None, jax.block_until_ready(run(n_lo, S, *ops))
        wall = {}
        S = jax.block_until_ready(run(n_lo, S, *ops))  # compile, warm
        for n in (n_lo, n_hi):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                S = jax.block_until_ready(run(n, S, *ops))
                best = min(best, time.perf_counter() - t0)
            wall[n] = best
        busy_us, k_us, S = traced_us(run, n_hi, S, ops)
        return (wall[n_hi] - wall[n_lo]) / (n_hi - n_lo) * 1e6, busy_us, k_us, S

    us = lambda x: "not measured" if x is None else f"{x:9.1f} us"

    def row(gname, T, form_name, hb, mark, form, S, ops):
        try:
            call_us, busy_us, k_us, S = measure(form, S, ops)
        except Exception as e:  # noqa: BLE001 — whatever the compiler says is the row
            print(f"  {gname:<8} T {T:3d}  {form_name:<6} hb {hb:2d}   refused: "
                  f"{str(e).strip().splitlines()[-1][:120]}", flush=True)
            results.append({"geometry": gname, "T": T, "form": form_name,
                            "heads_per_step": hb, "refused": True})
            return rnd(*S.shape, scale=0.1)  # the refused call may have taken the donated one
        print(f"  {gname:<8} T {T:3d}  {form_name:<6} hb {hb:2d}{mark}  call {us(call_us)}  "
              f"device busy {us(busy_us)}  kernel {us(k_us)}", flush=True)
        results.append({"geometry": gname, "T": T, "form": form_name, "heads_per_step": hb,
                        "module_choice": mark == "*", "call_us": call_us,
                        "busy_us": busy_us, "kernel_us": k_us})
        return S

    table = GEOMETRY if on_chip else TOY
    for gname, (H, dk, dv, per_channel) in table.items():
        if only and gname not in only:
            continue
        print(f"\n{gname}: H {H}, dk {dk}, dv {dv}, a decay {'a key channel' if per_channel else 'a head'}"
              f" (block budget {gd._CHUNK_BLOCK_BYTES} bytes; * the module's choice)")
        rule = gd.chunk_heads_per_step
        for T in tokens:
            ops = operands(T, H, dk, dv, per_channel)
            S = rnd(1, H, dk, dv, scale=0.1)
            S = row(gname, T, "xla", 0, " ", gd.gated_delta_chunk_xla, S, ops)
            C = min(T, gd.SUB_CHUNK)
            chosen = rule(H, C, dk, dv, per_channel)
            for hb in (c for c in range(1, H + 1) if H % c == 0):
                if hb > 2 * chosen and hb != H:
                    continue
                # the function itself, traced anew inside this width's loop: the
                # jitted entry keeps its first trace
                gd.chunk_heads_per_step = lambda *a, hb=hb: hb
                S = row(gname, T, "pallas", hb, "*" if hb == chosen else " ",
                        functools.partial(gd.gated_delta_chunk.__wrapped__, interpret=not on_chip),
                        S, ops)
            gd.chunk_heads_per_step = rule
            # parity at the module's own width, on a fresh nonzero state
            S = rnd(1, H, dk, dv, scale=0.1)
            o_k, S_k = gd.gated_delta_chunk(*ops, S, interpret=not on_chip)
            o_x, S_x = jax.jit(gd.gated_delta_chunk_xla)(*ops, S)
            o_r, S_r = jax.jit(gd.gated_delta_recurrent)(*ops, S)
            rel = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())
            err_x = max(rel(o_k, o_x), rel(S_k, S_x))
            err_r = max(rel(o_k, o_r), rel(S_k, S_r))
            ok = err_x < 1e-5 and err_r < 5e-5
            failed |= not ok
            print(f"  parity {gname} T {T} hb {chosen}: max |diff| over the largest value, against the XLA "
                  f"twin {err_x:.3e}, against the recurrence {err_r:.3e} (the twin against it "
                  f"{max(rel(o_x, o_r), rel(S_x, S_r)):.3e}) {'PASS' if ok else 'FAIL'}", flush=True)
            results.append({"geometry": gname, "T": T, "parity": "gated_delta_chunk",
                            "against_xla": err_x, "against_recurrence": err_r, "ok": ok})

    if args.json_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.json_out)), exist_ok=True)
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump({"tool": "delta_chunk_sweep", "platform": dev.platform,
                       "device_kind": dev.device_kind, "iters": [n_lo, n_hi], "rows": results}, f)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

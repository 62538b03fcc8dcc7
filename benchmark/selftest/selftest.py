#!/usr/bin/env python3
"""The benchmark's own self-test: ``python3 benchmark/selftest/selftest.py``.

Runs on the CPU, outside ``tests/``, in about five minutes. It rehearses the
whole command at a tiny preset (``selftest/manifest.json``; the tiny
configurations name their device ``cpu`` and report counts only) and checks
the parts of the yardstick that need no chip:

1. the trace reduction on ``fixtures/tiny.xplane.pb`` (busy union, idle share,
   collective share, ``op_label`` names under their program's, widest-program
   choice);
2. the peaks table (an unknown device kind raises) and the dense decoders'
   bytes and FLOPs functions (``counts.py``) on numbers worked by hand;
3. the traffic generator (a seed repeats itself; every seed gets the same
   multiset of sizes and gaps), and every ``test_*.py`` beside this file
   (pytest): ``test_trace_slice.py`` (every mix's traced slice is anchored on
   arrivals, a line that lacks a metric names it, the breakdown names programs
   and tick phases), ``test_modules.py`` (the seam through which a
   configuration brings its reference, weight-maker and counts) and
   ``test_broken_path.py`` (the program alters tokens: ``correct`` false);
4. the command end to end: the output line's keys, a Qwen3-style block against
   the reference, the tp=4 path on four virtual devices, the negative control
   turning ``correct`` false, every workload of ``manifest.json`` that carries
   a ``selftest`` entry (``{"seed": n, "controls": [..]}``: ``correct`` true,
   and false under each control; how a configuration with modules of its own
   joins the self-test by adding files and entries), and every real cell
   refusing to run without a TPU.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

FAILED: list[str] = []


def check(name: str, ok: bool, detail: str = "") -> None:
    print(("ok   " if ok else "FAIL ") + name + (f"  [{detail}]" if detail and not ok else ""), flush=True)
    if not ok:
        FAILED.append(name)


def near(a: float, b: float, rel: float = 1e-6) -> bool:
    return abs(a - b) <= rel * max(1.0, abs(b))


def test_trace_reduce() -> None:
    import trace_reduce as tr

    r = tr.reduce(os.path.join(BENCH, "fixtures", "tiny.xplane.pb"), 0.010)
    check("trace: two device lanes, Async line ignored", r["n_devices"] == 2 and r["n_events"] == 7)
    check("trace: busy union 6 ms (nested wait not counted twice)", near(r["busy_s"], 0.006))
    check("trace: idle share 0.4", near(r["idle_share"], 0.4))
    check("trace: collective 1.5 ms (all-reduce by opcode, psum by name)", near(r["collective_s"], 0.0015))
    check("trace: op_label names, each under the program that ran it",
          r["device_ops"][0][0] == "paged_sampled_step_guarded/fusion.1 fusion" and near(r["device_ops"][0][1], 0.004)
          and ["forward/fusion.1 fusion", 0.001] in [[k, round(v, 9)] for k, v in r["device_ops"]]
          and tr.op_label("%psum.9 = f32[4096]{0} all-reduce(%x)") == "psum.9 all-reduce"
          and tr.op_label("dot_general.1") == "dot_general.1")
    check("trace: the gap is labelled by the callback's span", r["idle_gaps"] == [["bench.on_token", r["idle_gaps"][0][1]]]
          and near(r["idle_gaps"][0][1], 0.002))
    from readers_common import module_seconds

    ctx = {"trace": r}
    check("trace: widest of the programs that share a name", near(module_seconds(ctx, "jit_forward"), 0.003)
          and near(module_seconds(ctx, "paged_sampled_step"), 0.007)
          and module_seconds(ctx, "no_such_program") is None)


def test_peaks() -> None:
    import counts
    import peaks

    try:
        peaks.peaks("TPU v9 imaginary")
        check("peaks: unknown device kind raises", False)
    except peaks.UnknownDeviceKind:
        check("peaks: unknown device kind raises", True)
    p = peaks.peaks("TPU v5 lite")
    check("peaks: v5e row", p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9)
    m = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
         "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768}
    w = 32 * (4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336)
    check("peaks: Mistral-7B layer weights", counts.layer_matmul_weights(m) == w == 6979321856)
    want = w * (1 + 2 / 32) + 32768 * 4096 * 2 + 2 * 32 * 1024 * 2 * 8000 + 16 * 4096 * 2
    check("peaks: decode step bytes", near(counts.decode_step_bytes(m, rows=16, context_tokens=8000), want))
    want_f = 2 * 256 * w + 4 * 32 * 4096 * (256 * 1000 + 256 * 257 / 2)
    check("peaks: prefill chunk FLOPs", near(counts.prefill_chunk_flops(m, chunk=256, context_before=1000), want_f))
    t, roof = peaks.roofline_seconds(counts.decode_step_flops(m, rows=16, context_tokens=8000),
                                     counts.decode_step_bytes(m, rows=16, context_tokens=8000), "TPU v5 lite")
    check("peaks: a 16-row decode step is memory-bound", roof == "memory" and 0.009 < t < 0.012)


def test_traffic() -> None:
    import traffic

    for name in ("batch-decode", "chat", "long-prompt"):
        mix = traffic.load(os.path.join(BENCH, "traffic", name + ".json"))
        a = traffic.plan(mix, seed=7, seconds=20, vocab_size=1000)
        b = traffic.plan(mix, seed=7, seconds=20, vocab_size=1000)
        c = traffic.plan(mix, seed=3000000011, seconds=20, vocab_size=1000)
        same = [(r.due_s, r.new_tokens, r.max_tokens) for r in a.requests] == \
               [(r.due_s, r.new_tokens, r.max_tokens) for r in b.requests]
        sizes = lambda p: sorted((len(r.new_tokens), r.max_tokens) for r in p.requests)  # noqa: E731
        check(f"traffic {name}: a seed repeats itself", same)
        check(f"traffic {name}: every seed gets the same sizes", sizes(a) == sizes(c)
              and [r.new_tokens for r in a.requests] != [r.new_tokens for r in c.requests])
        if a.loop == "open":
            check(f"traffic {name}: all arrivals inside the window", all(0 <= r.due_s < 20 for r in a.requests)
                  and len(a.requests) == len(c.requests) == round(mix["rate_per_s"] * 20))


def test_pytest_files() -> None:
    for path in sorted(glob.glob(os.path.join(HERE, "test_*.py"))):
        p = subprocess.run([sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider"],
                           env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
                           capture_output=True, text=True, timeout=900)
        check(f"pytest: {os.path.basename(path)} passes", p.returncode == 0, p.stdout[-600:])


def run_cmd(args: list[str], *, devices: int = 1, manifest: bool = True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(BENCH, "run.py")] + args
    if manifest:
        cmd += ["--manifest", os.path.join(HERE, "manifest.json")]
    p = subprocess.run(cmd, env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p.returncode, (lines[-1] if lines else ""), p.stderr


def test_command() -> None:
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    rc, line, err = run_cmd(["--workload", "tiny.closed", "--seed", "4000000123", "--seconds", "3", "--trace", "0"])
    r = json.loads(line) if rc == 0 and line else {}
    check("command: tiny closed loop exits 0 with the line's keys", rc == 0 and keys <= set(r), err[-400:])
    check("command: correct, nothing failed, device named cpu",
          r.get("correct") is True and r.get("failed") == 0 and r.get("attempted", 0) > 0
          and r.get("device", {}).get("platform") == "cpu")
    check("command: a CPU run reports counts only",
          all(m["unit"] == "count" for m in r.get("metrics", {}).values()) and bool(r.get("metrics")))
    check("command: nothing compiled inside the window", r.get("metrics", {}).get("window_compiles", {}).get("value") == 0)

    rc, line, err = run_cmd(["--workload", "tiny.open", "--seed", "5", "--seconds", "3", "--trace", "1"])
    r = json.loads(line) if rc == 0 and line else {}
    check("command: tiny open loop, traced, has busy_s, window_s and a breakdown",
          rc == 0 and r.get("correct") is True and r["device"].get("busy_s", 0) > 0
          and r["device"].get("window_s", 0) > 0 and "device_ops" in r.get("breakdown", {}), err[-400:])

    rc, line, err = run_cmd(["--workload", "tiny-qwen3.closed", "--seed", "6", "--seconds", "2"])
    r = json.loads(line) if rc == 0 and line else {}
    check("command: q/k norm and half-split rope agree with the reference", rc == 0 and r.get("correct") is True, err[-400:])

    rc, line, err = run_cmd(["--workload", "tiny.tp4", "--seed", "7", "--seconds", "2"], devices=4)
    r = json.loads(line) if rc == 0 and line else {}
    check("command: tp=4 on four virtual devices", rc == 0 and r.get("correct") is True
          and r.get("device", {}).get("count") == 4, err[-400:])

    rc, line, err = run_cmd(["--workload", "tiny.closed", "--seed", "8", "--seconds", "2", "--control", "droplayer"])
    r = json.loads(line) if rc == 0 and line else {}
    check("command: the negative control turns correct false", rc == 0 and r.get("correct") is False
          and r["gap"]["max"] > r["gap"]["tolerance"], err[-400:])

    for w in json.load(open(os.path.join(HERE, "manifest.json"), encoding="utf-8"))["workloads"]:
        spec = w.get("selftest")
        if spec is None:
            continue
        for control in ["none"] + spec["controls"]:
            rc, line, err = run_cmd(["--workload", w["name"], "--seed", str(spec["seed"]), "--seconds", "2",
                                     "--control", control], devices=w["chips"])
            r = json.loads(line) if rc == 0 and line else {}
            check(f"command: {w['name']} is " + ("correct against its own reference" if control == "none"
                                                 else f"not correct under its control {control}"),
                  rc == 0 and r.get("correct") is (control == "none") and r.get("failed") == 0, err[-400:])

    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8"))
    for w in manifest["workloads"]:
        rc, line, err = run_cmd(["--workload", w["name"], "--seed", "1", "--seconds", "1"],
                                devices=w["chips"], manifest=False)
        check(f"command: {w['name']} refuses to run without a TPU", rc != 0 and not line, f"rc={rc} line={line[:80]}")


def main() -> int:
    test_trace_reduce()
    test_peaks()
    test_traffic()
    test_pytest_files()
    if "--fast" not in sys.argv:
        test_command()
    print(f"{len(FAILED)} failed" if FAILED else "all passed", flush=True)
    return 1 if FAILED else 0


if __name__ == "__main__":
    raise SystemExit(main())

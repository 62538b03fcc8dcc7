#!/usr/bin/env python3
"""One run of one benchmark cell: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

One process holds the chip(s): it builds the real ``InferenceEngine`` with
``kv_block_size`` set (so ``BatchScheduler`` runs ``PagedGenerator``), warms the
cell's shapes, probes the reference gap, drives ``BatchScheduler.submit`` for
``--seconds`` from the benchmark's own load generator, checks the outputs the
server produced under load against the plain reference, and prints one JSON
line. Everything cell-specific is data: ``BENCHMARK.json`` names the cell's
configuration file and traffic file, and each per-layer metric's reader; the
configuration names its ``reference``, ``weights`` and ``counts`` modules, or
gets the dense decoders' (``load_modules``; README, "Adding things").
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here: before any heavy import

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = os.path.join(ROOT, ".bench_work")      # sparse model files, traces: git-ignored
for _p in (ROOT, HERE):      # the program, then the benchmark's own modules first
    if _p in sys.path:
        sys.path.remove(_p)
    sys.path.insert(0, _p)

PROBE_PROMPTS = (321, 40)    # one spans two prefill buckets (1 + 256 + 64), one is short
PROBE_TOKENS = 32
WARM_TOKENS = 4
CHECK_REQUESTS = 4


# a configuration file's own sections; every other top-level key is a published key of the model
HARNESS_SECTIONS = ("program", "engine", "device", "assumed", "deployment", "memory", "modules", "name", "source")
# what a configuration's "modules" may name, and the dense decoders' module where it names none
DEFAULT_MODULES = {"reference": "reference", "weights": "weights", "counts": "counts"}


def _fail(msg: str, code: int = 3) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def _note(msg: str) -> None:
    """Progress on stderr (the result line is stdout's last line)."""
    print(f"benchmark[{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _import_file(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the rest is for the builder and the self-test; the driver passes none of it
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--control", default="none",
                   help="negative control: break the REFERENCE this way (one of its module's CONTROLS: "
                        "shift, droplayer, dropblock, ...); correct must come out false")
    p.add_argument("--jitter-ms", type=float, default=0.0,
                   help="perturb clients' start times and gaps by up to this much")
    p.add_argument("--jitter-seed", type=int, default=0)
    p.add_argument("--check-requests", type=int, default=CHECK_REQUESTS,
                   help="how many completed requests the post-window check teacher-forces")
    p.add_argument("--rate", type=float, default=None, help="override an open loop's rate (sweeps)")
    p.add_argument("--sweep", default=None,
                   help="comma-separated rates: after ONE set-up run a window at each and print a table "
                        "(how the knee of an open-loop cell is found); no result line")
    p.add_argument("--dump", default=None, help="write the run's details (gaps, samples) here")
    return p.parse_args(argv)


def model_view(conf: dict) -> dict:
    """The model as the configuration's modules see it: every published key
    (top-level, not one of the harness's sections; ``null`` passes), then what
    the program needs said, then ``norm_epsilon``."""
    model = {k: v for k, v in conf.items() if k not in HARNESS_SECTIONS and not k.startswith("reduced")}
    model.update(conf["program"])
    if "rms_norm_eps" in conf:
        model["norm_epsilon"] = conf["rms_norm_eps"]
    return model


def load_modules(conf: dict) -> dict:
    """``{"reference", "weights", "counts"}``: the files the configuration names
    under ``modules`` (paths under ``benchmark/``), the dense decoders' where
    it names none. Each is loaded once, here."""
    named = conf.get("modules", {})
    if set(named) - set(DEFAULT_MODULES):
        _fail(f"configuration {conf['name']!r}: modules may name {sorted(DEFAULT_MODULES)}, "
              f"not {sorted(set(named) - set(DEFAULT_MODULES))}", 2)
    mods = {}
    for kind, default in DEFAULT_MODULES.items():
        if kind not in named:
            mods[kind] = importlib.import_module(default)    # the one place the defaults are resolved
            continue
        path = os.path.normpath(os.path.join(HERE, named[kind]))
        if not path.startswith(HERE + os.sep) or not os.path.isfile(path):
            _fail(f"configuration {conf['name']!r} names its {kind} module {named[kind]!r}: "
                  f"no file {path} under {HERE}", 2)
        mods[kind] = _import_file(f"bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in conf["name"]),
                                  path)
    return mods


def resolve_cell(manifest: dict, manifest_path: str, workload: str):
    base = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        _fail(f"no workload {workload!r} in {manifest_path} (has: {sorted(cells)})", 2)
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    conf_path = os.path.join(base, conf_entry["file"])
    conf = _load_json(conf_path)
    conf["model"] = model_view(conf)
    traffic_path = os.path.join(os.path.dirname(os.path.dirname(conf_path)), "traffic",
                                cell["traffic"] + ".json")
    return cell, conf, traffic_path, load_modules(conf)


def need_devices(conf: dict, chips: int):
    """The accelerator the configuration names, or no run."""
    import jax

    want = conf["device"]["platform"]
    try:
        devs = jax.devices()
    except RuntimeError as e:
        _fail(f"JAX found no backend: {e}")
    if devs[0].platform != want:
        _fail(f"this configuration runs on {want!r}; JAX found {devs[0].platform!r} "
              f"({devs[0].device_kind}). A cell never falls back to another platform.")
    if len(devs) < chips:
        _fail(f"the cell needs {chips} {want} device(s); JAX found {len(devs)}")
    return devs


class CompileCounter:
    """Counts jaxpr traces and backend compiles through jax.monitoring."""

    def __init__(self):
        from jax import monitoring

        self.traces = 0
        self.backend = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1


class Sampler(threading.Thread):
    """Polls the KV-block gauges while a traced window runs (they are gauges:
    a peak has to be watched). Not started in untraced runs."""

    def __init__(self, period_s: float = 0.02):
        super().__init__(daemon=True)
        from dllama_tpu.runtime import telemetry

        reg = telemetry.registry()
        self._used = reg.gauge(telemetry.KV_BLOCKS_USED)
        self._total = reg.gauge(telemetry.KV_BLOCKS_TOTAL)
        self._period = period_s
        self._halt = threading.Event()
        self.used: list[float] = []

    def run(self) -> None:
        while not self._halt.wait(self._period):
            self.used.append(self._used.value())

    def stop(self) -> dict:
        self._halt.set()
        self.join(5.0)
        total = self._total.value()
        return {"kv_blocks_total": total, "kv_used_peak": max(self.used, default=0.0),
                "kv_used_mean": (sum(self.used) / len(self.used)) if self.used else 0.0}


def counters_snapshot() -> dict:
    from dllama_tpu.runtime import telemetry

    reg = telemetry.registry()
    return {"steps": reg.histogram(telemetry.BATCH_STEP_MS).count(),
            "step_ms_sum": reg.histogram(telemetry.BATCH_STEP_MS).sum(),
            "tokens": reg.counter(telemetry.BATCH_TOKENS).total(),
            "nonfinite": reg.counter(telemetry.NONFINITE).total()}


def build_engine(conf: dict, mix_engine: dict, chips: int, seed: int, weights):
    from dllama_tpu import compile_cache

    compile_cache.enable()       # JAX_COMPILATION_CACHE_DIR if set, else .xla_cache/ in the checkout

    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    model, eng = conf["model"], conf["engine"]
    extra = {k: v for k, v in mix_engine.items() if k not in ("slots", "max_seq_len")}
    path = os.path.join(WORK_DIR, conf["name"] + ".m")
    weights.write_sparse_model(path, model)
    weights.install_seam(seed)
    engine = InferenceEngine(path, None, tp=chips, max_seq_len=int(eng["max_seq_len"]),
                             compute_dtype=eng["compute_dtype"],
                             kv_block_size=int(eng["kv_block_size"]), **extra)
    sched = BatchScheduler(engine, n_slots=int(eng["slots"]))
    return engine, sched


def run_fixed(sched, prompts: list[list[int]], max_tokens: int) -> list:
    reqs = [sched.submit(p, max_tokens, stop_on_eos=False) for p in prompts]
    for r in reqs:
        if not r.done.wait(900.0):
            _fail("a set-up request did not finish in 900 s")
        if r.error:
            _fail(f"a set-up request failed: {r.error}")
    return reqs


def warm_up(engine, sched, mix: dict, rng) -> None:
    """Every program variant the window can reach, through the real scheduler.

    The prefill executable is keyed on the bucket AND on where the column came
    from: a request's first chunk takes the column from the block gather, a
    later chunk takes the previous chunk's output, and the two key different
    executables (found on the chip: a window's first 128-token first chunk
    compiled). So: one prompt of 1 + b tokens for each bucket b the longest
    prompt reaches (b as a first chunk), one that walks all of them (each as a
    later chunk), and one of two widest chunks where the traffic reaches it.

    Two passes. The second sees the pool as the first left it, which is how
    the window will see it, and shares half a block of prefix with the first,
    so the copy-on-write block copy is compiled against the steady pool too:
    with a 32768-word vocabulary two of a window's prompts start with the same
    token more often than not."""
    import traffic

    _lo, hi = traffic.reachable_prompt_lengths(mix)
    hi = min(hi, engine.cfg.seq_len - WARM_TOKENS - 1)
    buckets = [b for b in engine.prefill_buckets if b <= hi - 1] or [min(engine.prefill_buckets)]
    lengths = {1 + b for b in buckets} | {1 + sum(buckets)}
    if 1 + 2 * max(buckets) <= hi:
        lengths.add(1 + 2 * max(buckets))
    lengths = sorted(min(n, hi) for n in lengths)
    vocab = engine.cfg.vocab_size
    first = [rng.integers(0, vocab, size=n).tolist() for n in lengths]
    run_fixed(sched, first, WARM_TOKENS)
    half = max(1, engine.kv_block_size // 2)
    second = [p[:half] + rng.integers(0, vocab, size=max(1, len(p) - half)).tolist() for p in first]
    run_fixed(sched, second, WARM_TOKENS)


def gap_check(reference, conf: dict, engine, pairs: list[tuple[list[int], list[int]]], control: str) -> dict:
    """Reference gaps of (prompt, emitted) pairs, pooled."""
    import numpy as np

    gaps, margins, stds, finite = [], [], [], True
    for prompt, emitted in pairs:
        if not emitted:
            continue
        r = reference.reference_gaps(conf["model"], engine.params, prompt, emitted, control=control)
        gaps.append(r["gap"])
        margins.append(r["margin"])
        stds.append(r["std"])
        finite = finite and r["finite"]
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    return {"gap": cat(gaps), "margin": cat(margins), "std": cat(stds), "finite": finite}


def pick_checked(completed: list, k: int, seed: int) -> list:
    """k completed requests chosen by the seed, the longest among them."""
    import numpy as np

    if len(completed) <= k:
        return list(completed)
    longest = max(range(len(completed)),
                  key=lambda i: len(completed[i].prompt) + len(completed[i].req.tokens))
    rest = [i for i in range(len(completed)) if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    chosen = [longest] + rng.choice(rest, size=k - 1, replace=False).tolist()
    return [completed[i] for i in chosen]


def sweep(args, sched, mix: dict, conf: dict, seconds: float) -> int:
    """One window per rate, after the one set-up. The knee is the highest rate
    at which the backlog at the window's end stays small and the drain short."""
    import loadgen
    import traffic

    for rate in [float(r) for r in args.sweep.split(",")]:
        mix_r = dict(mix, rate_per_s=rate)
        plan = traffic.plan(mix_r, seed=args.seed, seconds=seconds, vocab_size=conf["model"]["vocab_size"])
        gen = loadgen.LoadGenerator(sched, plan)
        gen.run(seconds)
        s = gen.summary()
        t_done = max((t for x in gen.sent for t in x.token_times), default=gen.t_end)
        unfinished = sum(1 for x in gen.sent if not x.token_times or x.token_times[-1] > gen.t_end)
        row = {"rate": rate, "sent": len(gen.sent), "completed": len(s["completed"]), "failed": len(s["failed"]),
               "unfinished_at_window_end": unfinished, "drain_s": round(t_done - gen.t_end, 2),
               "ttft_p50_ms": round(percentile(s["ttft_ms"], 50), 1), "ttft_p95_ms": round(percentile(s["ttft_ms"], 95), 1),
               "itl_p50_ms": round(percentile(s["itl_ms"], 50), 1), "itl_p95_ms": round(percentile(s["itl_ms"], 95), 1),
               "out_tok_s": round(s["tokens_in_window"] / s["window_s"], 1),
               "longest_request_s": round(max((x.token_times[-1] - x.t_due) for x in gen.sent if x.token_times), 2)}
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


def percentile(xs, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) if len(xs) else float("nan")


def metrics_for_cell(manifest: dict, section: str, workload: str) -> list[dict]:
    return [m for m in manifest[section] if "workloads" not in m or workload in m["workloads"]]


def read_metrics(entries: list[dict], folder: str, ctx: dict) -> tuple[dict, list[tuple[str, str]]]:
    """The line's metrics from the manifest's entries, and the (metric, reader)
    pairs left out of it. Each metric is a file ``<folder>/<name>.json`` naming
    its reader and arguments; a reader that finds nothing returns ``None``."""
    metrics, missing = {}, []
    for m in entries:
        spec = _load_json(os.path.join(HERE, folder, m["name"] + ".json"))
        reader = _import_file("reader_" + spec["reader"], os.path.join(HERE, "readers", spec["reader"] + ".py"))
        value = reader.read(ctx, **spec.get("args", {}))
        if value is None:
            missing.append((m["name"], spec["reader"]))
        else:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, missing


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest = _load_json(args.manifest)
    cell, conf, traffic_path, mods = resolve_cell(manifest, args.manifest, args.workload)
    reference = mods["reference"]
    if args.control not in reference.CONTROLS:
        _fail(f"--control {args.control!r}: {conf['name']}'s reference knows {list(reference.CONTROLS)}", 2)
    seconds = float(args.seconds if args.seconds is not None else manifest["run_seconds"])
    chips = int(cell["chips"])

    devs = need_devices(conf, chips)
    platform, kind = devs[0].platform, devs[0].device_kind
    on_device = platform == "tpu"    # only a chip run may report a time or a rate

    import jax
    import numpy as np

    import loadgen
    import traffic

    # an unknown device kind is an error before anything runs
    device_peaks = importlib.import_module("peaks").peaks(kind) if on_device else None
    compiles = CompileCounter()
    mix = traffic.load(traffic_path)
    if args.rate is not None:
        mix["rate_per_s"] = args.rate
    plan = traffic.plan(mix, seed=args.seed, seconds=seconds, vocab_size=conf["model"]["vocab_size"])
    # a mix may set its own slots and context (they are traffic's parameters too)
    conf["engine"].update({k: plan.engine[k] for k in ("slots", "max_seq_len") if k in plan.engine})
    if plan.max_context > int(conf["engine"]["max_seq_len"]):
        _fail(f"traffic reaches {plan.max_context} tokens; the configuration's context is "
              f"{conf['engine']['max_seq_len']}", 2)
    if args.trace:
        slice_start_s, slice_s = traffic.trace_slice(mix, seconds)
        fault = traffic.slice_fault(plan, slice_start_s, slice_s, seconds)
        if fault:
            _fail(f"{traffic_path}: {fault}", 2)

    engine, sched = build_engine(conf, plan.engine, chips, args.seed, mods["weights"])
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, args.seed >> 32, 1])
    try:
        # ---- set-up: warm the cell's shapes, then the reference probe ----------
        _note("engine built; warming up")
        warm_up(engine, sched, mix, rng)
        _note("warm; probing the reference gap")
        vocab = engine.cfg.vocab_size
        probe_prompts = [rng.integers(0, vocab, size=min(n, engine.cfg.seq_len - PROBE_TOKENS - 1)).tolist()
                         for n in PROBE_PROMPTS]
        probe = run_fixed(sched, probe_prompts, PROBE_TOKENS)
        tol = reference.tolerance(conf["engine"]["compute_dtype"])
        probe_gaps = gap_check(reference, conf, engine,
                               [(p, list(r.tokens)) for p, r in zip(probe_prompts, probe)], args.control)
        compiles_before = (compiles.traces, compiles.backend)
        counters_before = counters_snapshot()

        if args.sweep:
            return sweep(args, sched, mix, conf, seconds)

        # ---- the window ----------------------------------------------------------
        gen = loadgen.LoadGenerator(sched, plan, jitter_ms=args.jitter_ms, jitter_seed=args.jitter_seed)
        tracer = sampler = None
        trace_dir = os.path.join(WORK_DIR, "trace", args.workload)
        if args.trace:
            import trace_window

            sampler = Sampler()
            sampler.start()
            tracer = trace_window.TraceWindow(trace_dir, start_after_s=slice_start_s, seconds=slice_s)
            tracer.start()
        if os.environ.get("BENCH_LOG_COMPILES"):
            jax.config.update("jax_log_compiles", True)    # builder's aid: name what compiles in the window
        setup_s = time.monotonic() - T_START
        _note(f"set-up done; window of {seconds:g} s starts"
              + (f", traced from {slice_start_s:g} to {slice_start_s + slice_s:g} s" if args.trace else ""))
        gen.run(seconds)
        _note(f"window and drain done: {len(gen.sent)} sent")
        traced = tracer.finish() if tracer else None
        samples = sampler.stop() if sampler else {}
        counters_after = counters_snapshot()
        window_compiles = (compiles.traces - compiles_before[0]) + (compiles.backend - compiles_before[1])
        summary = gen.summary()
        if not summary["completed"]:
            errors = sorted({str(s.req.error if s.req is not None else s.error) for s in gen.sent})[:3]
            _fail(f"no request completed in the window ({len(gen.sent)} sent; errors: {errors})", 1)

        # ---- correct: the reference gap on what the server did under load ------
        checked = pick_checked(summary["completed"], args.check_requests, args.seed)
        window_gaps = gap_check(reference, conf, engine, [(s.prompt, list(s.req.tokens)) for s in checked],
                                args.control)
        finished_ok = [s for s in gen.sent if s.finished and not s.cut and not s.late
                       and s.req.error is None]
        invariants = {
            "token_counts": all(len(s.req.tokens) == s.planned.max_tokens for s in finished_ok),
            "ids_in_vocab": all(0 <= t < vocab for s in gen.sent if s.req is not None for t in s.req.tokens),
            "no_server_error": not any(s.req is not None and s.req.server_error for s in gen.sent),
            "tripwire_quiet": counters_after["nonfinite"] == 0,
            "reference_finite": probe_gaps["finite"] and window_gaps["finite"],
            "something_checked": len(probe_gaps["gap"]) > 0 and len(window_gaps["gap"]) > 0,
        }
        all_gaps = np.concatenate([probe_gaps["gap"], window_gaps["gap"]])
        correct = bool(all(invariants.values()) and len(all_gaps) and float(all_gaps.max()) <= tol)

        # ---- the line ------------------------------------------------------------
        device = {"platform": platform, "kind": kind, "count": chips,
                  "memory_peak_bytes": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                                           for d in devs[:chips])}
        result = {"correct": correct, "attempted": summary["attempted"],
                  "failed": len(summary["failed"]), "metrics": {}, "device": device}
        ctx = {"summary": summary, "samples": samples, "model": conf["model"], "conf": conf,
               "mix": mix, "cell": cell, "chips": chips, "device_kind": kind, "engine": engine,
               "counts": mods["counts"], "peaks": device_peaks,
               "counters": {k: counters_after[k] - counters_before[k] for k in counters_after},
               "window_compiles": window_compiles, "trace": None, "sent": gen.sent, "setup_s": setup_s}
        if traced is not None:
            import trace_reduce

            ctx["trace"] = trace_reduce.reduce(traced["path"], traced["seconds"])
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"][:10],
                                   "idle_gaps": ctx["trace"]["idle_gaps"][:10]}
        if not on_device:
            # a CPU rehearsal reports counts, never a time under a device metric's name
            result["metrics"] = {
                "requests_completed": {"value": len(summary["completed"]), "unit": "count"},
                "tokens_emitted": {"value": summary["tokens_in_window"], "unit": "count"},
                "decode_steps": {"value": ctx["counters"]["steps"], "unit": "count"},
                "window_compiles": {"value": window_compiles, "unit": "count"}}
            if ctx["trace"] is not None:
                result["metrics"]["trace_device_events"] = {"value": ctx["trace"]["n_events"], "unit": "count"}
        else:
            # --trace 0: the cell's end-to-end metrics; --trace 1: its per-layer metrics.
            # Each metric is a file naming its reader; a reader that finds nothing returns None.
            section, folder = (("per_layer", "layer_metrics") if args.trace else ("end_to_end", "end_to_end"))
            result["metrics"], missing = read_metrics(metrics_for_cell(manifest, section, args.workload),
                                                      folder, ctx)
            for name, reader in missing:
                # the driver refuses a line that lacks a metric its parent's line had
                _note(f"{section} metric {name!r} is NOT in the line: its reader readers/{reader}.py "
                      f"found nothing to read in this run")
        result["gap"] = {"tolerance": tol, "max": float(all_gaps.max()) if len(all_gaps) else None,
                         "positions": int(len(all_gaps)), "control": args.control,
                         "invariants": invariants}
        if args.dump:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
            with open(args.dump, "w", encoding="utf-8") as f:
                json.dump({"result": result, "setup_s": setup_s,
                           "probe": {k: np.asarray(v).tolist() for k, v in probe_gaps.items() if k != "finite"},
                           "window": {k: np.asarray(v).tolist() for k, v in window_gaps.items() if k != "finite"},
                           "ttft_ms": summary["ttft_ms"], "n_itl": len(summary["itl_ms"]),
                           "itl_ms": [float(x) for x in summary["itl_ms"]],
                           "itl_p50_ms": percentile(summary["itl_ms"], 50) if summary["itl_ms"] else None,
                           "lateness_ms": summary["lateness_ms"], "samples": samples,
                           "counters": ctx["counters"], "window_compiles": window_compiles,
                           "n_sent": len(gen.sent), "n_completed": len(summary["completed"]),
                           "trace": ({k: v for k, v in ctx["trace"].items() if k != "modules"}
                                     if ctx["trace"] else None)}, f)
    finally:
        sched.close()
        engine.close()
    # what `correct` compared, each number beside its limit: stderr's last line (the line's "gap" key has the same)
    gap = result["gap"]
    _note(f"correct {result['correct']}: gap_max {gap['max']} limit {gap['tolerance']} over {gap['positions']} positions; "
          + " ".join(f"{name} {held}" for name, held in gap["invariants"].items()))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""CLI — the dllama equivalent.

Modes and flags mirror the reference CLI (reference: src/dllama.cpp:260-285,
arg parsing src/app.cpp:24-131) where they are meaningful on TPU:

    python -m dllama_tpu inference  --model m.m --tokenizer t.t --prompt "..." --steps 64
    python -m dllama_tpu chat       --model m.m --tokenizer t.t
    python -m dllama_tpu perplexity --model m.m --tokenizer t.t --file text.txt
    python -m dllama_tpu api        --model m.m --tokenizer t.t --port 9990

Reference flags that are executor/network specifics (--nthreads, --workers,
--net-turbo, --gpu-index, --gpu-segments) are accepted-and-ignored or replaced
by ``--tp`` (device count; the reference's nNodes) — the TPU runtime has no
worker processes to address. ``worker`` mode exists for multi-host launches
via ``jax.distributed`` (one process per host, same program — replaces
runWorkerApp, app.cpp:299-358).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from ..formats.quants import F32, Q80
from ..ops.linear import QUANT_MODES
from ..runtime import telemetry as _telemetry
from ..runtime.engine import InferenceEngine
from ..tokenizer.chat import (ChatItem, ChatTemplateGenerator,
                              ChatTemplateType)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dllama_tpu",
                                description="TPU-native distributed-llama")
    p.add_argument("mode", choices=["inference", "chat", "perplexity", "eval",
                                    "api", "worker", "verify", "audit",
                                    "timeline", "router", "fleettrace"])
    p.add_argument("--model", required=False, help=".m model file")
    p.add_argument("--tokenizer", required=False, help=".t tokenizer file")
    p.add_argument("--verify-weights", action="store_true",
                   help="crc-verify every weight tensor against the .m.sums "
                        "checksum manifest before any device staging (the "
                        "loader always verifies tensors it reads when a "
                        "manifest exists; this forces the full offline "
                        "sweep first). See also the 'verify' mode")
    p.add_argument("--write", action="store_true",
                   help="verify mode: (re)generate the .m.sums checksum "
                        "manifest for --model instead of checking it — the "
                        "migration path for models converted before "
                        "manifests existed")
    p.add_argument("--prompt", default=None)
    p.add_argument("--file", default=None, help="text file (perplexity mode)")
    p.add_argument("--steps", type=int, default=0, help="max total positions")
    p.add_argument("--max-seq-len", type=int, default=0)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--chat-template", default=None,
                   choices=["llama2", "llama3", "deepSeek3", "chatml"],
                   help="force the chat template family instead of "
                        "auto-detecting from the tokenizer (reference "
                        "--chat-template, app.cpp:17-22; chatml is ours)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--buffer-float-type", choices=["f32", "q80"], default="q80",
                   help="activation sync quantization parity mode")
    p.add_argument("--weight-mode",
                   choices=["auto", "f32", "bf16", "offload"], default="auto",
                   help="auto: Q40 planes resident on device; f32/bf16: "
                        "dequantized dense; offload: Q40 planes in host DRAM, "
                        "streamed per layer during forward (70B/405B on "
                        "small-HBM chips)")
    p.add_argument("--compute-dtype", choices=["f32", "bf16"], default="f32",
                   help="activation dtype: f32 for reference parity, "
                        "bf16 for TPU serving throughput")
    p.add_argument("--wire", choices=["f32", "q80"], default=None,
                   help="collective wire format for the explicit col-split "
                        "partial merges (parallel/qcollectives.py): q80 "
                        "ships int8 codes + f16 block scales (~1/4 of f32 "
                        "bytes) and dequant-sums locally — the reference's "
                        "quantized sync pipes (llm.cpp:167, report fig. 6) "
                        "as an XLA collective; for DCN-bound multihost. "
                        "Don't combine with --buffer-float-type q80: the "
                        "cast-site emulation plus the wire would quantize "
                        "the same partials twice (the reference does it "
                        "once)")
    p.add_argument("--quant-mode",
                   choices=list(QUANT_MODES), default="auto",
                   help="quantized-matmul numerics (ops/linear.py): exact = "
                        "f32 dequant + HIGHEST-precision dots (golden "
                        "parity); fast = bf16 dequant, one MXU pass, f32 "
                        "accumulation; auto = fast iff "
                        "--compute-dtype bf16")
    p.add_argument("--kv-dtype", choices=["auto", "f32", "bf16", "f8"],
                   default="auto",
                   help="KV cache dtype (auto = compute dtype). f8 "
                        "(float8_e4m3) halves bf16's cache footprint and "
                        "read bandwidth — long-context decode is "
                        "KV-bandwidth-bound")
    p.add_argument("--kv-block-size", type=int, default=0, metavar="N",
                   help="api mode with --batch-slots: paged KV serving — "
                        "the cache becomes a pool of N-row blocks with "
                        "per-sequence block tables (runtime/kvblocks.py). "
                        "Admission is priced in blocks, prefix reuse is "
                        "block-level sharing + copy-on-write. N must be a "
                        "power of two tiling the padded context; 0 (the "
                        "default) keeps the dense slot pool")
    p.add_argument("--kv-host-blocks", type=int, default=0, metavar="N",
                   help="with --kv-block-size: tiered KV memory — a "
                        "host-DRAM mirror pool of up to N blocks "
                        "(runtime/kvblocks.py). Under allocation "
                        "pressure, cold cached blocks (idle sessions' "
                        "KV) spill device->host in batched block copies "
                        "instead of dropping; a resumed/prefix-matched "
                        "session pages them back in at admission, "
                        "bit-exact. Sized against the host DRAM budget "
                        "(hbm.fit_host_pool; DLLAMA_HOST_KV_BYTES "
                        "overrides). 0 (the default) = tiering off")
    p.add_argument("--comm-overlap", default="off", metavar="{off,auto,N}",
                   help="compute/communication overlap for the two per-"
                        "layer tp partial merges (parallel/qcollectives): "
                        "split each merge into N chunks reduced by "
                        "independent ppermute ring chains so chunk i's "
                        "in-flight hops overlap chunk i+1's compute "
                        "(TokenWeave shape; the q80 wire rides the same "
                        "hops under --wire q80). 'auto' picks the largest "
                        "Q80-block-divisible chunking <= 4 and degrades "
                        "to off on one device; an explicit N must divide "
                        "the model dim and needs --tp >= 2. Decode-regime "
                        "dispatches only; prefill keeps the monolithic "
                        "psum")
    p.add_argument("--nbatches", type=int, default=None,
                   help="pin a fixed prefill chunk size (reference default "
                        "32, app.cpp:28); unset = TPU-sized adaptive "
                        "buckets (engine.PREFILL_BUCKETS)")
    p.add_argument("--decode-chunk", type=int, default=1, metavar="K",
                   help="fuse K decode steps into one dispatch (tokens feed "
                        "back on device; output identical to K=1, EOS "
                        "overshoot discarded). Cuts per-token dispatch "
                        "overhead; streaming granularity becomes K tokens")
    p.add_argument("--spec-lookup", type=int, default=0, metavar="K",
                   help="prompt-lookup speculative decode (greedy only): "
                        "verify K history-drafted tokens per dispatch; "
                        "output identical to plain greedy, accepted drafts "
                        "multiply decode throughput (HBM cost of a verify "
                        "is one decode step)")
    p.add_argument("--host-sampling", action="store_true",
                   help="sample on host from downloaded logits (parity oracle) "
                        "instead of the fused on-device sampler")
    p.add_argument("--tp", type=int, default=None,
                   help="tensor-parallel device count (reference: number of nodes)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel device count (ring attention; "
                        "long-context — no reference equivalent)")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel device count: shards the batch axis "
                        "of --batch-slots serving over the mesh (requires "
                        "batch-slots divisible by dp; no reference "
                        "equivalent)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stage count (layer stages; "
                        "pp-1 activation hand-offs + one activation "
                        "all-reduce per forward vs tp's 2 all-reduces per "
                        "layer — the low-bandwidth scale-out axis; no "
                        "reference equivalent)")
    p.add_argument("--compile-cache", default="on", choices=["on", "off"],
                   help="persistent XLA compilation cache: repeat runs skip "
                        "the multi-second jit compiles (first-token latency "
                        "on restart). It lives where "
                        "JAX_COMPILATION_CACHE_DIR says, else in the "
                        "checkout's .xla_cache/; 'off' disables")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a JAX/XLA profiler trace to DIR (the TPU-side "
                        "Eval/Sync breakdown: per-op + collective time; view "
                        "with TensorBoard or Perfetto). Replaces the "
                        "reference's per-step-type executor timers")
    p.add_argument("--profile-split", action="store_true",
                   help="measure and print the per-token Eval/Sync split and "
                        "collective Sent/Recv traffic (the reference's "
                        "per-token metrics, dllama.cpp:59-67): one short "
                        "profiler capture classifies collective vs compute "
                        "device time; traffic comes from the compiled HLO "
                        "(costs one extra XLA compile, absorbed by the "
                        "persistent compile cache)")
    p.add_argument("--numerics-taps", action="store_true",
                   help="collect per-layer activation stats (rms/abs-max/"
                        "non-finite count/Q80 roundtrip error per block "
                        "site) on prefill and canary forwards "
                        "(runtime/numerics; surfaced via /debug/numerics "
                        "and dllama_activation_* gauges). Off by default: "
                        "the untapped trace is byte-identical and "
                        "compile-ledger-quiet")
    p.add_argument("--numerics-failfast", action="store_true",
                   help="turn the always-on non-finite logits tripwire "
                        "into fail-fast: a poisoned request dies with an "
                        "explicit numerics error (HTTP 5xx naming the "
                        "site) instead of emitting garbage tokens; "
                        "default counts dllama_nonfinite_total only")
    p.add_argument("--canary-interval", type=float, default=0.0,
                   metavar="SEC",
                   help="api mode: replay a fixed-seed canary prompt "
                        "every SEC seconds and compare token ids + a "
                        "logit fingerprint against the golden recorded "
                        "at startup (drift → dllama_canary_drift_total, "
                        "--stats drift=N!, WARN names the first "
                        "divergent layer when --numerics-taps is on); "
                        "0 = off")
    p.add_argument("--dump", default=None, metavar="FILE",
                   help="timeline mode: the flight-recorder JSON to "
                        "convert — a crash postmortem "
                        "(dllama-flight-*.json) or a saved GET "
                        "/debug/flight body (runtime/flightrec.py)")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="timeline mode: write the Chrome trace-event JSON "
                        "here (default: stdout); load the file in "
                        "ui.perfetto.dev or chrome://tracing")
    p.add_argument("--router-dump", default=None, metavar="FILE",
                   help="fleettrace mode: a saved GET /debug/fleet body "
                        "(the router's probe + span snapshot)")
    p.add_argument("--replica-dump", action="append", default=None,
                   metavar="NAME=FILE",
                   help="fleettrace mode: one replica's saved GET "
                        "/debug/flight body, labeled with the replica "
                        "name (repeat the flag per replica); bare FILE "
                        "uses the filename stem as the track name")
    p.add_argument("--slo", default=None, metavar="SPEC",
                   help="router mode: declarative serving objectives — "
                        "'ttft_p95_ms=500,itl_p50_ms=40,shed_rate=0.01' "
                        "or the path of a JSON file mapping objective "
                        "names to thresholds. Compliance + error-budget "
                        "burn rates at GET /debug/slo, "
                        "dllama_slo_compliance / dllama_slo_burn_rate "
                        "gauges on /metrics, and an slo= fragment in "
                        "--stats (runtime/slo.py)")
    p.add_argument("--audit-json", action="store_true",
                   help="audit mode: print the per-tensor table as one "
                        "JSON object instead of text")
    p.add_argument("--data", default=None, metavar="FILE.jsonl",
                   help="eval mode: the teacher-forced eval dataset — one "
                        "JSON object per line with 'tokens' (token-id "
                        "list) or 'text' (tokenized with --tokenizer), "
                        "plus an optional 'id' (runtime/evalharness.py)")
    p.add_argument("--compare", default=None, metavar="CONFIG",
                   choices=list(_telemetry.EVAL_CONFIGS),
                   help="eval mode: ALSO score the dataset under CONFIG "
                        "(single/dense/paged/paged_spec) and assert its "
                        "total NLL is BIT-IDENTICAL to the primary run's "
                        "— a mismatch is parity drift and exits non-zero")
    p.add_argument("--json", action="store_true",
                   help="eval mode: print the run summary as one JSON "
                        "line (what tools/quality_baseline.py consumes) "
                        "instead of the human table")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="append per-request phase spans (queue/prefill/"
                        "decode/verify) as JSONL trace events to FILE "
                        "(runtime.telemetry.SpanTracer; schema documented "
                        "in runtime/TELEMETRY.md)")
    p.add_argument("--stats", type=float, default=0.0, metavar="SEC",
                   help="api mode: print a one-line telemetry summary every "
                        "SEC seconds (requests, in-flight, queue depth, "
                        "batch/KV occupancy, tok/s, ttft/itl p50, eval/sync "
                        "share) — the serving-era version of the reference's "
                        "per-token console line")
    p.add_argument("--port", type=int, default=9990,
                   help="api/router mode port")
    p.add_argument("--host", default="127.0.0.1",
                   help="api/router mode bind host")
    p.add_argument("--replica", action="append", default=None,
                   metavar="URL",
                   help="router mode: one api-server replica base URL "
                        "(http://host:port; repeat the flag per replica). "
                        "The router probes each replica's /readyz + "
                        "/metrics and dispatches least-loaded with "
                        "session affinity (serve/router.py)")
    p.add_argument("--probe-interval", type=float, default=2.0,
                   metavar="SEC",
                   help="router mode: health-probe interval per replica "
                        "(jittered ±20%% so a fleet of routers never "
                        "synchronizes its probe bursts)")
    p.add_argument("--max-stream-resumes", type=int, default=1,
                   metavar="N",
                   help="router mode: how many mid-stream replica deaths "
                        "one streaming request may survive — each death "
                        "re-dispatches the stream to a healthy replica "
                        "as a token-exact spliced continuation (0 = the "
                        "first death is the terminal SSE 502, the "
                        "pre-failover behavior). Batched replicas "
                        "(--batch-slots) stamp their chunks with token "
                        "indices to make the splice exactly-once; "
                        "unstamped streams keep the terminal-502 "
                        "contract regardless")
    p.add_argument("--batch-slots", type=int, default=0, metavar="N",
                   help="api mode: continuous batching over N concurrent "
                        "sequence slots (one ragged decode program; requests "
                        "queue beyond the pool). 0/1 = single-sequence mode "
                        "with prefix KV reuse")
    p.add_argument("--role", choices=("prefill", "decode"), default=None,
                   help="api mode, batched paged serving: disaggregation "
                        "tag advertised on /readyz. The fleet router keeps "
                        "'prefill' replicas out of the decode dispatch "
                        "pool and uses them to compute prompt KV that "
                        "decode replicas pull over the checksummed Q80 "
                        "wire (POST /v1/kv/export) instead of recomputing")
    p.add_argument("--max-queue", type=int, default=0, metavar="N",
                   help="api mode, batched serving: bound the admission "
                        "queue at N waiting requests; submits beyond it are "
                        "shed with HTTP 429 + Retry-After instead of "
                        "building unbounded latency (0 = unbounded). "
                        "/readyz reports unready while the queue is full")
    p.add_argument("--tenant-limits", default=None, metavar="SPEC",
                   help="api mode, batched serving: per-tenant fair-share "
                        "limits — a JSON object (inline or a file path) "
                        "mapping tenant ids (or '*' for the default) to "
                        "{weight, max_slots, tokens_per_s}. Admission "
                        "drains per-tenant FIFOs by weighted round-robin; "
                        "a tenant at max_slots is skipped (others keep "
                        "admitting), one over its token rate is shed with "
                        "its own HTTP 429 (runtime/tenancy.py; identity "
                        "from the X-Dllama-Tenant header, absent → anon)")
    p.add_argument("--usage-ledger", default=None, metavar="FILE",
                   help="api mode: append periodic per-tenant usage "
                        "snapshots (monotonic cumulative totals — tokens, "
                        "sheds, KV block-seconds) to FILE as JSONL, the "
                        "billing/capacity artifact; diff any two lines for "
                        "an interval's usage (GET /debug/tenants serves "
                        "the live view)")
    p.add_argument("--request-timeout", type=float, default=0.0,
                   metavar="SEC",
                   help="api mode: default per-request deadline. Past it a "
                        "queued request fails (HTTP 408) and an in-flight "
                        "one is cancelled at the next step boundary "
                        "(finish_reason \"timeout\", partial output). The "
                        "request body's 'timeout' field overrides per "
                        "request; 0 = no deadline. Router mode: the wall "
                        "budget a mid-stream failover must fit inside — a "
                        "spliced continuation is only dispatched within "
                        "the remaining deadline")
    p.add_argument("--drain-timeout", type=float, default=5.0, metavar="SEC",
                   help="api mode: on SIGTERM/shutdown, stop admitting "
                        "(readyz → 503) and let active requests finish for "
                        "up to SEC seconds before failing the remainder "
                        "explicitly")
    # multi-host SPMD (replaces the reference's --workers TCP list; every
    # process — root and workers — runs the same binary with the same model
    # files, reference runWorkerApp → parallel.multihost):
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address (process 0)")
    p.add_argument("--nprocs", type=int, default=None,
                   help="total process count for multi-host")
    p.add_argument("--procid", type=int, default=None,
                   help="this process's id (0 = root)")
    p.add_argument("--worker-timeout", type=float, default=None, metavar="SEC",
                   help="worker mode: exit if no control packet arrives for "
                        "SEC seconds (root presumed dead; default: wait "
                        "forever, matching a long-idle root). NOTE: size it "
                        "for the INTER-PACKET gap — a root using "
                        "--decode-chunk K sends one packet per K tokens")
    p.add_argument("--worker-reserve", action="store_true",
                   help="worker mode: run under a supervisor that respawns "
                        "the worker on root loss and waits for a new root at "
                        "the same coordinator address (the reference's "
                        "runWorkerApp outer loop, app.cpp:299-358)")
    # accepted for reference-flag compatibility; no-ops on TPU:
    p.add_argument("--nthreads", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--workers", nargs="*", default=None, help=argparse.SUPPRESS)
    p.add_argument("--net-turbo", type=int, default=None, help=argparse.SUPPRESS)
    return p


def start_stats_reporter(interval: float) -> "threading.Thread":
    """Daemon thread printing one telemetry summary line every ``interval``
    seconds (``--stats``). Tok/s is the PER-STEP emission counters' delta
    over the window (batched + single-sequence decode), so the rate is live
    during a long in-flight generation — not a burst when it finishes —
    and an idle server prints 0.0 instead of a lifetime average."""
    import threading

    from ..runtime import telemetry

    reg = telemetry.registry()

    def _emitted() -> float:
        return (reg.counter(telemetry.BATCH_TOKENS).total()
                + reg.counter(telemetry.DECODE_TOKENS).total())

    def _loop() -> None:
        prev = _emitted()
        while True:
            time.sleep(interval)
            cur = _emitted()
            print(telemetry.stats_line(reg, window_tokens=cur - prev,
                                       window_s=interval), flush=True)
            prev = cur

    t = threading.Thread(target=_loop, daemon=True, name="dllama-stats")
    t.start()
    return t


def _maybe_init_distributed(args) -> bool:
    """Join the jax.distributed cluster when multi-host flags are present;
    returns True when running multi-host."""
    if args.nprocs is None or args.nprocs <= 1:
        return False
    from ..parallel.multihost import init_distributed

    init_distributed(args.coordinator, args.nprocs, args.procid,
                     platform=os.environ.get("JAX_PLATFORMS") or None)
    return True


# whether THIS process's make_engine wrote DLLAMA_TPU_QUANT_MODE (vs the
# user), and the user's pre-existing value to restore when it did
_cli_wrote_quant_mode = False
_env_quant_before_cli: str | None = None
_cli_wrote_wire = False
_env_wire_before_cli: str | None = None


def make_engine(args, multihost: bool | None = None) -> InferenceEngine:
    if multihost is None:
        multihost = getattr(args, "_multihost", False)
    if not args.model or not args.tokenizer:
        raise SystemExit("--model and --tokenizer are required")
    seed = args.seed if args.seed is not None else int(time.time())
    global _cli_wrote_quant_mode, _env_quant_before_cli
    if getattr(args, "quant_mode", "auto") != "auto":
        if not _cli_wrote_quant_mode:
            _env_quant_before_cli = os.environ.get("DLLAMA_TPU_QUANT_MODE")
        os.environ["DLLAMA_TPU_QUANT_MODE"] = args.quant_mode
        _cli_wrote_quant_mode = True
    elif _cli_wrote_quant_mode:
        # auto must mean auto, not whatever a PRIOR make_engine in this
        # process wrote — but a user-exported DLLAMA_TPU_QUANT_MODE is
        # theirs to keep (restored, not popped)
        if _env_quant_before_cli is None:
            os.environ.pop("DLLAMA_TPU_QUANT_MODE", None)
        else:
            os.environ["DLLAMA_TPU_QUANT_MODE"] = _env_quant_before_cli
        _cli_wrote_quant_mode = False
    # --wire mirrors the quant-mode discipline: an explicit flag value is
    # set (and overrides a user export), the unset default restores
    # whatever a PRIOR make_engine in this process overwrote
    global _cli_wrote_wire, _env_wire_before_cli
    if getattr(args, "wire", None) is not None:
        if not _cli_wrote_wire:
            _env_wire_before_cli = os.environ.get("DLLAMA_TPU_WIRE")
        os.environ["DLLAMA_TPU_WIRE"] = args.wire
        _cli_wrote_wire = True
    elif _cli_wrote_wire:
        if _env_wire_before_cli is None:
            os.environ.pop("DLLAMA_TPU_WIRE", None)
        else:
            os.environ["DLLAMA_TPU_WIRE"] = _env_wire_before_cli
        _cli_wrote_wire = False
    engine = InferenceEngine(
        args.model, args.tokenizer,
        tp=args.tp, sp=args.sp, pp=args.pp, dp=getattr(args, "dp", 1),
        max_seq_len=args.max_seq_len,
        weight_mode=args.weight_mode,
        compute_dtype="bfloat16" if args.compute_dtype == "bf16" else "float32",
        sync_type=Q80 if args.buffer_float_type == "q80" else F32,
        n_batches=args.nbatches,
        temperature=args.temperature, topp=args.topp, seed=seed,
        multihost=multihost, host_sampling=args.host_sampling,
        decode_chunk=args.decode_chunk,
        spec_lookup=getattr(args, "spec_lookup", 0),
        kv_dtype=getattr(args, "kv_dtype", "auto"),
        kv_block_size=getattr(args, "kv_block_size", 0),
        kv_host_blocks=getattr(args, "kv_host_blocks", 0),
        comm_overlap=getattr(args, "comm_overlap", "off"),
        profile_split=getattr(args, "profile_split", False),
        verify_weights=getattr(args, "verify_weights", False),
        numerics_taps=getattr(args, "numerics_taps", False),
        numerics_failfast=(True if getattr(args, "numerics_failfast", False)
                           else None),
    )
    h = engine.model_file.header
    print(f"💡 Arch: {h.arch_type.name}  Dim: {h.dim}  Layers: {h.n_layers}  "
          f"Heads: {h.n_heads}/{h.n_kv_heads}  SeqLen: {h.seq_len}")
    import jax

    from .. import native

    dev = jax.devices()
    # every run names its device: chip_smoke.py (and any reader of a log)
    # takes platform/kind/count from the process that actually held the chip
    print(f"🕸️ TP devices: {engine.tp}  SP devices: {engine.sp}  "
          f"PP stages: {engine.pp}  on {dev[0].platform} "
          f"\"{dev[0].device_kind}\" x{len(dev)}")
    print(f"💾 weight codec: {native.describe()}")
    if engine.plan is not None:
        # where the weight bytes actually sit: a mesh that silently
        # replicated (or landed on one device) shows here, not in a rate
        by_dev: dict = {}
        for leaf in jax.tree_util.tree_leaves(engine.params):
            for sh in getattr(leaf, "addressable_shards", ()):
                by_dev[sh.device.id] = (by_dev.get(sh.device.id, 0)
                                        + sh.data.nbytes)
        placed = sum(by_dev.values())
        print(f"🕸️ weights on {len(by_dev)} devices: "
              + " ".join(f"#{d} {100 * b / max(1, placed):.1f}%"
                         for d, b in sorted(by_dev.items()))
              + f" of {placed / 2 ** 30:.2f} GiB placed")
    if engine.cfg.comm_overlap:
        # the ACTUAL wire format(s), from the same per-merge pricing the
        # metrics use — non-32-divisible chunks ride f32 hops even under
        # --wire q80, and the banner must not contradict /metrics labels
        wires = sorted({w for _, w, _ in engine._wire_traffic}) or ["f32"]
        print(f"🕸️ overlapped collectives: {engine.cfg.comm_overlap} "
              f"chunks per merge, {'/'.join(wires)} wire "
              f"(dllama_comm_exposed_ms after a --profile-split capture)")
    return engine


def run_inference(args) -> int:
    from contextlib import nullcontext

    if args.prompt is None:
        raise SystemExit("Prompt is required")
    if args.steps == 0:
        raise SystemExit("Number of steps is required")
    from ..runtime import introspection

    engine = make_engine(args)
    print(introspection.hbm_budget_line(engine))
    print(introspection.startup_line(engine))
    print(args.prompt)
    ids = engine.tokenizer.encode(args.prompt)
    max_new = max(0, min(args.steps, engine.cfg.seq_len) - len(ids))

    def on_token(tid, piece):
        sys.stdout.write(piece if piece is not None else "")
        sys.stdout.flush()

    # one jax.profiler.trace code path for every capture surface: the CLI,
    # POST /debug/profile, and measure_eval_sync all go through
    # profiling.capture (which also serializes sessions)
    from ..runtime import profiling

    prof = profiling.capture(args.profile) if args.profile else nullcontext()
    with prof:
        result = engine.generate(ids, max_new, on_token=on_token,
                                 stop_on_eos=False)
    if args.profile:
        print(f"🔬 profiler trace written to {args.profile}")
    print()
    n_eval = sum(s.n_tokens for s in result.steps if s.kind == "eval")
    n_pred = sum(s.n_tokens for s in result.steps if s.kind == "pred")
    print("\nEvaluation")
    buckets = engine.prefill_buckets
    print(f"   nBatches: {buckets[0] if len(buckets) == 1 else list(buckets)}")
    print(f"    nTokens: {n_eval}")
    print(f"   tokens/s: {result.eval_tok_per_s:.2f} "
          f"({result.eval_ms / max(1, n_eval):.2f} ms/tok)")
    if getattr(args, "profile_split", False) and engine.split is not None:
        # per-token lines in the reference's 🔶 style (dllama.cpp:59-67);
        # printed after the stream so they don't garble the generated text
        tr = engine.traffic
        for s in result.steps:
            if s.kind != "pred" or s.sync_ms is None:
                continue
            # traffic is measured on the single-token program; chunked /
            # speculative dispatches scale by their DISPATCH width (a verify
            # runs K+1 columns even when one draft is accepted), not the
            # kept-token count
            skb = f"{tr.sent_kb * s.width:7.1f}" if tr else "    0.0"
            print(f"🔶 P {s.ms:8.2f} ms  E {s.eval_only_ms:8.2f} ms  "
                  f"S {s.sync_ms:6.2f} ms  Sent {skb} kB  Recv {skb} kB"
                  + (f"  ({s.n_tokens} tok)" if s.n_tokens > 1 else ""))
    print("Prediction")
    print(f"    nTokens: {n_pred}")
    print(f"   tokens/s: {result.pred_tok_per_s:.2f} "
          f"({result.pred_ms / max(1, n_pred):.2f} ms/tok)")
    if n_pred and result.pred_tok_per_s:
        # roofline context (runtime/roofline): the measured decode rate
        # against the chip's HBM ceiling — every decode step streams the
        # weight planes, so ceiling_GBps / weight_GB is the speed limit.
        # Probe-file ceilings when present, nameplate otherwise; the
        # source is printed because the two are different claims.
        from ..runtime import roofline as _roofline

        try:
            ceil = _roofline.load_ceilings()
        except _roofline.UnknownDeviceKind as e:
            print(f"   roofline: not computed ({e})")
        else:
            rf = _roofline.rate_roofline(
                result.pred_tok_per_s,
                engine.hbm_estimate["weights_bytes"] / 1e9, ceil)
            print(f"   roofline: {100 * rf['roofline_fraction']:.1f}% of "
                  f"{rf['roofline_tok_per_s']:.0f} tok/s "
                  f"[{rf['ceiling_source']}]")
    if getattr(args, "profile_split", False) and engine.split is not None:
        sp = engine.split
        tr = engine.traffic
        print(f"  eval/sync: {sp.eval_ms:.2f}/{sp.sync_ms:.2f} ms device time "
              f"per decode step (sync {100 * sp.sync_frac:.1f}%)")
        pf = engine.split_prefill
        if pf is not None and pf.n_steps > 0:
            # the prefill program's own fraction (MXU-bound wide chunks
            # sync differently than HBM-bound decode)
            print(f"             {pf.eval_ms:.2f}/{pf.sync_ms:.2f} ms per "
                  f"prefill chunk (sync {100 * pf.sync_frac:.1f}%)")
        if tr:
            print(f"    traffic: {tr.sent_kb:.1f} kB/token/device over "
                  f"{tr.n_collectives} collectives "
                  + " ".join(f"{k}={v:.1f}kB" for k, v in tr.by_kind.items()))
    if engine.spec_active:
        n_disp = sum(1 for s in result.steps if s.kind == "pred")
        print(f"  spec rate: {n_pred / max(1, n_disp):.2f} tokens/dispatch "
              f"({n_disp} dispatches)")
    introspection.compile_report(engine.introspection_scope)
    engine.close()
    return 0


def run_chat(args) -> int:
    """Interactive chat REPL (reference: dllama.cpp:174-258)."""
    engine = make_engine(args)
    tok = engine.tokenizer
    eos_piece = (tok.vocab[tok.eos_token_ids[0]].decode("utf-8", "replace")
                 if tok.eos_token_ids else "")
    template = ChatTemplateGenerator(
        tok.chat_template, eos=eos_piece,
        type=ChatTemplateType(args.chat_template or "unknown"))
    from .api import _EosGate  # function-level: api imports make_engine from us

    stop_pieces = [tok.vocab[t].decode("utf-8", "replace") for t in tok.eos_token_ids]

    def _print_delta(d: str) -> None:
        sys.stdout.write(d)
        sys.stdout.flush()

    first = True
    while True:
        try:
            user = input("\n💻 > " if first else "\n💻 > ")
        except EOFError:
            break
        if not user.strip():
            continue
        items = [ChatItem("user", user)]
        chat = template.generate(items, append_generation_prompt=True)
        ids = tok.encode(chat.content, is_start=first, add_special_tokens=True)
        first = False
        if engine.pos + len(ids) >= engine.cfg.seq_len:
            print("🚧 context is full (seq_len reached), stopping")
            break
        if chat.public_prompt:
            sys.stdout.write(chat.public_prompt)
        sys.stdout.write("\n🤖 ")
        sys.stdout.flush()

        _, _ = engine.prefill(ids[:-1]) if len(ids) > 1 else (None, [])
        token = ids[-1]
        gate = _EosGate(tok, stop_pieces, emit=_print_delta)
        tok.reset_decoder()
        stopped = False
        while engine.pos < engine.cfg.seq_len and not stopped:
            token = engine.next_token(token)
            stopped = gate.feed(token, tok.decode(token))
        if not stopped:
            # flush anything still buffered as MAYBE_EOS when the loop exits
            # on the seq_len bound rather than a stop match
            gate.flush_tail()
            sys.stdout.flush()
        print()
    engine.close()
    return 0


def run_verify(args) -> int:
    """``python -m dllama_tpu verify --model m.m [--write]`` — offline
    weight-integrity check (or manifest generation with ``--write``)
    against the .m.sums sidecar. Pure host-side: no jax, no device."""
    from ..formats import mfile as _mfile
    from ..runtime.weights import WeightIntegrityError, verify_weights

    if not args.model:
        raise SystemExit("--model is required for verify mode")
    try:
        if args.write:
            out = _mfile.write_manifest(args.model)
            with _mfile.ModelFile.open(args.model) as mf:
                n = len(mf.tensors)
            print(f"🔏 checksum manifest written: {out} ({n} tensors)")
            return 0
        with _mfile.ModelFile.open(args.model) as mf:
            try:
                res = verify_weights(mf, emit=print)
            except WeightIntegrityError as e:
                print(f"❌ {e}")
                return 2
    except (OSError, ValueError) as e:
        # structurally broken file (bad magic, truncation, stale manifest):
        # a clean diagnostic, not a traceback — this tool's whole job is
        # reporting damage
        print(f"❌ {args.model}: {e}")
        return 1
    if res["corrupt"]:
        print(f"❌ {len(res['corrupt'])} of {res['tensors']} tensors "
              f"corrupt: {', '.join(res['corrupt'])}")
        return 1
    print(f"✅ {res['tensors']} tensors verified against "
          f"{_mfile.manifest_path(args.model)}")
    return 0


def run_audit(args) -> int:
    """``python -m dllama_tpu audit --model m.m [--audit-json]`` — offline
    per-tensor quant-error audit (runtime/numerics.audit_model): Q40/Q80
    reconstruction health (non-finite values, scale range, roundtrip
    SNR/MSE via the formats/quants reference codecs). Pure host-side: no
    jax, no device. Exit 1 when any tensor carries non-finite values."""
    from ..runtime.numerics import audit_model

    if not args.model:
        raise SystemExit("--model is required for audit mode")
    try:
        res = audit_model(args.model,
                          emit=None if args.audit_json else print)
    except (OSError, ValueError) as e:
        print(f"❌ {args.model}: {e}")
        return 1
    if args.audit_json:
        print(json.dumps(res))
    return 1 if res["nonfinite_tensors"] else 0


def run_timeline(args) -> int:
    """``python -m dllama_tpu timeline --dump flight.json [--out t.json]``
    — offline converter from a flight-recorder dump (crash postmortem or
    a saved ``GET /debug/flight`` body) to Perfetto-loadable Chrome
    trace-event JSON, with structural validation (per-track monotonic
    timestamps, complete request flows). Pure host-side: no jax."""
    from ..runtime import flightrec

    if not args.dump:
        raise SystemExit("--dump FILE (a flight-recorder dump, or a saved "
                         "GET /debug/flight body) is required for timeline "
                         "mode")
    try:
        with open(args.dump, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print(f"❌ {args.dump}: {e}")
        return 1
    if not isinstance(data, dict):
        print(f"❌ {args.dump}: not a flight-recorder dump (expected a "
              f"JSON object, got {type(data).__name__})")
        return 1
    try:
        trace = flightrec.to_chrome_trace(data)
        problems = flightrec.validate_chrome_trace(trace)
    except (KeyError, TypeError, AttributeError) as e:
        # a truncated / hand-edited dump missing structural fields must
        # fail with a name, not a traceback
        print(f"❌ {args.dump}: malformed flight dump "
              f"({type(e).__name__}: {e})")
        return 1
    payload = json.dumps(trace)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload)
        print(f"🧾 {len(trace['traceEvents'])} trace events "
              f"({len(data.get('ticks') or [])} ticks, "
              f"{len(data.get('spans') or [])} spans) → {args.out} — load "
              f"in ui.perfetto.dev or chrome://tracing")
    else:
        print(payload)
    for prob in problems:
        print(f"⚠️ {prob}", file=sys.stderr)
    return 1 if problems else 0


def run_fleettrace(args) -> int:
    """``python -m dllama_tpu fleettrace --router-dump F
    --replica-dump name=F ...`` — offline joiner from a saved router
    ``GET /debug/fleet`` body plus per-replica ``GET /debug/flight``
    bodies to one fleet-wide Chrome trace: router track + one track per
    replica, requests joined across tiers by the X-Dllama-Request-Id
    fleet id (one flow per request; a retried request's flow crosses
    two replica tracks). Pure host-side: no jax. Exit 1 on malformed
    input or when nothing joins."""
    from ..runtime import flightrec

    if not args.router_dump:
        raise SystemExit("--router-dump FILE (a saved GET /debug/fleet "
                         "body) is required for fleettrace mode")

    def _load(path: str):
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError(f"expected a JSON object, got "
                             f"{type(data).__name__}")
        return data

    try:
        router_dump = _load(args.router_dump)
    except (OSError, ValueError) as e:
        print(f"❌ {args.router_dump}: {e}")
        return 1
    replica_dumps: dict = {}
    for spec in args.replica_dump or []:
        name, sep, path = spec.partition("=")
        if not sep:
            # bare FILE: the filename stem names the replica track
            name, path = os.path.splitext(os.path.basename(spec))[0], spec
        try:
            replica_dumps[name] = _load(path)
        except (OSError, ValueError) as e:
            print(f"❌ {path}: {e}")
            return 1
    try:
        trace = flightrec.fleet_chrome_trace(router_dump, replica_dumps)
        problems = flightrec.validate_chrome_trace(trace)
    except (KeyError, TypeError, AttributeError) as e:
        # a truncated / hand-edited dump missing structural fields must
        # fail with a name, not a traceback
        print(f"❌ malformed dump ({type(e).__name__}: {e})")
        return 1
    join = trace.get("fleetJoin", {})
    payload = json.dumps(trace)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(payload)
        print(f"🧾 {len(trace['traceEvents'])} trace events "
              f"({join.get('router_requests', 0)} router requests, "
              f"{join.get('joined', 0)} joined across "
              f"{join.get('replicas', 0)} replica dump(s)) → {args.out} "
              f"— load in ui.perfetto.dev or chrome://tracing")
    else:
        print(payload)
    for prob in problems:
        print(f"⚠️ {prob}", file=sys.stderr)
    if (replica_dumps and join.get("router_requests", 0) > 0
            and join.get("joined", 0) == 0):
        print("⚠️ no request joined across tiers (trace-id propagation "
              "broken, or dumps from different runs)", file=sys.stderr)
        return 1
    return 1 if problems else 0


def run_perplexity(args) -> int:
    engine = make_engine(args)
    if args.file:
        text = open(args.file, encoding="utf-8").read()
    elif args.prompt is not None:
        text = args.prompt
    else:
        raise SystemExit("--file or --prompt required for perplexity")
    ids = engine.tokenizer.encode(text)
    if args.max_seq_len:
        ids = ids[: args.max_seq_len]
    ids = ids[: engine.cfg.seq_len]
    t0 = time.perf_counter()
    ppl = engine.perplexity(ids)
    dt = time.perf_counter() - t0
    print(f"📊 nTokens: {len(ids)}")
    print(f"📊 Perplexity: {ppl:.4f}")
    print(f"📊 Time: {dt:.2f}s ({len(ids) / dt:.1f} tok/s)")
    engine.close()
    return 0


def _eval_primary_config(args) -> str:
    """The PRIMARY eval config implied by the serving flags (one of
    telemetry.EVAL_CONFIGS — the closed world dlint rule eval-names
    lints)."""
    if args.batch_slots and args.batch_slots > 1:
        if args.kv_block_size:
            return "paged_spec" if args.spec_lookup else "paged"
        return "dense"
    return "single"


def _eval_args_for(args, config: str):
    """A copy of ``args`` shaped for one eval config: the config name
    decides the generator family; unset sizing flags get eval-sized
    defaults so ``--compare paged`` works without extra flags."""
    import copy

    a = copy.copy(args)
    if config == "single":
        a.batch_slots, a.kv_block_size, a.spec_lookup = 0, 0, 0
        a.kv_host_blocks = 0
    elif config == "dense":
        a.kv_block_size, a.spec_lookup, a.kv_host_blocks = 0, 0, 0
    elif config == "paged":
        a.kv_block_size = args.kv_block_size or 16
        a.spec_lookup = 0
    else:  # paged_spec
        a.kv_block_size = args.kv_block_size or 16
        a.spec_lookup = args.spec_lookup or 4
    return a


def _run_eval_config(args, seqs, dataset: str, config: str) -> dict:
    """Build the serving stack for ``config``, score ``seqs``, tear it
    down. Each config gets its own engine so the comparison covers the
    REAL construction path, not a mutated shared one."""
    from ..runtime import evalharness
    from ..runtime.serving import BatchScheduler

    eng = make_engine(_eval_args_for(args, config))
    sched = None
    try:
        if config == "single":
            return evalharness.run_eval(seqs, dataset=dataset,
                                        config=config, engine=eng)
        n_slots = args.batch_slots if args.batch_slots > 1 else 4
        sched = BatchScheduler(eng, n_slots=n_slots)
        return evalharness.run_eval(seqs, dataset=dataset, config=config,
                                    sched=sched)
    finally:
        if sched is not None:
            sched.close()
        eng.close()


def run_eval_mode(args) -> int:
    """``eval`` mode: teacher-forced NLL over ``--data`` through the
    real serving stack (runtime/evalharness.py). ``--json`` emits the
    one-line summary tools/quality_baseline.py consumes; ``--compare``
    re-scores under a second config and asserts BIT-IDENTICAL total NLL
    (exit 1 on parity drift). A mid-run failure exits 1 with a
    partial-results JSON naming completed vs in-flight sequences."""
    import json as _json

    from ..runtime import evalharness, failpoints

    if not args.data:
        raise SystemExit("--data FILE.jsonl is required for eval mode")
    if failpoints.configure_from_env():
        print("💣 fault injection armed from DLLAMA_FAILPOINTS="
              f"{os.environ.get('DLLAMA_FAILPOINTS')}", file=sys.stderr)
    dataset = os.path.splitext(os.path.basename(args.data))[0]
    tok = None
    if args.tokenizer:
        from ..tokenizer.bpe import Tokenizer

        tok = Tokenizer.load(args.tokenizer)
    seq_cap = args.max_seq_len or 0
    seqs = evalharness.load_dataset(args.data, tok, seq_len=seq_cap)
    primary = _eval_primary_config(args)
    try:
        result = _run_eval_config(args, seqs, dataset, primary)
        if args.compare and args.compare != primary:
            cmp_res = _run_eval_config(args, seqs, dataset, args.compare)
            result = dict(result)
            result["compare"] = cmp_res
            result["parity_drift"] = (
                cmp_res["total_nll_hex"] != result["total_nll_hex"])
    except evalharness.EvalAborted as e:
        print(_json.dumps(e.partial), flush=True)
        print(f"💥 {e}", file=sys.stderr)
        return 1
    if args.json:
        print(_json.dumps(result), flush=True)
    else:
        print(f"📊 eval {dataset} [{result['config']}]: "
              f"{result['n_seqs']} seqs, {result['n_tokens']} tokens")
        print(f"📊 Perplexity: {result['perplexity']:.4f}  "
              f"total NLL: {result['total_nll']:.6f} "
              f"({result['total_nll_hex']})")
        print(f"📊 Time: {result['wall_s']:.2f}s "
              f"({result['eval_tok_per_s']:.1f} tok/s)")
        if "compare" in result:
            c = result["compare"]
            print(f"📊 compare [{c['config']}]: perplexity "
                  f"{c['perplexity']:.4f} ({c['total_nll_hex']})")
    if result.get("parity_drift"):
        print(f"💥 parity drift: total NLL differs bit-from-bit between "
              f"{result['config']} and {result['compare']['config']} — "
              f"these configs are exact-parity by contract",
              file=sys.stderr)
        return 1
    return 0


def _worker_supervisor(args) -> int:
    """--worker-reserve outer loop — the reference worker's while(true)
    re-serve (app.cpp:299-358) at process granularity: jax.distributed cannot
    re-initialize in-process, and on coordinator loss the jax client's
    error-polling thread can LOG(FATAL)-abort the worker before any Python
    cleanup runs, so resilience must live OUTSIDE the process that holds the
    distributed client.

    Exit codes can't classify the death: the jax fatal fires on a C++ thread
    and exits with a generic rc (observed: 1 — same as any Python traceback)
    before our handlers run. Instead the child touches a phase-sentinel file
    the moment it has joined the cluster; the supervisor respawns on ANY
    nonzero exit that happened after the join (by then config/model/startup
    are proven good and the only thing left to lose is the root) and
    propagates pre-join failures (argparse rc 2, bad model path, jax init)
    instead of hot-looping. Backoff resets once a child has served long
    enough that the next death is a new incident, not the same flapping
    root. SIGTERM/SIGINT forward to the child so killing the supervisor
    never orphans the worker; delivery is blocked across the spawn itself so
    a signal can't slip between fork/exec and the bookkeeping that lets the
    handler find the child."""
    import signal
    import subprocess
    import tempfile

    phase_file = os.path.join(
        tempfile.mkdtemp(prefix="dllama-worker-"), "joined")
    child_env = dict(os.environ, DLLAMA_WORKER_CHILD="1",
                     DLLAMA_WORKER_PHASE_FILE=phase_file)
    cmd = [sys.executable, "-m", "dllama_tpu",
           *getattr(args, "_argv", sys.argv[1:])]
    state: dict = {"child": None}
    _SIGS = {signal.SIGTERM, signal.SIGINT}

    def _forward(sig, _frame):
        child = state["child"]
        if child is not None and child.poll() is None:
            child.terminate()
        os._exit(128 + sig)

    signal.signal(signal.SIGTERM, _forward)
    signal.signal(signal.SIGINT, _forward)

    backoff = 1.0
    try:
        while True:
            if os.path.exists(phase_file):
                os.unlink(phase_file)
            signal.pthread_sigmask(signal.SIG_BLOCK, _SIGS)
            try:
                # the blocked mask is inherited across exec; the CHILD
                # unblocks it at interpreter start (cli.main's
                # DLLAMA_WORKER_CHILD branch) — not via preexec_fn, which is
                # deadlock-prone in a threaded parent (jax is IMPORTED here,
                # for the compile-cache config only: the supervisor never
                # initialises a backend, so it never holds the chip its
                # child needs)
                state["child"] = subprocess.Popen(cmd, env=child_env)
            finally:
                signal.pthread_sigmask(signal.SIG_UNBLOCK, _SIGS)
            rc = state["child"].wait()
            if rc == 0:
                return 0  # clean STOP from the root
            joined_at = (os.path.getmtime(phase_file)
                         if os.path.exists(phase_file) else None)
            abort_shaped = rc in (-signal.SIGABRT, 128 + signal.SIGABRT)
            if joined_at is None and not abort_shaped:
                # died before joining (or withdrew the sentinel on a Python
                # startup error): argparse (2), bad model path, jax init
                # failure, ... — permanent, don't hot-loop. A SIGABRT with no
                # sentinel is the jax fatal racing the join window (root died
                # mid-init): still root-loss-shaped, still respawn.
                print(f"⭕ worker failed rc={rc} (startup/config, not root "
                      f"loss) — giving up", flush=True)
                return rc
            if joined_at is not None and time.time() - joined_at > 60.0:
                backoff = 1.0  # served a healthy root for a while: fresh
                # incident, not the same flapping root (join time, not spawn
                # time — model load must not count toward "served")
            print(f"⭕ worker exited rc={rc}; re-serving: waiting for a new "
                  f"root", flush=True)
            time.sleep(backoff)
            backoff = min(backoff * 2, 30.0)
    finally:
        import shutil

        shutil.rmtree(os.path.dirname(phase_file), ignore_errors=True)


def run_worker(args) -> int:
    """Multi-host worker: join the cluster and co-execute the root's program.

    Under SPMD every process must run the same jitted programs in the same
    order (or process 0 deadlocks at the first collective), so the worker
    builds the same engine from its local copy of the model files and then
    replays each dispatch the root broadcasts — the TPU-native runWorkerApp
    (reference: src/app.cpp:299-358; the config/weight wire protocol,
    nn-network.cpp:621-901, is replaced by each host loading its own shards).
    """
    if args.worker_reserve and not os.environ.get("DLLAMA_WORKER_CHILD"):
        return _worker_supervisor(args)

    import jax

    from ..parallel.multihost import RootLostError, init_distributed, worker_serve

    if args.nprocs is None:
        init_distributed()  # TPU pod: topology comes from the environment
    else:
        _maybe_init_distributed(args)
    print(f"⭕ worker: process {jax.process_index()} of {jax.process_count()}, "
          f"{jax.local_device_count()} local devices")
    # Phase sentinel for the supervisor: present = this incarnation joined
    # the cluster, so a later death is root-loss-shaped. A *Python* exception
    # below (bad model path, loader failure) withdraws it before propagating;
    # the jax C++ fatal on root death can't run this cleanup — which is
    # exactly the distinction the supervisor needs.
    phase = os.environ.get("DLLAMA_WORKER_PHASE_FILE")
    if phase:
        open(phase, "w").close()
    try:
        engine = make_engine(args, multihost=True)
    except BaseException:  # incl. SystemExit from argument validation
        if phase and os.path.exists(phase):
            os.unlink(phase)
        raise
    try:
        served = worker_serve(engine, timeout_s=args.worker_timeout)
    except RootLostError as e:
        # Exit IMMEDIATELY: the jax client's error-polling abort races any
        # cleanup here. os._exit(3) usually wins; when it doesn't, the
        # supervisor (above) treats the abort exit identically.
        print(f"⭕ {e}", flush=True)
        os._exit(3)
    # Other exceptions propagate with their traceback; the supervisor's
    # phase sentinel (not the rc) classifies the death, so nothing is
    # gained by flattening them to a bare exit code here.
    print(f"⭕ worker done: served {served} dispatches")
    return 0


def _setup_compile_cache(args) -> None:
    """Persistent jit-compile cache (defaults on): dllama restarts reuse
    every compiled program instead of re-paying 20-40s-per-program TPU
    compiles. WHERE it lives is :mod:`dllama_tpu.compile_cache`'s one rule
    (``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's fixed
    directory); ``--compile-cache off`` disables. Applied via env BEFORE
    any backend use so worker subprocesses inherit it too."""
    if getattr(args, "compile_cache", "on") == "off":
        return
    from ..compile_cache import enable

    enable()  # this module's imports already loaded jax: its config is set too


def main(argv=None) -> int:
    if os.environ.get("DLLAMA_WORKER_CHILD"):
        # the supervisor blocks SIGTERM/SIGINT around its spawn (so a kill
        # can't slip between fork/exec and its child bookkeeping) and the
        # blocked mask is inherited across exec — undo it HERE, in the
        # child's own interpreter, rather than via Popen(preexec_fn=...):
        # CPython documents preexec_fn as deadlock-prone once the parent has
        # threads (the supervisor imported jax, which starts several) and it
        # forces fork over the faster posix_spawn path.
        import signal

        signal.pthread_sigmask(signal.SIG_UNBLOCK,
                               {signal.SIGTERM, signal.SIGINT})
    args = build_parser().parse_args(argv)
    # raw argv for the worker supervisor's respawn command: honors explicit
    # programmatic argv (tests call cli.main([...])), not the host process's
    args._argv = list(argv) if argv is not None else sys.argv[1:]
    args._multihost = False
    if args.mode == "verify":
        # pure host-side integrity check: no jax backend, no compile cache
        return run_verify(args)
    if args.mode == "audit":
        # host-side quant-error audit (runtime/numerics): no jax either
        return run_audit(args)
    if args.mode == "timeline":
        # offline flight-dump → Chrome trace converter: no jax either
        return run_timeline(args)
    if args.mode == "fleettrace":
        # offline router+replica dump joiner → fleet Chrome trace: no jax
        return run_fleettrace(args)
    if args.mode == "router":
        # fleet router tier: no model, no device, no backend init — it
        # fronts api-server replicas over plain HTTP (serve/router.py)
        from .router import run_router

        return run_router(args)
    _setup_compile_cache(args)
    if args.mode != "worker":
        # Worker mode must not touch the backend here:
        # jax.distributed.initialize() requires a fresh one.
        import jax

        # multi-host root: join the cluster BEFORE any backend use
        args._multihost = _maybe_init_distributed(args)
        need = max(1, (args.tp or 1)) * max(1, args.sp) * max(1, args.pp)
        if need > len(jax.devices()):
            raise SystemExit(
                f"requested tp×sp×pp = {need} devices but only "
                f"{len(jax.devices())} visible (for a virtual mesh: "
                f"JAX_PLATFORMS=cpu "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need})")
    if args.trace_out and args.mode != "api":
        # api mode configures (and closes) the tracer itself so the banner
        # prints next to the listen line; other modes wire it here
        from ..runtime import telemetry

        telemetry.tracer().configure(args.trace_out)
        print(f"🔬 request trace (JSONL spans) → {args.trace_out}")
    if args.mode == "inference":
        return run_inference(args)
    if args.mode == "chat":
        return run_chat(args)
    if args.mode == "perplexity":
        return run_perplexity(args)
    if args.mode == "eval":
        return run_eval_mode(args)
    if args.mode == "api":
        from .api import run_api_server

        return run_api_server(args)
    if args.mode == "worker":
        return run_worker(args)
    raise SystemExit(f"unknown mode {args.mode}")

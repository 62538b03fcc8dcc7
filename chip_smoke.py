#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that dllama-tpu still starts on the chip.

Drives the normal entry points (``python -m dllama_tpu inference`` / ``api``)
once, on one TPU chip, at the full published width of meta-llama/Llama-3.2-1B
(``config.json``: dim 2048, hidden 8192, 16 layers, 32 heads, 8 KV heads,
head_dim 64, vocab 128256, rope theta 500000 with the Llama-3.1 scaling; no
width and no layer cut; context run at ``--max-seq-len 4096``), with Q40
weights and a 128256-entry tokenizer written from ``--seed`` by the repo's
own writers. Checks what comes out by the repo's own means and prints, as
the LAST line of stdout, one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It exits nonzero, and prints no such line, when any phase fails — which
includes "JAX found no TPU": every child is started with JAX_PLATFORMS=tpu.

    python chip_smoke.py               # one chip: the driver's run
    python chip_smoke.py --chips 4     # only the tp=4 path vs tp=1 (builder's run)
    python chip_smoke.py --rehearse    # CPU children, toy widths: control flow
                                       # only, never a result (tests use it)

One process per chip: THIS process never imports jax. It starts each phase as
a child, one after another, and waits for each to exit. It imports numpy and
``dllama_tpu.formats`` (plus the stdlib-only ``dllama_tpu.compile_cache``).
The two checks that need jax run as children of their own, through
``--child`` below.

Where things live: the generated model under ``chip_smoke_model/``, the
compile cache where ``JAX_COMPILATION_CACHE_DIR`` says or else ``.xla_cache/``,
each phase's full output under ``chiprun_out/chip_smoke/`` — all git-ignored.
"""

from __future__ import annotations

import argparse
import atexit
import json
import os
import re
import select
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL_DIR = os.path.join(HERE, "chip_smoke_model")
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# meta-llama/Llama-3.2-1B config.json, as .m header keys (ints, as the
# reference converter writes them: convert/hf.py)
LLAMA_3_2_1B = dict(
    version=0, arch_type=0xABCD00, hidden_act=1, dim=2048, hidden_dim=8192,
    n_layers=16, n_heads=32, n_kv_heads=8, weight_float_type=2,  # Q40
    seq_len=131072, vocab_size=128256, n_experts=0, n_active_experts=0,
    rope_theta=500000, rope_scaling_factor=32, rope_scaling_low_freq_factor=1,
    rope_scaling_high_freq_factory=4, rope_scaling_orig_max_seq_len=8192,
    rope_type=2, head_dim=64, norm_epsilon=5)
# --rehearse: the same file layout at toy widths (CPU children)
TOY = dict(LLAMA_3_2_1B, dim=256, hidden_dim=512, n_layers=2, n_heads=4,
           n_kv_heads=2, vocab_size=1024, seq_len=512)
MAX_SEQ_LEN = 4096
N_SPECIAL = 256  # Llama 3: 128000 regular tokens, then 256 special ones

# 99 ASCII characters: the generated vocabulary has every single byte and no
# ASCII merge, so this is 99 tokens + BOS = a 100-token prompt
PROMPT = ("The quick brown fox jumps over the lazy dog while a distributed "
          "llama shards its tensors over chips")
assert len(PROMPT) == 99
# the bf16 phase's: 255 characters + BOS = ONE 256-row prefill bucket (the
# widest of engine.PREFILL_BUCKETS), so `forward` is traced at that width
PROMPT_256 = ((PROMPT + " ") * 3)[:255]
NEW_TOKENS = 64


def say(msg: str) -> None:
    print(msg, flush=True)


def die(msg: str) -> None:
    """A failed phase ends the script: no phase is wrapped in try/except and
    nothing prints "ok" after this."""
    say(f"FAIL: {msg}")
    sys.exit(1)


# -- the model, from a seed -------------------------------------------------


def write_model(params: dict, seed: int, m_path: str, t_path: str) -> None:
    """A Q40 ``.m`` and a ``.t`` at ``params``' widths, random from ``seed``,
    through formats/mfile.py, formats/quants.py and formats/tfile.py (the
    layout of tests/helpers.py::write_tiny_model; tensor order is
    ModelFile._walk's). The published model ties the logits head to the
    embedding; the .m format stores both, and here the head is a tensor of
    its own: a random tied pair would make every step predict its own input
    token, and a generation that repeats one token checks little."""
    import numpy as np

    from dllama_tpu.formats import mfile, quants, tfile

    rng = np.random.default_rng(seed)
    dim, hid, vocab = params["dim"], params["hidden_dim"], params["vocab_size"]
    q_dim = params["head_dim"] * params["n_heads"]
    kv_dim = params["head_dim"] * params["n_kv_heads"]

    def rand(rows: int, cols: int) -> "np.ndarray":
        # uniform with std 0.02 (the published initializer_range)
        a = 0.02 * 3 ** 0.5
        return (rng.random((rows, cols), dtype=np.float32) - 0.5) * (2 * a)

    with open(m_path + ".tmp", "wb") as f:
        mfile.write_header(f, params)
        f.write(rand(vocab, dim).tobytes())
        for _ in range(params["n_layers"]):
            for rows, cols in ((q_dim, dim), (kv_dim, dim), (kv_dim, dim),
                               (dim, q_dim), (hid, dim), (dim, hid),
                               (hid, dim)):  # q k v wo w1 w2 w3
                f.write(quants.quantize_q40(rand(rows, cols).reshape(-1)))
            for _ in range(2):  # the two rms norms
                f.write((1.0 + rand(1, dim)).astype(np.float32).tobytes())
        f.write((1.0 + rand(1, dim)).astype(np.float32).tobytes())
        f.write(quants.quantize_q40(rand(vocab, dim).reshape(-1)))
    os.replace(m_path + ".tmp", m_path)

    # tokenizer: 256 single bytes, then unique two-character tokens from
    # U+0100.. (valid UTF-8, never in an ASCII prompt), then the specials
    n_regular = vocab - N_SPECIAL
    cps = [chr(c) for c in range(0x100, 0x800)]
    vocab_b = [bytes([b]) for b in range(256)]
    i = 0
    while len(vocab_b) < n_regular:
        a, b = divmod(i, len(cps))
        vocab_b.append((cps[a] + cps[b]).encode())
        i += 1
    special = {0: "<|begin_of_text|>", 1: "<|end_of_text|>",
               6: "<|start_header_id|>", 7: "<|end_header_id|>",
               9: "<|eot_id|>"}
    vocab_b += [special.get(j, f"<|reserved_special_token_{j}|>").encode()
                for j in range(N_SPECIAL)]
    td = tfile.TokenizerData(
        vocab=vocab_b, scores=[0.0] * len(vocab_b), bos_id=n_regular,
        add_bos=True, eos_token_ids=[n_regular + 1, n_regular + 9],
        chat_template="{{ '<|start_header_id|>' + message['role'] + "
                      "'<|end_header_id|>' }}",
        max_token_length=max(len(t) for t in vocab_b))
    tfile.write_tfile(t_path, td)


def ensure_model(params: dict, seed: int, tag: str) -> tuple[str, str]:
    """The fixed, git-ignored model path; reused when seed and config match."""
    os.makedirs(MODEL_DIR, exist_ok=True)
    m_path = os.path.join(MODEL_DIR, f"{tag}.m")
    t_path = os.path.join(MODEL_DIR, f"{tag}.t")
    stamp_path = os.path.join(MODEL_DIR, f"{tag}.json")
    stamp = {"seed": seed, "params": params}
    try:
        with open(stamp_path) as f:
            fresh = json.load(f) == stamp and os.path.exists(m_path) \
                and os.path.exists(t_path)
    except (OSError, ValueError):
        fresh = False
    if fresh:
        say(f"model: reusing {m_path} (seed {seed} matches)")
        return m_path, t_path
    t0 = time.monotonic()
    write_model(params, seed, m_path, t_path)
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    say(f"model: wrote {m_path} ({os.path.getsize(m_path) / 2**30:.2f} GiB, "
        f"Q40) and {t_path} ({params['vocab_size']} tokens) from seed {seed} "
        f"in {time.monotonic() - t0:.1f} s")
    return m_path, t_path


# -- children ---------------------------------------------------------------


class Child:
    """One child process, its stdout teed to ``<name>.log`` and its stderr
    sent to ``<name>.err``; killed by its own process group so nothing it
    started outlives it."""

    def __init__(self, name: str, cmd: list[str], env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self.log = open(self.log_path, "wb")
        self.err_path = os.path.join(LOG_DIR, f"{name}.err")
        self.t0 = time.monotonic()
        with open(self.err_path, "wb") as err:
            self.proc = subprocess.Popen(
                cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, stderr=err,
                start_new_session=True)
        self.buf = b""
        self.stamps: list[tuple[int, float]] = []  # (bytes so far, seconds)
        atexit.register(self.kill)  # whatever ends this script, no child stays

    def pump(self, timeout: float) -> bool:
        """Read what the child wrote within ``timeout``; False at EOF."""
        r, _, _ = select.select([self.proc.stdout], [], [], timeout)
        if not r:
            return True
        chunk = os.read(self.proc.stdout.fileno(), 65536)
        if not chunk:
            return False
        self.log.write(chunk)
        self.log.flush()
        self.buf += chunk
        self.stamps.append((len(self.buf), time.monotonic() - self.t0))
        return True

    def time_of_byte(self, offset: int) -> float:
        """Seconds after spawn at which output byte ``offset`` had arrived."""
        return next(t for n, t in self.stamps if n > offset)

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)  # the whole group
        except ProcessLookupError:
            pass  # it, and everything it started, is gone already
        self.proc.wait()
        self.log.close()

    def text(self) -> str:
        return self.buf.decode("utf-8", "replace")

    def fail(self, why: str) -> None:
        self.kill()
        with open(self.err_path, errors="replace") as f:
            err = "\n".join(ln[:400] for ln in f.read().splitlines()[-30:])
        out = "\n".join(self.text().splitlines()[-30:])
        die(f"phase {self.name}: {why} (full output: {self.log_path}, "
            f"{self.err_path})\n--- last lines of stdout ---\n{out}\n"
            f"--- last lines of stderr ---\n{err}")

    def wait_exit(self, timeout: float) -> int:
        """Pump until the child exits; a child over its time is a failure."""
        deadline = self.t0 + timeout
        while self.pump(1.0):
            if time.monotonic() > deadline:
                self.fail(f"still running after {timeout:.0f} s")
        rc = self.proc.wait()
        self.log.close()
        return rc

    def wait_for(self, pattern: str, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while pattern.encode() not in self.buf:
            if self.proc.poll() is not None and not self.pump(0):
                self.fail(f"exited rc={self.proc.returncode} before "
                          f"printing {pattern!r}")
            if time.monotonic() > deadline:
                self.fail(f"did not print {pattern!r} within {timeout:.0f} s")
            self.pump(1.0)


DEVICE_RE = re.compile(r'TP devices: (\d+) .* on (\w+) "([^"]*)" x(\d+)')
BUDGET_RE = re.compile(r"HBM budget/device: (.*)")


def banner_facts(ch: Child, platform: str, n_devices: int) -> dict:
    """What the child's start-up banner says about the process that actually
    held the chip: device, weight codec, HBM budget. Requires ``platform``."""
    text = ch.text()
    m = DEVICE_RE.search(text)
    if not m:
        ch.fail("printed no device banner")
    dev = {"platform": m.group(2), "kind": m.group(3), "count": int(m.group(4))}
    if dev["platform"] != platform or dev["count"] != n_devices:
        ch.fail(f"ran on {dev}, wanted {n_devices} x {platform}")
    codec = re.search(r"weight codec: (.*)", text)
    budget = BUDGET_RE.search(text)
    if not codec or not budget:
        ch.fail("printed no codec or no HBM budget line")
    if platform == "tpu" and "of " not in budget.group(1):
        ch.fail(f"HBM budget shows no device limit: {budget.group(1)}")
    say(f"  [{ch.name}] device: {dev['platform']} \"{dev['kind']}\" "
        f"x{dev['count']}, tp={m.group(1)}; codec: {codec.group(1)}")
    say(f"  [{ch.name}] HBM budget/device: {budget.group(1)}")
    return dev


def run_inference(name: str, ctx: dict, extra: list[str],
                  prompt: str = PROMPT) -> dict:
    """``python -m dllama_tpu inference`` with the README quick-start
    defaults, greedy; returns the generated text and what the run printed."""
    ch = Child(name, [
        sys.executable, "-m", "dllama_tpu", "inference",
        "--model", ctx["model"], "--tokenizer", ctx["tokenizer"],
        "--max-seq-len", str(ctx["max_seq_len"]), "--prompt", prompt,
        "--steps", str(len(prompt) + 1 + NEW_TOKENS), "--temperature", "0",
        "--seed", "1", *extra], ctx["env"])
    rc = ch.wait_exit(ctx["timeout"])
    if rc != 0:
        ch.fail(f"exited rc={rc}")
    dev = banner_facts(ch, ctx["platform"], ctx["n_devices"])
    text = ch.text()
    head = text.index(prompt + "\n") + len(prompt) + 1
    end = text.index("\nEvaluation\n", head)
    generated = text[head:end]
    n_pred = int(re.search(r"Prediction\n\s+nTokens: (\d+)", text).group(1))
    if n_pred != NEW_TOKENS or not generated.strip():
        ch.fail(f"generated {n_pred} tokens ({generated!r}), wanted "
                f"{NEW_TOKENS}")
    # time to first token, from the prompt's echo (the engine is loaded, the
    # prompt goes in) to the first generated byte: prefill and first decode,
    # compiles included; process start and the weight load are not in it
    echo = (prompt + "\n").encode()
    at = ch.buf.index(echo)
    loaded_s = ch.time_of_byte(at)
    first_token_s = ch.time_of_byte(at + len(echo)) - loaded_s
    comp = re.search(r"compiles: (\d+) in \S+ ([\d.]+) s wall, ([\d.]+) s in "
                     r"the XLA backend", text)
    if not comp:
        ch.fail("printed no compile report")
    kernels = {m.group(1): m.group(2) for m in re.finditer(
        r"compiled (\w+): .*Pallas kernels: (.*)", text)}
    say(f"  [{name}] loaded after {loaded_s:.1f} s, first token "
        f"{first_token_s:.2f} s after the prompt, {n_pred} tokens; "
        f"{comp.group(1)} compiles {comp.group(2)} s wall, {comp.group(3)} s "
        f"in the XLA backend")
    for prog, kern in kernels.items():
        say(f"  [{name}]   {prog}: Pallas kernels compiled in: {kern}")
    paths = re.search(r"q40 matmuls: (.*)", text)
    paths = paths.group(1) if paths else ""
    say(f"  [{name}]   q40 matmuls by path: {paths or 'none counted'}")
    return {"text": generated, "first_token_s": first_token_s, "dev": dev,
            "backend_s": float(comp.group(3)), "kernels": kernels,
            "q40_paths": paths, "log": text, "child": ch}


def cache_entries(ctx: dict) -> int:
    try:
        return len(os.listdir(ctx["cache_dir"]))
    except FileNotFoundError:
        return 0


def phase_inference_f32(ctx: dict) -> dict:
    """The exact path, twice: same text, and the second run is a cache hit."""
    say("phase inference-f32: exact numerics (f32 compute), run twice")
    n_before = cache_entries(ctx)
    cold = run_inference("inference-f32-cold", ctx, [])
    n_after_cold = cache_entries(ctx)
    warm = run_inference("inference-f32-warm", ctx, [])
    n_after_warm = cache_entries(ctx)
    if cold["text"] != warm["text"]:
        die(f"inference-f32: two greedy runs differ:\n{cold['text']!r}\n"
            f"{warm['text']!r}")
    say(f"  compile cache {ctx['cache_dir']}: {n_before} entries before, "
        f"{n_after_cold} after the first run, {n_after_warm} after the "
        f"second; XLA backend seconds {cold['backend_s']:.2f} -> "
        f"{warm['backend_s']:.2f}; time to first token "
        f"{cold['first_token_s']:.2f} s -> {warm['first_token_s']:.2f} s")
    if n_after_warm != n_after_cold:
        die("inference-f32: the second run added entries to the compile "
            "cache — it was not a cache hit")
    if n_after_cold == 0:
        die("inference-f32: the first run cached nothing")
    if n_after_cold == n_before:
        # a machine that came with JAX_COMPILATION_CACHE_DIR set and kept an
        # earlier call's entries: both runs were hits, nothing to compare
        say("  the first run added no entry either: the cache was already "
            "warm, so first-token times are not compared")
    elif not warm["first_token_s"] < cold["first_token_s"]:
        die(f"inference-f32: the cache hit reached its first token "
            f"{warm['first_token_s']:.2f} s after the prompt, not sooner "
            f"than the cold run ({cold['first_token_s']:.2f} s)")
    if ctx["platform"] == "tpu":
        # the f32 decode program must CONTAIN the kernels, compiled: a
        # Mosaic custom call is in the HLO only when interpret=False
        k = cold["kernels"].get("greedy_step", "")
        if "quant_matmul" not in k or "_call" not in k:
            die(f"inference-f32: the decode program's compiled Pallas "
                f"kernels are {k!r}; wanted quant_matmul and the flash "
                f"attention _call")
    return cold


def phase_inference_bf16(ctx: dict) -> dict:
    say("phase inference-bf16: serving numerics (on a TPU the fused "
        "dequant-GEMV at M = 1 and the same kernel's chunk regime for a "
        "256-token prompt: one 256-row bucket)")
    out = run_inference("inference-bf16", ctx, ["--compute-dtype", "bf16"],
                        prompt=PROMPT_256)
    if ctx["platform"] != "tpu":
        return out
    if not re.search(r"greedy_step 0 chunk / [1-9]\d* fused / 0 tiled / 0 xla",
                     out["q40_paths"]):
        # `auto` has no row floor: one row is a decode-shaped dispatch
        out["child"].fail("the bf16 decode step (M = 1) did not take the "
                          f"fused kernel: {out['q40_paths']!r}")
    if not re.search(r"forward [1-9]\d* chunk / 0 fused / 0 tiled / 0 xla",
                     out["q40_paths"]):
        # a chunk that fell back dequantizes in passes through HBM again
        out["child"].fail("the bf16 prefill (one 256-row bucket) did not "
                          "take the fused kernel's chunk regime on every "
                          f"Q40 matmul: {out['q40_paths']!r}")
    return out


def http(port: int, path: str, body: dict | None = None,
         timeout: float = 300.0) -> tuple[int, bytes]:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def metric(text: str, name: str) -> float:
    """Sum of a Prometheus metric's samples over its label sets."""
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith(name) and ln[len(name)] in " {")


def phase_api(ctx: dict, extra: list[str], n_tokens: int = 24) -> dict:
    """The batched paged server: readiness, concurrent + streamed + repeated
    completions, metrics, the compile ledger, SIGTERM drain."""
    name = "api" + "".join(extra).replace("--", "-")
    say(f"phase {name}: api --batch-slots 4 --kv-block-size 16 bf16, greedy")
    port = free_port()
    ch = Child(name, [
        sys.executable, "-m", "dllama_tpu", "api", "--model", ctx["model"],
        "--tokenizer", ctx["tokenizer"], "--max-seq-len",
        str(ctx["max_seq_len"]), "--port", str(port), "--batch-slots", "4",
        "--kv-block-size", "16", "--compute-dtype", "bf16",
        "--temperature", "0", "--seed", "1", *extra], ctx["env"])
    ch.wait_for("listening on", ctx["timeout"])
    dev = banner_facts(ch, ctx["platform"], ctx["n_devices"])
    deadline = time.monotonic() + 120
    while http(port, "/readyz")[0] != 200:
        if time.monotonic() > deadline or ch.proc.poll() is not None:
            ch.fail("/readyz never answered 200")
        ch.pump(0.5)
    say(f"  [{name}] /readyz 200 after {time.monotonic() - ch.t0:.1f} s")

    def body(msg: str, **kw) -> dict:
        return dict(messages=[{"role": "user", "content": msg}],
                    max_tokens=n_tokens, temperature=0, **kw)

    bodies = [body("Tell me about llamas on tensor processing units."),
              body("Why do chips need high bandwidth memory?")]
    replies: list = [None, None]

    def post(i: int) -> None:
        replies[i] = http(port, "/v1/chat/completions", bodies[i])

    threads = [threading.Thread(target=post, args=(i,)) for i in range(2)]
    t0 = time.monotonic()
    for th in threads:
        th.start()
    while any(th.is_alive() for th in threads):
        ch.pump(0.2)
        if time.monotonic() - t0 > ctx["timeout"]:
            ch.fail("concurrent completions did not return")

    def checked(status: int, raw: bytes, what: str) -> str:
        if status != 200:
            ch.fail(f"{what}: HTTP {status}: {raw[:300]!r}")
        doc = json.loads(raw)
        content = doc["choices"][0]["message"]["content"]
        got = doc["usage"]["completion_tokens"]
        if not content or got != n_tokens:
            ch.fail(f"{what}: content {content!r}, completion_tokens {got}, "
                    f"asked for {n_tokens}")
        return content

    texts = [checked(*replies[i], f"concurrent request {i}") for i in range(2)]
    say(f"  [{name}] 2 concurrent completions: {n_tokens} tokens each in "
        f"{time.monotonic() - t0:.1f} s (first dispatches compile)")

    status, raw = http(port, "/v1/chat/completions",
                       dict(bodies[0], stream=True))
    if status != 200:
        ch.fail(f"streamed request: HTTP {status}: {raw[:300]!r}")
    events = [ln[6:] for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    if events[-1] != "[DONE]":
        ch.fail(f"stream did not end with [DONE]: {events[-3:]}")
    streamed = "".join(json.loads(e)["choices"][0]["delta"].get("content", "")
                       for e in events[:-1])
    if streamed != texts[0]:
        ch.fail(f"streamed reply differs from the non-streamed reply to the "
                f"same body:\n{streamed!r}\n{texts[0]!r}")
    repeat = checked(*http(port, "/v1/chat/completions", bodies[1]),
                     "repeated request")
    if repeat != texts[1]:
        ch.fail(f"identical bodies, different replies:\n{repeat!r}\n"
                f"{texts[1]!r}")
    say(f"  [{name}] streamed == non-streamed, repeat == original")

    mtext = http(port, "/metrics")[1].decode()
    served = metric(mtext, "dllama_completion_tokens_total")
    reused = metric(mtext, "dllama_prefix_reuse_tokens_total")
    if served != 4 * n_tokens or reused <= 0:
        ch.fail(f"/metrics: completion tokens {served} (wanted "
                f"{4 * n_tokens}), prefix reuse tokens {reused} (wanted > 0)")
    ledger = json.loads(http(port, "/debug/compiles")[1])
    compiled = [p for p in ledger["programs"] if p["compiles"]]
    if not compiled or any(not p["hbm_total_bytes"] for p in compiled):
        ch.fail("/debug/compiles: a compiled program has no measured HBM "
                f"bytes: {[(p['program'], p['hbm_total_bytes']) for p in compiled]}")
    say(f"  [{name}] /metrics: {served:.0f} completion tokens, {reused:.0f} "
        f"prefix-reuse tokens; /debug/compiles: {len(compiled)} programs, "
        f"{sum(p['total_compile_s'] for p in compiled):.1f} s compile wall")
    for p in compiled:
        kern = (p["analysis"] or {}).get("kernels") or {}
        paths = p.get("q40_paths") or {}
        say(f"  [{name}]   {p['program']}: HBM "
            f"{p['hbm_total_bytes'] / 2**30:.2f} GiB, Pallas kernels "
            f"compiled in: "
            + (" ".join(f"{k}x{n}" for k, n in sorted(kern.items()))
               or "none")
            + ("; q40 matmuls " + " / ".join(f"{n} {k}"
                                             for k, n in paths.items())
               if paths else ""))

    os.kill(ch.proc.pid, signal.SIGTERM)
    rc = ch.wait_exit(time.monotonic() - ch.t0 + 60)
    if rc != 0 or "SIGTERM: draining" not in ch.text():
        ch.fail(f"SIGTERM did not drain to a clean exit (rc={rc})")
    say(f"  [{name}] SIGTERM drained, exit 0")
    return {"dev": dev, "compiled": compiled, "texts": texts}


def phase_hw_tier(ctx: dict) -> None:
    """tests/test_tpu_hw.py in its own process. Under the smoke a skip is a
    failure: the file skips when it finds no TPU, right for CI, wrong here."""
    say("phase hw-tier: tests/test_tpu_hw.py on the chip")
    ch = Child("hw-tier", [
        sys.executable, "-m", "pytest", "tests/test_tpu_hw.py", "-m", "tpu",
        "-q", "-rs", "-p", "no:cacheprovider", "-p", "no:xdist",
        "-p", "no:randomly"], dict(ctx["env"], DLLAMA_TESTS_TPU="1"))
    rc = ch.wait_exit(ctx["timeout"])
    last = ch.text().strip().splitlines()[-1]
    counts = {k: int(n) for n, k in re.findall(r"(\d+) (\w+)", last)}
    if rc != 0 or counts.get("passed", 0) < 13 or \
            set(counts) - {"passed", "warnings", "warning"}:
        ch.fail(f"rc={rc}: {last}")
    say(f"  [hw-tier] {last.strip('= ')}")


def phase_block_until_ready(ctx: dict) -> None:
    """Does ``jax.block_until_ready`` wait for the device on this chip?
    Notes from before PR 22 claim that it does not (a transport that is
    gone). Reported, not judged."""
    say("phase sync-check: does jax.block_until_ready wait for the device?")
    ch = Child("sync-check", [sys.executable, os.path.abspath(__file__),
                              "--child", "sync-check"], ctx["env"])
    rc = ch.wait_exit(ctx["timeout"])
    if rc != 0:
        ch.fail(f"exited rc={rc}")
    res = json.loads(ch.text().strip().splitlines()[-1])
    if res["platform"] != ctx["platform"]:
        ch.fail(f"ran on {res['platform']}, wanted {ctx['platform']}")
    say(f"  [sync-check] on {res['platform']} \"{res['kind']}\": one jitted "
        f"elementwise pass, {res['gib']:.2f} GiB read + written: "
        f"block_until_ready returned after {res['block_ms']:.2f} ms, a "
        f"data-dependent host fetch after {res['fetch_ms']:.2f} ms, dispatch "
        f"alone {res['dispatch_ms']:.2f} ms -> block_until_ready "
        f"{'WAITS' if res['waits'] else 'does NOT wait'} for the device")


def child_sync_check() -> int:
    """(child; imports jax) Time one large jitted op three ways: dispatch
    only, dispatch + block_until_ready, dispatch + fetch of a dependent
    scalar. If block_until_ready waits, its time is close to the fetch's."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    n = 2 ** 28 if dev.platform == "tpu" else 2 ** 22  # f32: 1 GiB on a chip

    @jax.jit
    def chain(x):  # one pass: read the buffer, write it
        return x * 1.0001 + 1.0

    x = jnp.ones((n,), jnp.float32)
    jax.device_get(chain(x)[0])  # compile + settle

    def timed(finish) -> float:
        best = float("inf")
        for _ in range(5):
            jax.device_get(x[0])  # queue empty
            t0 = time.perf_counter()
            finish(chain(x))
            best = min(best, 1e3 * (time.perf_counter() - t0))
            jax.device_get(chain(x)[0])  # drain before the next reading
        return best

    dispatch_ms = timed(lambda y: None)
    block_ms = timed(jax.block_until_ready)
    fetch_ms = timed(lambda y: jax.device_get(y[0]))
    print(json.dumps({
        "platform": dev.platform, "kind": dev.device_kind,
        "gib": 2 * 4 * n / 2 ** 30, "dispatch_ms": dispatch_ms,
        "block_ms": block_ms, "fetch_ms": fetch_ms,
        # "waits": much nearer the fetch than the bare dispatch
        "waits": block_ms - dispatch_ms > 0.5 * (fetch_ms - dispatch_ms)}))
    return 0


# -- four chips: the tp=4 path against tp=1, and nothing else ---------------


def phase_four_chips(ctx: dict) -> dict:
    say("phase tp4: inference --tp 1 vs --tp 4 --profile-split, exact (f32 "
        "compute, f32 sync buffers)")
    # --buffer-float-type f32: the CLI's default (q80, the reference's wire
    # format) rounds activations to int8 at every sync point, and a last-bit
    # difference in a value near a rounding boundary becomes a 1/127 step:
    # two meshes then part ways where logits are nowhere near a tie (on the
    # chip: at new token 12, top-2 margin 0.036; PR 22). Mesh invariance is a
    # statement about the exact path, so it is checked without that rounding.
    exact = ["--buffer-float-type", "f32"]
    one = run_inference("inference-tp1", ctx, ["--tp", "1", *exact])
    four = run_inference("inference-tp4", ctx,
                         ["--tp", "4", "--profile-split", *exact])
    log = four["log"]
    place = re.search(r"weights on (\d+) devices: (.*)", log)
    if not place or int(place.group(1)) != 4:
        four["child"].fail("weights do not sit on four distinct devices")
    shares = [float(s) for s in re.findall(r"([\d.]+)%", place.group(2))]
    say(f"  [inference-tp4] weight bytes per device: {place.group(2)}")
    if any(abs(s - 25.0) > 8.0 for s in shares):
        four["child"].fail(f"weight shares {shares} are not about a quarter "
                           f"each")
    traffic = re.search(r"traffic: .* over (\d+) collectives.*", log)
    if not traffic or int(traffic.group(1)) <= 0:
        four["child"].fail("no collectives in the traffic line")
    say(f"  [inference-tp4] {traffic.group(0).strip()}")
    same = one["text"] == four["text"]
    if not same or ctx["platform"] != "tpu":  # the rehearsal walks this too
        # with seeded random weights a near-tie may flip under another
        # reduction order: show the first differing position with both
        # runs' top-2 margin, for the builder to judge and record
        runs = {tp: margins(ctx, tp) for tp in (1, 4)}
        pos = next((i for i, (a, b) in enumerate(zip(runs[1], runs[4]))
                    if a["token"] != b["token"]), None)
        if pos is None:
            say(f"  step-by-step forward: tp=1 and tp=4 pick the same "
                f"{len(runs[1])} tokens; smallest top-2 margin "
                f"{min(r['margin'] for r in runs[1]):.3g} (tp=1) / "
                f"{min(r['margin'] for r in runs[4]):.3g} (tp=4)")
        else:
            say(f"  step-by-step forward: first differing token at new "
                f"position {pos}: tp=1 picks {runs[1][pos]['token']} with "
                f"top-2 margin {runs[1][pos]['margin']:.3g} over "
                f"{runs[1][pos]['second']}, tp=4 picks "
                f"{runs[4][pos]['token']} with margin "
                f"{runs[4][pos]['margin']:.3g} over {runs[4][pos]['second']}")
    if not same:
        die(f"tp4: text differs from tp=1 (mesh invariance, README "
            f"'Testing'):\n tp1: {one['text']!r}\n tp4: {four['text']!r}")
    say("  tp=4 text == tp=1 text")
    api = phase_api(ctx, ["--tp", "4"])
    say("  [api-tp4] under a mesh plan paged_attention.kernel_choice returns "
        "None: this path ran the gather+oracle attention, NOT the paged "
        "Pallas kernel")
    if api["dev"] != four["dev"]:
        die("tp4: phases ran on different devices")
    return four["dev"]


def margins(ctx: dict, tp: int) -> list[dict]:
    """A child that replays the greedy generation one forward at a time and
    reports each new position's winner, runner-up and logit margin."""
    ch = Child(f"margins-tp{tp}", [
        sys.executable, os.path.abspath(__file__), "--child", "margins",
        "--tp", str(tp), "--model", ctx["model"], "--tokenizer",
        ctx["tokenizer"], "--max-seq-len", str(ctx["max_seq_len"])],
        ctx["env"])
    rc = ch.wait_exit(ctx["timeout"])
    if rc != 0:
        ch.fail(f"exited rc={rc}")
    return json.loads(ch.text().strip().splitlines()[-1])


def child_margins(args) -> int:
    """(child; imports jax) prefill, then NEW_TOKENS single forwards."""
    import numpy as np

    from dllama_tpu.runtime.engine import InferenceEngine

    eng = InferenceEngine(args.model, args.tokenizer, tp=args.tp,
                          max_seq_len=args.max_seq_len,
                          compute_dtype="float32", temperature=0.0, seed=1)
    ids = eng.tokenizer.encode(PROMPT)
    eng.prefill(ids[:-1])
    tok, out = ids[-1], []
    for _ in range(NEW_TOKENS):
        logits = eng.decode_step(tok)
        second, tok = (int(i) for i in np.argsort(logits)[-2:])
        out.append({"token": tok, "second": second,
                    "margin": float(logits[tok] - logits[second])})
    eng.close()
    print(json.dumps(out))
    return 0


# -- main -------------------------------------------------------------------


def clean_native_products() -> None:
    """What loads the weights must be built from the committed .cpp on THIS
    machine (or be the numpy codec): a copied disk can hold a .so built
    elsewhere. Remove build products; first use rebuilds them."""
    d = os.path.join(HERE, "dllama_tpu", "native")
    for f in os.listdir(d):
        if f.endswith(".so") or ".so.tmp." in f or f == "tsan_stress":
            os.unlink(os.path.join(d, f))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU children at toy widths: control flow only")
    for opt in ("--child", "--model", "--tokenizer"):  # children of this file
        ap.add_argument(opt, help=argparse.SUPPRESS)
    for opt in ("--tp", "--max-seq-len"):
        ap.add_argument(opt, type=int, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return (child_sync_check() if args.child == "sync-check"
                else child_margins(args))

    t_start = time.monotonic()
    from dllama_tpu import compile_cache, native  # noqa: E402 — jax-free

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "DLLAMA_TESTS_TPU")}
    env.update(
        JAX_PLATFORMS="cpu" if args.rehearse else "tpu",  # no chip: jax refuses
        PYTHONUNBUFFERED="1", PYTHONPATH=HERE,
        # cache EVERY program, so that "the warm run adds no entry" holds
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        DLLAMA_INTROSPECT_ANALYZE="1")     # measured HBM bytes + kernels
    if args.rehearse:
        global LOG_DIR
        LOG_DIR = os.path.join(MODEL_DIR, "logs")  # not among a chip run's
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.chips}"
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(MODEL_DIR, "xla_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env["JAX_COMPILATION_CACHE_DIR"]
        shutil.rmtree(env["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)
    ctx = {"env": env, "platform": "cpu" if args.rehearse else "tpu",
           "n_devices": args.chips, "cache_dir": compile_cache.cache_dir(),
           "timeout": 900.0,
           "max_seq_len": TOY["seq_len"] if args.rehearse else MAX_SEQ_LEN}

    # the cheapest child first: with no chip, fail before touching anything
    phase_block_until_ready(ctx)
    if not args.rehearse:
        clean_native_products()
    params, tag = ((TOY, "toy") if args.rehearse
                   else (LLAMA_3_2_1B, "llama-3.2-1b-q40"))
    say(f"model: {tag} dim {params['dim']} hidden {params['hidden_dim']} "
        f"layers {params['n_layers']} heads {params['n_heads']}/"
        f"{params['n_kv_heads']} head_dim {params['head_dim']} vocab "
        f"{params['vocab_size']}; context run at --max-seq-len "
        f"{ctx['max_seq_len']} (published: {params['seq_len']})")
    ctx["model"], ctx["tokenizer"] = ensure_model(params, args.seed, tag)
    say(f"codec in this (writing) process: {native.describe()}")

    if args.chips == 4:
        dev = phase_four_chips(ctx)
    else:
        dev = phase_inference_f32(ctx)["dev"]
        for other in (phase_inference_bf16(ctx)["dev"],
                      phase_api(ctx, [])["dev"]):
            if other != dev:
                die(f"phases ran on different devices: {dev} vs {other}")
        if not args.rehearse:
            phase_hw_tier(ctx)
    say(f"all phases passed in {time.monotonic() - t_start:.0f} s")
    result = {"ok": True, "device": dev}
    if args.rehearse:
        result["rehearsal"] = True  # CPU children, toy widths: not a result
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

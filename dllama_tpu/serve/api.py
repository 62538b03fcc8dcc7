"""OpenAI-compatible HTTP API server.

Endpoint-compatible with the reference server (reference: src/dllama-api.cpp):

* ``POST /v1/chat/completions`` — messages → completion, optional SSE
  streaming (``"stream": true``), ``temperature``/``top_p``/``seed``/
  ``max_tokens`` per request (dllama-api.cpp:341-361);
* ``GET /v1/models`` — single-model listing (dllama-api.cpp:523-532);
* the **NaiveCache**: KV reuse keyed on message-history prefix — a repeated
  conversation continues from its cached position instead of re-prefilling
  (dllama-api.cpp:294-339).

Built on http.server (stdlib) rather than hand-parsed sockets. Two serving
modes:

* default: single-threaded, one sequence at a time with the NaiveCache —
  matching the reference's accept loop;
* ``--batch-slots N``: a ThreadingHTTPServer front end over the continuous
  batching scheduler (runtime/serving.py) — N concurrent sequences share one
  ragged decode program, requests beyond the pool queue, every request's
  output is identical to a solo run. New capability; the reference is
  strictly one-request-at-a-time. (Prefix KV reuse is per-engine state and
  is disabled in batched mode.)
"""

from __future__ import annotations

import json
import os
import queue
import re
import threading
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, HTTPServer, ThreadingHTTPServer

from ..runtime import (evalharness, failpoints, flightrec, introspection,
                       numerics, profiling, roofline, telemetry, tenancy)
from ..runtime.engine import InferenceEngine
from ..runtime.serving import (HbmAdmissionError, QueueFullError,
                               RequestTimeoutError,
                               SchedulerUnavailableError,
                               check_hbm_admission)
from ..tokenizer.chat import (ChatItem, ChatTemplateGenerator,
                              ChatTemplateType, EosDetector, EosResult)

# known routes for the HTTP request counter's route label — anything else is
# folded into "other" so a scanner can't explode the label cardinality.
# Closed-world: every route literal a handler matches on must be listed here
# (dlint rule route-labels enforces it in `make lint`).
_ROUTES = ("/v1/chat/completions", "/v1/kv/export", "/v1/models", "/metrics",
           "/health", "/healthz", "/readyz", "/debug",
           "/debug/compiles", "/debug/requests", "/debug/profile",
           "/debug/numerics", "/debug/flight", "/debug/timeline",
           "/debug/roofline", "/debug/eval", "/debug/tenants")

# the GET /debug index: one line per diagnostic endpoint. Closed-world with
# _ROUTES (dlint rule route-labels: every /debug/* route has exactly one
# entry here and vice versa), so the index can never silently omit a surface.
_DEBUG_INDEX = {
    "/debug/compiles": "GET: compile ledger — every XLA trace+compile event "
                       "with program/scope/plan, wall time, HBM/FLOPs "
                       "analysis, retrace-sentinel state",
    "/debug/requests": "GET: recent per-request phase timelines from the "
                       "always-on span ring",
    "/debug/profile": "POST ?ms=N[&ops=1]: live profiler window over the "
                      "serving loop — Eval/Sync split, collective traffic, "
                      "and (ops=1) the per-op class attribution",
    "/debug/numerics": "GET: numerics observatory — tripwire totals, tapped "
                       "activation stats, canary status",
    "/debug/flight": "GET: flight-recorder rings — per-tick scheduler "
                     "decisions + request lifecycle events + loop stalls",
    "/debug/timeline": "GET: Perfetto-loadable Chrome trace of the flight "
                       "rings + span ring",
    "/debug/roofline": "GET: roofline observatory — per-program achieved "
                       "bytes/FLOPs vs chip ceilings, memory- vs "
                       "compute-bound classification",
    "/debug/eval": "GET: quality observatory — the most recent "
                   "teacher-forced eval run's summary (per-sequence NLL, "
                   "perplexity, bit-exact total-NLL hex; partial + "
                   "completed/in-flight ids after an aborted run)",
    "/debug/tenants": "GET: tenant observatory — per-tenant cumulative "
                      "usage (tokens, sheds, latency quantiles, KV "
                      "block-seconds), configured limits, and the "
                      "windowed fairness view (Jain index, shares)",
}

# POST /debug/profile capture-window bounds (ms): long enough to catch a few
# decode steps, short enough that a handler thread never parks for minutes
_PROFILE_MS_DEFAULT = 500
_PROFILE_MS_MAX = 10_000

# absurd-deadline guard: a request may not park a slot (or a queue entry)
# for more than an hour — longer values are a client bug, rejected 400
_MAX_TIMEOUT_S = 3600.0

# the closed machine-readable readiness vocabulary: every /readyz and
# 5xx-backpressure body (here and on the fleet router, serve/router.py)
# carries one of these in its "code" field next to the human "reason" —
# the router branches on the code, operators read the reason, and the
# router's probe parse SANITIZES against this tuple (out-of-vocabulary
# codes degrade to "crashed"). "loading" is the router-side state for a
# replica it has not successfully probed yet.
READY_CODES = ("ok", "draining", "crashed", "queue_full", "loading")

# the closed finish_reason vocabulary: every terminal SSE chunk and
# non-streaming response (here and the router's terminal abort event,
# serve/router.py) spells one of these — "length"/"stop" are the normal
# completions, "timeout" a request-deadline truncation, "error" the
# mid-stream abort marker. Closed-world with the fallback-reason and
# resume-outcome vocabularies by tools/dlint's failure-taxonomy rule.
FINISH_REASONS = ("length", "stop", "timeout", "error")

# one Retry-After policy for every backpressure answer — the 429 shed
# path, the 503 drain/crash/unready paths, and /readyz 503, here and in
# serve/router.py — so the surfaces can't drift: 429 is transient queue
# pressure (retry soon), 503 means the process needs orchestrator time.
# Bounded random jitter is ADDED to the base (integer seconds — the
# header grammar) so clients backpressured in the same instant don't
# come back in one synchronized stampede against a recovering replica.
RETRY_AFTER_S = {429: 1, 503: 5}
RETRY_AFTER_JITTER_S = {429: 1, 503: 3}


def backpressure_headers(status: int) -> dict:
    """The shared Retry-After header block for a 429/503 answer, with
    bounded random jitter (base..base+jitter seconds) to de-synchronize
    retry waves."""
    import random

    return {"Retry-After": str(RETRY_AFTER_S[status]
                               + random.randint(
                                   0, RETRY_AFTER_JITTER_S[status]))}


# fleet trace identity (serve/router.py is the usual sender): one request
# id names a request at every tier — the router mints it (or sanitizes a
# client-supplied one) and stamps the dispatch attempt index, both as
# headers on every hop. The replica binds them to its engine-local
# integer rid (telemetry.tracer().bind_fleet + a "fleet_rid" lifecycle
# event), echoes the id back on its response, and threads it into the
# opt-in timing block — so a fleet dump joins by one key end to end.
FLEET_RID_HEADER = "X-Dllama-Request-Id"
FLEET_HOP_HEADER = "X-Dllama-Hop"
FLEET_RID_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")

# KV migration hint (serve/router.py sends it): "host:port" of a peer
# replica whose paged pool holds this prompt's prefix. The replica pulls
# the prefix over the kvwire stream (POST /v1/kv/export on the peer)
# before admission instead of recomputing it; ANY wire failure degrades
# to ordinary chunked prefill. Advisory by construction — an unsanitary
# or stale value is dropped, never an error.
KV_PEER_HEADER = "X-Dllama-KV-Peer"
KV_PEER_RE = re.compile(r"^[A-Za-z0-9._\-\[\]:]{1,255}:\d{1,5}$")


def kv_peer(headers) -> str | None:
    """Parse + sanitize the KV migration hint header (values feed
    ``http.client`` connections and flight-ring notes — out-of-vocabulary
    strings are dropped, never stored)."""
    peer = headers.get(KV_PEER_HEADER)
    if not peer or not KV_PEER_RE.match(peer):
        return None
    return peer


# tenant identity (runtime/tenancy): who this request's tokens, latency,
# KV residency, and shed decisions are attributed to. Same charset
# contract as the fleet request id above; absent or malformed degrades
# to "anon" — attribution, never authentication. Echoed (sanitized) on
# every completion response, and forwarded by the fleet router across
# retries, stream resumes, and KV-donor warm requests so failover
# traffic keeps its owner.
TENANT_HEADER = "X-Dllama-Tenant"


def tenant_identity(headers) -> str:
    """The sanitized tenant label off a request's headers (the one
    parse both the api server and the fleet router use)."""
    return tenancy.sanitize_tenant(headers.get(TENANT_HEADER))


def fleet_identity(headers) -> tuple[str, int] | None:
    """Parse the fleet trace headers off a request: ``(fleet_id, hop)``,
    or None when absent/unsanitary (an out-of-vocabulary id is dropped,
    never stored — header values go into dumps and logs)."""
    rid = headers.get(FLEET_RID_HEADER)
    if not rid or not FLEET_RID_RE.match(rid):
        return None
    try:
        hop = int(headers.get(FLEET_HOP_HEADER) or 0)
    except ValueError:
        hop = 0
    return rid, max(0, hop)


class ClientDisconnect(Exception):
    """The SSE peer vanished mid-stream (BrokenPipeError /
    ConnectionResetError on the socket). Counted per route as
    ``status="client_disconnect"`` — an aborted download is load
    information, not a server error."""


def _validate_body(body: dict) -> None:
    """Schema-check a /v1/chat/completions body; raises ``ValueError``
    (→ HTTP 400) with a client-actionable message. Every malformed shape
    must die here — a 500 from a typed field is a server bug
    (tests/test_fuzz.py sweeps this)."""
    if not isinstance(body, dict):
        raise ValueError("body must be a JSON object")
    # an explicit JSON null means "absent" (OpenAI semantics): drop the
    # key so downstream float()/int() conversions see their defaults
    # instead of None (a null temperature must not become a 500)
    for k in [k for k, v in body.items() if v is None]:
        del body[k]
    messages = body.get("messages")
    if not isinstance(messages, list) or not messages:
        raise ValueError("messages must be a non-empty list")
    for i, m in enumerate(messages):
        if not isinstance(m, dict):
            raise ValueError(f"messages[{i}] must be an object")
        if not isinstance(m.get("role", "user"), str):
            raise ValueError(f"messages[{i}].role must be a string")
        if not isinstance(m.get("content", ""), str):
            raise ValueError(f"messages[{i}].content must be a string")

    def _number(key, lo, hi):
        v = body.get(key)
        if v is None:
            return
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            raise ValueError(f"{key} must be a number")
        if not (lo <= float(v) <= hi):
            raise ValueError(f"{key} must be in [{lo}, {hi}]")

    _number("temperature", 0.0, 100.0)
    _number("top_p", 0.0, 1.0)
    mt = body.get("max_tokens")
    if mt is not None:
        if isinstance(mt, bool) or not isinstance(mt, int):
            raise ValueError("max_tokens must be an integer")
        if mt < 0:
            raise ValueError("max_tokens must be >= 0")
    seed = body.get("seed")
    if seed is not None and (isinstance(seed, bool)
                             or not isinstance(seed, int)):
        raise ValueError("seed must be an integer")
    timeout = body.get("timeout")
    if timeout is not None:
        if isinstance(timeout, bool) or not isinstance(timeout, (int, float)):
            raise ValueError("timeout must be a number (seconds)")
        if not (0 < float(timeout) <= _MAX_TIMEOUT_S):
            raise ValueError(
                f"timeout must be in (0, {_MAX_TIMEOUT_S:.0f}] seconds")
    timing = body.get("timing")
    if timing is not None and not isinstance(timing, bool):
        raise ValueError("timing must be a boolean")
    stop = body.get("stop")
    if stop is not None and not isinstance(stop, (str, list)):
        raise ValueError("stop must be a string or a list of strings")
    if isinstance(stop, list) and not all(isinstance(s, str) for s in stop):
        raise ValueError("stop must be a string or a list of strings")
    # mid-stream resume (the fleet router sends these on a failover
    # re-dispatch, never ordinary clients): the already-emitted token
    # history rides in the body so admission can treat it as prompt
    rf = body.get("resume_from")
    rtoks = body.get("resume_tokens")
    if rf is not None or rtoks is not None:
        if isinstance(rf, bool) or not isinstance(rf, int) or rf < 1:
            raise ValueError("resume_from must be a positive integer")
        if (not isinstance(rtoks, list) or len(rtoks) != rf
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           and t >= 0 for t in rtoks)):
            raise ValueError("resume_tokens must be a list of exactly "
                             "resume_from non-negative token ids")


@dataclass
class CachedMessage:
    role: str
    content: str
    end_pos: int


@dataclass
class NaiveCache:
    """Message-prefix KV cache (reference: NaiveCache, dllama-api.cpp:294-339)."""

    items: list[CachedMessage] = field(default_factory=list)

    def resolve_delta(self, messages: list[dict]) -> tuple[list[dict], int]:
        """If ``messages`` strictly extends the cached history, return the new
        suffix plus the cached end position; else clear and return all."""
        n = len(self.items)
        if n and len(messages) > n:
            for i, item in enumerate(self.items):
                m = messages[i]
                if item.role != m.get("role") or item.content != m.get("content"):
                    break
            else:
                return messages[n:], self.items[n - 1].end_pos
        self.items.clear()
        return messages, 0

    def push(self, messages: list[dict], end_pos: int) -> None:
        for m in messages:
            self.items.append(CachedMessage(m.get("role", ""), m.get("content", ""),
                                            end_pos))


def _request_stops(base: list[str], body: dict) -> list[str]:
    """Tokenizer stop pieces + the request's OpenAI ``stop`` strings (str or
    list). The reference parses this field but never feeds it to its
    detector (dllama-api.cpp:509-513 vs :537-539) — honoring it is ours."""
    req = body.get("stop")
    if isinstance(req, str):
        req = [req]
    if not isinstance(req, list):
        return base
    return base + [s for s in req if isinstance(s, str) and s]


class _EosGate:
    """EosDetector + text accumulation + delta emission, shared by both
    serving modes so EOS/stop-string semantics can't drift between them."""

    def __init__(self, tok, stop_pieces, emit=None):
        # padding is in BYTES (the detector buffers UTF-8): a multi-byte
        # request stop with char-sized padding could be scanned past and
        # leak to the client (review finding)
        max_stop = max((len(s.encode("utf-8")) for s in stop_pieces), default=0)
        self.detector = EosDetector(tok.eos_token_ids, stop_pieces,
                                    max_stop, max_stop)
        self.emit = emit
        self.parts: list[str] = []

    def _out(self, d: str) -> None:
        if d:
            self.parts.append(d)
            if self.emit:
                self.emit(d)

    def feed(self, token: int, piece: str | None) -> bool:
        """Process one decoded token; True when a stop sequence completed."""
        res = self.detector.append(token, piece)
        if res in (EosResult.NOT_EOS, EosResult.EOS):
            self._out(self.detector.get_delta())
            self.detector.reset()
        return res == EosResult.EOS

    def flush_tail(self) -> None:
        """Emit text still buffered as a MAYBE_EOS prefix when generation
        ends by length — otherwise up to max_stop chars silently vanish."""
        self._out(self.detector.get_delta())


class ApiState:
    """Engine + chat plumbing shared across requests."""

    def __init__(self, engine: InferenceEngine, model_name: str = "dllama-tpu",
                 template_type: ChatTemplateType = ChatTemplateType.UNKNOWN,
                 request_timeout: float = 0.0):
        self.engine = engine
        self.model_name = model_name
        self.request_timeout = request_timeout  # server default (0 = none)
        tok = engine.tokenizer
        eos_piece = (tok.vocab[tok.eos_token_ids[0]].decode("utf-8", "replace")
                     if tok.eos_token_ids else "")
        self.template = ChatTemplateGenerator(tok.chat_template, eos=eos_piece,
                                              type=template_type)
        self.stop_pieces = [tok.vocab[t].decode("utf-8", "replace")
                            for t in tok.eos_token_ids]
        self.cache = NaiveCache()
        self._rid = 0  # request counter for trace spans (single-threaded)

    def readiness(self) -> tuple[bool, str, str]:
        """Single-sequence mode has no queue or supervisor, but the step
        watchdog still applies: a wedged dispatch must flip /readyz.
        Same (ready, reason, code) contract as the batch scheduler."""
        wd = getattr(self.engine, "watchdog", None)
        if wd is not None and wd.stalled:
            return (False, "step watchdog tripped (wedged device dispatch)",
                    "crashed")
        return True, "ok", "ok"

    def complete(self, body: dict, emit=None, fleet=None,
                 kv_peer: str | None = None,
                 tenant: str = tenancy.ANON) -> dict:
        """Run one chat completion; ``emit(text)`` streams deltas when set.
        ``kv_peer`` is accepted for interface parity with the batched
        state and ignored — the single-sequence engine has no paged pool
        to migrate into (its NaiveCache already reuses local prefixes).
        ``fleet`` is the optional ``(fleet_request_id, hop)`` trace
        identity from :func:`fleet_identity` — bound to this request's
        engine-local rid so spans and lifecycle events join fleet-wide.
        ``tenant`` (:func:`tenant_identity`) binds the same rid to its
        caller so single-sequence spans stay attributable too; the full
        accounting registry is batched-scheduler work.

        Flow mirrors ApiServer::complete (dllama-api.cpp:363-484): resolve the
        delta prompt against the cache, template + encode, chunked prefill,
        then sample/decode with the EosDetector gating emitted text.
        """
        engine = self.engine
        tok = engine.tokenizer
        _validate_body(body)
        if body.get("resume_from"):
            # mid-stream resume admission is scheduler work (prompt+
            # history prefill + positioned coin stream); the single-
            # sequence mode never stamps resumable chunks, so a resume
            # dispatch landing here is a router/client bug — 400-shaped
            raise ValueError("stream resume requires batched serving "
                             "(--batch-slots N)")
        # retrace sentinel (runtime.introspection): a completion that ran
        # end-to-end without a single compile is the single-sequence
        # definition of steady state — from then on, recompiles are WARNed
        led = introspection.ledger()
        scope = getattr(engine, "introspection_scope", None)
        compiles_before = led.compile_count(scope) if scope else 0
        messages = body["messages"]
        timeout_s = float(body.get("timeout") or self.request_timeout or 0)
        deadline = (telemetry.now_ns() + int(timeout_s * 1e9)
                    if timeout_s > 0 else 0)
        self._rid += 1
        engine.trace_rid = self._rid  # stamps the engine's prefill span
        if fleet is not None:
            # one id from router to kernel: every span and lifecycle
            # event for this local rid now carries the fleet identity
            telemetry.tracer().bind_fleet(self._rid, fleet[0], fleet[1])
            flightrec.recorder().note("fleet_rid", rid=self._rid,
                                      reason=fleet[0], hop=fleet[1])
        telemetry.tracer().bind_tenant(
            self._rid, tenancy.registry().resolve(tenant))
        t_req0 = telemetry.now_ns()  # TTFT attribution origin (queue = 0:
        # the single-threaded server has no scheduler queue)
        rt = telemetry.RequestTimer()
        if "temperature" in body:
            engine.sampler.set_temp(float(body["temperature"]))
        if "seed" in body:
            engine.sampler.set_seed(int(body["seed"]))
        if "top_p" in body:
            engine.sampler.topp = float(body["top_p"])
        max_tokens = int(body.get("max_tokens") or 0)

        delta, start_pos = self.cache.resolve_delta(messages)
        if start_pos == 0:
            engine.reset()
        else:
            engine.pos = start_pos

        items = [ChatItem(m.get("role", "user"), m.get("content", "")) for m in delta]
        prompt = self.template.generate(items, append_generation_prompt=True)
        ids = tok.encode(prompt.content, is_start=start_pos == 0,
                         add_special_tokens=True)
        # HBM admission guard (single-sequence twin of the scheduler's
        # submit-time check): refuse before prefill, not via an XLA OOM
        check_hbm_admission(engine, len(ids),
                            engine.hbm_estimate["need_per_device"])

        prompt_end = min(start_pos + len(ids) - 1, engine.cfg.seq_len)
        max_pred = min(engine.cfg.seq_len,
                       prompt_end + max_tokens if max_tokens > 0 else engine.cfg.seq_len)
        self.cache.push(delta, prompt_end)

        stops = _request_stops(self.stop_pieces, body)
        custom_stops = len(stops) > len(self.stop_pieces)
        gate = _EosGate(tok, stops, emit)
        if prompt.public_prompt:
            gate._out(prompt.public_prompt)

        prefill_ms = 0.0
        if len(ids) > 1:
            _, pf_metrics = engine.prefill(ids[: prompt_end - start_pos])
            prefill_ms = sum(s.ms for s in pf_metrics)
        token = ids[prompt_end - start_pos] if prompt_end - start_pos < len(ids) else ids[-1]
        tok.reset_decoder()

        proposer = None
        n_drafted = n_spec_acc = 0
        if engine.spec_active:
            from ..runtime.speculative import NgramProposer

            proposer = NgramProposer(engine.spec_lookup)
            proposer.extend(ids)

        n_completion = 0
        finish_reason = "length"
        t_decode = telemetry.now_ns()
        while engine.pos < max_pred:
            if deadline and telemetry.now_ns() >= deadline:
                # in-line deadline: the decode loop runs on the handler
                # thread, so cancelling is simply stopping the loop
                telemetry.registry().counter(
                    telemetry.REQUEST_TIMEOUTS).inc()
                if n_completion == 0:
                    raise RequestTimeoutError(
                        f"no output within timeout ({timeout_s:g}s)")
                finish_reason = "timeout"
                break
            if (proposer is not None
                    and max_pred - engine.pos >= engine.spec_lookup + 1):
                run = engine.speculative_tokens(token, proposer.draft())
                n_drafted += engine.spec_lookup
                n_spec_acc += len(run) - 1
                n_keep, stopped = len(run), False
                for j, t in enumerate(run):
                    rt.token()
                    if gate.feed(t, tok.decode(t)):
                        n_keep, stopped = j + 1, True
                        break
                engine.commit_chunk(n_keep)
                n_completion += n_keep
                proposer.extend(run[:n_keep])
                token = run[n_keep - 1]
                if stopped:
                    finish_reason = "stop"
                    break
                continue
            token = engine.next_token(token)
            n_completion += 1
            rt.token()
            if gate.feed(token, tok.decode(token)):
                finish_reason = "stop"
                break
        if finish_reason in ("length", "timeout"):
            gate.flush_tail()
        rt.done(len(ids), n_completion)
        telemetry.tracer().emit(self._rid, "decode", t_decode,
                                telemetry.now_ns(), n_tokens=n_completion)
        # TTFT attribution, single-sequence shape: t_admit == t_submit
        # (no scheduler queue → queue = 0); admission = template/encode/
        # cache work, prefill = the measured chunk dispatch wall — the
        # phase formula itself is flightrec.ttft_phases, shared with the
        # batched path so the two surfaces can never drift apart.
        timing = None
        if rt.first_ns is not None:
            bd = flightrec.ttft_phases(t_req0, t_req0, t_decode,
                                       rt.first_ns, prefill_ms)
            flightrec.record_ttft(
                telemetry.registry().histogram(telemetry.TTFT_ATTRIB_MS), bd)
            timing = {k: round(v, 3) for k, v in bd.items()}
            if fleet is not None:
                # the fleet-wide id + the hop that served this attempt:
                # the timing block names itself in a joined trace
                timing["request_id"] = fleet[0]
                timing["hop"] = fleet[1]
            if n_drafted:
                # single-sequence speculative decode: per-request accept
                # rate, same field names as the batched timing block
                timing["spec_drafted"] = n_drafted
                timing["spec_accepted"] = n_spec_acc
                timing["spec_accept_rate"] = round(n_spec_acc / n_drafted, 4)

        if not (custom_stops and finish_reason == "stop"):
            # a custom-stop finish leaves the hidden stop text and an
            # unterminated assistant turn in KV — a cached continuation from
            # engine.pos would decode against malformed context. Skip the
            # push; the next request re-prefills the assistant text from the
            # prompt cache point instead (correct, merely less cached).
            self.cache.push(
                [{"role": "assistant", "content": "".join(gate.parts)}],
                engine.pos)
        if scope and led.compile_count(scope) == compiles_before:
            led.mark_steady(scope)
        # canary piggyback (single-sequence mode has no scheduler loop):
        # the handler thread owns every dispatch, so replaying the canary
        # between completions can never race a request's decode. Known
        # trade-off: once per interval, one request's response write
        # waits out the canary forward — acceptable for the low-traffic
        # single-sequence mode (batched mode replays on the scheduler
        # tick instead)
        can = getattr(engine, "canary", None)
        if can is not None:
            can.maybe_run()
        out = {
            "text": "".join(gate.parts),
            "finish_reason": finish_reason,
            "prompt_tokens": len(ids),
            "completion_tokens": n_completion,
        }
        if body.get("timing") and timing is not None:
            out["timing"] = timing  # opt-in latency attribution block
        return out


class BatchedApiState:
    """Continuous-batching twin of :class:`ApiState`: same ``complete``
    contract, requests fan into the BatchScheduler and decode concurrently.
    Handler threads block on a per-request queue fed by the scheduler
    thread's ``on_token`` callback."""

    # how many prefix keys the residency advertisement remembers: enough
    # for a fleet's worth of sticky sessions, small enough that /readyz
    # bodies stay probe-sized
    KV_PREFIX_MAX = 64
    # advertisement TTL (seconds): the paged pool evicts cached blocks
    # independently, so an advertisement older than this is more likely
    # stale than resident — expiring it keeps a dead or recycled prefix
    # at one 404 export probe worst-case, never a doomed migration plan
    KV_PREFIX_TTL_S = 120.0

    def __init__(self, engine: InferenceEngine, n_slots: int,
                 model_name: str = "dllama-tpu",
                 template_type: ChatTemplateType = ChatTemplateType.UNKNOWN,
                 max_queue: int = 0, request_timeout: float = 0.0,
                 role: str | None = None):
        from ..runtime.serving import BatchScheduler

        self.engine = engine
        self.model_name = model_name
        self.request_timeout = request_timeout  # server default (0 = none)
        # disaggregation tag (--role prefill|decode, None = untagged):
        # advertised on /readyz so the fleet router can keep prefill
        # replicas out of the decode dispatch pool
        self.role = role
        tok = engine.tokenizer
        eos_piece = (tok.vocab[tok.eos_token_ids[0]].decode("utf-8", "replace")
                     if tok.eos_token_ids else "")
        self.template = ChatTemplateGenerator(tok.chat_template, eos=eos_piece,
                                              type=template_type)
        self.stop_pieces = [tok.vocab[t].decode("utf-8", "replace")
                            for t in tok.eos_token_ids]
        self.sched = BatchScheduler(engine, n_slots, max_queue=max_queue)
        # prefix-residency advertisement: affinity keys (serve/router.py
        # affinity_key — the router joins on the same function) of
        # prompts whose KV this replica's paged pool RECENTLY held.
        # Advisory: the pool evicts independently, so a stale entry just
        # costs one export probe that returns "not resident". Bounded
        # LRU with a TTL (key → monotonic stamp); handler threads write
        # it, the probe reader snapshots it, both prune expired entries.
        self._kv_prefixes: OrderedDict[str, float] = OrderedDict()
        self._kv_lock = threading.Lock()

    def readiness(self) -> tuple[bool, str, str]:
        return self.sched.readiness()

    def eval_resident(self) -> int:
        """Teacher-forced eval sequences queued/admitted right now —
        surfaced on /readyz so the router sees WHY depth is elevated."""
        return self.sched.eval_resident()

    def note_kv_prefix(self, key: str | None) -> None:
        """Record (LRU-front, TTL-stamped) a prefix this replica's pool
        now holds; a re-note refreshes the stamp."""
        if not key:
            return
        with self._kv_lock:
            self._kv_prefixes.pop(key, None)
            self._kv_prefixes[key] = time.monotonic()
            self._prune_kv_prefixes()

    def drop_kv_prefix(self, key: str | None) -> None:
        """Evict one advertisement early (retire-time eviction or an
        export probe that answered "not resident")."""
        if not key:
            return
        with self._kv_lock:
            self._kv_prefixes.pop(key, None)

    def _prune_kv_prefixes(self) -> None:
        # caller holds _kv_lock
        cutoff = time.monotonic() - self.KV_PREFIX_TTL_S
        for k in [k for k, ts in self._kv_prefixes.items() if ts < cutoff]:
            del self._kv_prefixes[k]
        while len(self._kv_prefixes) > self.KV_PREFIX_MAX:
            self._kv_prefixes.popitem(last=False)

    def kv_prefix_list(self) -> list[str]:
        """Most-recent-first snapshot for the /readyz advertisement
        (expired entries pruned on read — a probe never sees them)."""
        with self._kv_lock:
            self._prune_kv_prefixes()
            return list(reversed(self._kv_prefixes))

    def begin_drain(self) -> None:
        self.sched.begin_drain()

    def close(self, drain_s: float = 0.0) -> None:
        self.sched.close(drain_s)

    def complete(self, body: dict, emit=None, fleet=None,
                 kv_peer: str | None = None,
                 tenant: str = tenancy.ANON) -> dict:
        tok = self.engine.tokenizer
        _validate_body(body)
        messages = body["messages"]
        items = [ChatItem(m.get("role", "user"), m.get("content", ""))
                 for m in messages]
        prompt = self.template.generate(items, append_generation_prompt=True)
        ids = tok.encode(prompt.content, is_start=True, add_special_tokens=True)
        # mid-stream resume (serve/router.py failover re-dispatch): the
        # dead replica's already-emitted tokens are PROMPT now — they
        # ride the tail of ids through the one ordinary admission path
        # (match/share/chunked prefill, kv_peer migration included) and
        # decode continues from position n with the coin stream
        # fast-forwarded by the same count (scheduler-side)
        resume_from = int(body.get("resume_from") or 0)
        if resume_from:
            ids = ids + [int(t) for t in body["resume_tokens"]]
        max_tokens = int(body.get("max_tokens") or 0)
        if max_tokens <= 0:
            max_tokens = max(1, self.engine.cfg.seq_len - len(ids))
        else:
            # the client's bound covers the WHOLE generation; n of it
            # was already delivered by the dead replica
            max_tokens = max(1, max_tokens - resume_from)
        timeout_s = float(body.get("timeout") or self.request_timeout or 0)

        # SSE token stamping: each streamed chunk carries the cumulative
        # generated-token index plus the ids emitted since the previous
        # chunk, so the fleet router can keep a resume record and splice
        # a failover continuation with exactly-once delivery
        n_fed = resume_from
        since: list[int] = []
        memit = None
        if emit is not None:
            def memit(d):
                emit(d, {"index": n_fed, "tokens": since.copy()})
                since.clear()

        sampler = self.engine.sampler  # CLI flags are the per-request defaults
        q: queue.Queue = queue.Queue()
        req = self.sched.submit(
            ids, max_tokens,
            temperature=float(body.get("temperature", sampler.temperature)),
            topp=float(body.get("top_p", sampler.topp)),
            seed=int(body.get("seed", 0xB1A5)),
            stop_on_eos=True,
            timeout_s=timeout_s if timeout_s > 0 else None,
            on_token=lambda t, p: q.put((t, p)),
            kv_peer=kv_peer, resume_from=resume_from, tenant=tenant)
        if fleet is not None:
            # bound AFTER submit (the scheduler assigns the rid there);
            # the submit span predates the binding, but every later
            # span — queue, prefill, decode, retire — joins fleet-wide
            telemetry.tracer().bind_fleet(req.rid, fleet[0], fleet[1])
            flightrec.recorder().note("fleet_rid", rid=req.rid,
                                      reason=fleet[0], hop=fleet[1])

        gate = _EosGate(tok, _request_stops(self.stop_pieces, body), memit)
        if resume_from:
            # prime the gate with the history (emission suppressed: the
            # client already holds those tokens) so the stop-string
            # detector's buffer and the UTF-8 decode carry-over match
            # the dead replica's state at the splice point exactly
            import copy

            gate.emit = None
            dec = copy.copy(tok)
            dec._pending = bytearray()
            for t in ids[len(ids) - resume_from:]:
                gate.feed(t, dec.decode(t))
            gate.emit = memit
        rt = telemetry.RequestTimer()
        n_completion = 0
        finish_reason = "length"
        try:
            # inside the try: the public-prompt echo is the FIRST socket
            # write, so a peer that disconnected right after POSTing must
            # cancel the slot here too, not only mid-stream (a resume
            # never re-echoes: the client got the echo from the first
            # replica already)
            if prompt.public_prompt and not resume_from:
                gate._out(prompt.public_prompt)
            while True:
                try:
                    t, piece = q.get(timeout=0.1)
                except queue.Empty:
                    if req.done.is_set() and q.empty():
                        break
                    continue
                n_completion += 1
                n_fed += 1
                since.append(t)
                rt.token()
                if gate.feed(t, piece):
                    # stop STRING matched (spelled by ordinary tokens — the
                    # scheduler's raw-eos check can't see it): cancel the slot
                    # so it stops burning batch steps, and stop consuming
                    finish_reason = "stop"
                    req.cancel.set()
                    break
        except (BrokenPipeError, ConnectionResetError) as e:
            # the SSE peer vanished mid-stream (emit raised inside
            # gate.feed): free the slot and reclassify — this is not a 500
            req.cancel.set()
            raise ClientDisconnect(str(e)) from e
        # the scheduler guarantees done is set on every path (retire,
        # timeout, crash fail-all, shutdown); the alive check is the belt
        # against the scheduler thread dying in a way supervision missed
        while not req.done.wait(timeout=5.0):
            if not self.sched.is_alive():
                raise SchedulerUnavailableError(
                    "scheduler stopped while the request was in flight")
        if req.timed_out and finish_reason == "length":
            # "length" here just means "no stop matched yet" — the real
            # cause was the deadline (a stop-string finish keeps "stop")
            if n_completion == 0:
                raise RequestTimeoutError(
                    f"no output within timeout ({timeout_s:g}s)")
            finish_reason = "timeout"
        elif req.error:
            if req.server_error:  # crash/shutdown: 503 + retry, not a 400
                raise SchedulerUnavailableError(req.error)
            raise ValueError(req.error)
        if finish_reason in ("length", "timeout"):
            gate.flush_tail()
        rt.done(len(ids), n_completion)
        if hasattr(self.sched.gen, "wire_geometry"):
            # paged pool: the retired request's prefix blocks are parked
            # in the cached LRU, matchable — advertise residency so the
            # fleet router can migrate the KV instead of recomputing
            # (serve/router.py joins on the same affinity_key)
            from .router import affinity_key

            self.note_kv_prefix(affinity_key(body))
        out = {
            "text": "".join(gate.parts),
            "finish_reason": finish_reason,
            "prompt_tokens": len(ids),
            "completion_tokens": n_completion,
        }
        bd = req.ttft_breakdown() if body.get("timing") else None
        if bd is not None:
            # opt-in latency attribution (scheduler-side stamps; the phase
            # formula lives in Request.ttft_breakdown — the histogram
            # twins land in dllama_ttft_attrib_ms / dllama_itl_attrib_ms
            # at first-token / retire)
            out["timing"] = {k: round(v, 3) for k, v in bd.items()}
            if fleet is not None:
                out["timing"]["request_id"] = fleet[0]
                out["timing"]["hop"] = fleet[1]
            out["timing"]["decode_step_ms"] = round(req.ms_decode_steps, 3)
            out["timing"]["preempt_ms"] = round(req.ms_preempt, 3)
            if req.ms_verify:
                # a request can spend its whole decode phase in verify
                # dispatches without ever drafting (zero-length lens,
                # degraded proposer) — the wall must not vanish from
                # the report, so it gates on its own accumulator
                out["timing"]["verify_ms"] = round(req.ms_verify, 3)
            if req.spec_drafted:
                # speculative serving: this request's own accept rate —
                # the per-request view of dllama_spec_*_tokens_total
                out["timing"]["spec_drafted"] = req.spec_drafted
                out["timing"]["spec_accepted"] = req.spec_accepted
                out["timing"]["spec_accept_rate"] = round(
                    req.spec_accepted / req.spec_drafted, 4)
        return out


def _completion_json(state, out: dict) -> dict:
    resp = {
        "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": state.model_name,
        "choices": [{
            "index": 0,
            "message": {"role": "assistant", "content": out["text"]},
            "finish_reason": out["finish_reason"],
        }],
        "usage": {
            "prompt_tokens": out["prompt_tokens"],
            "completion_tokens": out["completion_tokens"],
            "total_tokens": out["prompt_tokens"] + out["completion_tokens"],
        },
    }
    if "timing" in out:
        # opt-in (body "timing": true) TTFT/ITL attribution block —
        # non-streaming responses only (SSE chunks stay OpenAI-shaped)
        resp["timing"] = out["timing"]
    return resp


def _chunk_json(state: ApiState, delta: dict, finish_reason=None) -> dict:
    return {
        "id": "chatcmpl-stream",
        "object": "chat.completion.chunk",
        "created": int(time.time()),
        "model": state.model_name,
        "choices": [{"index": 0, "delta": delta, "finish_reason": finish_reason}],
    }


def make_handler(state: ApiState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # whole-socket timeout (reads AND writes): a client that declares a
        # Content-Length then stalls, or an SSE consumer that stops reading
        # for 2 minutes while the send buffer fills, can otherwise block
        # the single-threaded server forever. Disconnecting such clients is
        # intended; generation itself does no socket ops during a step, so
        # a slow MODEL never trips this — only a stalled PEER does
        timeout = 120

        def log_message(self, fmt, *args):  # quieter default logging
            print(f"🕸️ {self.address_string()} {fmt % args}")

        _counted = False  # whether THIS request hit the telemetry counter
        # the current request's fleet trace id (echoed on every response
        # so callers — and the router's own client — can correlate);
        # reset per request: keep-alive reuses the handler instance
        _fleet_rid: str | None = None
        # the current POST's sanitized tenant label, echoed back so the
        # caller sees what it was attributed as (a malformed header
        # echoes "anon" — silent misattribution is the failure mode
        # this surfaces); reset per request like the fleet id
        _tenant: str | None = None

        def _route(self) -> str:
            # route matching and the counter label both ignore the query
            # string (`/debug/profile?ms=200` is the /debug/profile route,
            # not an "other")
            return self.path.split("?", 1)[0]

        def _count(self, status: int | str) -> None:
            # status is an HTTP code or a symbolic outcome like
            # "client_disconnect" (an aborted SSE peer is not a 500)
            path = self._route()
            route = path if path in _ROUTES else "other"
            telemetry.registry().counter(telemetry.HTTP_REQUESTS).inc(
                route=route, status=str(status))
            self._counted = True

        def _json(self, code: int, payload: dict,
                  headers: dict | None = None) -> None:
            self._count(code)
            body = json.dumps(payload).encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if self._fleet_rid:
                self.send_header(FLEET_RID_HEADER, self._fleet_rid)
            if self._tenant is not None:
                self.send_header(TENANT_HEADER, self._tenant)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _not_found(self) -> None:
            # always a JSON body, never a silent empty response: clients and
            # probes get something parseable plus the route list
            self._json(404, {"error": "not found", "path": self.path,
                             "routes": list(_ROUTES)})

        def do_GET(self):
            self._fleet_rid = None  # keep-alive: no stale POST echo
            self._tenant = None
            path = self._route()
            if path == "/v1/models":
                self._json(200, {"object": "list", "data": [{
                    "id": state.model_name, "object": "model",
                    "created": int(time.time()), "owned_by": "dllama_tpu",
                }]})
            elif path == "/metrics":
                self._count(200)
                body = telemetry.registry().render().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path in ("/health", "/healthz"):
                # liveness: the process is up and serving HTTP — always 200
                # (readiness is /readyz; the split matters during drain and
                # after a crash-exhausted scheduler, when the process should
                # NOT be restarted but should stop receiving traffic)
                self._json(200, {"status": "ok"})
            elif path == "/readyz":
                # machine-readable body: "code" from READY_CODES (the
                # fleet router consumes it; humans debug with "reason"),
                # plus the shared Retry-After on the unready answer
                ready, reason, code = state.readiness()
                rz = {"status": "ok" if ready else "unready",
                      "reason": reason, "code": code}
                # disaggregation/migration advertisement (batched paged
                # replicas only): the fleet router's probe reads these
                # off the same body it already parses — role keeps
                # prefill replicas out of the decode pool, kv_prefixes
                # feeds the migration donor map
                if getattr(state, "role", None):
                    rz["role"] = state.role
                kv_list = getattr(state, "kv_prefix_list", None)
                if kv_list is not None:
                    rz["kv_prefixes"] = kv_list()
                # quality-observatory residency: how many teacher-forced
                # eval sequences are queued/admitted RIGHT NOW. Eval work
                # inflates queue depth without producing decode tokens, so
                # the fleet router's least-loaded dispatch needs to SEE
                # the reason, not just the symptom
                ev = getattr(state, "eval_resident", None)
                if ev is not None:
                    n_eval = ev()
                    if n_eval:
                        rz["eval_resident"] = n_eval
                self._json(200 if ready else 503, rz,
                           headers=None if ready
                           else backpressure_headers(503))
            elif path == "/debug":
                # the diagnostic surface's index: every /debug/* endpoint
                # with a one-line description (closed-world vs _ROUTES —
                # dlint rule route-labels)
                self._json(200, {"endpoints": dict(_DEBUG_INDEX)})
            elif path == "/debug/roofline":
                # the roofline observatory: per-program achieved bandwidth/
                # compute vs the chip ceilings, joined from the compile
                # ledger + step histograms (runtime/roofline; pure host
                # reads — never dispatches or compiles anything)
                self._json(200, roofline.snapshot())
            elif path == "/debug/compiles":
                # the compile ledger: every trace+compile event with program,
                # scope, plan, wall time, HBM/FLOPs analysis, and the retrace
                # sentinel's per-scope steady flags
                self._json(200, introspection.ledger().snapshot())
            elif path == "/debug/requests":
                # bounded in-memory ring of recent per-request phase
                # timelines (SpanTracer; no --trace-out needed)
                self._json(200,
                           {"requests": telemetry.tracer().recent_requests()})
            elif path == "/debug/flight":
                # the flight recorder's live rings: per-tick scheduler
                # decisions + request lifecycle events (runtime/flightrec),
                # plus the span ring — the fleet timeline joiner
                # (flightrec.fleet_chrome_trace) reads both off this one
                # body, so one GET per replica suffices
                data = flightrec.recorder().snapshot()
                data["spans"] = telemetry.tracer().raw_spans()
                self._json(200, data)
            elif path == "/debug/timeline":
                # Perfetto-loadable Chrome trace of the live rings + the
                # span ring (save the body, load in ui.perfetto.dev)
                data = flightrec.recorder().snapshot()
                data["spans"] = telemetry.tracer().raw_spans()
                self._json(200, flightrec.to_chrome_trace(data))
            elif path == "/debug/numerics":
                # the numerics observatory: tripwire totals per site, the
                # last tapped dispatch's per-layer stats, canary status
                self._json(200, numerics.debug_snapshot(
                    getattr(state, "engine", None)))
            elif path == "/debug/eval":
                # the quality observatory: last eval run scored in THIS
                # process (runtime/evalharness.last_run) — includes the
                # bit-exact total-NLL hex quality_baseline gates on, or
                # the partial-results shape after an aborted run
                last = evalharness.last_run()
                self._json(200, last if last is not None
                           else {"run": None,
                                 "note": "no eval run in this process "
                                         "(python -m dllama_tpu eval)"})
            elif path == "/debug/tenants":
                # the tenant observatory: cumulative per-tenant usage,
                # configured limits, and the windowed fairness view
                # (runtime/tenancy — pure host reads)
                self._json(200, tenancy.registry().snapshot())
            else:
                self._not_found()

        def _drain_small_body(self) -> None:
            # drain a SMALL body before responding (closing with unread
            # request bytes can RST the connection under the client's
            # feet before it reads the response) — but never trust the
            # client's Content-Length for an unbounded read on a path
            # that doesn't consume the body anyway: oversized declarations
            # skip the drain and drop keep-alive instead
            try:
                length = int(self.headers.get("Content-Length", 0))
            except ValueError:
                length = 0
            if 0 < length <= (1 << 20):
                try:
                    self.rfile.read(length)
                except OSError:
                    pass
            elif length:
                self.close_connection = True

        def _debug_profile(self) -> None:
            # POST /debug/profile?ms=N — hold a live jax.profiler window
            # over the serving loop's decode steps and return the
            # Eval/Sync split + static collective traffic as JSON
            from urllib.parse import parse_qs, urlsplit

            self._drain_small_body()
            try:
                qs = parse_qs(urlsplit(self.path).query)
                ms = int(qs.get("ms", [_PROFILE_MS_DEFAULT])[0])
                ops = int(qs.get("ops", ["0"])[0])
            except ValueError:
                self._json(400, {"error": "ms and ops must be integers"})
                return
            if not (10 <= ms <= _PROFILE_MS_MAX):
                self._json(400, {"error": f"ms must be in "
                                          f"[10, {_PROFILE_MS_MAX}]"})
                return
            try:
                self._json(200, profiling.live_split_summary(
                    state.engine, ms / 1000.0, include_ops=bool(ops)))
            except profiling.CaptureBusyError as e:
                self._json(409, {"error": str(e)})
            except Exception as e:  # noqa: BLE001 — diagnostics must fail as JSON, never wedge serving
                self._json(503, {"error": f"{type(e).__name__}: {e}"})

        def _kv_export(self) -> None:
            # POST /v1/kv/export {"tokens": [...]} → kvwire frame stream
            # of the paged-KV blocks covering the longest resident prefix
            # of ``tokens``. 404 when nothing is resident (the importer
            # treats any failure as "recompute locally"); the stream has
            # no Content-Length, so the connection closes to delimit it.
            from ..runtime import kvwire

            sched = getattr(state, "sched", None)
            if sched is None or not hasattr(sched, "request_kv_export"):
                self._drain_small_body()
                self._json(404, {"error": "KV export needs batched paged "
                                          "serving (--batch-slots N with "
                                          "--kv-block-size)"})
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError):
                self._json(400, {"error": "invalid JSON body"})
                return
            tokens = body.get("tokens") if isinstance(body, dict) else None
            if (not isinstance(tokens, list) or not tokens
                    or not all(isinstance(t, int) for t in tokens)):
                self._json(400, {"error": "body must carry a non-empty "
                                          "integer token list in 'tokens'"})
                return
            try:
                n_tokens, blocks = sched.request_kv_export(tokens)
            except SchedulerUnavailableError as e:
                self._json(503, {"error": str(e), "code": "draining"
                                 if "draining" in str(e) else "crashed"},
                           headers=backpressure_headers(503))
                return
            except Exception as e:  # noqa: BLE001 — export must fail as JSON; importer falls back
                self._json(503, {"error": f"{type(e).__name__}: {e}",
                                 "code": "crashed"},
                           headers=backpressure_headers(503))
                return
            if not n_tokens:
                self._json(404, {"error": "prefix not resident"})
                return
            geometry = dict(sched.gen.wire_geometry(),
                            n_blocks=len(blocks), n_tokens=n_tokens)
            self._count(200)
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Connection", "close")
            self.end_headers()
            try:
                kvwire.write_stream(self.wfile, geometry, blocks)
            except OSError:
                pass  # importer vanished mid-stream: its problem, not ours
            self.close_connection = True

        def do_POST(self):
            path = self._route()
            if path == "/debug/profile":
                self._debug_profile()
                return
            if path == "/v1/kv/export":
                self._kv_export()
                return
            if path not in ("/v1/chat/completions",):
                self._drain_small_body()
                self._not_found()
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError):
                self._json(400, {"error": "invalid JSON body"})
                return
            if not isinstance(body, dict):
                self._json(400, {"error": "body must be a JSON object"})
                return
            fleet = fleet_identity(self.headers)
            self._fleet_rid = fleet[0] if fleet else None
            tenant = tenant_identity(self.headers)
            self._tenant = tenant
            peer = kv_peer(self.headers)
            stream = bool(body.get("stream", False))
            inflight = telemetry.registry().gauge(telemetry.REQUESTS_IN_FLIGHT)
            inflight.add(1)
            # the finally records whatever happened: streamed requests can't
            # count via _json, and a non-ValueError engine failure in either
            # mode would otherwise vanish from the counter entirely — the
            # failing requests are exactly the ones an operator must see
            self._counted = False
            status: int | str = 500
            # SSE headers are sent lazily at the FIRST delta, so failures
            # before any output (shed, timeout, malformed body, scheduler
            # down) still return a real status code even on stream requests
            headers_sent = False

            def start_stream() -> None:
                nonlocal headers_sent
                if headers_sent:
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                if self._fleet_rid:
                    self.send_header(FLEET_RID_HEADER, self._fleet_rid)
                if self._tenant is not None:
                    self.send_header(TENANT_HEADER, self._tenant)
                self.end_headers()
                headers_sent = True

            def emit(text: str, meta: dict | None = None) -> None:
                failpoints.fire("emit")
                start_stream()
                chunk = _chunk_json(state, {"content": text})
                if meta is not None:
                    # resume stamping (batched mode): monotonic token
                    # index + the ids this chunk covers — the fleet
                    # router's per-request resume record reads these
                    chunk["dllama"] = meta
                self.wfile.write(
                    b"data: " + json.dumps(chunk).encode("utf-8") + b"\n\n")
                self.wfile.flush()

            def stream_abort(reason: str) -> None:
                # headers already went out as 200: terminate the SSE
                # stream with an explicit finish_reason + [DONE] so the
                # client can tell a server-side abort from a dropped
                # socket (the status COUNTER still records the real
                # outcome; the wire status can no longer change)
                try:
                    final = _chunk_json(state, {}, reason)
                    self.wfile.write(b"data: "
                                     + json.dumps(final).encode("utf-8")
                                     + b"\n\n")
                    self.wfile.write(b"data: [DONE]\n\n")
                except OSError:
                    pass
                self.close_connection = True

            try:
                if stream:
                    out = state.complete(body, emit=emit, fleet=fleet,
                                         kv_peer=peer, tenant=tenant)
                    start_stream()  # zero-delta completion: headers now
                    final = _chunk_json(state, {}, out["finish_reason"])
                    self.wfile.write(
                        b"data: " + json.dumps(final).encode("utf-8") + b"\n\n")
                    self.wfile.write(b"data: [DONE]\n\n")
                    status = 200
                else:
                    out = state.complete(body, fleet=fleet, kv_peer=peer,
                                         tenant=tenant)
                    self._json(200, _completion_json(state, out))
                    status = 200
            except QueueFullError as e:
                status = 429  # load shed: bounded queue, explicit backoff
                if not headers_sent:
                    self._json(429, {"error": str(e), "code": "queue_full"},
                               headers=backpressure_headers(429))
                else:
                    stream_abort("error")
            except (SchedulerUnavailableError, HbmAdmissionError) as e:
                # draining, crashed-unready, watchdog-stalled, or the HBM
                # admission guard refused the request — all 503-shaped:
                # the server cannot take this work right now (same
                # Retry-After policy as /readyz and the 429 shed). The
                # body's machine code tells the fleet router whether
                # this replica is draining/saturated (reclassify) or
                # broken (feed the circuit breaker): an HBM reject is
                # load pressure, not damage.
                status = 503
                code = ("queue_full" if isinstance(e, HbmAdmissionError)
                        else "draining" if "draining" in str(e)
                        else "crashed")
                if not headers_sent:
                    self._json(503, {"error": str(e), "code": code},
                               headers=backpressure_headers(503))
                else:
                    stream_abort("error")
            except RequestTimeoutError as e:
                status = 408  # deadline expired before any output
                if not headers_sent:
                    self._json(408, {"error": str(e)})
                else:
                    stream_abort("timeout")
            except numerics.NumericsError as e:
                # fail-fast tripwire: the model produced non-finite
                # decode-step logits — an explicit 5xx naming the site,
                # never garbage tokens (runtime/numerics)
                status = 500
                if not headers_sent:
                    self._json(500, {"error": str(e)})
                else:
                    stream_abort("error")
            except (ClientDisconnect, BrokenPipeError,
                    ConnectionResetError):
                # the peer hung up: nothing left to write, and this is
                # load information rather than a server error
                status = "client_disconnect"
                self.close_connection = True
            except ValueError as e:
                status = 400
                if not headers_sent:
                    self._json(400, {"error": str(e)})
                else:  # mid-stream model/request failure: can't re-status
                    status = 500
                    stream_abort("error")
            finally:
                inflight.add(-1)
                if not self._counted:
                    self._count(status)

    return Handler


def run_api_server(args) -> int:
    import signal
    import threading

    from .cli import make_engine, start_stats_reporter

    if getattr(args, "dp", 1) > 1 and (getattr(args, "batch_slots", 0) or 0) <= 1:
        raise SystemExit("--dp shards the --batch-slots pool; without "
                         "batched serving it only replicates batch-1 work "
                         "(set --batch-slots N with N % dp == 0, or drop --dp)")
    if (getattr(args, "kv_block_size", 0) or 0) > 0 \
            and (getattr(args, "batch_slots", 0) or 0) <= 1:
        raise SystemExit("--kv-block-size is the paged BATCHED serving "
                         "cache; it needs --batch-slots N (N > 1) to serve "
                         "through the continuous-batching scheduler")
    if getattr(args, "trace_out", None):
        telemetry.tracer().configure(args.trace_out)
        print(f"🔬 request trace (JSONL spans) → {args.trace_out}")
    # tenant observatory (runtime/tenancy): fair-share limits + the
    # usage ledger configure the process-global registry BEFORE the
    # scheduler builds, so its FairQueue weights apply from request one
    if getattr(args, "tenant_limits", None):
        try:
            limits = tenancy.load_limits(args.tenant_limits)
        except ValueError as e:
            raise SystemExit(f"--tenant-limits: {e}")
        tenancy.registry().set_limits(limits)
        print(f"🕸️ tenant limits: {len(limits)} "
              f"entr{'y' if len(limits) == 1 else 'ies'} "
              f"(weighted round-robin admission; over-budget → 429)")
    if getattr(args, "usage_ledger", None):
        tenancy.ledger().configure(args.usage_ledger)
        print(f"📒 usage ledger (per-tenant cumulative JSONL) → "
              f"{args.usage_ledger}")
    if failpoints.configure_from_env():
        print("💣 fault injection armed from DLLAMA_FAILPOINTS="
              f"{os.environ['DLLAMA_FAILPOINTS']}")
    engine = make_engine(args)
    # compile introspection: per-miss memory/cost analysis is ON in serving
    # mode (it re-lowers and re-compiles identical HLO, which the persistent
    # compile cache absorbs); DLLAMA_INTROSPECT_ANALYZE=0 opts out for
    # cold-start-critical deploys. The startup report then prints the HBM
    # budget table (weights vs KV vs per-program temp/output bytes).
    if os.environ.get("DLLAMA_INTROSPECT_ANALYZE") != "0":
        introspection.ledger().analyze = True
    try:
        introspection.hbm_startup_report(engine)
    except Exception as e:  # noqa: BLE001 — the report is advisory; serving must start anyway
        print(f"🚧 HBM startup report unavailable: {type(e).__name__}: {e}")
    if engine._wire_traffic:
        # multichip wire price (analytic, parallel/qcollectives
        # .wire_traffic_model): what each emitted token costs the ICI/DCN
        # in col-split merge bytes, counted live into
        # dllama_collective_bytes_total{op,wire}
        per_tok = sum(b for _, _, b in engine._wire_traffic)
        modes = ", ".join(sorted({f"{op}/{wire}"
                                  for op, wire, _ in engine._wire_traffic}))
        print(f"🕸️ multichip wire: ~{per_tok / 1024:.1f} kB/token of "
              f"col-split merges ({modes}) → "
              f"dllama_collective_bytes_total")
    if getattr(args, "stats", 0):
        start_stats_reporter(float(args.stats))
    # golden canary drift sentinel (--canary-interval SEC): record the
    # golden NOW — before serving reaches steady state, so the canary's
    # programs compile while compiles are still expected; every later
    # replay is a compile-cache hit (ledger-quiet by construction)
    canary_s = float(getattr(args, "canary_interval", 0.0) or 0.0)
    if canary_s > 0:
        if engine.multihost:
            print("🚧 --canary-interval ignored under multihost (the "
                  "canary's scratch dispatches are not broadcast to "
                  "worker mirrors)")
        else:
            engine.canary = numerics.CanarySentinel(engine,
                                                    interval_s=canary_s)
            engine.canary.ensure_golden()
            print(f"🐤 canary sentinel: fixed-seed replay every "
                  f"{canary_s:g}s (drift → dllama_canary_drift_total, "
                  f"WARN names the divergent layer"
                  + (")" if engine.numerics_taps
                     else " with --numerics-taps)"))
    n_slots = getattr(args, "batch_slots", 0) or 0
    max_queue = getattr(args, "max_queue", 0) or 0
    request_timeout = getattr(args, "request_timeout", 0.0) or 0.0
    drain_timeout = getattr(args, "drain_timeout", 5.0)
    role = getattr(args, "role", None) or None
    if role and (n_slots <= 1 or not (getattr(args, "kv_block_size", 0) or 0)):
        raise SystemExit("--role tags a disaggregated replica; it needs "
                         "batched paged serving (--batch-slots N with "
                         "--kv-block-size) so the KV wire has blocks to "
                         "export and import")
    ttype = ChatTemplateType(getattr(args, "chat_template", None) or "unknown")
    if n_slots > 1:
        state: ApiState | BatchedApiState = BatchedApiState(
            engine, n_slots, template_type=ttype, max_queue=max_queue,
            request_timeout=request_timeout, role=role)
        server = ThreadingHTTPServer((args.host, args.port),
                                     make_handler(state))
        print(f"🕸️ continuous batching: {state.sched.n_slots} slots"
              + (f" (HBM-degraded from {n_slots})"
                 if state.sched.n_slots != n_slots else "")
              + (f", queue bound {max_queue} (429 beyond)" if max_queue
                 else ""))
        if getattr(engine, "kv_block_size", 0):
            pool = state.sched.gen.pool
            print(f"🕸️ paged KV: {pool.n_blocks - 1} blocks × "
                  f"{pool.block_size} rows (block-priced admission, "
                  f"block-level prefix sharing)")
            if pool.n_host_blocks:
                mirror = state.sched.gen.mirror
                print(f"🕸️ tiered KV memory: {pool.n_host_blocks} host "
                      f"blocks ({mirror.kind or 'numpy host buffers'}) — "
                      f"cold blocks spill under pressure, resumed "
                      f"sessions page back in "
                      f"(dllama_kv_spill/pagein_* metrics)")
            elif getattr(engine, "kv_host_blocks", 0):
                print("⚠️ tiered KV memory requested but the host tier "
                      "came up empty (budget or transfer warmup) — "
                      "serving untiered")
            print(f"🕸️ KV migration: POST /v1/kv/export serves resident "
                  f"prefixes over the checksummed Q80 wire"
                  + (f"; role={role} advertised on /readyz" if role
                     else ""))
        if engine.spec_lookup:
            paged = bool(getattr(engine, "kv_block_size", 0))
            print(f"🕸️ speculative serving: verify K={engine.spec_lookup} "
                  f"per slot "
                  + ("(greedy exact + rejection-sampled temperature>0)"
                     if paged else "(greedy requests)"))
        print("🕸️ quality observatory: teacher-forced eval rides these "
              "slots (resident runs advertised on /readyz as "
              "eval_resident; last summary on GET /debug/eval)")
    else:
        state = ApiState(engine, template_type=ttype,
                         request_timeout=request_timeout)
        server = HTTPServer((args.host, args.port), make_handler(state))
    if request_timeout:
        print(f"🕸️ per-request deadline: {request_timeout:g}s "
              f"(request 'timeout' field overrides)")

    def _on_sigterm(signum, frame):
        # graceful drain: flip /readyz (load balancer stops routing), stop
        # admitting, then stop the accept loop from ANOTHER thread —
        # shutdown() called here would deadlock the serve_forever poll
        print("🛑 SIGTERM: draining (readyz → 503, no new admissions)",
              flush=True)
        if isinstance(state, BatchedApiState):
            state.begin_drain()
        threading.Thread(target=server.shutdown, daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded/test usage): no signal hook
    print(f"🕸️ listening on http://{args.host}:{args.port}")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        if isinstance(state, BatchedApiState):
            # drain active slots up to the deadline, then fail the
            # remainder explicitly (their handler threads get errors,
            # never a silent hang)
            state.close(drain_s=drain_timeout)
        engine.close()
        telemetry.tracer().configure(None)
    return 0

"""Fleet router tests (serve/router.py): health-driven dispatch over
replica api-servers with circuit breaking, retry, affinity, shedding,
and replica-churn survival.

Most tests drive the router against STUB replicas — tiny deterministic
HTTP servers speaking exactly the api-server surface the router consumes
(/readyz with the machine-readable ``code``, /metrics load gauges, SSE +
JSON completions) — so failure timing is exact and golden byte
comparison is possible. One test fronts a real tiny CPU-mesh engine to
prove end-to-end compatibility. The chaos acceptance test (3 replicas,
mid-run kill + restart under continuous mixed traffic) is the ISSUE-12
contract: zero silent failures, retries visible in telemetry, explicit
terminal 502s for mid-stream victims, breaker re-admission after the
restart."""

import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dllama_tpu.runtime import failpoints as fp
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.serve.router import (FleetRouter, affinity_key,
                                     make_router_handler)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.registry().clear()
    yield
    fp.registry().clear()


def _wait(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# -- stub replica ------------------------------------------------------------


class StubReplica:
    """A deterministic api-server stand-in. ``behavior`` is mutated by
    tests mid-run; the handler reads it per request."""

    def __init__(self, name: str):
        self.name = name
        self.port: int | None = None
        self.httpd: ThreadingHTTPServer | None = None
        self.behavior: dict = {
            "ready": True,          # /readyz 200 vs 503
            "ready_code": "ok",     # unready code when not ready
            "queue_depth": 0,       # /metrics load gauges
            "inflight": 0,
            "completion_status": 200,   # non-200: error passthrough body
            "error_code": None,         # machine code in the error body
            "stream_chunks": ["Hel", "lo ", "fleet"],
            "chunk_delay_s": 0.0,
            "die_after_chunks": None,   # RST mid-stream after N chunks
            "truncate_nonstream": False,  # declare CL, RST mid-body
            "nonstream_delay_s": 0.0,
            "role": None,               # /readyz disaggregation tag
            "kv_prefixes": [],          # /readyz residency advertisement
            # stamped streaming (serve/api.py batched mode): chunks carry
            # the dllama {"index", "tokens"} resume meta, and a body with
            # resume_from is honored — continuation starts AT that index
            # (replaying it once; the router must dedup), exactly like a
            # real replica racing the splice
            "stamp": False,
            # emit a terminal finish_reason "error" chunk + [DONE] after
            # N token chunks — what a killed api-server's fail-all path
            # actually writes (ThreadingHTTPServer handlers survive
            # shutdown; the scheduler fails the slot, the socket FINs
            # cleanly)
            "error_after_chunks": None,
            # a canned /debug/tenants snapshot (None -> 404), so the
            # router's fleet-wide tenant join can be driven end to end
            "tenants_snapshot": None,
        }
        self.n_completions = 0
        # resume capture: one dict per STREAM completion attempt with the
        # X-Dllama-Resume-From header and the request body as received
        self.seen_resumes: list = []
        # KV migration capture: the X-Dllama-KV-Peer value (or None)
        # seen on each completion attempt, in arrival order
        self.seen_kv_peers: list = []
        # tenant capture: the X-Dllama-Tenant value (or None) seen on
        # each completion attempt, in arrival order
        self.seen_tenants: list = []
        # fleet-trace capture: (fleet_rid, hop) per completion attempt,
        # plus a flight-shaped dump served at /debug/flight so the
        # router's fleet-timeline join can be driven end to end
        self.seen_fleet: list = []
        self.flight_events: list = []
        self.flight_spans: list = []
        self._rid_lock = threading.Lock()
        self._local_rid = 0

    def note_fleet(self, frid, fhop) -> int:
        """Record a completion attempt's fleet identity headers the way
        serve/api.py binds them; returns the engine-local rid."""
        with self._rid_lock:
            self._local_rid += 1
            local = self._local_rid
        if frid is not None:
            hop = int(fhop or 0)
            self.seen_fleet.append((frid, hop))
            self.flight_events.append(
                {"event": "fleet_rid", "rid": local, "reason": frid,
                 "hop": hop, "t_ns": time.monotonic_ns()})
        return local

    def note_span(self, local, t0_ns, frid, fhop) -> None:
        s = {"request_id": local, "phase": "decode", "start_ns": t0_ns,
             "end_ns": time.monotonic_ns(), "slot": 0, "n_tokens": 3}
        if frid is not None:
            s["fleet"] = frid
            s["hop"] = int(fhop or 0)
        self.flight_spans.append(s)

    def start(self) -> None:
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _rst(self):
                # force an RST (not a clean FIN): an EOF-delimited SSE
                # stream must look DEAD, not complete. The LINGER(1,0)
                # option rides the fd; the abort fires when the handler
                # teardown closes the last file object over it —
                # close_connection makes that happen NOW instead of
                # parking in the keep-alive readline
                self.connection.setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER,
                    struct.pack("ii", 1, 0))
                self.close_connection = True

            def _json(self, code, payload, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                b = stub.behavior
                if self.path == "/readyz":
                    extra = {}
                    if b["role"]:
                        extra["role"] = b["role"]
                    if b["kv_prefixes"]:
                        extra["kv_prefixes"] = list(b["kv_prefixes"])
                    if b["ready"]:
                        self._json(200, {"status": "ok", "reason": "ok",
                                         "code": "ok", **extra})
                    else:
                        self._json(503, {"status": "unready",
                                         "reason": b["ready_code"],
                                         "code": b["ready_code"], **extra},
                                   headers={"Retry-After": "5"})
                elif self.path == "/metrics":
                    text = (f"dllama_queue_depth {b['queue_depth']}\n"
                            f"dllama_requests_in_flight {b['inflight']}\n")
                    body = text.encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif self.path == "/v1/models":
                    self._json(200, {"object": "list", "data": [
                        {"id": f"stub-{stub.name}", "object": "model"}]})
                elif self.path == "/debug/tenants":
                    if b["tenants_snapshot"] is None:
                        self._json(404, {"error": "not found"})
                    else:
                        self._json(200, b["tenants_snapshot"])
                elif self.path == "/debug/flight":
                    self._json(200, {
                        "tick_seq": 0, "ticks": [], "dumps": [],
                        "events": list(stub.flight_events),
                        "spans": list(stub.flight_spans)})
                else:
                    self._json(404, {"error": "not found"})

            def do_POST(self):
                b = stub.behavior
                length = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(length)
                if self.path != "/v1/chat/completions":
                    self._json(404, {"error": "not found"})
                    return
                stub.n_completions += 1
                frid = self.headers.get("X-Dllama-Request-Id")
                fhop = self.headers.get("X-Dllama-Hop")
                stub.seen_kv_peers.append(
                    self.headers.get("X-Dllama-KV-Peer"))
                stub.seen_tenants.append(
                    self.headers.get("X-Dllama-Tenant"))
                t0_ns = time.monotonic_ns()
                local = stub.note_fleet(frid, fhop)
                if b["nonstream_delay_s"]:
                    time.sleep(b["nonstream_delay_s"])
                if b["completion_status"] != 200:
                    hdrs = ({"Retry-After": "5"}
                            if b["completion_status"] in (429, 503) else {})
                    payload = {"error": f"stub error "
                                        f"{b['completion_status']}"}
                    if b["error_code"]:
                        payload["code"] = b["error_code"]
                    self._json(b["completion_status"], payload,
                               headers=hdrs)
                    stub.note_span(local, t0_ns, frid, fhop)
                    return
                try:
                    body = json.loads(raw or b"{}")
                except ValueError:
                    self._json(400, {"error": "invalid JSON body"})
                    return
                if body.get("stream"):
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-cache")
                    self.send_header("Connection", "close")
                    self.end_headers()

                    def send(piece, finish=None, meta=None):
                        chunk = {"object": "chat.completion.chunk",
                                 "replica": stub.name,
                                 "choices": [{"index": 0,
                                              "delta": ({"content": piece}
                                                        if piece else {}),
                                              "finish_reason": finish}]}
                        if meta is not None:
                            chunk["dllama"] = meta
                        self.wfile.write(b"data: "
                                         + json.dumps(chunk).encode()
                                         + b"\n\n")
                        self.wfile.flush()

                    if b["stamp"]:
                        stub.seen_resumes.append({
                            "header": self.headers.get(
                                "X-Dllama-Resume-From"),
                            "body": body})
                        resume_from = int(body.get("resume_from") or 0)
                        pieces = list(b["stream_chunks"])
                        if resume_from == 0:
                            # the prompt-echo chunk, index 0
                            send("", meta={"index": 0, "tokens": []})
                        n_emitted = 0
                        # a resume replays its splice index once — the
                        # router's exactly-once filter must drop it
                        for i in range(max(1, resume_from),
                                       len(pieces) + 1):
                            send(pieces[i - 1],
                                 meta={"index": i, "tokens": [100 + i]})
                            n_emitted += 1
                            if b["chunk_delay_s"]:
                                time.sleep(b["chunk_delay_s"])
                            if b["die_after_chunks"] is not None \
                                    and n_emitted >= b["die_after_chunks"]:
                                self.close_connection = True
                                stub.note_span(local, t0_ns, frid, fhop)
                                return
                            if b["error_after_chunks"] is not None \
                                    and n_emitted >= \
                                    b["error_after_chunks"]:
                                send("", finish="error")
                                self.wfile.write(b"data: [DONE]\n\n")
                                self.close_connection = True
                                stub.note_span(local, t0_ns, frid, fhop)
                                return
                        # the real final chunk is unstamped (api.py
                        # writes it outside the emit path)
                        send("", finish="length")
                        self.wfile.write(b"data: [DONE]\n\n")
                        self.close_connection = True
                        stub.note_span(local, t0_ns, frid, fhop)
                        return
                    for i, piece in enumerate(b["stream_chunks"]):
                        send(piece)
                        if b["chunk_delay_s"]:
                            time.sleep(b["chunk_delay_s"])
                        if b["die_after_chunks"] is not None \
                                and i + 1 >= b["die_after_chunks"]:
                            # a dying replica closes with a clean FIN
                            # and no [DONE] — exactly what a killed
                            # api-server's SSE stream looks like
                            self.close_connection = True
                            stub.note_span(local, t0_ns, frid, fhop)
                            return
                    self.wfile.write(b"data: [DONE]\n\n")
                    self.close_connection = True
                    stub.note_span(local, t0_ns, frid, fhop)
                    return
                if b["truncate_nonstream"]:
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", "1000")
                    self.end_headers()
                    self.wfile.write(b'{"partial": tru')
                    self.wfile.flush()
                    self._rst()
                    stub.note_span(local, t0_ns, frid, fhop)
                    return
                self._json(200, {
                    "object": "chat.completion", "replica": stub.name,
                    "choices": [{"index": 0,
                                 "message": {"role": "assistant",
                                             "content": "".join(
                                                 b["stream_chunks"])},
                                 "finish_reason": "stop"}],
                    "usage": {"prompt_tokens": 3, "completion_tokens": 3,
                              "total_tokens": 6}})
                stub.note_span(local, t0_ns, frid, fhop)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", self.port or 0),
                                         Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def kill(self) -> None:
        """Replica death: the listening socket closes — new connections
        are refused (in-flight handler threads die on their own RSTs)."""
        self.httpd.shutdown()
        self.httpd.server_close()
        self.httpd = None

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"


def make_router(stubs, **kw):
    """Router + HTTP front end over the given stubs, with test-speed
    probe/breaker timings; returns (base_url, fleet, closer)."""
    kw.setdefault("probe_interval_s", 0.05)
    kw.setdefault("eject_after", 2)
    kw.setdefault("backoff_min_s", 0.1)
    kw.setdefault("backoff_max_s", 0.4)
    kw.setdefault("connect_timeout_s", 2.0)
    fleet = FleetRouter([s.url for s in stubs], **kw)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                make_router_handler(fleet))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def closer():
        httpd.shutdown()
        httpd.server_close()
        fleet.close()

    return f"http://127.0.0.1:{port}", fleet, closer


def _post(url, payload, timeout=30):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _body(prompt, stream=False, **extra):
    return {"messages": [{"role": "user", "content": prompt}],
            "max_tokens": 8, "stream": stream, **extra}


def _up(fleet, name):
    return tm.registry().gauge(tm.ROUTER_REPLICA_UP).value(replica=name)


# -- surfaces ----------------------------------------------------------------


def test_router_surfaces_and_replica_up(tmp_path):
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        # readiness flips at the FIRST dispatchable replica; wait for
        # both probes before asserting fleet-wide state
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        assert fleet.readiness()[0]
        with urllib.request.urlopen(url + "/readyz", timeout=10) as r:
            body = json.loads(r.read())
        assert body == {"status": "ok", "reason": "ok", "code": "ok"}
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(url + "/debug/fleet", timeout=10) as r:
            snap = json.loads(r.read())
        assert {s["replica"] for s in snap["replicas"]} \
            == {r.name for r in fleet.replicas}
        assert all(s["state"] == "up" for s in snap["replicas"])
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "dllama_router_replica_up{" in text
        # /v1/models proxies to a live replica
        with urllib.request.urlopen(url + "/v1/models", timeout=10) as r:
            assert json.loads(r.read())["object"] == "list"
        # unknown routes: JSON 404
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/nope", timeout=10)
        assert e.value.code == 404
    finally:
        close()
        a.kill(), b.kill()


def test_least_loaded_dispatch_uses_probed_queue_depth():
    a, b = StubReplica("a"), StubReplica("b")
    a.behavior["queue_depth"] = 50
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[0].load_score() >= 50,
              what="probe load refresh")
        # distinct prompts (distinct affinity keys): all land on the
        # unloaded replica
        for i in range(3):
            with _post(url, _body(f"p{i}")) as r:
                assert json.loads(r.read())["replica"] == "b"
    finally:
        close()
        a.kill(), b.kill()


def test_session_affinity_sticks_while_healthy():
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    hits = tm.registry().counter(tm.ROUTER_AFFINITY_HITS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        h0 = hits.total()
        with _post(url, _body("sticky conversation")) as r:
            first = json.loads(r.read())["replica"]
        # load now favors the OTHER replica; affinity must still win
        (a if first == "a" else b).behavior["queue_depth"] = 50
        _wait(lambda: max(r.load_score() for r in fleet.replicas) >= 50,
              what="probe load refresh")
        for _ in range(3):
            with _post(url, _body("sticky conversation")) as r:
                assert json.loads(r.read())["replica"] == first
        assert hits.total() >= h0 + 3
        # an explicit session_id key overrides the prefix hash
        k1 = affinity_key({"session_id": "s1", "messages": []})
        k2 = affinity_key(_body("sticky conversation"))
        assert k1.startswith("sid:") and k2.startswith("pfx:")
    finally:
        close()
        a.kill(), b.kill()


def test_affinity_rebinds_when_sticky_replica_dies():
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        with _post(url, _body("rebind me")) as r:
            first = json.loads(r.read())["replica"]
        victim = a if first == "a" else b
        survivor = b if first == "a" else a
        victim.kill()
        _wait(lambda: _up(fleet, f"127.0.0.1:{victim.port}") == 0,
              what="victim ejected")
        with _post(url, _body("rebind me")) as r:
            assert json.loads(r.read())["replica"] == survivor.name
        # the session is now stuck to the survivor — even after the old
        # replica returns, the sticky map keeps it where its KV lives
        victim.start()
        _wait(lambda: _up(fleet, f"127.0.0.1:{victim.port}") == 1,
              what="victim re-admitted")
        with _post(url, _body("rebind me")) as r:
            assert json.loads(r.read())["replica"] == survivor.name
    finally:
        close()
        for s in (a, b):
            if s.httpd is not None:
                s.kill()


# -- retry / circuit breaker -------------------------------------------------


def test_proxy_failpoint_drives_transparent_retry():
    """Armed `proxy` failpoint severs the first upstream connection —
    the request transparently retries on a different replica and
    completes; the retry is visible in dllama_router_retries_total."""
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    retries = tm.registry().counter(tm.ROUTER_RETRIES)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        r0 = retries.total()
        fp.arm("proxy", "conn_reset", times=1)
        with _post(url, _body("retry me")) as r:
            out = json.loads(r.read())
        assert out["replica"] in ("a", "b")
        assert retries.total() == r0 + 1
    finally:
        close()
        a.kill(), b.kill()


def test_midbody_death_retries_before_first_client_byte():
    """A replica that dies mid-body on a Content-Length response fails
    before anything reached the client — retried, not a 502."""
    a, b = StubReplica("a"), StubReplica("b")
    a.behavior["truncate_nonstream"] = True
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    retries = tm.registry().counter(tm.ROUTER_RETRIES)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        r0 = retries.total()
        n_ok = 0
        for i in range(4):  # distinct keys: some land on the truncator
            with _post(url, _body(f"q{i}")) as r:
                out = json.loads(r.read())
            assert out["replica"] == "b"  # only b can COMPLETE one
            n_ok += 1
        assert n_ok == 4
        # at least one request was dispatched to a first and retried
        assert retries.total() >= r0 + 1
    finally:
        close()
        a.kill(), b.kill()


def test_circuit_breaker_ejects_then_halfopen_readmits():
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    reg = tm.registry()
    ejects = reg.counter(tm.ROUTER_EJECTS)
    readmits = reg.counter(tm.ROUTER_READMITS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        name = f"127.0.0.1:{a.port}"
        e0, ra0 = ejects.total(replica=name), readmits.total(replica=name)
        # seed sticky sessions; the entries pointing at the victim must
        # be purged at ejection (affinity hygiene), not left to rot as
        # one dispatchable() miss per returning session
        purged = tm.registry().counter(tm.ROUTER_AFFINITY_PURGED)
        p0 = purged.total(replica=name)
        stuck_on_a = 0
        for i in range(6):
            with _post(url, _body(f"warm-{i}",
                                  session_id=f"sess-{i}")) as r:
                if json.loads(r.read())["replica"] == "a":
                    stuck_on_a += 1
        assert stuck_on_a  # at least one sticky entry names the victim
        a.kill()
        _wait(lambda: ejects.total(replica=name) == e0 + 1,
              what="breaker ejection")
        assert _up(fleet, name) == 0
        assert purged.total(replica=name) - p0 == stuck_on_a
        with fleet._lock:
            assert not any(rep.name == name
                           for rep in fleet._affinity.values())
        snap = [s for s in fleet.fleet_snapshot()["replicas"]
                if s["replica"] == name][0]
        assert snap["state"] == "down" and snap["backoff_s"] > 0
        # traffic keeps flowing on the survivor meanwhile
        with _post(url, _body("meanwhile")) as r:
            assert json.loads(r.read())["replica"] == "b"
        # restart: a bounded-backoff half-open probe re-admits it
        a.start()
        _wait(lambda: readmits.total(replica=name) == ra0 + 1,
              what="half-open re-admission")
        assert _up(fleet, name) == 1
        # dispatch returns to the re-admitted replica
        _wait(lambda: _served_by(url, "a"), timeout=10,
              what="dispatch back on a")
    finally:
        close()
        for s in (a, b):
            if s.httpd is not None:
                s.kill()


def _served_by(url, name, n=6):
    for i in range(n):
        with _post(url, _body(f"probe-{name}-{i}-{time.monotonic_ns()}")) \
                as r:
            if json.loads(r.read())["replica"] == name:
                return True
    return False


# -- KV migration orchestration ----------------------------------------------


def test_kv_donor_header_on_residency_hit():
    """A peer advertising the prompt's affinity key on /readyz becomes
    the KV donor: the dispatch carries X-Dllama-KV-Peer naming it. When
    the chosen replica itself advertises the key, no donor is named
    (migrating a prefix onto the replica that already holds it would be
    pure wire waste)."""
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        key = "sid:donor-sess"
        b.behavior["kv_prefixes"] = [key]
        _wait(lambda: any(r.holds_prefix(key) for r in fleet.replicas),
              what="residency advertisement probed")
        with _post(url, _body("migrate me",
                              session_id="donor-sess")) as r:
            assert json.loads(r.read())["replica"] == "a"
        assert a.seen_kv_peers[-1] == f"127.0.0.1:{b.port}"
        # /debug/fleet surfaces the advertisement
        snap = fleet.fleet_snapshot()["replicas"]
        assert [s for s in snap
                if s["replica"] == f"127.0.0.1:{b.port}"][0][
                    "kv_prefixes"] == [key]
        # chosen replica already resident: no donor header
        a.behavior["kv_prefixes"] = [key]
        rep_a = [r for r in fleet.replicas
                 if r.name == f"127.0.0.1:{a.port}"][0]
        _wait(lambda: rep_a.holds_prefix(key),
              what="chosen replica's own advertisement probed")
        with _post(url, _body("already here",
                              session_id="donor-sess")) as r:
            r.read()
        assert a.seen_kv_peers[-1] is None
    finally:
        close()
        a.kill(), b.kill()


def test_prefill_role_warms_then_names_donor():
    """Explicit disaggregation: a prefill-role replica never serves
    decode traffic; with no resident donor, the router first runs a
    one-token warm-up on it, then dispatches to the decode replica with
    the prefill replica named as KV donor."""
    p, d = StubReplica("p"), StubReplica("d")
    p.start(), d.start()
    p.behavior["role"] = "prefill"
    url, fleet, close = make_router([p, d])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        rep_p = [r for r in fleet.replicas
                 if r.name == f"127.0.0.1:{p.port}"][0]
        _wait(lambda: rep_p.is_prefill(), what="prefill role probed")
        with _post(url, _body("disaggregate me",
                              session_id="disagg-sess")) as r:
            assert json.loads(r.read())["replica"] == "d"
        # the prefill replica saw exactly the warm-up (no donor header,
        # max_tokens clamped to 1, not streamed)
        assert p.n_completions == 1
        assert p.seen_kv_peers == [None]
        # the decode dispatch names the prefill replica as donor
        assert d.seen_kv_peers[-1] == f"127.0.0.1:{p.port}"
    finally:
        close()
        p.kill(), d.kill()


# -- shedding / drain --------------------------------------------------------


def test_all_replicas_saturated_sheds_429_with_retry_after():
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    shed = tm.registry().counter(tm.ROUTER_SHED)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        for s in (a, b):
            s.behavior.update(ready=False, ready_code="queue_full")
        _wait(lambda: not fleet.readiness()[0], what="fleet saturated")
        assert fleet.readiness()[2] == "queue_full"
        s0 = shed.total()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, _body("shed me"))
        assert e.value.code == 429
        assert e.value.headers["Retry-After"] is not None
        assert json.loads(e.value.read())["code"] == "queue_full"
        assert shed.total() == s0 + 1
        # replicas recover -> dispatch resumes
        for s in (a, b):
            s.behavior.update(ready=True)
        _wait(lambda: fleet.readiness()[0], what="fleet recovered")
        with _post(url, _body("recovered")) as r:
            assert r.status == 200
    finally:
        close()
        a.kill(), b.kill()


def test_router_max_queue_bound_sheds():
    a = StubReplica("a")
    a.behavior["nonstream_delay_s"] = 0.6
    a.start()
    url, fleet, close = make_router([a], max_inflight=1)
    shed = tm.registry().counter(tm.ROUTER_SHED)
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        s0 = shed.total()
        codes = []

        def slow():
            with _post(url, _body("slow one"), timeout=30) as r:
                codes.append(r.status)

        t = threading.Thread(target=slow)
        t.start()
        _wait(lambda: fleet.fleet_snapshot()["inflight_total"] >= 1,
              what="first request in flight")
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, _body("beyond the bound"))
        assert e.value.code == 429
        assert e.value.headers["Retry-After"] is not None
        assert shed.total() == s0 + 1
        t.join(timeout=30)
        assert codes == [200]  # the in-flight one finished fine
    finally:
        close()
        a.kill()


def test_dispatch_503_draining_reclassifies_without_eject():
    """The drain-awareness contract on the DISPATCH path: a replica
    whose completions answer 503 code=draining (the probe hasn't
    noticed yet) is reclassified unready — the request retries on the
    other replica and the circuit breaker is NOT fed (a draining pod
    must never be ejected into the crash-backoff schedule)."""
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    # probes too slow to see the drain first: the dispatch path must
    # handle the classification itself
    url, fleet, close = make_router([a, b], probe_interval_s=30.0)
    ejects = tm.registry().counter(tm.ROUTER_EJECTS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        name_a = f"127.0.0.1:{a.port}"
        e0 = ejects.total(replica=name_a)
        a.behavior.update(completion_status=503, error_code="draining")
        for i in range(4):
            with _post(url, _body(f"drain-race-{i}")) as r:
                assert json.loads(r.read())["replica"] == "b"
        assert ejects.total(replica=name_a) == e0  # reclassified, NOT ejected
        snap = [s for s in fleet.fleet_snapshot()["replicas"]
                if s["replica"] == name_a][0]
        assert snap["state"] == "unready" and snap["code"] == "draining"
    finally:
        close()
        a.kill(), b.kill()


def test_probe_sanitizes_unknown_ready_codes():
    """An out-of-vocabulary /readyz code degrades to "crashed" — the
    READY_CODES closed world is enforced at the router's probe parse,
    not just documented."""
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router([a])
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        a.behavior.update(ready=False, ready_code="weird_code")
        name = f"127.0.0.1:{a.port}"
        _wait(lambda: _up(fleet, name) == 0, what="unready observed")
        snap = fleet.fleet_snapshot()["replicas"][0]
        assert snap["state"] == "unready" and snap["code"] == "crashed"
    finally:
        close()
        a.kill()


def test_draining_replica_stops_new_dispatch():
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        a.behavior.update(ready=False, ready_code="draining")
        name = f"127.0.0.1:{a.port}"
        _wait(lambda: _up(fleet, name) == 0, what="drain observed")
        snap = [s for s in fleet.fleet_snapshot()["replicas"]
                if s["replica"] == name][0]
        assert snap["state"] == "unready" and snap["code"] == "draining"
        for i in range(4):  # nothing new lands on the draining replica
            with _post(url, _body(f"drain-{i}")) as r:
                assert json.loads(r.read())["replica"] == "b"
        # drain is not an ejection: no breaker backoff involved, and
        # recovery is immediate on the next probe
        a.behavior.update(ready=True)
        _wait(lambda: _up(fleet, name) == 1, what="drain ended")
    finally:
        close()
        a.kill(), b.kill()


# -- single-replica degradation (golden) -------------------------------------


def test_single_replica_router_is_byte_identical_passthrough():
    """ISSUE-12 satellite: a router fronting ONE replica returns byte-
    identical bodies to direct access — non-streaming, streaming, and
    error statuses (with Retry-After) pass through unmangled."""
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router([a], eject_after=100)
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")

        def both(payload):
            direct = _post(a.url, payload)
            routed = _post(url, payload)
            with direct, routed:
                return (direct.status, direct.read(),
                        routed.status, routed.read())

        # non-streaming completion
        ds, db, rs, rb = both(_body("golden"))
        assert (ds, db) == (rs, rb)
        # streaming completion: the SSE byte stream is identical
        ds, db, rs, rb = both(_body("golden", stream=True))
        assert (ds, db) == (rs, rb)
        assert b"data: [DONE]" in rb
        # error statuses pass through unmangled (status, body, and the
        # upstream's own Retry-After header)
        for status in (400, 429, 503):
            a.behavior["completion_status"] = status
            errs = []
            for base in (a.url, url):
                with pytest.raises(urllib.error.HTTPError) as e:
                    _post(base, _body("err"))
                errs.append((e.value.code, e.value.read(),
                             e.value.headers.get("Retry-After")))
            assert errs[0] == errs[1], status
        a.behavior["completion_status"] = 200
    finally:
        close()
        a.kill()


# -- mid-stream death --------------------------------------------------------


def test_midstream_death_gets_terminal_502_event_never_a_hang():
    a = StubReplica("a")
    a.behavior["die_after_chunks"] = 2
    a.start()
    url, fleet, close = make_router([a])
    http = tm.registry().counter(tm.HTTP_REQUESTS)
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        c0 = http.total(route="/v1/chat/completions", status="502")
        with _post(url, _body("doomed stream", stream=True),
                   timeout=30) as r:
            raw = r.read().decode()
        # the two relayed chunks arrived, then the EXPLICIT terminal
        # event naming the 502 — and the stream still ends with [DONE]
        # (a client can always tell this abort from a dropped socket)
        assert raw.count('"delta"') == 2
        assert '"upstream_error"' in raw and '"code": 502' in raw
        assert raw.rstrip().endswith("data: [DONE]")
        assert http.total(route="/v1/chat/completions",
                          status="502") == c0 + 1
    finally:
        close()
        a.kill()


# -- durable streams: mid-stream failover ------------------------------------


def _sse_events(raw: bytes) -> list:
    """Parsed data events of an SSE transcript, [DONE] as the string."""
    out = []
    for evt in raw.split(b"\n\n"):
        evt = evt.strip()
        if not evt.startswith(b"data:"):
            continue
        data = evt[5:].strip()
        out.append("[DONE]" if data == b"[DONE]" else json.loads(data))
    return out


def _stamp_indices(events) -> list:
    return [e["dllama"]["index"] for e in events
            if isinstance(e, dict) and "dllama" in e]


def _resume_totals():
    c = tm.registry().counter(tm.ROUTER_STREAM_RESUMES)
    return {o: c.total(outcome=o)
            for o in ("resumed", "exhausted", "no_budget", "failed")}


def test_midstream_death_splices_resume_exactly_once():
    """The tentpole contract at the router tier: a stamped stream whose
    replica dies mid-flight is re-dispatched to a healthy replica as a
    spliced continuation (resume_from + full token history + the
    X-Dllama-Resume-From header), the replayed splice index is dropped,
    and the client sees one gapless duplicate-free transcript ending in
    a normal finish — with the resume on the outcome counter, the
    latency histogram, and an rt_resume span, and the dying replica
    (still advertising the prefix) named as KV donor."""
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
        s.behavior["stream_chunks"] = ["t1 ", "t2 ", "t3 ", "t4 ", "t5"]
    a.behavior["die_after_chunks"] = 2
    a.behavior["kv_prefixes"] = ["sid:resume-sess"]
    b.behavior["queue_depth"] = 50  # first dispatch lands on a
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    h_resume = tm.registry().histogram(tm.ROUTER_STREAM_RESUME_MS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50
              and any(r.holds_prefix("sid:resume-sess")
                      for r in fleet.replicas),
              what="probes: up + load + residency")
        t0, n0 = _resume_totals(), h_resume.count()
        with _post(url, _body("durable", stream=True,
                              session_id="resume-sess", timeout=30),
                   timeout=30) as r:
            raw = r.read()
        events = _sse_events(raw)
        # gapless, duplicate-free: echo once, every index exactly once
        assert _stamp_indices(events) == [0, 1, 2, 3, 4, 5]
        assert b'"upstream_error"' not in raw
        finals = [e for e in events if isinstance(e, dict)
                  and e.get("choices")
                  and e["choices"][0].get("finish_reason")]
        assert [e["choices"][0]["finish_reason"] for e in finals] \
            == ["length"]
        assert events[-1] == "[DONE]"
        # both replicas contributed — the splice really happened
        assert {e["replica"] for e in events if isinstance(e, dict)} \
            == {"a", "b"}
        d = {k: v - t0[k] for k, v in _resume_totals().items()}
        assert d == {"resumed": 1, "exhausted": 0, "no_budget": 0,
                     "failed": 0}
        assert h_resume.count() == n0 + 1
        # the resume dispatch b saw: splice position 2, the 2 relayed
        # ids as history, the remaining deadline re-budgeted
        res = b.seen_resumes[-1]
        assert res["header"] == "2"
        assert res["body"]["resume_from"] == 2
        assert res["body"]["resume_tokens"] == [101, 102]
        assert 0 < res["body"]["timeout"] <= 30
        # the dying donor still serves the prefix over the KV wire
        assert b.seen_kv_peers[-1] == f"127.0.0.1:{a.port}"
        spans = [s for s in fleet.fleet_snapshot()["spans"]
                 if s["phase"] == "rt_resume"]
        assert spans and spans[-1]["resume_from"] == 2
        assert spans[-1]["replica"] == f"127.0.0.1:{b.port}"
        for k in ("detect_ms", "redispatch_ms", "first_token_ms"):
            assert spans[-1][k] >= 0
    finally:
        close()
        a.kill(), b.kill()


def test_upstream_error_chunk_is_resumed_not_relayed():
    """The third death signal: a killed api-server's handler threads
    outlive the process shutdown and write a terminal finish_reason
    "error" chunk over a cleanly-FINed socket. On a stamped stream the
    router holds that chunk back, treats it as mid-stream death, and
    splices a continuation — the client never sees the error."""
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
        s.behavior["stream_chunks"] = ["x1 ", "x2 ", "x3 ", "x4"]
    a.behavior["error_after_chunks"] = 2
    b.behavior["queue_depth"] = 50
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50,
              what="probes: up + load")
        t0 = _resume_totals()
        with _post(url, _body("heal the error", stream=True),
                   timeout=30) as r:
            raw = r.read()
        events = _sse_events(raw)
        assert _stamp_indices(events) == [0, 1, 2, 3, 4]
        reasons = [e["choices"][0].get("finish_reason") for e in events
                   if isinstance(e, dict) and e.get("choices")]
        assert "error" not in reasons and reasons[-1] == "length"
        assert b'"upstream_error"' not in raw
        assert _resume_totals()["resumed"] == t0["resumed"] + 1
    finally:
        close()
        a.kill(), b.kill()


def test_resume_budget_exhausted_ends_with_terminal_502():
    """Per-attempt + terminal accounting: the resume target dies too —
    its splice counts \"resumed\" (a continued token reached the
    client), the next death finds the --max-stream-resumes budget spent
    (\"exhausted\") and the stream ends with the explicit terminal 502
    event + [DONE], everything delivered so far intact."""
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
        s.behavior["stream_chunks"] = ["y1 ", "y2 ", "y3 ", "y4 ", "y5"]
        s.behavior["die_after_chunks"] = 2
    b.behavior["queue_depth"] = 50
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    http = tm.registry().counter(tm.HTTP_REQUESTS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50,
              what="probes: up + load")
        t0 = _resume_totals()
        c0 = http.total(route="/v1/chat/completions", status="502")
        with _post(url, _body("doubly doomed", stream=True),
                   timeout=30) as r:
            raw = r.read()
        events = _sse_events(raw)
        # a delivered 1,2; b replayed 2 (dropped) and delivered 3, then
        # died — the transcript stays gapless and duplicate-free
        assert _stamp_indices(events) == [0, 1, 2, 3]
        assert b'"upstream_error"' in raw and b'"code": 502' in raw
        assert events[-1] == "[DONE]"
        d = {k: v - t0[k] for k, v in _resume_totals().items()}
        assert d == {"resumed": 1, "exhausted": 1, "no_budget": 0,
                     "failed": 0}
        assert http.total(route="/v1/chat/completions",
                          status="502") == c0 + 1
    finally:
        close()
        a.kill(), b.kill()


def test_max_stream_resumes_zero_keeps_legacy_contract():
    """--max-stream-resumes 0 is the pre-failover behavior: the death is
    classified (\"exhausted\") and the stream ends with the terminal 502
    event immediately — no re-dispatch ever leaves the router."""
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
    a.behavior["die_after_chunks"] = 1
    b.behavior["queue_depth"] = 50
    a.start(), b.start()
    url, fleet, close = make_router([a, b], max_stream_resumes=0)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50,
              what="probes: up + load")
        t0 = _resume_totals()
        n_b0 = b.n_completions
        with _post(url, _body("no budget at all", stream=True),
                   timeout=30) as r:
            raw = r.read()
        assert b'"upstream_error"' in raw
        assert raw.rstrip().endswith(b"data: [DONE]")
        d = {k: v - t0[k] for k, v in _resume_totals().items()}
        assert d == {"resumed": 0, "exhausted": 1, "no_budget": 0,
                     "failed": 0}
        assert b.n_completions == n_b0  # nothing was re-dispatched
    finally:
        close()
        a.kill(), b.kill()


def test_resume_outside_request_timeout_is_no_budget():
    """A spliced continuation must fit inside the remaining
    --request-timeout budget: with the deadline already burned at
    detection time the outcome is \"no_budget\" and the stream ends
    with the terminal 502, not a hopeless re-dispatch."""
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
    a.behavior["die_after_chunks"] = 1
    b.behavior["queue_depth"] = 50
    a.start(), b.start()
    url, fleet, close = make_router([a, b], request_timeout_s=0.04)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50,
              what="probes: up + load")
        t0 = _resume_totals()
        with _post(url, _body("late already", stream=True),
                   timeout=30) as r:
            raw = r.read()
        assert b'"upstream_error"' in raw
        d = {k: v - t0[k] for k, v in _resume_totals().items()}
        assert d == {"resumed": 0, "exhausted": 0, "no_budget": 1,
                     "failed": 0}
    finally:
        close()
        a.kill(), b.kill()


# -- the ISSUE-12 chaos acceptance test --------------------------------------


def test_fleet_survives_replica_kill_and_restart_under_traffic():
    """3 replicas, continuous mixed traffic, one replica killed mid-run:
    every request that had not yet streamed a byte completes via retry
    on a survivor (zero silent failures; retries visible in
    dllama_router_retries_total), mid-stream victims get the explicit
    terminal 502 event, and after the restart the circuit breaker
    re-admits the replica and dispatch returns to all 3 — all
    telemetry-asserted."""
    stubs = [StubReplica(f"r{i}") for i in range(3)]
    for s in stubs:
        s.behavior["stream_chunks"] = ["a", "b", "c", "d"]
        s.behavior["chunk_delay_s"] = 0.01
        s.start()
    url, fleet, close = make_router(stubs)
    reg = tm.registry()
    retries = reg.counter(tm.ROUTER_RETRIES)
    ejects = reg.counter(tm.ROUTER_EJECTS)
    readmits = reg.counter(tm.ROUTER_READMITS)
    dispatch = reg.counter(tm.ROUTER_DISPATCHES)
    victim = stubs[1]
    vname = f"127.0.0.1:{victim.port}"
    r0, e0, ra0 = (retries.total(), ejects.total(replica=vname),
                   readmits.total(replica=vname))
    outcomes: list = []  # ("ok"|"midstream_502"|"silent"|..., detail)
    out_lock = threading.Lock()
    stop = threading.Event()

    def traffic(i):
        n = 0
        while not stop.is_set():
            n += 1
            stream = (i + n) % 2 == 0
            try:
                with _post(url, _body(f"t{i}-{n}", stream=stream),
                           timeout=30) as r:
                    raw = r.read()
                if not stream:
                    ok = r.status == 200 and b'"usage"' in raw
                    rec = ("ok" if ok else "silent", raw[:120])
                elif b'"upstream_error"' in raw:
                    rec = ("midstream_502", raw[-200:])
                elif b"[DONE]" in raw:
                    rec = ("ok", b"")
                else:
                    rec = ("silent", raw[:120])
            except urllib.error.HTTPError as e:
                rec = (f"http_{e.code}", e.read()[:120])
            except Exception as e:  # noqa: BLE001 — recorded, asserted below
                rec = ("silent", repr(e)[:120])
            with out_lock:
                outcomes.append(rec)
            time.sleep(0.01)

    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="all 3 replicas up")
        threads = [threading.Thread(target=traffic, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)  # steady traffic over all three
        # mid-run kill: streams in flight on the victim die with an RST
        # mid-chunk; new connections are refused
        victim.behavior["die_after_chunks"] = 1
        time.sleep(0.1)
        victim.kill()
        _wait(lambda: ejects.total(replica=vname) == e0 + 1,
              what="victim ejection", timeout=15)
        time.sleep(0.4)  # traffic continues on the 2 survivors
        victim.behavior["die_after_chunks"] = None
        victim.start()
        _wait(lambda: readmits.total(replica=vname) == ra0 + 1,
              what="victim re-admission", timeout=15)
        d_back = dispatch.total(replica=vname)
        time.sleep(0.5)  # dispatch spreads back over all 3
        stop.set()
        for t in threads:
            t.join(timeout=30)

        silent = [o for o in outcomes if o[0] == "silent"]
        assert not silent, silent[:3]
        errors = [o for o in outcomes if o[0].startswith("http_")]
        assert not errors, errors[:3]  # retries absorbed every pre-byte death
        n_ok = sum(1 for o in outcomes if o[0] == "ok")
        assert n_ok >= 20, f"only {n_ok} completions of {len(outcomes)}"
        # the kill was actually felt: pre-byte deaths were retried ...
        assert retries.total() > r0
        # ... and the re-admitted replica serves again
        assert dispatch.total(replica=vname) > d_back
        assert _up(fleet, vname) == 1
    finally:
        stop.set()
        close()
        for s in stubs:
            if s.httpd is not None:
                s.kill()


# -- fleet tracing + SLO observatory ------------------------------------------


def _post_raw(url, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=timeout)


def test_fleet_rid_minted_forwarded_and_echoed():
    """The trace-identity contract: the router mints (or accepts a
    sanitary) X-Dllama-Request-Id, forwards it with a hop index, and
    echoes it on the response."""
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router([a])
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        # no client id: router mints one, forwards it at hop 0, echoes
        with _post(url, _body("mint me")) as r:
            rid = r.headers["X-Dllama-Request-Id"]
        assert rid and a.seen_fleet[-1] == (rid, 0)
        # a sanitary client id is honored end to end
        with _post_raw(url, _body("keep me"),
                       headers={"X-Dllama-Request-Id": "client.id-7"}) as r:
            assert r.headers["X-Dllama-Request-Id"] == "client.id-7"
        assert a.seen_fleet[-1] == ("client.id-7", 0)
        # an unsanitary id is replaced, never trusted
        with _post_raw(url, _body("spoof me"),
                       headers={"X-Dllama-Request-Id": "bad id!{}"}) as r:
            rid = r.headers["X-Dllama-Request-Id"]
        assert rid != "bad id!{}" and rid.startswith("r")
        assert a.seen_fleet[-1] == (rid, 0)
    finally:
        close()
        a.kill()


def test_retry_carries_hop_index_to_replica():
    """ISSUE-16 satellite: a retried request is visible AT THE REPLICA —
    the serving hop arrives with X-Dllama-Hop: 1 under the same fleet
    id, and dllama_router_retry_hops_total counts both hops."""
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    hops = tm.registry().counter(tm.ROUTER_RETRY_HOPS)
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        h0, h1 = hops.total(hop="0"), hops.total(hop="1")
        fp.arm("proxy", "conn_reset", times=1)
        with _post(url, _body("retry with id")) as r:
            rid = r.headers["X-Dllama-Request-Id"]
        assert hops.total(hop="0") == h0 + 1
        assert hops.total(hop="1") == h1 + 1
        # the hop that actually served carries index 1 — the replica's
        # flight dump can name which attempt it was
        served = [s for s in (a, b) if (rid, 1) in s.seen_fleet]
        assert len(served) == 1
        # the retry/TTFT/connect histograms populated on /metrics
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "dllama_router_ttft_ms_bucket" in text
        assert "dllama_router_connect_ms_bucket" in text
        assert "dllama_router_retry_ms_count 1" in text \
            or "dllama_router_retry_ms_count" in text
        assert 'dllama_router_retry_hops_total{hop="1"}' in text
    finally:
        close()
        a.kill(), b.kill()


def test_fleet_timeline_joins_chaos_run(tmp_path):
    """ISSUE-16 satellite: a 3-replica run with a mid-run kill/restart
    joins into ONE strictly-valid Chrome trace — every completed
    request id in exactly one flow, a pre-byte-retried request's flow
    crossing two replica tracks, no orphaned replica spans — and the
    same join runs offline through the fleettrace CLI."""
    from dllama_tpu.runtime import flightrec
    from dllama_tpu.serve.cli import main as cli_main

    stubs = [StubReplica(f"r{i}") for i in range(3)]
    for s in stubs:
        s.start()
    url, fleet, close = make_router(stubs)
    completed: list = []
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="all 3 replicas up")

        def go(prompt, stream=False):
            with _post(url, _body(prompt, stream=stream)) as r:
                raw = r.read()
                assert (b"[DONE]" in raw) if stream else (b"usage" in raw)
                completed.append(r.headers["X-Dllama-Request-Id"])

        for i in range(6):           # steady phase, mixed traffic
            go(f"steady-{i}", stream=i % 2 == 0)
        # churn phase 1: r0 answers then dies mid-body — the router
        # retries pre-first-byte, so the SAME fleet id lands on two
        # replica tracks
        stubs[0].behavior["truncate_nonstream"] = True
        retries = tm.registry().counter(tm.ROUTER_RETRIES)
        r0 = retries.total()
        for i in range(4):
            go(f"churn-{i}")
        assert retries.total() > r0
        stubs[0].behavior["truncate_nonstream"] = False
        # churn phase 2: hard kill + restart under sequential traffic
        victim = stubs[1]
        vname = f"127.0.0.1:{victim.port}"
        victim.kill()
        _wait(lambda: _up(fleet, vname) == 0, what="victim ejected",
              timeout=15)
        for i in range(3):
            go(f"post-kill-{i}")
        victim.start()
        _wait(lambda: _up(fleet, vname) == 1, what="victim re-admitted",
              timeout=15)
        for i in range(3):
            go(f"post-restart-{i}", stream=True)

        with urllib.request.urlopen(url + "/debug/fleet/timeline",
                                    timeout=10) as r:
            trace = json.loads(r.read())
        assert flightrec.validate_chrome_trace(
            trace, expect_rids=completed) == []
        evs = trace["traceEvents"]
        # every completed request id: exactly one flow (one "s" start)
        starts: dict = {}
        for e in evs:
            if e.get("cat") == "fleet" and e["ph"] == "s":
                starts[e["id"]] = starts.get(e["id"], 0) + 1
        for rid in completed:
            assert starts.get(rid) == 1, rid
        # the retried ids cross two replica tracks (two distinct pids>1)
        repl_pids: dict = {}
        for e in evs:
            if e.get("ph") == "X" and e.get("cat") == "replica":
                repl_pids.setdefault(
                    e["args"]["request_id"], set()).add(e["pid"])
        assert any(len(pids) >= 2 for pids in repl_pids.values())
        # no orphaned replica spans: all traffic came via the router
        assert trace["fleetJoin"]["unjoined_replica_spans"] == 0
        assert trace["fleetJoin"]["joined"] >= len(set(completed))
        # router track present with the full phase story
        phases = {e["args"]["phase"] for e in evs
                  if e.get("ph") == "X" and e.get("cat") == "router"}
        assert {"rt_queue", "rt_dispatch", "rt_connect", "rt_first_byte",
                "rt_stream", "rt_retry"} <= phases

        # -- offline joiner over saved dumps ------------------------------
        with urllib.request.urlopen(url + "/debug/fleet",
                                    timeout=10) as r:
            (tmp_path / "fleet.json").write_bytes(r.read())
        args = ["fleettrace", "--router-dump",
                str(tmp_path / "fleet.json"),
                "--out", str(tmp_path / "trace.json")]
        for s in stubs:
            with urllib.request.urlopen(s.url + "/debug/flight",
                                        timeout=10) as r:
                (tmp_path / f"{s.name}.json").write_bytes(r.read())
            args += ["--replica-dump",
                     f"{s.name}={tmp_path / f'{s.name}.json'}"]
        assert cli_main(args) == 0
        offline = json.loads((tmp_path / "trace.json").read_text())
        assert flightrec.validate_chrome_trace(
            offline, expect_rids=completed) == []
        assert offline["fleetJoin"]["joined"] >= len(set(completed))
    finally:
        close()
        for s in stubs:
            if s.httpd is not None:
                s.kill()


def test_fleettrace_cli_rejects_malformed_and_unjoinable(tmp_path):
    from dllama_tpu.serve.cli import main as cli_main

    # malformed: not JSON at all
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert cli_main(["fleettrace", "--router-dump", str(bad)]) == 1
    # malformed: spans that are not span-shaped
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps({"spans": [{"wrong": 1}]}))
    assert cli_main(["fleettrace", "--router-dump", str(broken)]) == 1
    # unjoinable: router saw requests, replica dump shares none of them
    router_dump = tmp_path / "router.json"
    router_dump.write_text(json.dumps({"spans": [
        {"request_id": "r1-1", "phase": "rt_queue",
         "start_ns": 1000, "end_ns": 2000}]}))
    replica_dump = tmp_path / "replica.json"
    replica_dump.write_text(json.dumps(
        {"ticks": [], "events": [], "spans": []}))
    assert cli_main(["fleettrace", "--router-dump", str(router_dump),
                     "--replica-dump", f"r0={replica_dump}"]) == 1
    # the same dumps WITH a joining replica span succeed
    replica_dump.write_text(json.dumps({"ticks": [], "events": [], "spans": [
        {"request_id": 5, "phase": "decode", "start_ns": 1200,
         "end_ns": 1800, "slot": 0, "fleet": "r1-1", "hop": 0}]}))
    out = tmp_path / "ok.json"
    assert cli_main(["fleettrace", "--router-dump", str(router_dump),
                     "--replica-dump", f"r0={replica_dump}",
                     "--out", str(out)]) == 0
    assert json.loads(out.read_text())["fleetJoin"]["joined"] == 1


def test_debug_slo_endpoint_and_metrics():
    """--slo objectives evaluated from router-measured observations:
    /debug/slo compliance + burn, gauges on /metrics, 404 without
    --slo."""
    a = StubReplica("a")
    a.behavior["chunk_delay_s"] = 0.01
    a.start()
    url, fleet, close = make_router(
        [a], slo_objectives={"ttft_p95_ms": 60000.0, "itl_p50_ms": 60000.0,
                             "shed_rate": 0.9})
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        for i in range(3):
            with _post(url, _body(f"slo-{i}", stream=i % 2 == 0)) as r:
                r.read()
        with urllib.request.urlopen(url + "/debug/slo", timeout=10) as r:
            body = json.loads(r.read())
        assert body["windows"] == ["5m", "1h"]
        objs = body["objectives"]
        assert set(objs) == {"ttft_p95_ms", "itl_p50_ms", "shed_rate"}
        assert objs["ttft_p95_ms"]["n"] >= 3
        assert objs["ttft_p95_ms"]["compliant"]      # loose threshold
        assert objs["itl_p50_ms"]["n"] >= 1          # SSE chunk gaps
        assert objs["shed_rate"]["estimate"] == 0.0
        assert all(b == 0.0 for b in objs["ttft_p95_ms"]["burn"].values())
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert 'dllama_slo_compliance{objective="ttft_p95_ms"} 1' in text
        assert 'dllama_slo_burn_rate{objective="shed_rate",window="5m"}' \
            in text
    finally:
        close()
        a.kill()


def test_debug_slo_404_without_objectives():
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router([a])
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/debug/slo", timeout=10)
        assert e.value.code == 404
    finally:
        close()
        a.kill()


def test_shed_feeds_slo_outcome():
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router(
        [a], slo_objectives={"shed_rate": 0.25})
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        with _post(url, _body("admitted one")) as r:
            r.read()
        # the admitted outcome is fed after the response is written, so
        # the client can get here first — wait for it to land
        _wait(lambda: fleet.slo.evaluate()
              ["objectives"]["shed_rate"]["n"] >= 1,
              what="admitted outcome observed")
        a.behavior.update(ready=False, ready_code="queue_full")
        _wait(lambda: not fleet.readiness()[0], what="fleet saturated")
        for _ in range(3):
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(url, _body("shed me"))
            assert e.value.code == 429
        body = json.loads(urllib.request.urlopen(
            url + "/debug/slo", timeout=10).read())
        rec = body["objectives"]["shed_rate"]
        assert rec["n"] == 4 and rec["estimate"] == pytest.approx(0.75)
        assert not rec["compliant"]         # 75% shed vs a 25% budget
        assert rec["burn"]["5m"] == pytest.approx(0.75 / 0.25)
    finally:
        close()
        a.kill()


# -- end-to-end against a real engine ----------------------------------------


def test_router_fronts_real_engine_replica(tmp_path):
    """One real tiny CPU-mesh api-server behind the router: a chat
    completion through the router matches direct access (content +
    usage; ids/timestamps differ by design)."""
    import numpy as np
    from http.server import HTTPServer

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.serve.api import ApiState, make_handler

    from helpers import (byte_vocab_tokenizer, tiny_header_params,
                         write_tiny_model)

    mpath, tpath = tmp_path / "m.m", tmp_path / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(9))
    td = byte_vocab_tokenizer()
    td.chat_template = "<|start_header_id|>"
    tfile.write_tfile(tpath, td)
    engine = InferenceEngine(str(mpath), str(tpath), temperature=0.0, seed=3)
    state = ApiState(engine)
    httpd = HTTPServer(("127.0.0.1", 0), make_handler(state))
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url, fleet, close = make_router([_FakeStub(port)])
    try:
        _wait(lambda: fleet.readiness()[0], what="engine replica up",
              timeout=30)
        body = {"messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 6, "temperature": 0}
        # the replica is ONE thread: while it serves a completion (whose
        # first call compiles, seconds on a loaded machine) the router's
        # health probes go unanswered and it is ejected until the next
        # probe after the answer. So each routed call waits for the EVENT
        # "the router sees the replica up again", not for a margin of
        # wall time that six workers on one machine can outlast.
        def ready():
            _wait(lambda: fleet.readiness()[0], what="engine replica up",
                  timeout=120)

        with _post(f"http://127.0.0.1:{port}", body, timeout=300) as r:
            direct = json.loads(r.read())
        ready()
        with _post(url, body, timeout=300) as r:
            routed = json.loads(r.read())
        assert routed["choices"] == direct["choices"]
        assert routed["usage"] == direct["usage"]
        # and the streaming path relays the real SSE stream
        ready()
        with _post(url, dict(body, stream=True), timeout=300) as r:
            raw = r.read().decode()
        assert "data: [DONE]" in raw
        ready()
        # trace identity reaches the REAL replica: a completion routed
        # with a client-chosen id lands in the api server's flight dump
        # as a fleet_rid binding with the serving hop, its span ring
        # records carry the fleet id, and the opt-in timing block names
        # the request by the same id
        req = urllib.request.Request(
            url + "/v1/chat/completions",
            data=json.dumps(dict(body, timing=True)).encode(),
            headers={"Content-Type": "application/json",
                     "X-Dllama-Request-Id": "e2e.trace-1"})
        with urllib.request.urlopen(req, timeout=300) as r:
            assert r.headers["X-Dllama-Request-Id"] == "e2e.trace-1"
            timed = json.loads(r.read())
        assert timed["timing"]["request_id"] == "e2e.trace-1"
        assert timed["timing"]["hop"] == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/flight", timeout=30) as r:
            flight = json.loads(r.read())
        binds = [ev for ev in flight["events"]
                 if ev.get("event") == "fleet_rid"
                 and ev.get("reason") == "e2e.trace-1"]
        assert len(binds) == 1 and binds[0]["hop"] == 0
        fleet_spans = [s for s in flight["spans"]
                       if s.get("fleet") == "e2e.trace-1"]
        assert fleet_spans and all(s["hop"] == 0 for s in fleet_spans)
    finally:
        close()
        httpd.shutdown()
        httpd.server_close()
        engine.close()


class _FakeStub:
    """Adapter so make_router can front an arbitrary local port."""

    def __init__(self, port):
        self.port = port

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"


# -- tenant observatory -------------------------------------------------------


def test_tenant_header_echoed_forwarded_and_sanitized():
    """The tenant-identity contract at the router tier: a sanitary
    X-Dllama-Tenant is forwarded to the replica and echoed on the
    response; a malformed one collapses to "anon"; no header is "anon"
    too — the router never invents or trusts unsanitary identity."""
    from dllama_tpu.runtime import tenancy

    tenancy.reset()
    a = StubReplica("a")
    a.start()
    url, fleet, close = make_router([a])
    try:
        _wait(lambda: fleet.readiness()[0], what="replica up")
        with _post_raw(url, _body("bill me"),
                       headers={"X-Dllama-Tenant": "acme"}) as r:
            assert r.headers["X-Dllama-Tenant"] == "acme"
        assert a.seen_tenants[-1] == "acme"
        # malformed id: never forwarded verbatim — collapses to anon
        with _post_raw(url, _body("spoof me"),
                       headers={"X-Dllama-Tenant": "no spaces!{}"}) as r:
            assert r.headers["X-Dllama-Tenant"] == "anon"
        assert a.seen_tenants[-1] == "anon"
        # absent header: anon, still forwarded so the replica bills it
        with _post(url, _body("nameless")) as r:
            assert r.headers["X-Dllama-Tenant"] == "anon"
        assert a.seen_tenants[-1] == "anon"
        # the router's own registry saw both identities
        snap = tenancy.registry().snapshot()
        assert {"acme", "anon"} <= set(snap["tenants"])
    finally:
        close()
        a.kill()
        tenancy.reset()


def test_router_shed_names_tenant_and_reason():
    """A router-tier shed is attributable: the 429 carries the tenant
    echo, dllama_tenant_shed_total counts it under the closed-world
    reason router_queue_full, and the rt_queue span names both."""
    from dllama_tpu.runtime import tenancy

    tenancy.reset()
    a, b = StubReplica("a"), StubReplica("b")
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        for s in (a, b):
            s.behavior.update(ready=False, ready_code="queue_full")
        _wait(lambda: not fleet.readiness()[0], what="fleet saturated")
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(url, _body("shed me"),
                      headers={"X-Dllama-Tenant": "flooder"})
        assert e.value.code == 429
        assert e.value.headers["X-Dllama-Tenant"] == "flooder"
        snap = tenancy.registry().snapshot()
        assert snap["tenants"]["flooder"]["sheds"] \
            == {"router_queue_full": 1}
        shed = tm.registry().counter("dllama_tenant_shed_total")
        assert shed.total(tenant="flooder",
                          reason="router_queue_full") == 1
        spans = [s for s in fleet.fleet_snapshot()["spans"]
                 if s["phase"] == "rt_queue"
                 and s.get("reason") == "router_queue_full"]
        assert spans and spans[-1]["tenant"] == "flooder"
    finally:
        close()
        a.kill(), b.kill()
        tenancy.reset()


def test_stream_resume_carries_originating_tenant():
    """ISSUE-20 satellite: a mid-stream failover continuation must NOT
    land on the resume replica as "anon" — the re-dispatch carries the
    originating tenant so the continuation bills to the caller."""
    from dllama_tpu.runtime import tenancy

    tenancy.reset()
    a, b = StubReplica("a"), StubReplica("b")
    for s in (a, b):
        s.behavior["stamp"] = True
        s.behavior["stream_chunks"] = ["t1 ", "t2 ", "t3 ", "t4 ", "t5"]
    a.behavior["die_after_chunks"] = 2
    b.behavior["queue_depth"] = 50  # first dispatch lands on a
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas)
              and fleet.replicas[1].load_score() >= 50,
              what="probes: up + load")
        req = urllib.request.Request(
            url + "/v1/chat/completions",
            data=json.dumps(_body("durable", stream=True,
                                  timeout=30)).encode(),
            headers={"Content-Type": "application/json",
                     "X-Dllama-Tenant": "acme"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers["X-Dllama-Tenant"] == "acme"
            raw = r.read()
        events = _sse_events(raw)
        assert _stamp_indices(events) == [0, 1, 2, 3, 4, 5]
        # the splice happened, and BOTH hops saw the tenant: the
        # original dispatch on a, the resume re-dispatch on b
        assert {e["replica"] for e in events if isinstance(e, dict)} \
            == {"a", "b"}
        assert a.seen_tenants[-1] == "acme"
        assert b.seen_resumes[-1]["body"]["resume_from"] == 2
        assert b.seen_tenants[-1] == "acme"
    finally:
        close()
        a.kill(), b.kill()
        tenancy.reset()


def test_prefill_warm_carries_originating_tenant():
    """ISSUE-20 satellite: the disaggregation warm-up request the
    router sends to a prefill-role replica carries the caller's tenant
    — warm-up work bills to the tenant who triggered it, not "anon"."""
    from dllama_tpu.runtime import tenancy

    tenancy.reset()
    p, d = StubReplica("p"), StubReplica("d")
    p.start(), d.start()
    p.behavior["role"] = "prefill"
    url, fleet, close = make_router([p, d])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        rep_p = [r for r in fleet.replicas
                 if r.name == f"127.0.0.1:{p.port}"][0]
        _wait(lambda: rep_p.is_prefill(), what="prefill role probed")
        with _post_raw(url, _body("disaggregate me",
                                  session_id="disagg-sess"),
                       headers={"X-Dllama-Tenant": "acme"}) as r:
            assert json.loads(r.read())["replica"] == "d"
        # the warm-up on the prefill replica carried the tenant, and so
        # did the decode dispatch
        assert p.seen_tenants == ["acme"]
        assert d.seen_tenants[-1] == "acme"
    finally:
        close()
        p.kill(), d.kill()
        tenancy.reset()


def test_fleet_tenants_join_sums_replicas():
    """GET /debug/fleet/tenants joins per-replica usage registries:
    numeric totals and shed maps sum per tenant, the fleet Jain index
    covers the summed decode tokens, dead replicas contribute nothing,
    and the router's own registry rides along."""
    from dllama_tpu.runtime import tenancy

    tenancy.reset()
    a, b = StubReplica("a"), StubReplica("b")
    a.behavior["tenants_snapshot"] = {
        "cap": 64, "n_tenants": 2, "overflow_total": 0,
        "tenants": {
            "acme": {"decode_tokens": 300, "prefill_tokens": 40,
                     "sheds": {"queue_full": 2}},
            "zed": {"decode_tokens": 100, "prefill_tokens": 10,
                    "sheds": {}}}}
    b.behavior["tenants_snapshot"] = {
        "cap": 64, "n_tenants": 1, "overflow_total": 0,
        "tenants": {
            "acme": {"decode_tokens": 100, "prefill_tokens": 5,
                     "sheds": {"queue_full": 1,
                               "tenant_rate_budget": 3}}}}
    a.start(), b.start()
    url, fleet, close = make_router([a, b])
    try:
        _wait(lambda: all(_up(fleet, r.name) for r in fleet.replicas),
              what="both replicas up")
        with urllib.request.urlopen(url + "/debug/fleet/tenants",
                                    timeout=10) as r:
            body = json.loads(r.read())
        assert body["replicas_joined"] == 2
        acme = body["tenants"]["acme"]
        assert acme["decode_tokens"] == 400
        assert acme["prefill_tokens"] == 45
        assert acme["sheds"] == {"queue_full": 3, "tenant_rate_budget": 3}
        assert body["tenants"]["zed"]["decode_tokens"] == 100
        # Jain over (400, 100): 500^2 / (2 * 170000) ~= 0.735
        assert abs(body["fleet_jain_index"]
                   - 500 ** 2 / (2 * (400 ** 2 + 100 ** 2))) < 1e-9
        assert body["router"]["cap"] == 64
        # a dead replica contributes nothing, join count says so
        b.kill()
        with urllib.request.urlopen(url + "/debug/fleet/tenants",
                                    timeout=10) as r:
            body = json.loads(r.read())
        assert body["replicas_joined"] == 1
        assert body["tenants"]["acme"]["decode_tokens"] == 300
    finally:
        close()
        a.kill()
        if b.httpd is not None:
            b.kill()
        tenancy.reset()

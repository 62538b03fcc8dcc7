"""GET /metrics end-to-end: a real API server over a tiny model serves one
completion, then the scrape must show non-zero TTFT/ITL histograms, token
counters, occupancy gauges, and the HTTP route counters — plus the JSON 404
for unknown routes (satellite)."""

import json
import threading
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.serve.api import BatchedApiState, make_handler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("metrics_api")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(9)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    td = byte_vocab_tokenizer()
    td.chat_template = "<|start_header_id|>"  # detected as llama3
    tfile.write_tfile(tpath, td)
    engine = InferenceEngine(str(mpath), str(tpath), temperature=0.0, seed=3)
    state = BatchedApiState(engine, n_slots=2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()
    state.close()
    engine.close()


def _scrape(url: str) -> dict[str, float]:
    """Parse the exposition text into {sample_name_with_labels: value}."""
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, value = line.rpartition(" ")
        out[name] = float(value)
    return out


def test_metrics_endpoint_after_one_completion(server):
    req = urllib.request.Request(
        server + "/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hello"}],
                         "max_tokens": 6, "temperature": 0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())
    n_out = out["usage"]["completion_tokens"]
    assert n_out >= 2  # need >= 2 tokens for a non-zero ITL histogram

    samples = _scrape(server)
    # acceptance set: request count, TTFT, ITL, batch + KV occupancy,
    # per-token collective bytes
    assert samples[
        'dllama_http_requests_total{route="/v1/chat/completions",'
        'status="200"}'] >= 1
    assert samples["dllama_ttft_ms_count"] >= 1
    assert samples["dllama_ttft_ms_sum"] > 0
    assert samples["dllama_itl_ms_count"] >= n_out - 1
    assert "dllama_batch_occupancy" in samples
    assert samples["dllama_batch_slots"] == 2
    # the request has retired by scrape time, so pooled KV occupancy is
    # back to 0 (live-rows semantics); the gauge itself must be present
    assert 0.0 <= samples["dllama_kv_occupancy"] <= 1.0
    assert "dllama_collective_sent_kb_per_token" in samples
    assert "dllama_collective_recv_kb_per_token" in samples
    assert "dllama_sync_fraction" in samples
    # token counters
    assert samples["dllama_prompt_tokens_total"] >= 1
    assert samples["dllama_completion_tokens_total"] >= n_out
    assert samples["dllama_batch_tokens_total"] >= n_out
    # serving pipeline counters
    assert samples["dllama_admissions_total"] >= 1
    assert samples["dllama_retires_total"] >= 1
    assert samples["dllama_queue_wait_ms_count"] >= 1
    assert samples["dllama_batch_step_ms_count"] >= 1
    assert samples["dllama_hbm_need_bytes"] > 0
    assert samples["dllama_requests_in_flight"] == 0

    # the scrape itself is counted on the next scrape
    samples2 = _scrape(server)
    assert samples2[
        'dllama_http_requests_total{route="/metrics",status="200"}'] >= 1


def test_metrics_names_all_match_convention(server):
    """Every sample name on the wire derives from a dllama_[a-z0-9_]+
    metric (the contract dlint rule metrics-names lints at the source
    level; digits admitted for format names like q80)."""
    import re

    pat = re.compile(r"^dllama_[a-z0-9_]+(_bucket|_sum|_count)?(\{.*\})?$")
    for name in _scrape(server):
        assert pat.match(name), name


def test_unknown_route_returns_json_404(server):
    for method, path in (("GET", "/nope"), ("GET", "/v1/metrics"),
                         ("POST", "/v1/completions")):
        req = urllib.request.Request(server + path, method=method,
                                     data=b"{}" if method == "POST" else None)
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 404
        assert e.value.headers["Content-Type"] == "application/json"
        body = json.loads(e.value.read())
        assert body["error"] == "not found"
        assert body["path"] == path
        assert "/metrics" in body["routes"]
    # 404s are visible in the route counter under the bounded "other" label
    samples = _scrape(server)
    assert samples['dllama_http_requests_total{route="other",'
                   'status="404"}'] >= 3

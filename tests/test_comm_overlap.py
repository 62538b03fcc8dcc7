"""Overlapped quantized multichip decode (ISSUE 8) — engine-level coverage.

The collective-level invariants (bit-exact chunking, q80 ring == reference
merge, poison site) live in tests/test_qcollectives.py; here the knob is
exercised through the REAL engine on the CPU mesh: token parity against
overlap-off, startup refusals, the compile ledger staying quiet, and the
new collective telemetry family."""

import numpy as np
import pytest

from dllama_tpu.runtime import introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("overlap")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(0x51)
    write_tiny_model(mpath, tiny_header_params(
        dim=256, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=64, vocab_size=268, seq_len=128), rng)
    from dllama_tpu.formats import tfile

    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


def _tokens(model_files, *, overlap, n=12, **kw):
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap=overlap,
                          temperature=0.0, **kw)
    try:
        return eng.generate([1, 5, 9, 13], n, stop_on_eos=False).tokens
    finally:
        eng.close()


def test_auto_resolves_chunks_and_tokens_identical_to_off(model_files):
    """The ISSUE acceptance invariant: on a >=2-device mesh, decode with
    --comm-overlap auto produces tokens IDENTICAL to overlap-off for the
    f32 wire (the ring's rank-order sums replace the GSPMD psum without
    changing what the model emits)."""
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="auto")
    assert eng.cfg.comm_overlap == 4  # dim 256 -> four 64-wide chunks
    eng.close()
    assert _tokens(model_files, overlap="auto") \
        == _tokens(model_files, overlap="off")


def test_chunked_decode_dispatch_rides_the_overlapped_merge(model_files):
    """--decode-chunk fuses K steps into one scan whose body is the same
    T=1 forward — the ring merges trace inside it and the chunked stream
    stays identical to overlap-off."""
    assert _tokens(model_files, overlap="auto", decode_chunk=4) \
        == _tokens(model_files, overlap="off", decode_chunk=4)


def test_explicit_n_needs_tp_and_divisibility(model_files):
    mpath, tpath = model_files
    with pytest.raises(ValueError, match="tensor-parallel"):
        InferenceEngine(mpath, tpath, tp=1, comm_overlap=4)
    with pytest.raises(ValueError, match="does not divide"):
        InferenceEngine(mpath, tpath, tp=2, comm_overlap=7)
    # auto degrades to off on one device instead of refusing
    eng = InferenceEngine(mpath, tpath, tp=1, comm_overlap="auto")
    assert eng.cfg.comm_overlap == 0
    eng.close()


def test_unsupported_combos_refused_at_startup(model_files):
    mpath, tpath = model_files
    with pytest.raises(ValueError, match="--sp"):
        InferenceEngine(mpath, tpath, tp=2, sp=2, comm_overlap=4)
    with pytest.raises(ValueError, match="--pp"):
        InferenceEngine(mpath, tpath, tp=2, pp=2, comm_overlap=4)
    with pytest.raises(ValueError, match="offload"):
        InferenceEngine(mpath, tpath, tp=2, weight_mode="offload",
                        comm_overlap=4)
    with pytest.raises(ValueError, match="off.*auto.*integer"):
        InferenceEngine(mpath, tpath, tp=2, comm_overlap="bananas")


def test_pricing_tracks_per_merge_fallback(tmp_path):
    """A merge whose quantized shard can't split its scale rows falls
    back to the monolithic path at trace time — the bytes counter must
    price THAT merge as the all-reduce it actually is (hidden_dim 96 at
    tp=2 → 48-row shards, not 32-divisible; q_dim 64 still overlaps)."""
    mpath, tpath = tmp_path / "m.m", tmp_path / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=64),
                     np.random.default_rng(5))
    from dllama_tpu.formats import tfile

    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=2, comm_overlap="auto")
    try:
        assert eng.cfg.comm_overlap == 2  # dim 64 -> two 32-wide chunks
        traffic = {(op, w): b for op, w, b in eng._wire_traffic}
        # wo (q_dim 64): overlapped ring; w2 (hidden 96): monolithic
        assert ("ppermute", "f32") in traffic
        assert ("all_reduce", "f32") in traffic
    finally:
        eng.close()


def test_zero_post_steady_compiles_with_overlap_enabled(model_files):
    """The chunked ring is STATIC trace config (cfg.comm_overlap): once the
    program family is warm, further generations must not retrace — the
    continuous-serving requirement every feature in this tree meets."""
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="auto")
    try:
        eng.generate([1, 5, 9, 13], 6, stop_on_eos=False)  # warm
        eng.reset()
        c0 = introspection.ledger().compile_count(eng.introspection_scope)
        eng.generate([2, 6, 8, 12], 6, stop_on_eos=False)
        assert introspection.ledger().compile_count(
            eng.introspection_scope) == c0, \
            "post-steady recompile with --comm-overlap enabled"
    finally:
        eng.close()


def test_collective_bytes_counter_prices_decode_tokens(model_files):
    """dllama_collective_bytes_total{op,wire}: each emitted decode token
    charges the analytic col-split wire bytes fixed at construction
    (qcollectives.wire_traffic_model x 2 merges x n_layers)."""
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="auto")
    try:
        [(op, wire, per_tok)] = eng._wire_traffic
        assert (op, wire) == ("ppermute", "f32")
        # 2 merges/layer x 2 layers x (n-1) x 4 B/value x dim
        assert per_tok == pytest.approx(4 * 1 * 4.0 * 256)
        ctr = tm.registry().counter(tm.COLLECTIVE_BYTES)
        b0 = ctr.total(op=op, wire=wire)
        n = len(eng.generate([1, 5, 9, 13], 8, stop_on_eos=False).tokens)
        assert ctr.total(op=op, wire=wire) == pytest.approx(
            b0 + n * per_tok)
    finally:
        eng.close()


def test_overlap_off_prices_the_gspmd_all_reduce(model_files):
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="off")
    try:
        [(op, wire, per_tok)] = eng._wire_traffic
        assert (op, wire) == ("all_reduce", "f32")
        assert per_tok == pytest.approx(4 * 2 * (2 - 1) / 2 * 4.0 * 256)
    finally:
        eng.close()


def test_measure_split_publishes_exposed_comm_gauge(model_files):
    """dllama_comm_exposed_ms: measure_split's capture classifies the
    EXPOSED collective wall (sync lane time not covered by concurrent
    compute) and publishes it next to the sync fraction. On the CPU
    thunk runtime collectives execute synchronously, so exposure is
    positive whenever the program has collectives at all."""
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="auto")
    try:
        eng.generate([1, 5, 9], 4, stop_on_eos=False)  # warm + position
        split = eng.measure_split()
        assert split.exposed_ms >= 0.0
        assert split.exposed_ms <= split.sync_ms + 1e-9
        g = tm.registry().gauge(tm.COMM_EXPOSED_MS)
        assert g.value() == pytest.approx(split.exposed_ms)
    finally:
        eng.close()


def test_multihost_fingerprint_includes_overlap(model_files):
    """A root/worker --comm-overlap mismatch compiles different programs
    and must be caught by the cluster fingerprint, not a collective
    deadlock. Single-process: just pin the field's presence."""
    mpath, tpath = model_files
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap="auto")
    try:
        assert eng.cfg.comm_overlap == 4  # the value the fingerprint ships
    finally:
        eng.close()


def test_spec_lookup_beyond_overlap_width_refused(model_files):
    """A K+1-wide verify past the overlap width gate would trace the
    monolithic psum while greedy traces the ring — refusing preserves the
    engine's spec≡greedy bit-identity invariant."""
    mpath, tpath = model_files
    with pytest.raises(ValueError, match="spec-lookup"):
        InferenceEngine(mpath, tpath, tp=2, comm_overlap=4, spec_lookup=16)
    # inside the width gate the combo stays legal
    eng = InferenceEngine(mpath, tpath, tp=2, comm_overlap=4, spec_lookup=4)
    eng.close()

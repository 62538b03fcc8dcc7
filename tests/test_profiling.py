"""Eval/Sync split + collective-traffic accounting (runtime.profiling) —
the reference's per-token `Eval ms / Sync ms / Sent kB / Recv kB` metrics
(src/dllama.cpp:59-67, socket counters nn-network.cpp:493-508), re-derived
the TPU way: measured collective device time from a profiler capture, and
exact payload bytes from the compiled HLO."""

import os
import shutil

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.profiling import (TrafficStats, collective_traffic,
                                          split_from_trace, union_span)

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

GOLDEN_XPLANE = os.path.join(os.path.dirname(__file__), "goldens",
                             "synthetic.xplane.pb")


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("prof")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(55)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=48), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


# -- xplane parsing against the checked-in synthetic fixture -----------------
# (regenerate with tools/make_xplane_fixture.py; the expected numbers are
# derived in that script's docstring)


def test_union_span_basics():
    assert union_span([]) == 0
    assert union_span([(0, 10)]) == 10
    assert union_span([(0, 10), (20, 30)]) == 20          # disjoint
    assert union_span([(0, 10), (5, 15)]) == 15           # overlapping
    assert union_span([(0, 10), (2, 8)]) == 10            # nested
    assert union_span([(0, 10), (10, 20)]) == 20          # adjacent
    # unsorted input with a span swallowing everything
    assert union_span([(50, 60), (0, 100), (10, 20)]) == 100


def test_split_from_trace_synthetic_fixture(tmp_path):
    """Known-answer test: two device lanes, nested rendezvous inside an
    all-reduce (must not double-count), compute overlapping sync (counts
    once, as sync), an ExecuteHelper noise event, and a host plane that must
    be ignored — numbers from tools/make_xplane_fixture.py."""
    shutil.copy(GOLDEN_XPLANE, tmp_path / "t.xplane.pb")
    s = split_from_trace(str(tmp_path), n_steps=2)
    assert s.n_lanes == 2
    assert s.n_steps == 2
    assert s.sync_ms == pytest.approx(0.75)
    assert s.eval_ms == pytest.approx(2.0)
    assert s.sync_frac == pytest.approx(0.75 / 2.75)


def test_split_from_trace_nested_dirs_picks_newest(tmp_path):
    """The capture layout nests xplane.pb files under plugins/...; the
    recursive glob must find them."""
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    shutil.copy(GOLDEN_XPLANE, d / "host.xplane.pb")
    s = split_from_trace(str(tmp_path), n_steps=1)
    assert s.n_lanes == 2
    assert s.sync_ms == pytest.approx(1.5)  # n_steps=1: per-lane avg only


def test_split_from_trace_empty_dir_raises(tmp_path):
    with pytest.raises(RuntimeError, match="no xplane.pb"):
        split_from_trace(str(tmp_path), n_steps=1)


def test_split_from_trace_malformed_pb_raises(tmp_path):
    (tmp_path / "bad.xplane.pb").write_bytes(b"\xff\xff\x9c\x01garbage")
    with pytest.raises(RuntimeError, match="malformed xplane trace"):
        split_from_trace(str(tmp_path), n_steps=1)


def test_split_from_trace_no_device_lanes(tmp_path):
    """A structurally valid trace with zero device events (an idle window,
    or the profiler's occasionally-empty first session) yields the zero
    split, not an error — POST /debug/profile depends on this."""
    (tmp_path / "empty.xplane.pb").write_bytes(b"")  # valid: empty XSpace
    s = split_from_trace(str(tmp_path), n_steps=3)
    assert s.n_lanes == 0
    assert s.eval_ms == 0.0 and s.sync_ms == 0.0
    assert s.sync_frac == 0.0


def _xplane_module():
    """The lazily-loaded xplane proto module (shared with the parser so the
    test can synthesize traces in the exact format it reads)."""
    from dllama_tpu.runtime import profiling

    profiling._load_xplane(os.devnull)  # empty file = valid empty XSpace
    return profiling._xplane_pb2


def _write_trace(path, planes):
    """planes: [(plane_name, [(line_name, [(event, start_ps, dur_ps)])])]"""
    pb = _xplane_module()
    xs = pb.XSpace()
    mid = 0
    for pname, lines in planes:
        plane = xs.planes.add()
        plane.name = pname
        for lname, events in lines:
            line = plane.lines.add()
            line.name = lname
            for name, start, dur in events:
                mid += 1
                plane.event_metadata[mid].id = mid
                plane.event_metadata[mid].name = name
                ev = line.events.add()
                ev.metadata_id = mid
                ev.offset_ps = start
                ev.duration_ps = dur
    with open(path, "wb") as f:
        f.write(xs.SerializeToString())


def test_split_lane_family_priority(tmp_path):
    """The thunk-based CPU runtime puts op events on tf_XLAEigen* pools and
    scaffolding on tf_XLATfrtCpuClient* dispatch threads: only ONE family
    may count as device lanes, or the per-lane average is diluted by
    threads that aren't devices."""
    ms = 10 ** 9
    _write_trace(tmp_path / "cpu.xplane.pb", [
        ("/host:CPU", [
            ("python", [("$builtins isinstance", 0, ms)]),
            ("tf_XLAEigen/-111", [("fusion.1", 0, 3 * ms),
                                  ("all-reduce.2", 3 * ms, ms)]),
            ("tf_XLAEigen/-222", [("fusion.1", 0, 3 * ms),
                                  ("all-reduce.2", 3 * ms, ms)]),
            ("tf_XLATfrtCpuClient/-333", [
                ("TfrtCpuExecutable::ExecuteHelper", 0, 5 * ms),
                ("broadcast.9", 0, ms)]),
        ]),
    ])
    s = split_from_trace(str(tmp_path), n_steps=1)
    assert s.n_lanes == 2  # the Eigen pools only, not the client thread
    assert s.sync_ms == pytest.approx(1.0)
    assert s.eval_ms == pytest.approx(3.0)


def test_split_falls_back_to_client_lanes(tmp_path):
    """With no PjRt/Eigen lanes at all, the TfrtCpuClient dispatch threads
    are better than nothing (small thunks can execute inline there)."""
    ms = 10 ** 9
    _write_trace(tmp_path / "cpu.xplane.pb", [
        ("/host:CPU", [
            ("tf_XLATfrtCpuClient/-1", [("dot_fusion.3", 0, 2 * ms),
                                        ("psum.1", 2 * ms, 2 * ms)]),
        ]),
    ])
    s = split_from_trace(str(tmp_path), n_steps=2)
    assert s.n_lanes == 1
    assert s.sync_ms == pytest.approx(1.0)
    assert s.eval_ms == pytest.approx(1.0)


def test_collective_traffic_empty_and_collective_free_hlo():
    assert not collective_traffic("", n_devices=8)
    hlo = "%add.1 = f32[4] add(f32[4] %a, f32[4] %b)"
    tr = collective_traffic(hlo, n_devices=8)
    assert tr.n_collectives == 0 and tr.sent_kb == 0.0 and not tr


def test_capture_serializes_sessions(tmp_path):
    """capture() is THE jax.profiler.trace entry point (CLI --profile, POST
    /debug/profile, measure_eval_sync): a second concurrent session must
    fail fast with CaptureBusyError, not corrupt the active one."""
    from dllama_tpu.runtime import profiling

    assert profiling._capture_lock.acquire(timeout=1)
    try:
        with pytest.raises(profiling.CaptureBusyError):
            with profiling.capture(str(tmp_path)):
                pass
    finally:
        profiling._capture_lock.release()
    # and the lock is released on normal exit: a second session works
    with profiling.capture(str(tmp_path / "a")):
        pass
    with profiling.capture(str(tmp_path / "b")):
        pass


def test_collective_traffic_parses_hlo():
    hlo = """
  %all-reduce.3 = f32[4,1024] all-reduce(f32[4,1024] %x), replica_groups={}
  %ag = bf16[8,256] all-gather(bf16[1,256] %y), dimensions={0}
  %noise = f32[4] add(f32[4] %a, f32[4] %b)
"""
    tr = collective_traffic(hlo, n_devices=8)
    assert tr.n_collectives == 2
    # all-reduce: 2 * payload * 7/8; all-gather: 1 * payload * 7/8
    ar = 2 * (4 * 1024 * 4 / 1024) * 7 / 8
    ag = 1 * (8 * 256 * 2 / 1024) * 7 / 8
    assert tr.sent_kb == pytest.approx(ar + ag)
    assert tr.recv_kb == tr.sent_kb
    assert set(tr.by_kind) == {"all-reduce", "all-gather"}
    assert bool(tr)
    assert not TrafficStats(0.0, 0.0, 0, {})


def test_collective_traffic_async_pairs_and_consumers_count_once():
    """TPU HLO uses all-reduce-start/-done async pairs, and consumers name
    the collective as an operand — exactly one count, from the -start."""
    hlo = """
  %all-reduce-start.1 = (f32[4,1024], f32[4,1024]) all-reduce-start(f32[4,1024] %x), replica_groups={}
  %all-reduce-done.1 = f32[4,1024] all-reduce-done((f32[4,1024], f32[4,1024]) %all-reduce-start.1)
  %copy.2 = f32[4,1024] copy(f32[4,1024] %all-reduce-done.1)
"""
    tr = collective_traffic(hlo, n_devices=8)
    assert tr.n_collectives == 1
    assert tr.sent_kb == pytest.approx(2 * (4 * 1024 * 4 / 1024) * 7 / 8)


@pytest.mark.parametrize("name,label,sync", [
    # a v5e lane names an event with the whole instruction (PR 22)
    ('%psum.22 = f32[1,1,2048]{2,1,0:T(1,128)S(1)} all-reduce(%bitcast.253), '
     'channel_id=1, replica_groups={{0,1,2,3}}', "psum.22 all-reduce", True),
    # a fusion that CONSUMES a collective is not one
    ('%fusion.3 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} fusion(f32[2048,2048]'
     ' %all-reduce.3), kind=kLoop', "fusion.3 fusion", False),
    ('%copy-start = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)}, '
     'u32[]{:S(2)}) copy-start(f32[8,128]{1,0:T(8,128)} %x.1)',
     "copy-start copy-start", False),
    # CPU thunk names pass through
    ("dot.31", "dot.31", False),
    ("Rendezvous", "Rendezvous", True),
])
def test_op_label_reduces_tpu_instruction_names(name, label, sync):
    from dllama_tpu.runtime import profiling

    assert profiling.op_label(name) == label
    assert bool(profiling._SYNC_RE.search(profiling.op_label(name))) is sync


def test_collective_traffic_reads_the_tpu_compilers_layouts():
    """Lines as the v5e compiler writes them (the tp=4 Llama-3.2-1B decode
    step, PR 22): tiled layouts carry parentheses, results can be tuples,
    and the per-layer psums sit in the layer scan's while body."""
    hlo = """
%wide.region_0.5.clone (wide.arg: (s32[], f32[1,1,2048])) -> (s32[], f32[1,1,2048]) {
  %psum.22 = f32[1,1,2048]{2,1,0:T(1,128)S(1)} all-reduce(%bitcast.253), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_2.6, metadata={op_name="jit(greedy_step_guarded)/while/body/closed_call/shard_map/psum"}
}
ENTRY %main.7 (p0: f32[4]) -> f32[4] {
  %while.1 = (s32[], f32[1,1,2048]{2,1,0:T(1,128)}) while(%tuple.1), condition=%cond.3, body=%wide.region_0.5.clone
  %all-reduce.3 = (s32[4]{0:T(128)S(1)}, s32[1]{0:T(128)}) all-reduce(%dynamic-update-slice.8, %bitcast.11), channel_id=6, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.1
  %get-tuple-element.686 = s32[4]{0:T(128)S(1)} get-tuple-element(%all-reduce.3), index=0
}
"""
    tr = collective_traffic(hlo, n_devices=4, loop_multiplier=16)
    assert tr.n_collectives == 16 + 1
    psum = 16 * 2 * (2048 * 4 / 1024) * 3 / 4
    head = 2 * (4 * 4 / 1024) * 3 / 4
    assert tr.by_kind["all-reduce"] == pytest.approx(psum + head)


def test_collective_traffic_replica_groups_and_reduce_scatter():
    """Ring model runs over each op's own replica group, not the global
    device count; reduce-scatter moves (n-1) x its shard-sized result."""
    hlo = """
  %all-reduce.9 = f32[1024] all-reduce(f32[1024] %x), replica_groups={{0,1},{2,3},{4,5},{6,7}}
  %rs.1 = f32[128] reduce-scatter(f32[1024] %y), replica_groups=[1,8]<=[8], dimensions={0}
"""
    tr = collective_traffic(hlo, n_devices=8)
    assert tr.n_collectives == 2
    ar = 2 * (1024 * 4 / 1024) * 1 / 2          # tp-pair group: 2(n-1)/n, n=2
    rs = (128 * 4 / 1024) * 7                   # (n-1) x shard, n=8
    assert tr.by_kind["all-reduce"] == pytest.approx(ar)
    assert tr.by_kind["reduce-scatter"] == pytest.approx(rs)


def test_collective_traffic_while_body_multiplier():
    """Per-layer collectives live inside the layer-scan's while body: one HLO
    instruction, n_layers executions. loop_multiplier scales them; top-level
    collectives (the argmax epilogue) stay at 1."""
    hlo = """
%region_0.5 (arg: (s32[], f32[1,64])) -> (s32[], f32[1,64]) {
  %all-reduce.10 = f32[1,64] all-reduce(%x), replica_groups={}
}
ENTRY %main.42 (p0: f32[1,64]) -> f32[1,64] {
  %w = (s32[], f32[1,64]) while(%init), condition=%cond.2, body=%region_0.5
  %all-gather.3 = f32[1,8] all-gather(%y), replica_groups={}
}
"""
    tr1 = collective_traffic(hlo, n_devices=8, loop_multiplier=1)
    tr32 = collective_traffic(hlo, n_devices=8, loop_multiplier=32)
    ar = 2 * (64 * 4 / 1024) * 7 / 8
    ag = (8 * 4 / 1024) * 7 / 8
    assert tr1.sent_kb == pytest.approx(ar + ag)
    assert tr32.sent_kb == pytest.approx(32 * ar + ag)
    assert tr32.n_collectives == 33


def test_single_device_engine_sync_is_zero(model_files):
    """tp=1: the compiled decode program has no collectives, so the split is
    (eval, 0) by construction and no profiler trace is taken."""
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=7, tp=1, profile_split=True)
    r = e.generate("hello world", 4, stop_on_eos=False)
    assert e.split is not None
    assert e.split.sync_ms == 0.0
    assert e.traffic is not None and not e.traffic
    pred = [s for s in r.steps if s.kind == "pred"]
    assert pred and all(s.sync_ms == 0.0 for s in pred)
    assert all(s.eval_only_ms == s.ms for s in pred)
    # no collectives in ANY program: the prefill split is zero too
    assert e.split_prefill is not None and e.split_prefill.sync_ms == 0.0
    assert all(s.sync_ms == 0.0 for s in r.steps if s.kind == "eval")


def test_tp_engine_measures_collective_split(model_files):
    """tp=2 on the virtual CPU mesh: the compiled program carries psum
    collectives — traffic accounting sees them, and the measured split
    attributes a nonzero share of device time to sync."""
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=7, tp=2, profile_split=True)
    r = e.generate("hello world", 4, stop_on_eos=False)
    assert e.traffic is not None and e.traffic.n_collectives > 0
    assert e.traffic.sent_kb > 0
    assert e.split is not None and e.split.n_lanes >= 1
    assert e.split.sync_ms > 0.0
    assert 0.0 < e.split.sync_frac < 1.0
    pred = [s for s in r.steps if s.kind == "pred"]
    assert pred
    for s in pred:
        assert s.sync_ms is not None and 0.0 < s.sync_ms < s.ms
        assert s.eval_only_ms == pytest.approx(s.ms - s.sync_ms)
    # eval steps carry the PREFILL program's own fraction (per-phase split,
    # VERDICT r4 weak #5) — deterministic for this fixture (a bucket always
    # fits the remaining logical tail)
    assert e.split_prefill is not None and e.split_prefill.n_steps > 0
    ev = [s for s in r.steps if s.kind == "eval"]
    assert ev and all(s.sync_ms is not None and 0.0 <= s.sync_ms < s.ms
                      for s in ev)


def test_generation_unperturbed_by_split_measurement(model_files):
    """The scratch profiling dispatches must not change the transcript."""
    e1 = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                         seed=7, tp=2, profile_split=True)
    r1 = e1.generate("hello world", 6, stop_on_eos=False)
    e2 = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                         seed=7, tp=2)
    r2 = e2.generate("hello world", 6, stop_on_eos=False)
    assert r1.tokens == r2.tokens

"""The step's head and tail by name (PR 40): ``step_upload`` beside
``step_dispatch`` on all five step paths through one helper
(``_GeneratorCore._step_io``), the blocking fetches inside ``step_wait`` as
``dllama.step.fetch`` spans that only a profiler records, and the admission
phases naming the request that caused them. Tiny models on the CPU: nothing
here is a timing claim."""

import glob
import os
import statistics

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import flightrec
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.serving import BatchScheduler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

PROMPTS = ["hello world hello world", "hello", " world hello", "hell"]
# step path -> (engine flags, the fetches of one step in order)
PATHS = {
    "paged_step": ({"kv_block_size": 16}, ["tokens", "nonfinite"]),
    "paged_verify": ({"kv_block_size": 16, "spec_lookup": 3}, ["accepted", "tokens", "nonfinite"]),
    "dense_step": ({}, ["tokens", "nonfinite"]),
    "dense_step_chunk": ({"decode_chunk": 4}, ["tokens", "nonfinite"]),
    "dense_verify": ({"spec_lookup": 3}, ["accepted", "tokens", "nonfinite"]),
}
STEP_PHASES = ["step_upload", "step_dispatch", "step_wait"]


@pytest.fixture(autouse=True)
def _fresh_recorder():
    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("stepspans")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), np.random.default_rng(43))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


@pytest.fixture(scope="module", params=sorted(PATHS))
def path_engine(request, model_files):
    eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, **PATHS[request.param][0])
    yield request.param, eng
    eng.close()


def _submit(engine, sched, prompts, max_tokens=6):
    return [sched.submit(engine.tokenizer.encode(p, is_start=True), max_tokens, stop_on_eos=False)
            for p in prompts]


def _drive(sched, reqs, limit=400):
    for n in range(limit):
        if all(r.done.is_set() for r in reqs):
            assert all(r.error is None for r in reqs), [r.error for r in reqs]
            return n
        sched._tick()
    raise AssertionError("requests did not finish")


def test_the_vocabulary_has_twelve_phases_and_the_fetch_is_not_one():
    assert len(tm.TICK_PHASES) == 12 == len(set(tm.TICK_PHASES))
    at = tm.TICK_PHASES.index("step_upload")
    assert list(tm.TICK_PHASES[at:at + 3]) == STEP_PHASES
    assert not tm.STEP_FETCH_SPAN.startswith(tm.TICK_SPAN + ".")


def test_twelve_phases_tile_the_tick_on_every_step_path(path_engine):
    """Every step path names the same three phases at the same boundaries:
    each stepping tick holds ``step_upload``, ``step_dispatch``, ``step_wait``
    once, in that order, each starting where the one before ended (one clock
    read apart); the ticks use the closed vocabulary and their phases still
    sum to the tick's wall within 5% in the median."""
    name, eng = path_engine
    sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
    try:
        _drive(sched, _submit(eng, sched, PROMPTS))          # warm: the compiles land here
        flightrec.recorder().reset()
        _drive(sched, _submit(eng, sched, PROMPTS[:3]))
        ticks = [t for t in flightrec.recorder().snapshot()["ticks"] if not t.get("open")]
    finally:
        sched.close()
    stepping = [t for t in ticks if "step_wait" in t["phases"]]
    assert len(stepping) >= 4, name
    uncovered = []
    for t in ticks:
        assert set(t["phases"]) <= set(tm.TICK_PHASES), t["phases"]
        wall = (t["t_end_ns"] - t["t_start_ns"]) / 1e6
        total = sum(t["phases"].values())
        assert total <= wall + 1e-6
        uncovered.append(max(0.0, wall - total - 0.1) / wall)
    assert statistics.median(uncovered) <= 0.05, sorted(uncovered)[-5:]
    seams = []
    for t in stepping:
        names = [n for n, _off, _ms in t["phase_spans"]]
        at = names.index("step_upload")
        assert names[at:at + 3] == STEP_PHASES and names.count("step_upload") == 1, names
        assert names[at - 1] == "step_prepare" and names[at + 3] == "emit", names
        (_u, u_off, u_ms), (_d, d_off, d_ms), (_w, w_off, _w_ms) = t["phase_spans"][at:at + 3]
        seams += [d_off - (u_off + u_ms), w_off - (d_off + d_ms)]
    # a loaded machine can preempt the loop just there: the bound is on the median seam
    assert min(seams) >= 0.0 and statistics.median(seams) < 0.05, sorted(seams)[-5:]


def _capture(eng, tmp_path, prompts):
    """A profiler capture around hand-driven ticks: every ``dllama.*`` event
    as (line, name, start_ns, end_ns, stats), and the number of ticks."""
    import jax
    from jax.profiler import ProfileData

    sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
    try:
        _drive(sched, _submit(eng, sched, PROMPTS))          # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            reqs = _submit(eng, sched, prompts)
            n = _drive(sched, reqs)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.close()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    events = [((plane.name, li), ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes for li, line in enumerate(plane.lines)
              for ev in line.events if ev.name.startswith("dllama.")]
    return events, n, [r.rid for r in reqs]


def test_a_capture_holds_the_fetches_and_no_phase_outside_the_vocabulary(path_engine, tmp_path, monkeypatch):
    """Under a profiler: every span named ``dllama.tick.<x>`` is a phase of
    the vocabulary (a nested span under that prefix would be given the idle
    under it twice); each ``step_wait`` holds the step's fetches as
    ``dllama.step.fetch`` spans, in order, each inside it on its own line;
    ``step_upload`` says that it made ONE transfer, of the packed buffer's
    bytes: every field of the step and the poison selector (PR 41)."""
    from dllama_tpu.runtime import steppack

    name, eng = path_engine
    packed, real_pack = [], steppack.pack

    def pack(fields):
        words = real_pack(fields)
        packed.append((len(fields), sum(a.nbytes for a in fields), words.nbytes))
        return words

    monkeypatch.setattr(steppack, "pack", pack)
    events, _n, _rids = _capture(eng, tmp_path, PROMPTS[1:3])
    prefix = tm.TICK_SPAN + "."
    phases = [e for e in events if e[1].startswith(prefix)]
    assert phases and {e[1][len(prefix):] for e in phases} <= set(tm.TICK_PHASES)
    assert {e[1] for e in events} <= ({tm.TICK_SPAN, tm.STEP_FETCH_SPAN, tm.LOOP_GAP_SPAN}
                                      | {prefix + p for p in tm.TICK_PHASES})
    waits = [e for e in phases if e[1] == prefix + "step_wait"]
    fetches = [e for e in events if e[1] == tm.STEP_FETCH_SPAN]
    assert waits and len(fetches) == len(PATHS[name][1]) * len(waits)
    for line, _nm, s, e, _st in waits:
        inside = sorted((f for f in fetches if f[0] == line and s <= f[2] and f[3] <= e), key=lambda f: f[2])
        assert [f[4]["what"] for f in inside] == PATHS[name][1]
        assert all(a[3] <= b[2] for a, b in zip(inside, inside[1:]))        # one after the other
    uploads = [e for e in phases if e[1] == prefix + "step_upload"]
    assert len(uploads) == len(waits)
    n_fields = {"paged_step": 7, "paged_verify": 9, "dense_step": 6, "dense_step_chunk": 6, "dense_verify": 6}[name]
    assert {int(u[4]["arrays"]) for u in uploads} == {1}
    # the paged generator of a dense decoder sends every prefill chunk through the tick program
    # (forward_and_step: the step's fields less its three sampling rows, then the chunk's tokens and position), a layout a bucket,
    # and those with no live row go up outside any step_upload
    ticks = {p for p in packed if p[0] == n_fields - 1} if name == "paged_step" else set()
    assert (name == "paged_step") == bool(ticks)
    steps = set(packed) - ticks
    # one layout a program (the chunk path steps singly too, K times fewer coins)
    assert len(steps) == (2 if name == "dense_step_chunk" else 1)
    assert all(fields == n_fields and words == held > 4 * n_fields for fields, held, words in steps)
    assert all(words == held for _f, held, words in ticks)
    sent = {int(u[4]["bytes"]) for u in uploads}
    assert {w for _f, _h, w in steps} <= sent <= {w for _f, _h, w in packed}
    # the call alone carries no transfer count
    assert all("arrays" not in e[4] for e in phases if e[1] == prefix + "step_dispatch")


def test_admission_spans_name_their_cause(model_files, tmp_path):
    """``admit_begin`` names the requests it admitted, ``prefill_dispatch``
    its request with the chunk's valid tokens and the padded width dispatched,
    ``admit_commit`` its request."""
    eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, kv_block_size=16)
    try:
        prompts = ["alpha beta gamma", "delta"]
        events, n, rids = _capture(eng, tmp_path, prompts)
        lengths = {rid: len(eng.tokenizer.encode(p, is_start=True)) - 1 for rid, p in zip(rids, prompts)}
        seen = set(eng.seen_buckets)
    finally:
        eng.close()
    prefix = tm.TICK_SPAN + "."
    begins = [e[4] for e in events if e[1] == prefix + "admit_begin"]
    assert len(begins) == n and sum(int(b["admitted"]) for b in begins) == 2
    named = [str(b["rids"]).split("/") for b in begins if int(b["admitted"])]
    assert sorted(int(r) for rs in named for r in rs) == sorted(rids)
    assert all("rids" not in b for b in begins if not int(b["admitted"]))
    chunks = [e[4] for e in events if e[1] == prefix + "prefill_dispatch"]
    assert {int(c["rid"]) for c in chunks} == set(rids)
    for rid in rids:
        mine = [c for c in chunks if int(c["rid"]) == rid]
        # what a shared prefix covers (the BOS the warm wave left) is not prefilled again
        assert lengths[rid] - 1 <= sum(int(c["tokens"]) for c in mine) <= lengths[rid]
        assert all(int(c["bucket"]) >= int(c["tokens"]) > 0 and int(c["bucket"]) in seen for c in mine)
    commits = [e[4] for e in events if e[1] == prefix + "admit_commit"]
    assert sorted(int(c["rid"]) for c in commits) == sorted(rids)


def test_the_device_arguments_die_before_the_wait(path_engine, monkeypatch):
    """The uploaded argument (the step's packed words) is the call's
    temporary, as the seven were in one expression: by the first fetch it is
    not alive. Kept until the step returns
    they were freed between two phases, after the last token's ``done`` was
    set, and freeing a device buffer lets another thread take the GIL there (a
    waiting client then stopped a profiler inside the slice's last tick)."""
    import weakref

    import jax

    name, eng = path_engine
    sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
    program = {"paged_step": "_step", "paged_verify": "_verify", "dense_step": "_step",
               "dense_step_chunk": "_steps", "dense_verify": "_verify"}[name]
    real, refs, alive_at_fetch = getattr(sched.gen, program), [], []

    def spy(params, cfg, *args):
        refs[:] = [weakref.ref(a) for a in args if isinstance(a, jax.Array) and a.ndim <= 2]
        return real(params, cfg, *args)

    real_fetch = flightrec.fetch_span

    def fetch(what):
        alive_at_fetch.append(sum(r() is not None for r in refs))
        return real_fetch(what)

    monkeypatch.setattr(sched.gen, program, spy)
    monkeypatch.setattr(flightrec, "fetch_span", fetch)
    try:
        _drive(sched, _submit(eng, sched, PROMPTS[:2]))
    finally:
        sched.close()
    assert len(refs) == 1 and alive_at_fetch and set(alive_at_fetch) == {0}      # the packed words alone


def _series(text: str) -> set[str]:
    """The registry's series by name and labels (values dropped)."""
    return {ln.rsplit(" ", 1)[0] for ln in text.splitlines() if ln and not ln.startswith("#")}


def test_without_a_profiler_the_fetch_spans_leave_no_record(model_files):
    """No profiler: the fetch annotation is a no-op. The flight record holds
    the twelve phases and nothing named after a fetch, the registry no family
    and no series it did not have, and the phase counter exactly the
    vocabulary's series."""
    eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, kv_block_size=16)
    sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
    try:
        _drive(sched, _submit(eng, sched, PROMPTS))
        before = _series(tm.registry().render())
        flightrec.recorder().reset()
        _drive(sched, _submit(eng, sched, PROMPTS))              # the same wave: every series exists already
        snap = flightrec.recorder().snapshot()
        assert _series(tm.registry().render()) == before
    finally:
        sched.close()
        eng.close()
    names = {n for t in snap["ticks"] for n, _off, _ms in t["phase_spans"]}
    assert {"step_upload", "step_dispatch", "step_wait"} <= names <= set(tm.TICK_PHASES)
    assert "fetch" not in str(snap)
    text = tm.registry().render()
    assert sorted(p for p in tm.TICK_PHASES if f'{tm.TICK_PHASE_MS}{{phase="{p}"}}' in text) == sorted(tm.TICK_PHASES)
    assert text.count(tm.TICK_PHASE_MS + "{") == len(tm.TICK_PHASES) + len(tm.LOOP_GAPS)
    assert not any("fetch" in name for name in before)


def test_fetch_span_without_an_annotation_factory_is_a_null_context():
    """``flightrec`` stays importable and usable without jax: with no factory
    installed the fetch span does nothing, and nothing is recorded."""
    saved = flightrec._annotate
    flightrec.set_annotation_factory(None)
    try:
        rec = flightrec.FlightRecorder()
        rec.begin_tick(n_active=1)
        with rec.tick_phase("step_wait"):
            with flightrec.fetch_span("tokens"):
                pass
        rec.note("admit", 1)                 # a tick that decided nothing is dropped
        rec.end_tick()
        assert [s[0] for s in rec.snapshot()["ticks"][-1]["phase_spans"]] == ["step_wait"]
    finally:
        flightrec.set_annotation_factory(saved)

"""Continuous batched serving (runtime/serving.py).

THE correctness property: a request's output is byte-identical to running it
alone on the single-sequence engine — batch composition, admission order, and
slot reuse must be invisible. This extends the node-count-invariance test
philosophy (SURVEY.md §4) to the serving axis the reference doesn't have."""

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.serving import BatchedGenerator, BatchScheduler, Request

from helpers import (byte_vocab_tokenizer, require_pinned_host,
                     tiny_header_params, write_tiny_model)


PATHS = {}


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("serving")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    PATHS["m"], PATHS["t"] = str(mpath), str(tpath)
    return InferenceEngine(str(mpath), str(tpath), tp=1)


def solo(temperature=0.0, seed=7):
    """Fresh single-sequence engine on the same files — the oracle."""
    return InferenceEngine(PATHS["m"], PATHS["t"], tp=1,
                           temperature=temperature, seed=seed)


def test_batched_matches_solo_mixed_greedy_and_sampled(engine):
    """Four concurrent requests — different prompts, lengths, greedy and
    sampled, different seeds — each must equal its solo run."""
    prompts = ["hello world", "hello", " world hello world", "hell"]
    specs = [dict(temperature=0.0, seed=1), dict(temperature=0.8, seed=2),
             dict(temperature=0.0, seed=3), dict(temperature=1.2, seed=4)]
    n = 10

    want = []
    for p, s in zip(prompts, specs):
        e = solo(temperature=s["temperature"], seed=s["seed"])
        want.append(e.generate(p, n, stop_on_eos=False).tokens)

    gen = BatchedGenerator(engine, n_slots=4)
    reqs = []
    for i, (p, s) in enumerate(zip(prompts, specs)):
        ids = engine.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=n, stop_on_eos=False,
                    temperature=s["temperature"], topp=0.9, seed=s["seed"])
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid


def test_batched_slot_reuse_and_staggered_admission(engine):
    """Requests admitted mid-flight into freed slots must still match solo
    runs (stale KV from the previous occupant must be invisible)."""
    n_long, n_short = 12, 4
    want_long = solo().generate("hello world", n_long, stop_on_eos=False).tokens
    want_a = solo(temperature=0.9, seed=9).generate(
        "hello", n_short, stop_on_eos=False).tokens
    want_b = solo(temperature=0.9, seed=9).generate(
        " world", n_short, stop_on_eos=False).tokens

    gen = BatchedGenerator(engine, n_slots=2)
    enc = lambda p: engine.tokenizer.encode(p, is_start=True)
    r_long = Request(rid=0, prompt_ids=enc("hello world"),
                     max_tokens=n_long, stop_on_eos=False)
    r_a = Request(rid=1, prompt_ids=enc("hello"), max_tokens=n_short,
                  stop_on_eos=False, temperature=0.9, seed=9)
    gen.admit(r_long, 0)
    gen.admit(r_a, 1)
    while not r_a.done.is_set():
        gen.step()
    # slot 1 freed mid-run of r_long: admit r_b into it
    r_b = Request(rid=2, prompt_ids=enc(" world"), max_tokens=n_short,
                  stop_on_eos=False, temperature=0.9, seed=9)
    gen.admit(r_b, 1)
    while gen.n_active:
        gen.step()
    assert r_long.tokens == want_long
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_scheduler_queues_beyond_slots(engine):
    """6 requests through 2 slots: all complete, each equals its solo run."""
    sched = BatchScheduler(engine, n_slots=2)
    try:
        prompts = ["hello", " world", "hello world", "hell", "he", " w"]
        n = 5
        want = [solo().generate(p, n, stop_on_eos=False).tokens
                for p in prompts]
        reqs = [sched.submit(engine.tokenizer.encode(p, is_start=True), n,
                             stop_on_eos=False) for p in prompts]
        for r, w in zip(reqs, want):
            assert r.done.wait(timeout=300)
            assert r.error is None
            assert r.tokens == w
    finally:
        sched.close()


def test_scheduler_rejects_oversized_prompt(engine):
    sched = BatchScheduler(engine, n_slots=2)
    try:
        r = sched.submit(list(range(1, 200)), 4)  # > seq_len 96
        assert r.done.wait(timeout=60)
        assert r.error is not None and "seq_len" in r.error
    finally:
        sched.close()


def test_streaming_decoders_are_independent(engine):
    """Interleaved slots must not corrupt each other's UTF-8 streaming."""
    gen = BatchedGenerator(engine, n_slots=2)
    pieces: dict[int, list] = {0: [], 1: []}
    enc = lambda p: engine.tokenizer.encode(p, is_start=True)
    for rid, prompt in ((0, "hello"), (1, " world")):
        r = Request(rid=rid, prompt_ids=enc(prompt), max_tokens=6,
                    stop_on_eos=False,
                    on_token=lambda t, p, rid=rid: pieces[rid].append(p))
        gen.admit(r, rid)
        if rid == 0:
            gen.step()  # stagger so decoders interleave
    while gen.n_active:
        gen.step()
    # every emitted piece decodes through the request's own stream
    for rid in (0, 1):
        assert len([p for p in pieces[rid] if p is not None]) > 0


def test_cancel_retires_slot_next_step(engine):
    """Client-side cancel (stop-string matched in the text layer) frees the
    slot at the next step boundary while other slots continue."""
    gen = BatchedGenerator(engine, n_slots=2)
    enc = lambda p: engine.tokenizer.encode(p, is_start=True)
    r0 = Request(rid=0, prompt_ids=enc("hello"), max_tokens=50,
                 stop_on_eos=False)
    r1 = Request(rid=1, prompt_ids=enc(" world"), max_tokens=6,
                 stop_on_eos=False)
    gen.admit(r0, 0)
    gen.admit(r1, 1)
    gen.step()
    r0.cancel.set()
    gen.step()
    assert r0.done.is_set() and len(r0.tokens) == 1  # no token after cancel
    while gen.n_active:
        gen.step()
    assert len(r1.tokens) == 6  # neighbor unaffected


def test_incremental_prefill_interleaves_with_decode(tmp_path_factory):
    """A long prompt admitted mid-flight must NOT stall active decodes: with
    chunked admission, the active slot emits tokens BETWEEN the newcomer's
    prefill chunks — and both outputs still match their solo runs."""
    d = tmp_path_factory.mktemp("serving_inc")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    # tiny n_batches: the long prompt needs many prefill chunks
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4)
    long_ids = [int(x) for x in np.random.default_rng(3).integers(1, 200, 40)]

    solo_a = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4)
    want_a = solo_a.generate("hello world", 16, stop_on_eos=False).tokens
    solo_b = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4)
    want_b = solo_b.generate(long_ids, 4, stop_on_eos=False).tokens

    gen = BatchedGenerator(eng, n_slots=2)
    r_a = Request(rid=0, prompt_ids=eng.tokenizer.encode("hello world",
                                                         is_start=True),
                  max_tokens=16, stop_on_eos=False)
    gen.admit(r_a, 0)
    gen.step()  # r_a decoding
    a_before = len(r_a.tokens)

    r_b = Request(rid=1, prompt_ids=long_ids, max_tokens=4, stop_on_eos=False)
    adm = gen.begin_admit(r_b, 1)
    interleaved = 0
    while not gen.continue_admit(adm):
        gen.step()  # active slot keeps decoding between prefill chunks
        interleaved += 1
    assert interleaved >= 5  # 39 prompt tokens / 4 per chunk
    assert len(r_a.tokens) > a_before  # r_a made progress during admission
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_batched_under_tp_matches_solo(tmp_path_factory):
    """Batched serving composes with tensor parallelism: tp=4 engine, mixed
    batch, each request equals its solo tp=4 run."""
    d = tmp_path_factory.mktemp("serving_tp")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=4)

    s1 = InferenceEngine(str(mpath), str(tpath), tp=4)
    want_a = s1.generate("hello world", 8, stop_on_eos=False).tokens
    s2 = InferenceEngine(str(mpath), str(tpath), tp=4, temperature=0.8, seed=6)
    want_b = s2.generate("hello", 8, stop_on_eos=False).tokens

    gen = BatchedGenerator(eng, n_slots=2)
    enc = lambda p: eng.tokenizer.encode(p, is_start=True)
    r_a = Request(rid=0, prompt_ids=enc("hello world"), max_tokens=8,
                  stop_on_eos=False)
    r_b = Request(rid=1, prompt_ids=enc("hello"), max_tokens=8,
                  stop_on_eos=False, temperature=0.8, seed=6)
    gen.admit(r_a, 0)
    gen.admit(r_b, 1)
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_batched_speculative_matches_solo_mixed(tmp_path_factory):
    """Speculative batched serving: greedy rows ride verify runs, sampled
    rows keep their one-token/one-coin stream — every request must still be
    byte-identical to its solo (non-spec) run, and the greedy repetitive
    request must show multi-token acceptance (fewer steps than tokens)."""
    d = tmp_path_factory.mktemp("spec_serving")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng_spec = InferenceEngine(str(mpath), str(tpath), tp=1, spec_lookup=3)

    prompts = ["hello hello hello", "hello", " world hello world", "hell"]
    specs = [dict(temperature=0.0, seed=1), dict(temperature=0.8, seed=2),
             dict(temperature=0.0, seed=3), dict(temperature=1.2, seed=4)]
    n = 12
    want = []
    for p, s in zip(prompts, specs):
        e = InferenceEngine(str(mpath), str(tpath), tp=1,
                            temperature=s["temperature"], seed=s["seed"])
        want.append(e.generate(p, n, stop_on_eos=False).tokens)
        e.close()

    gen = BatchedGenerator(eng_spec, n_slots=4)
    assert gen.spec == 3
    reqs = []
    for i, (p, s) in enumerate(zip(prompts, specs)):
        ids = eng_spec.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=n, stop_on_eos=False,
                    temperature=s["temperature"], topp=0.9, seed=s["seed"])
        gen.admit(r, i)
        reqs.append(r)
    steps = steps_r0 = 0
    while gen.n_active:
        gen.step()
        steps += 1
        if not reqs[0].done.is_set():
            steps_r0 = steps
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    # the greedy repetitive request (slot 0) finished in fewer dispatches
    # than tokens — real multi-token acceptance (sampled rows stay 1/step)
    assert steps_r0 + 1 < n, (
        f"no acceptance on the greedy row: {steps_r0 + 1} steps for {n}")
    eng_spec.close()


def test_batched_speculative_under_tp_matches_solo(tmp_path_factory):
    """Speculative batched serving under tensor parallelism: the ragged
    verify dispatch runs inside the tp plan; outputs equal solo tp runs."""
    d = tmp_path_factory.mktemp("spec_tp")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    s1 = InferenceEngine(str(mpath), str(tpath), tp=2)
    want_a = s1.generate("hello hello hello", 10, stop_on_eos=False).tokens
    s1.close()
    s2 = InferenceEngine(str(mpath), str(tpath), tp=2, temperature=0.8, seed=6)
    want_b = s2.generate("hello", 10, stop_on_eos=False).tokens
    s2.close()

    eng = InferenceEngine(str(mpath), str(tpath), tp=2, spec_lookup=3)
    gen = BatchedGenerator(eng, n_slots=2)
    enc = lambda p: eng.tokenizer.encode(p, is_start=True)
    r_a = Request(rid=0, prompt_ids=enc("hello hello hello"), max_tokens=10,
                  stop_on_eos=False)
    r_b = Request(rid=1, prompt_ids=enc("hello"), max_tokens=10,
                  stop_on_eos=False, temperature=0.8, seed=6)
    gen.admit(r_a, 0)
    gen.admit(r_b, 1)
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b
    eng.close()


def test_batched_under_dp_tp_matches_solo(tmp_path_factory):
    """Batched serving with the slot pool SHARDED over a dp axis (dp=2 ×
    tp=2): every request equals its solo unsharded run — mesh invariance
    extended to the serving batch axis."""
    d = tmp_path_factory.mktemp("serving_dp")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    want = []
    for p, s in [("hello world", dict(temperature=0.0, seed=1)),
                 ("hello", dict(temperature=0.8, seed=2)),
                 (" world hello", dict(temperature=0.0, seed=3)),
                 ("hell", dict(temperature=1.2, seed=4))]:
        e = InferenceEngine(str(mpath), str(tpath), tp=1, **s)
        want.append(e.generate(p, 8, stop_on_eos=False).tokens)
        e.close()

    eng = InferenceEngine(str(mpath), str(tpath), dp=2, tp=2)
    gen = BatchedGenerator(eng, n_slots=4)
    reqs = []
    for i, (p, s) in enumerate([
            ("hello world", dict(temperature=0.0, seed=1)),
            ("hello", dict(temperature=0.8, seed=2)),
            (" world hello", dict(temperature=0.0, seed=3)),
            ("hell", dict(temperature=1.2, seed=4))]):
        ids = eng.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=8, stop_on_eos=False,
                    topp=0.9, **s)
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    eng.close()


def test_batched_dp_requires_divisible_slots(tmp_path_factory):
    d = tmp_path_factory.mktemp("serving_dp_bad")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), dp=2, tp=1)
    with pytest.raises(ValueError, match="divide over dp"):
        BatchedGenerator(eng, n_slots=3)
    eng.close()


def test_batched_speculative_near_cap_retires_early(tmp_path_factory):
    """A slot within spec+1 positions of seq_len retires instead of letting
    the K+1-wide cache write clamp and corrupt earlier rows — and every
    dispatch observed the safe bound. The emitted tokens must be a prefix of
    the non-spec run (speculation trades tail capacity, never content)."""
    d = tmp_path_factory.mktemp("spec_cap")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=32),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    prompt = "hello world hello"

    eng0 = InferenceEngine(str(mpath), str(tpath), tp=1)
    want = eng0.generate(prompt, 64, stop_on_eos=False).tokens
    eng0.close()

    eng = InferenceEngine(str(mpath), str(tpath), tp=1, spec_lookup=4)
    gen = BatchedGenerator(eng, n_slots=1)
    ids = eng.tokenizer.encode(prompt, is_start=True)
    r = Request(rid=0, prompt_ids=ids, max_tokens=64, stop_on_eos=False)
    gen.admit(r, 0)
    while gen.n_active:
        before, n_before = int(gen.pos[0]), len(r.tokens)
        gen.step()
        if len(r.tokens) > n_before:
            # a dispatch ran from `before`: its K+1-wide write must have fit
            # under seq_len (the REAL clamp-safety invariant)
            assert before + gen.spec + 1 <= eng.cfg.seq_len, before
    assert r.done.is_set() and len(r.tokens) >= 1
    assert r.tokens == want[: len(r.tokens)]
    eng.close()


def test_batched_spec_rejects_prompt_in_unsafe_zone(tmp_path_factory):
    """Prompts that would leave no room for a single K+1-wide dispatch are
    rejected at admission with a clear error (they would otherwise complete
    silently with zero tokens — review finding)."""
    d = tmp_path_factory.mktemp("spec_rej")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=32),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, spec_lookup=4)
    gen = BatchedGenerator(eng, n_slots=1)
    ids = list(range(1, 30))  # 29 tokens: >= seq_len(32) - spec(4)
    with pytest.raises(ValueError, match="usable context"):
        gen.begin_admit(Request(rid=0, prompt_ids=ids, max_tokens=8), 0)
    eng.close()


def test_cross_slot_prefix_reuse_exact_and_skips_prefill(engine):
    """Batched prefix KV reuse: a request sharing a prompt prefix with a
    previous (even retired) slot skips prefilling that prefix, and its
    output is identical to a solo run — the batched analogue of NaiveCache.
    Only the prefill-built region is matched (decode-built rows are
    excluded; see BatchedGenerator._ctx)."""
    sys_prompt = "hello world hello world "  # shared system prompt

    e1 = solo()
    want_b = e1.generate(sys_prompt + "abc", 8, stop_on_eos=False).tokens
    e1.close()
    e2 = solo(temperature=0.8, seed=5)
    want_c = e2.generate(sys_prompt + "xyz", 8, stop_on_eos=False).tokens
    e2.close()

    gen = BatchedGenerator(engine, n_slots=2)
    enc = lambda p: engine.tokenizer.encode(p, is_start=True)

    r_a = Request(rid=0, prompt_ids=enc(sys_prompt + "abc"), max_tokens=8,
                  stop_on_eos=False)
    gen.admit(r_a, 0)
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_b  # sanity: same request as want_b

    # request B: same prompt — admission must skip the ENTIRE prefix
    ids_b = enc(sys_prompt + "abc")
    adm = gen.begin_admit(Request(rid=1, prompt_ids=ids_b, max_tokens=8,
                                  stop_on_eos=False), 1)
    assert adm.pos == len(ids_b) - 1, "full-prefix reuse expected"
    while not gen.continue_admit(adm):
        pass
    while gen.n_active:
        gen.step()
    assert adm.req.tokens == want_b

    # request C: shares only the system prompt, then diverges (and samples)
    ids_c = enc(sys_prompt + "xyz")
    adm_c = gen.begin_admit(Request(rid=2, prompt_ids=ids_c, max_tokens=8,
                                    stop_on_eos=False, temperature=0.8,
                                    seed=5), 0)
    shared = 0
    for a, b in zip(ids_c[:-1], ids_b[:-1]):
        if a != b:
            break
        shared += 1
    assert adm_c.pos == shared > 4, "partial-prefix reuse expected"
    while not gen.continue_admit(adm_c):
        pass
    while gen.n_active:
        gen.step()
    assert adm_c.req.tokens == want_c


def test_paged_lifecycle_emits_spans_and_debug_requests_timeline(
        tmp_path_factory):
    """ISSUE-7 satellite: the paged lifecycle speaks the span vocabulary —
    admit / prefill_chunk spans per admission (on top of the shared
    queue/prefill/decode spans) — and the /debug/requests timeline payload
    shows them under a continuous-batching run."""
    from dllama_tpu.runtime import telemetry as tm

    d = tmp_path_factory.mktemp("serving_paged_spans")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(43)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=16)
    sched = BatchScheduler(eng, n_slots=2)
    t0 = tm.now_ns()
    try:
        # no prompt a prefix of another: admitted after the first one's
        # blocks are registered, "hello" shares them whole and runs no
        # prefill chunk (a race this test lost one run in two)
        prompts = ["hello world hello", "world", " again hello world"]
        reqs = [sched.submit(eng.tokenizer.encode(p, is_start=True), 4,
                             stop_on_eos=False) for p in prompts]
        for r in reqs:
            assert r.done.wait(timeout=300) and r.error is None
    finally:
        sched.close()
        eng.close()
    # raw ring, filtered to this run (the ring is process-global and
    # request ids restart per scheduler)
    spans = [s for s in tm.tracer().raw_spans() if s["start_ns"] >= t0]
    by_rid = {}
    for s in spans:
        by_rid.setdefault(s["request_id"], set()).add(s["phase"])
    for r in reqs:
        assert {"queue", "admit", "prefill_chunk", "prefill",
                "decode"} <= by_rid[r.rid], (r.rid, by_rid.get(r.rid))
    # every emitted phase is in the documented vocabulary (the lint's
    # runtime twin)
    assert {p for ps in by_rid.values() for p in ps} <= set(tm.PHASES)
    # and the /debug/requests payload (recent_requests) carries the paged
    # phases (the ring is shared process-wide, so assert our rids are
    # present with the new vocabulary rather than exact-matching)
    timelines = {t["request_id"]: t for t in tm.tracer().recent_requests()}
    for r in reqs:
        phases = [p["phase"] for p in timelines[r.rid]["phases"]]
        assert "admit" in phases and "prefill_chunk" in phases
        assert timelines[r.rid]["total_ms"] > 0


def test_batched_serving_on_moe_model(tmp_path_factory):
    """Continuous batching over a Mixture-of-Experts model: the ragged decode
    program rides the sparse MoE ffn (expert dispatch is positionwise, so
    per-row positions don't interact with it) — outputs equal solo runs."""
    d = tmp_path_factory.mktemp("serving_moe")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96,
                                               n_experts=4,
                                               n_active_experts=2),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    want = []
    cases = [("hello world", dict(temperature=0.0, seed=1)),
             ("hello", dict(temperature=0.8, seed=2))]
    for p, s in cases:
        e = InferenceEngine(str(mpath), str(tpath), tp=1, **s)
        want.append(e.generate(p, 8, stop_on_eos=False).tokens)
        e.close()

    eng = InferenceEngine(str(mpath), str(tpath), tp=1)
    gen = BatchedGenerator(eng, n_slots=2)
    reqs = []
    for i, (p, s) in enumerate(cases):
        ids = eng.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=8, stop_on_eos=False,
                    topp=0.9, **s)
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    eng.close()


def test_chunked_batched_matches_solo_mixed(engine):
    """K fused ragged steps per dispatch (step_chunk / models.sampled_steps):
    every request — greedy and sampled, different lengths — must still equal
    its solo single-step run: tokens AND coin streams (VERDICT r3 weak #5,
    the batched-serving host loop; chunking divides host ticks by K)."""
    prompts = ["hello world", "hello", " world hello world", "hell"]
    specs = [dict(temperature=0.0, seed=1), dict(temperature=0.8, seed=2),
             dict(temperature=0.0, seed=3), dict(temperature=1.2, seed=4)]
    n = 12

    want = []
    for p, s in zip(prompts, specs):
        e = solo(temperature=s["temperature"], seed=s["seed"])
        want.append(e.generate(p, n, stop_on_eos=False).tokens)

    gen = BatchedGenerator(engine, n_slots=4)
    reqs = []
    for i, (p, s) in enumerate(zip(prompts, specs)):
        ids = engine.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=n, stop_on_eos=False,
                    temperature=s["temperature"], topp=0.9, seed=s["seed"])
        gen.admit(r, i)
        reqs.append(r)
    ticks = 0
    while gen.n_active:
        gen.step_chunk(4)
        ticks += 1
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    # the chunk actually engaged: 12 tokens in 3 four-wide ticks
    assert ticks == 3


def test_chunked_batched_eos_truncates_and_rng_rewinds(engine):
    """A slot hitting EOS mid-chunk keeps only the prefix through EOS, and a
    sampled request admitted AFTER that still sees the exact coin stream its
    solo run would (the un-kept draws were never committed)."""
    tok = engine.tokenizer
    eos = tok.eos_token_ids[0]
    gen = BatchedGenerator(engine, n_slots=2)

    # greedy request whose max_tokens forces the single-step fallback tail
    ids = tok.encode("hello world", is_start=True)
    r1 = Request(rid=0, prompt_ids=ids, max_tokens=6, stop_on_eos=True,
                 temperature=0.0)
    gen.admit(r1, 0)
    while gen.n_active:
        gen.step_chunk(4)  # 4 + fallback(2): headroom guard takes the tail
    w = solo(temperature=0.0).generate("hello world", 6).tokens
    assert r1.tokens == w

    # sampled request: chunked transcript equals solo
    r2 = Request(rid=1, prompt_ids=tok.encode("hell", is_start=True),
                 max_tokens=8, stop_on_eos=False, temperature=0.9, seed=11)
    gen.admit(r2, 1)
    while gen.n_active:
        gen.step_chunk(4)
    w2 = solo(temperature=0.9, seed=11).generate("hell", 8,
                                                 stop_on_eos=False).tokens
    assert r2.tokens == w2
    assert eos >= 0  # (fixture sanity)


def test_scheduler_uses_chunked_steps(tmp_path_factory):
    """--decode-chunk composes with --batch-slots through the scheduler."""
    d = tmp_path_factory.mktemp("serving-chunk")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(43)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, decode_chunk=4)
    sched = BatchScheduler(eng, n_slots=2)
    try:
        got = sched.generate(eng.tokenizer.encode("hello world", is_start=True),
                             8, temperature=0.0, stop_on_eos=False)
        ref = InferenceEngine(str(mpath), str(tpath), tp=1)
        ids = ref.tokenizer.encode("hello world", is_start=True)
        want = ref.generate(ids, 8, stop_on_eos=False).tokens
        assert got == want
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# composition: batched serving × offload / f8 KV (round-4 matrix closure)
# ---------------------------------------------------------------------------


def test_batched_serving_with_offload_matches_solo(tmp_path_factory):
    """--weight-mode offload (host-DRAM layer streaming) composes with the
    slot pool: the ragged programs pull the same pinned-host stacks the solo
    forward does, so transcripts must match solo offload runs."""
    require_pinned_host()
    d = tmp_path_factory.mktemp("serving-off")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(61)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    ref = InferenceEngine(str(mpath), str(tpath), tp=1,
                          weight_mode="offload", temperature=0.0, seed=7)
    ids = ref.tokenizer.encode("hello world", is_start=True)
    want = ref.generate(ids, 6, stop_on_eos=False).tokens

    eng = InferenceEngine(str(mpath), str(tpath), tp=1,
                          weight_mode="offload", temperature=0.0, seed=7)
    gen = BatchedGenerator(eng, n_slots=2)
    r = Request(rid=0, prompt_ids=ids, max_tokens=6, temperature=0.0,
                stop_on_eos=False)
    gen.admit(r, 0)
    while gen.n_active:
        gen.step()
    assert r.tokens == want


def test_batched_serving_with_f8_kv_runs_and_is_deterministic(
        tmp_path_factory):
    """--kv-dtype f8 composes with the slot pool (the serving cache is
    created at engine.kv_dtype): same request twice -> same tokens."""
    import jax.numpy as jnp

    d = tmp_path_factory.mktemp("serving-f8")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(62)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    eng = InferenceEngine(str(mpath), str(tpath), tp=1, kv_dtype="f8",
                          compute_dtype="bfloat16", temperature=0.0, seed=7)
    gen = BatchedGenerator(eng, n_slots=2)
    assert gen.kv.k.dtype == jnp.float8_e4m3fn
    ids = eng.tokenizer.encode("hello world", is_start=True)
    outs = []
    for slot in (0, 1):
        r = Request(rid=slot, prompt_ids=ids, max_tokens=6,
                    temperature=0.0, stop_on_eos=False)
        gen.admit(r, slot)
        while gen.slots[slot] is not None:
            gen.step()
        outs.append(r.tokens)
    assert outs[0] == outs[1] and len(outs[0]) == 6


def test_batched_under_fast_matches_solo(tmp_path_factory):
    """Serving composes with fast numerics (bf16 engines, mode ``auto``):
    batched transcripts equal solo runs of the same mode, greedy and
    sampled rows mixed."""
    d = tmp_path_factory.mktemp("serving_fast")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(43)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    prompts = ["hello world", "hello", " world"]
    specs = [dict(temperature=0.0, seed=1), dict(temperature=0.8, seed=2),
             dict(temperature=0.0, seed=3)]
    n = 8
    want = []
    for p, s in zip(prompts, specs):
        e = InferenceEngine(str(mpath), str(tpath), tp=1,
                            compute_dtype="bfloat16", **s)
        want.append(e.generate(p, n, stop_on_eos=False).tokens)

    eng = InferenceEngine(str(mpath), str(tpath), tp=1,
                          compute_dtype="bfloat16")
    gen = BatchedGenerator(eng, n_slots=3)
    reqs = []
    for i, (p, s) in enumerate(zip(prompts, specs)):
        ids = eng.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=n, stop_on_eos=False,
                    temperature=s["temperature"], topp=0.9, seed=s["seed"])
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid


def test_batched_under_sp_matches_solo(tmp_path_factory):
    """Batched serving under an sp mesh (ragged per-slot depths through the
    ring/merge attention paths, parallel/ring.py): every request equals its
    solo unsharded run (VERDICT r4 next #6 — sp×ragged was an oracle-only
    hole)."""
    d = tmp_path_factory.mktemp("serving_sp")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(43))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    cases = [("hello world", dict(temperature=0.0, seed=1)),
             ("hello", dict(temperature=0.8, seed=2)),
             (" world", dict(temperature=0.0, seed=3))]
    want = []
    for p, s in cases:
        e = InferenceEngine(str(mpath), str(tpath), tp=1, **s)
        want.append(e.generate(p, 8, stop_on_eos=False).tokens)
        e.close()

    eng = InferenceEngine(str(mpath), str(tpath), sp=2, tp=2)
    gen = BatchedGenerator(eng, n_slots=3)
    reqs = []
    for i, (p, s) in enumerate(cases):
        ids = eng.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=8, stop_on_eos=False,
                    topp=0.9, **s)
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    eng.close()


def test_batched_under_pp_matches_solo(tmp_path_factory):
    """Batched serving under a pp mesh (VERDICT r4 next #7): ragged per-slot
    depths flow through the pipeline stages — both schedules (the GPipe
    microbatch path when the pool divides by pp, the sequential path
    otherwise) — and every request equals its solo unsharded run."""
    d = tmp_path_factory.mktemp("serving_pp")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(44))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    cases = [("hello world", dict(temperature=0.0, seed=1)),
             ("hello", dict(temperature=0.8, seed=2)),
             (" world", dict(temperature=0.0, seed=3)),
             ("hell", dict(temperature=1.2, seed=4))]
    want = []
    for p, s in cases:
        e = InferenceEngine(str(mpath), str(tpath), tp=1, **s)
        want.append(e.generate(p, 8, stop_on_eos=False).tokens)
        e.close()

    eng = InferenceEngine(str(mpath), str(tpath), tp=1, pp=2)
    gen = BatchedGenerator(eng, n_slots=4)  # 4 % pp2 == 0: microbatch path
    reqs = []
    for i, (p, s) in enumerate(cases):
        ids = eng.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=8, stop_on_eos=False,
                    topp=0.9, **s)
        gen.admit(r, i)
        reqs.append(r)
    while gen.n_active:
        gen.step()
    for r, w in zip(reqs, want):
        assert r.tokens == w, r.rid
    eng.close()

    # odd pool (sequential schedule) composed with tp
    eng2 = InferenceEngine(str(mpath), str(tpath), tp=2, pp=2)
    gen2 = BatchedGenerator(eng2, n_slots=3)
    reqs2 = []
    for i, (p, s) in enumerate(cases[:3]):
        ids = eng2.tokenizer.encode(p, is_start=True)
        r = Request(rid=i, prompt_ids=ids, max_tokens=8, stop_on_eos=False,
                    topp=0.9, **s)
        gen2.admit(r, i)
        reqs2.append(r)
    while gen2.n_active:
        gen2.step()
    for r, w in zip(reqs2, want[:3]):
        assert r.tokens == w, r.rid
    eng2.close()

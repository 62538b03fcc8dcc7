"""A step's host arguments as one packed transfer (PR 41): ``steppack`` packs
every 4-byte field of a step as its bit pattern into one int32 vector and the
jitted wrapper takes it apart again, so the model's own step function sees the
arguments it always had, bit for bit. Five step paths: the paged step and
verify, the dense step, step-chunk and verify. Tiny models on the CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dllama_tpu.formats import tfile
from dllama_tpu.formats.mfile import ArchType, RopeType
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime import failpoints as fp
from dllama_tpu.runtime import steppack
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.kvblocks import PagedKVCache
from dllama_tpu.runtime.kvcache import KVCache
from dllama_tpu.runtime.serving import BatchScheduler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

B, K, M, BS = 3, 2, 4, 16          # rows, drafts a row, table width, block size
NAN_PAYLOAD = np.array([0x7FC12345], np.uint32).view(np.float32)[0]
DENORMAL = np.array([0x00000001], np.uint32).view(np.float32)[0]


def _fields(path: str, rng) -> tuple[list, tuple]:
    """One step's host arguments on ``path`` (tokens, positions, the rest; the
    poison selector last) as the generators build them, and the static
    arguments. The floats hold what a cast would lose: -0.0, a denormal, a NaN
    with a payload; the ints a negative."""
    i32, f32 = np.int32, np.float32
    pos = np.array([5, 0, 17], i32)
    temps, topps = np.array([0.0, 0.8, -0.0], f32), np.array([0.9, 0.5, 1.0], f32)
    coins = np.array([0.25, DENORMAL, NAN_PAYLOAD], f32)
    tables = rng.permutation(np.arange(1, 1 + B * M)).astype(i32).reshape(B, M)
    wide = rng.integers(0, 100, (B, K + 1)).astype(i32)
    wide[0, 1] = -7
    poison = f32(0.0)
    if path == "paged_step":
        return [wide[:, :1], pos, tables, temps, topps, coins, poison], ()
    if path == "paged_verify":
        acoins = np.array([[0.1, -0.0], [DENORMAL, 0.7], [NAN_PAYLOAD, 0.3]], f32)
        return [wide, pos, tables, np.array([2, 0, 1], i32), temps, topps, acoins, coins, poison], ()
    if path == "dense_step":
        return [wide[:, :1], pos, temps, topps, coins, poison], ()
    if path == "dense_step_chunk":
        return [wide[:, 0], pos, temps, topps, np.stack([coins, coins[::-1]]), poison], (2,)
    assert path == "dense_verify"
    return [wide, pos, temps, topps, coins, poison], ()


PROGRAMS = {"paged_step": llama.paged_sampled_step_guarded, "paged_verify": llama.paged_verify_step_guarded,
            "dense_step": llama.sampled_step_guarded, "dense_step_chunk": llama.sampled_steps_guarded,
            "dense_verify": llama.ragged_verify_step_guarded}


def _bits(a) -> bytes:
    return np.ascontiguousarray(np.asarray(a)).tobytes()


@pytest.mark.parametrize("path", sorted(PROGRAMS))
def test_every_field_comes_out_with_the_bits_it_went_in_with(path):
    """pack -> one int32 vector of every field end to end -> unpack under jit:
    shapes, dtypes and BIT PATTERNS are the fields' own (a NaN keeps its
    payload, -0.0 its sign, a denormal is not flushed)."""
    fields, _static = _fields(path, np.random.default_rng(1))
    words, layout = steppack.pack(fields), steppack.layout_of(fields)
    assert words.dtype == np.int32 and words.ndim == 1 and words.nbytes == sum(a.nbytes for a in fields)
    assert hash(layout) == hash(steppack.layout_of(fields))           # a static argument
    out = jax.jit(steppack.unpack, static_argnums=1)(jnp.asarray(words), layout)
    assert len(out) == len(fields)
    for a, b in zip(fields, out):
        assert b.shape == a.shape and b.dtype == a.dtype
        assert _bits(b) == _bits(a)
    # a second pack is a fresh buffer: nothing of the first is written again
    again = steppack.pack(fields)
    assert again is not words and not np.shares_memory(again, words)


@pytest.mark.parametrize("bad", [np.zeros(2, np.float64), np.zeros(2, np.int16), np.zeros(2, np.int64), np.bool_(True)])
def test_a_field_of_another_width_is_an_error_not_a_cast(bad):
    with pytest.raises(TypeError, match="4 bytes wide"):
        steppack.pack([np.zeros(2, np.int32), bad])


@pytest.mark.parametrize("path", sorted(PROGRAMS))
def test_the_wrapper_bears_the_programs_name_and_its_own_signature(path):
    """The XLA module is named after the jitted function: the benchmark's
    readers find the step by ``paged_sampled_step``. The signature is the
    wrapper's own (jit resolves static and donated arguments against it)."""
    import inspect

    program = PROGRAMS[path]
    packed = steppack.packed_program(program)
    assert packed.__name__ == program.__name__ and packed.__qualname__ == program.__qualname__
    assert list(inspect.signature(packed).parameters) == ["params", "cfg", "words", "cache", "layout", "static"]
    jitted = steppack.jit_packed_step(program, scope="test-step-pack", name=path, n_static=path == "dense_step_chunk")
    assert jitted.program == path and jitted.__name__ == program.__name__


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                      vocab_size=128, seq_len=M * BS, norm_epsilon=1e-5, rope_theta=10000.0, rope_type=RopeType.LLAMA,
                      compute_dtype="bfloat16")
    return cfg, llama.init_random_params(cfg, quantized=True)


def _cache(path: str, cfg, rng):
    """A cache with something in it, so that a step's reads matter."""
    blank = (PagedKVCache.create(cfg, 1 + B * M, BS, dtype=jnp.bfloat16) if path.startswith("paged")
             else KVCache.create(cfg, batch_size=B, dtype=jnp.bfloat16))
    return jax.tree.map(lambda a: jnp.asarray(rng.standard_normal(a.shape).astype(np.float32) * 0.1, a.dtype), blank)


@pytest.mark.parametrize("poison", [0.0, 1.0], ids=["clean", "poisoned"])
@pytest.mark.parametrize("path", sorted(PROGRAMS))
def test_the_packed_step_is_the_models_own_step(tiny, path, poison):
    """Each of the five step programs through the packed wrapper, as the
    generators jit it, and as the model's own function jitted with its own
    signature: identical tokens (accept counts), ``nonfinite`` rows and cache,
    clean and with the tripwire's poison selector set; and no second trace on
    a second call with other values."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    fields, static = _fields(path, rng)
    fields[-1] = np.float32(poison)
    cache = _cache(path, cfg, rng)
    copy = lambda tree: jax.tree.map(jnp.copy, tree)
    packed = steppack.jit_packed_step(PROGRAMS[path], scope="test-step-pack", name=path, n_static=len(static))
    plain = jax.jit(PROGRAMS[path], static_argnums=(1,) + ((8,) if static else ()), donate_argnums=(4,))
    dev = [jnp.asarray(a) for a in fields]
    want_out, want_cache = plain(params, cfg, dev[0], dev[1], copy(cache), *dev[2:-1], *static, dev[-1])
    got_out, got_cache = packed(params, cfg, jnp.asarray(steppack.pack(fields)), copy(cache),
                                steppack.layout_of(fields), *static)
    for want, got in zip(jax.tree.leaves((want_out, want_cache)), jax.tree.leaves((got_out, got_cache)), strict=True):
        assert got.shape == want.shape and got.dtype == want.dtype and _bits(got) == _bits(want)
    nonfinite = np.asarray(got_out[-1])
    assert nonfinite.shape == (B,) and (nonfinite > 0).all() == bool(poison)
    # other values, the same layout: the one executable
    before = packed._cache_size()
    fields[1] = fields[1] + 1
    packed(params, cfg, jnp.asarray(steppack.pack(fields)), got_cache, steppack.layout_of(fields), *static)
    assert packed._cache_size() == before


# -- through the generators ---------------------------------------------------

# step path -> engine flags (tests/test_step_spans.py's table)
PATHS = {"paged_step": {"kv_block_size": 16}, "paged_verify": {"kv_block_size": 16, "spec_lookup": 3},
         "dense_step": {}, "dense_step_chunk": {"decode_chunk": 4}, "dense_verify": {"spec_lookup": 3}}


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("steppack")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96), np.random.default_rng(43))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


def _drive(sched, reqs, limit=400):
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            return
        sched._tick()
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_logits_failpoint_fails_the_poisoned_row_as_before(model_files, path):
    """The poison selector rides as the packed vector's last word: an armed
    ``logits`` failpoint still poisons one dispatch, the tripwire counts it at
    ``site=batch`` and, under fail-fast, fails that request 503-shaped while
    the next one serves clean."""
    eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, numerics_failfast=True, **PATHS[path])
    sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
    nf, fired = tm.registry().counter(tm.NONFINITE), tm.registry().counter(tm.FAILPOINTS_FIRED)
    b0, f0 = nf.total(site="batch"), fired.total(name="logits")
    ids = eng.tokenizer.encode("hello world hello", is_start=True)
    try:
        fp.arm("logits", "nonfinite", times=1)
        hit = sched.submit(ids, 8, stop_on_eos=False)
        _drive(sched, [hit])
        assert hit.error is not None and "non-finite" in hit.error and "site=batch" in hit.error
        assert hit.server_error
        assert nf.total(site="batch") == b0 + 1 and fired.total(name="logits") == f0 + 1
        ok = sched.submit(ids, 4, stop_on_eos=False)
        _drive(sched, [ok])
        assert ok.error is None and len(ok.tokens) == 4
    finally:
        fp.registry().clear()
        sched.close()
        eng.close()


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_generator_serves_what_the_unpacked_program_serves(model_files, path):
    """Greedy and sampled requests through the scheduler, once with the packed
    programs and once with every step program swapped for the model's own
    function behind its seven-odd arguments: the same tokens, request for
    request. After the warm wave the packed programs compile nothing more."""
    from dllama_tpu.runtime.introspection import ledger

    def step_compiles(eng) -> dict:
        """Compiles so far of the step programs of ``eng`` (its prefill's are not this test's)."""
        return {e["program"]: e["compiles"] for e in ledger().snapshot()["programs"]
                if e["scope"] == eng.introspection_scope and ("step" in e["program"] or "verify" in e["program"])}

    def serve(unpacked: bool):
        eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, **PATHS[path])
        sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
        if unpacked:
            for attr in ("_step", "_steps", "_verify"):
                jitted = getattr(sched.gen, attr, None)
                if jitted is not None:
                    setattr(sched.gen, attr, _unpacked(jitted))
        try:
            out = []
            for wave in range(2):
                reqs = [sched.submit(eng.tokenizer.encode(p, is_start=True), 7, stop_on_eos=False,
                                     temperature=t, seed=11 + i)
                        for i, (p, t) in enumerate([("hello world hello world", 0.0), ("hello", 0.9), (" world", 0.0)])]
                if wave:
                    n = step_compiles(eng)
                _drive(sched, reqs)
                assert all(r.error is None for r in reqs), [r.error for r in reqs]
                out.append([list(r.tokens) for r in reqs])
            if not unpacked:
                assert step_compiles(eng) == n and sum(n.values()) >= 1
            return out
        finally:
            sched.close()
            eng.close()

    assert serve(unpacked=False) == serve(unpacked=True)


def _unpacked(jitted):
    """What the generators dispatched before PR 41, behind the packed call's
    signature: the fields uploaded one by one to the model's own function."""
    program = next(p for p in PROGRAMS.values() if p.__name__ == jitted.__name__)
    plain = {}

    def call(params, cfg, words, cache, layout, *static):
        key = (layout, static)
        if key not in plain:
            plain[key] = jax.jit(program, static_argnums=(1,) + ((8,) if static else ()), donate_argnums=(4,))
        at, dev = 0, []
        for shape, dtype in layout:
            n = int(np.prod(shape, dtype=np.int64))          # not steppack's own arithmetic
            dev.append(jnp.asarray(np.asarray(words[at:at + n]).view(np.dtype(dtype)).reshape(shape)))
            at += n
        return plain[key](params, cfg, dev[0], dev[1], cache, *dev[2:-1], *static, dev[-1])

    return call


def test_a_second_generator_on_an_engine_compiles_no_step_program(model_files):
    """The slot-pool generator's two packed step programs belong to the engine:
    a second scheduler on it (a supervised restart builds one) dispatches the
    executables the first compiled, as it did when both were the engine's
    unpacked ``_sampled_step`` / ``_sampled_steps``."""
    from dllama_tpu.runtime.introspection import ledger

    eng = InferenceEngine(*model_files, tp=1, temperature=0.0, seed=3, decode_chunk=4)
    ids = eng.tokenizer.encode("hello world hello", is_start=True)

    def compiles():
        return {e["program"]: e["compiles"] for e in ledger().snapshot()["programs"]
                if e["scope"] == eng.introspection_scope and e["program"] in ("sampled_step", "sampled_steps")}

    try:
        seen = []
        for _ in range(2):
            sched = BatchScheduler(eng, n_slots=2, _start_thread=False)
            try:
                assert sched.gen._step is eng._packed_sampled_step and sched.gen._steps is eng._packed_sampled_steps
                reqs = [sched.submit(ids, 9, stop_on_eos=False), sched.submit(ids[:3], 3, stop_on_eos=False)]
                _drive(sched, reqs)
                assert all(r.error is None for r in reqs)
            finally:
                sched.close()
            seen.append(compiles())
        assert seen[0] == seen[1] and set(seen[0]) == {"sampled_step", "sampled_steps"} and min(seen[0].values()) >= 1
    finally:
        eng.close()

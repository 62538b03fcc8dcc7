"""The program store (``dllama_tpu/runtime/program_store.py``): every served
executable serialized beside the XLA cache, keyed on what its trace read, and
deserialized at the next start instead of traced.

On the CPU, at tiny sizes: a dense Llama (``helpers.write_tiny_model``) and
the hybrid decoder (the benchmark's own tiny configuration and weight-maker,
a recurrent state pool beside the K/V blocks, so the pickled trees hold the
package's own node types). A "second start" is a second engine built in the
same process on the same directory: fresh ``ObservedJit``s, nothing in
memory shared with the first (the store path never asks the jit's caches).
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny_header_params, write_tiny_model

from dllama_tpu import compile_cache
from dllama_tpu.runtime import introspection, program_store, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
HYBRID = os.path.join(BENCH, "olmo_hybrid")

# a mixed run of admissions: (prompt length, tokens, temperature, seed). The
# lengths reach two prefill buckets and a prompt of two chunks; the sampled
# rows draw their coins from the request's seed, so they are fixed
RUN = ((5, 6, 0.0, 1), (40, 5, 0.9, 11), (70, 6, 0.0, 3), (9, 4, 0.7, 12))


@pytest.fixture
def cache_on(tmp_path, monkeypatch):
    """The persistent cache enabled on a temporary directory, as
    ``compile_cache.enable()`` does it for the CLI and the benchmark, and
    everything it touched put back. The XLA cache persists nothing here (a
    compile time no tiny program reaches), so every compile is the backend's
    own: XLA:CPU cannot serialize again what its cache served, and the test
    below that wants such a compile lowers the threshold itself."""
    from jax._src import compilation_cache as xla_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    path = str(tmp_path / "cache")
    monkeypatch.setenv(compile_cache.ENV, path)
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "3600")
    monkeypatch.setattr(compile_cache, "_programs_dir", None)
    monkeypatch.setattr(program_store, "_said", set())
    xla_cache.reset_cache()        # the suite's own directory is initialized by now
    assert compile_cache.enable() == path
    yield os.path.join(path, "programs")
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    xla_cache.reset_cache()


@pytest.fixture(autouse=True)
def _engine_loader_put_back():
    """The hybrid's weight-maker replaces the engine's tensor-reading call
    for the process (``install_seam``): hand it back."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.models.llama import load_params_from_mfile

    yield
    engine_mod.load_params_from_mfile = load_params_from_mfile


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model_file(kind: str, folder) -> str:
    path = os.path.join(str(folder), f"tiny-{kind}.m")
    if kind == "dense":
        if not os.path.exists(path):
            write_tiny_model(path, tiny_header_params(seq_len=256),
                             np.random.default_rng(0))
        return path
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)   # as run.py puts it: its modules import each other by name
    import run as bench_run

    weights = _import("store_hybrid_weights", os.path.join(HYBRID, "weights.py"))
    with open(os.path.join(HYBRID, "selftest", "configs",
                           "tiny-olmo-hybrid.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    if not os.path.exists(path):
        weights.write_sparse_model(path, model)
    weights.install_seam(7)
    return path


def _serve(kind: str, folder, run=RUN, **engine_kw):
    """Build an engine and a scheduler on the tiny model, serve ``run`` and
    return every request's tokens with the engine's scope."""
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    engine_kw.setdefault("kv_block_size", 16)
    engine_kw.setdefault("tp", 1)     # the suite's eight virtual devices: no auto mesh
    engine = InferenceEngine(_model_file(kind, folder), None, max_seq_len=256,
                             compute_dtype="float32", **engine_kw)
    sched = BatchScheduler(engine, n_slots=2)
    try:
        rng = np.random.default_rng(5)
        reqs = [sched.submit(rng.integers(0, 128, size=n).tolist(), k,
                             temperature=t, seed=s, stop_on_eos=False)
                for n, k, t, s in run]
        for r in reqs:
            assert r.done.wait(300.0) and not r.error, r.error
        return [list(r.tokens) for r in reqs], engine.introspection_scope
    finally:
        sched.close()
        engine.close()


def _totals() -> dict:
    reg = telemetry.registry()
    return {name: reg.counter(getattr(telemetry, name)).total()
            for name in ("PROGRAMS_LOADED", "PROGRAMS_TRACED",
                         "PROGRAM_LOAD_SECONDS", "PROGRAM_TRACE_SECONDS")}


def _moved(before: dict) -> dict:
    after = _totals()
    return {k: after[k] - before[k] for k in after}


def _events(scope: str) -> list[dict]:
    return [e for e in introspection.ledger().snapshot()["events"]
            if e["scope"] == scope]


def _store_lines(text: str) -> list[str]:
    return [ln for ln in text.splitlines() if "program store" in ln]


# -- a second start loads what the first traced ------------------------------------


@pytest.mark.parametrize("kind", ["dense", "hybrid"])
def test_a_second_engine_loads_every_program_the_first_traced(
        kind, cache_on, tmp_path, monkeypatch):
    t0 = _totals()
    first, scope1 = _serve(kind, tmp_path)
    cold = _moved(t0)
    files = sorted(os.listdir(cache_on))
    assert cold["PROGRAMS_LOADED"] == 0 and cold["PROGRAMS_TRACED"] >= 3
    assert len(files) == cold["PROGRAMS_TRACED"]    # one file an executable
    assert not [f for f in files if ".tmp." in f]
    assert {e["source"] for e in _events(scope1)} == {"trace"}

    t1 = _totals()
    second, scope2 = _serve(kind, tmp_path)
    warm = _moved(t1)
    assert warm["PROGRAMS_LOADED"] == cold["PROGRAMS_TRACED"]
    assert warm["PROGRAMS_TRACED"] == 0 and warm["PROGRAM_TRACE_SECONDS"] == 0
    assert warm["PROGRAM_LOAD_SECONDS"] > 0
    assert {e["source"] for e in _events(scope2)} == {"store"}
    assert sorted(os.listdir(cache_on)) == files     # nothing written again
    # the jit path, as a process without the cache runs it: token for token
    # what the compiled and the loaded executables emitted, greedy and sampled
    monkeypatch.setattr(compile_cache, "_programs_dir", None)
    jitted, _ = _serve(kind, tmp_path)
    assert first == jitted and second == jitted
    # the start-up report is the account: a line a program, and the trace's
    # path notes travel with the executable
    lines = []
    introspection.compile_report(scope2, emit=lines.append)
    assert "loaded from the program store" in lines[0]
    assert sum("from the program store" in ln for ln in lines[1:]) \
        == warm["PROGRAMS_LOADED"]
    assert (introspection.ledger().q40_paths(scope2)
            == introspection.ledger().q40_paths(scope1))


# -- the key -------------------------------------------------------------------------


def _key_args(cfg=None, tokens=32, slots=2, dtype=jnp.float32):
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime.kvcache import KVCache

    cfg = cfg or ModelConfig(
        arch=ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, vocab_size=128, seq_len=128,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=RopeType.LLAMA,
        compute_dtype="float32")
    params = {"embed": jnp.zeros((8, 4), dtype), "layers": [jnp.zeros((4,), dtype)]}
    cache = KVCache(k=jnp.zeros((2, slots, 2, 16, 16), dtype),
                    v=jnp.zeros((2, slots, 2, 16, 16), dtype))
    return (params, cfg, jnp.zeros((1, tokens), jnp.int32), jnp.int32(0), cache)


def _key(*, args=None, plan="none", root=program_store.PACKAGE_ROOT,
         options=None) -> str:
    from dllama_tpu.models.llama import forward

    options = options or {"static_argnums": 1, "donate_argnums": (4,)}
    return program_store.program_key(
        program="forward", fun=forward, options=options,
        args=args or _key_args(), static=frozenset({1}), plan=plan,
        root=root)[0]


def _copied_root(tmp_path) -> str:
    root = str(tmp_path / "pkg")
    shutil.copytree(os.path.join(program_store.PACKAGE_ROOT, "ops"),
                    os.path.join(root, "ops"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def _change_source_byte(tmp_path, monkeypatch):
    root = _copied_root(tmp_path)
    before = _key(root=root)
    path = os.path.join(root, "ops", "linear.py")
    with open(path, "r+b") as f:
        f.seek(100)
        byte = f.read(1)
        f.seek(100)
        f.write(bytes([byte[0] ^ 1]))
    program_store._source_digests.pop(root)    # a new process reads it anew
    return before, _key(root=root)


def _change_cfg_field(tmp_path, monkeypatch):
    from dataclasses import replace

    args = _key_args()
    changed = (args[0], replace(args[1], rope_theta=500000.0)) + args[2:]
    return _key(args=args), _key(args=changed)


def _change_bucket(tmp_path, monkeypatch):
    return _key(args=_key_args(tokens=32)), _key(args=_key_args(tokens=64))


def _change_slot_count(tmp_path, monkeypatch):
    return _key(args=_key_args(slots=2)), _key(args=_key_args(slots=4))


def _change_dtype(tmp_path, monkeypatch):
    return (_key(args=_key_args(dtype=jnp.float32)),
            _key(args=_key_args(dtype=jnp.bfloat16)))


def _change_env(name, value):
    def change(tmp_path, monkeypatch):
        before = _key()
        monkeypatch.setenv(name, value)
        return before, _key()
    return change


def _change_plan(tmp_path, monkeypatch):
    return _key(plan="none"), _key(plan="tp=2")


def _change_jax_version(tmp_path, monkeypatch):
    before = _key()
    monkeypatch.setattr(jax, "__version__", jax.__version__ + ".post1")
    return before, _key()


def _change_jit_options(tmp_path, monkeypatch):
    return _key(), _key(options={"static_argnums": 1})


def _change_trace_context(tmp_path, monkeypatch):
    before = _key()
    with jax.default_matmul_precision("highest"):
        return before, _key()


KEY_CHANGES = {
    "a source byte": _change_source_byte,
    "a cfg field": _change_cfg_field,
    "the bucket": _change_bucket,
    "the slot count": _change_slot_count,
    "a dtype": _change_dtype,
    "DLLAMA_TPU_QUANT_KERNEL": _change_env("DLLAMA_TPU_QUANT_KERNEL", "fused"),
    "DLLAMA_TPU_QUANT_MODE": _change_env("DLLAMA_TPU_QUANT_MODE", "exact"),
    "DLLAMA_TPU_WIRE": _change_env("DLLAMA_TPU_WIRE", "q80"),
    "XLA_FLAGS": _change_env("XLA_FLAGS", "--xla_force_host_platform_device_count=8 "
                                          "--xla_cpu_enable_fast_math=false"),
    "the plan": _change_plan,
    "the jax version": _change_jax_version,
    "the jit options": _change_jit_options,
    "the matmul precision": _change_trace_context,
}


@pytest.mark.parametrize("what", sorted(KEY_CHANGES))
def test_the_key_changes_with(what, tmp_path, monkeypatch):
    before, after = KEY_CHANGES[what](tmp_path, monkeypatch)
    assert before != after


def test_the_key_holds_still_where_nothing_a_trace_reads_changed(
        tmp_path, monkeypatch):
    before = _key()
    assert _key() == before                       # fresh arrays, equal shapes
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))    # where, not what
    monkeypatch.setenv("BENCH_RUN", "3")
    assert _key() == before
    root = _copied_root(tmp_path)
    at_root = _key(root=root)
    with open(os.path.join(root, "ops", "notes.txt"), "w") as f:
        f.write("not a source file")
    program_store._source_digests.pop(root)
    assert _key(root=root) == at_root


_KEY_SCRIPT = """
import sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {tests!r})
import test_program_store as t
from dllama_tpu.runtime import program_store
print(t._key(), program_store.canonical(
    (frozenset({{"tp", "sp", "dp", "pp"}}), {{"b": 1, "a": (2.5, None)}})))
"""


def test_the_key_is_equal_across_processes_with_another_hash_seed():
    out = []
    for seed in ("1", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        p = subprocess.run(
            [sys.executable, "-c", _KEY_SCRIPT.format(
                root=ROOT, tests=os.path.join(ROOT, "tests"))],
            env=env, capture_output=True, text=True, timeout=180)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(p.stdout.strip().splitlines()[-1])
    assert out[0] == out[1] and len(out[0].split()[0]) == 64


@pytest.mark.parametrize("value", [object(), lambda x: x, np.zeros(3)],
                         ids=["an object", "a function outside the package",
                              "an array"])
def test_a_static_with_no_process_independent_form_is_unkeyable(value):
    with pytest.raises(program_store.Unkeyable):
        program_store.canonical(value)


# -- never fatal, never silent -------------------------------------------------------


def _truncate(path, other):
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)


def _another_keys_bytes(path, other):
    shutil.copyfile(other, path)


def _not_a_pickle(path, other):
    with open(path, "wb") as f:
        f.write(b"not a pickle at all")


@pytest.mark.parametrize("damage", [_truncate, _another_keys_bytes, _not_a_pickle],
                         ids=["truncated", "another key's bytes", "not a pickle"])
def test_a_file_that_does_not_load_is_removed_and_the_program_traced(
        damage, cache_on, tmp_path, capfd):
    first, _ = _serve("dense", tmp_path)
    files = sorted(os.listdir(cache_on))
    step = [f for f in files if f.startswith("paged_sampled_step")]
    forward = [f for f in files if f.startswith("forward")]
    assert len(step) == 1 and forward
    damage(os.path.join(cache_on, step[0]), os.path.join(cache_on, forward[0]))
    capfd.readouterr()
    t0 = _totals()
    second, _ = _serve("dense", tmp_path)
    moved = _moved(t0)
    lines = _store_lines(capfd.readouterr().err)
    assert len(lines) == 1 and step[0] in lines[0] and "does not load" in lines[0]
    assert moved["PROGRAMS_TRACED"] == 1
    assert moved["PROGRAMS_LOADED"] == len(files) - 1
    assert second == first
    # traced, and filed again under its name: the next start loads it
    assert sorted(os.listdir(cache_on)) == files
    t1 = _totals()
    _serve("dense", tmp_path)
    assert _moved(t1)["PROGRAMS_TRACED"] == 0


def test_an_unwritable_directory_is_said_once_and_programs_are_traced(
        cache_on, tmp_path, capfd, monkeypatch):
    os.makedirs(os.path.dirname(cache_on), exist_ok=True)
    with open(cache_on, "w") as f:      # a file where the directory would be
        f.write("in the way")
    capfd.readouterr()
    t0 = _totals()
    tokens, _ = _serve("dense", tmp_path)
    moved = _moved(t0)
    lines = _store_lines(capfd.readouterr().err)
    assert len(lines) == 1 and "cannot be written" in lines[0]
    assert moved["PROGRAMS_LOADED"] == 0 and moved["PROGRAMS_TRACED"] >= 3
    assert os.path.isfile(cache_on) and len(tokens) == len(RUN)


def test_on_the_cpu_a_compile_the_xla_cache_served_is_not_filed(
        cache_on, tmp_path, capfd, monkeypatch):
    # XLA:CPU cannot serialize again an executable its own cache loaded (the
    # blob dispatches into "Function ... not found"): such a compile is
    # served, said once, and left out of the store
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    monkeypatch.setattr(compile_cache, "_programs_dir", None)
    jitted, _ = _serve("dense", tmp_path, run=RUN[:2])      # fills the XLA cache
    monkeypatch.setattr(compile_cache, "_programs_dir", cache_on)
    capfd.readouterr()
    t0 = _totals()
    served, _ = _serve("dense", tmp_path, run=RUN[:2])
    moved = _moved(t0)
    lines = _store_lines(capfd.readouterr().err)
    assert len(lines) == 1 and "XLA:CPU" in lines[0]
    assert moved["PROGRAMS_LOADED"] == 0 and moved["PROGRAMS_TRACED"] >= 2
    assert not os.path.exists(cache_on) or not os.listdir(cache_on)
    assert served == jitted


def test_an_executable_that_refuses_its_arguments_falls_back_to_the_jit(
        cache_on, tmp_path, capfd):
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.kvcache import KVCache

    engine = InferenceEngine(_model_file("dense", tmp_path), None,
                             max_seq_len=256, compute_dtype="float32",
                             kv_block_size=16, tp=1)
    try:
        cfg, fwd = engine.cfg, engine._step

        def call(rows):
            shape = (cfg.n_layers, 1, cfg.n_kv_heads, rows, cfg.head_dim)
            cache = KVCache(k=jnp.zeros(shape, jnp.float32),
                            v=jnp.zeros(shape, jnp.float32))
            logits, _ = fwd(engine.params, cfg, jnp.ones((1, 32), jnp.int32),
                            jnp.int32(0), cache)
            return np.asarray(logits)

        served = call(64)
        (sig, exe), = fwd._programs.items()
        assert exe is not introspection._ASIDE
        capfd.readouterr()
        # the same top-level shapes, another cache under them
        other = call(128)
        lines = _store_lines(capfd.readouterr().err)
        assert len(lines) == 1 and "refused its arguments" in lines[0]
        assert fwd._programs[sig] is introspection._ASIDE
        np.testing.assert_allclose(other, served, rtol=0, atol=1e-5)
        np.testing.assert_array_equal(call(64), served)     # through the jit now
        assert not _store_lines(capfd.readouterr().err)
    finally:
        engine.close()


# -- donation, scope, the switch -----------------------------------------------------


def test_a_donated_cache_is_deleted_after_a_loaded_call(cache_on, tmp_path):
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    _serve("dense", tmp_path, run=RUN[:1])
    t0 = _totals()
    engine = InferenceEngine(_model_file("dense", tmp_path), None,
                             max_seq_len=256, compute_dtype="float32",
                             kv_block_size=16, tp=1)
    sched = BatchScheduler(engine, n_slots=2)
    try:
        pool_before = sched.gen.pkv.k
        req = sched.submit(np.random.default_rng(5).integers(0, 128, size=5).tolist(),
                           6, stop_on_eos=False)
        assert req.done.wait(300.0) and not req.error
        moved = _moved(t0)
        assert moved["PROGRAMS_LOADED"] >= 2 and moved["PROGRAMS_TRACED"] == 0
        assert pool_before.is_deleted()      # the step donated it, loaded or not
        assert not sched.gen.pkv.k.is_deleted()
    finally:
        sched.close()
        engine.close()


def test_the_tick_program_comes_from_the_store_with_both_caches_donated(
        cache_on, tmp_path):
    """``forward_and_step`` (PR 47: a dense decoder's every prefill chunk, the
    tick's decode rows riding it) is keyable, packed layout and all: filed a
    bucket on the first start, loaded on the second (``source`` ``store``),
    as many events as the first start had and every one loaded; the loaded
    executable takes BOTH donated arguments, the admission's column and the
    pool; tokens as the traced start's."""
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    first, scope1 = _serve("dense", tmp_path)
    cold = [(e["program"], e["source"]) for e in _events(scope1)]
    assert ("forward_and_step", "trace") in cold
    assert not any(program == "forward" for program, _source in cold)
    files = sorted(os.listdir(cache_on))
    assert sum(f.startswith("forward_and_step-") for f in files) \
        == sum(program == "forward_and_step" for program, _source in cold) >= 2

    t0 = _totals()
    second, scope2 = _serve("dense", tmp_path)
    warm = _moved(t0)
    assert sorted(program for program, _ in cold) \
        == sorted(e["program"] for e in _events(scope2))
    assert {e["source"] for e in _events(scope2)} == {"store"}
    # the benchmark's programs_loaded_share and program_trace_s over this start
    assert 100.0 * warm["PROGRAMS_LOADED"] / (warm["PROGRAMS_LOADED"]
                                               + warm["PROGRAMS_TRACED"]) == 100.0
    assert warm["PROGRAMS_LOADED"] == len(cold) and warm["PROGRAM_TRACE_SECONDS"] == 0
    assert sorted(os.listdir(cache_on)) == files and second == first

    t1 = _totals()
    engine = InferenceEngine(_model_file("dense", tmp_path), None,
                             max_seq_len=256, compute_dtype="float32",
                             kv_block_size=16, tp=1)
    try:
        gen = PagedGenerator(engine, n_slots=2)
        rng = np.random.default_rng(5)
        gen.admit(Request(rid=1, prompt_ids=rng.integers(0, 128, size=5).tolist(),
                          max_tokens=8, stop_on_eos=False), 0)
        gen.step()
        adm = gen.begin_admit(Request(rid=2, prompt_ids=rng.integers(0, 128, size=40).tolist(),
                                      max_tokens=8, stop_on_eos=False), 1)
        col_before, pool_before = adm.col.k, gen.pkv.k
        assert not gen.continue_admit(adm)          # the first of two chunks, row 0 riding it
        assert gen.take_rows_rode()
        assert col_before.is_deleted() and pool_before.is_deleted()
        assert not adm.col.k.is_deleted() and not gen.pkv.k.is_deleted()
        moved = _moved(t1)
        assert moved["PROGRAMS_TRACED"] == 0 and moved["PROGRAMS_LOADED"] >= 2
    finally:
        engine.close()


def test_a_mesh_plan_stands_the_store_aside(cache_on, tmp_path):
    t0 = _totals()
    tokens, scope = _serve("dense", tmp_path, run=RUN[:2], tp=2)
    moved = _moved(t0)
    assert moved["PROGRAMS_LOADED"] == 0 and moved["PROGRAMS_TRACED"] >= 2
    assert not os.path.exists(cache_on)
    assert {e["plan"] for e in _events(scope)} == {"tp=2"}
    assert len(tokens) == 2


def test_with_the_cache_not_enabled_nothing_is_read_or_written(
        tmp_path, monkeypatch):
    cache = str(tmp_path / "cache")
    monkeypatch.setenv(compile_cache.ENV, cache)     # named, never enabled
    monkeypatch.setattr(compile_cache, "_programs_dir", None)
    touched = []
    monkeypatch.setattr(program_store, "load",
                        lambda *a, **k: touched.append("load"))
    monkeypatch.setattr(program_store, "save",
                        lambda *a, **k: touched.append("save"))
    monkeypatch.setattr(program_store, "program_key",
                        lambda *a, **k: touched.append("key"))
    t0 = _totals()
    _serve("dense", tmp_path, run=RUN[:2])
    assert not touched and not os.path.exists(os.path.join(cache, "programs"))
    moved = _moved(t0)
    assert moved["PROGRAMS_LOADED"] == 0 and moved["PROGRAMS_TRACED"] >= 2


def test_enable_is_the_one_switch(tmp_path, monkeypatch):
    monkeypatch.setattr(compile_cache, "_programs_dir", None)
    assert compile_cache.programs_dir() is None
    blocked = tmp_path / "file"
    blocked.write_text("x")
    monkeypatch.setenv(compile_cache.ENV, str(blocked / "cache"))
    assert compile_cache.enable() is None            # cannot be created
    assert compile_cache.programs_dir() is None      # off with the cache


# -- no program changes ----------------------------------------------------------------


def test_the_lowering_is_the_same_with_the_store_on(cache_on, tmp_path, monkeypatch):
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.kvcache import KVCache

    texts = []
    for on in (False, True):
        monkeypatch.setattr(compile_cache, "_programs_dir", cache_on if on else None)
        engine = InferenceEngine(_model_file("dense", tmp_path), None,
                                 max_seq_len=256, compute_dtype="float32",
                                 kv_block_size=16, tp=1)
        try:
            cfg = engine.cfg
            shape = (cfg.n_layers, 1, cfg.n_kv_heads, 64, cfg.head_dim)
            cache = KVCache(k=jnp.zeros(shape, jnp.float32),
                            v=jnp.zeros(shape, jnp.float32))
            texts.append(engine._step.lower(
                engine.params, cfg, jnp.ones((1, 32), jnp.int32),
                jnp.int32(0), cache).as_text())
        finally:
            engine.close()
    assert texts[0] == texts[1]


def test_the_dense_goldens_hold_with_the_store_on(cache_on):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import dense_hlo_digest
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    with open(os.path.join(ROOT, "tests", "goldens", "dense_hlo_sha256.json"),
              encoding="utf-8") as f:
        assert dense_hlo_digest.digests() == json.load(f)

"""Test config: force an 8-device virtual CPU platform before JAX initializes.

This is the TPU build's equivalent of the reference's NnFakeNodeSynchronizer +
localhost-TCP-worker strategy (reference: src/nn/nn-executor.hpp:29-33,
examples/n-workers.sh): multi-chip behavior is tested on a single host by
letting XLA present 8 virtual CPU devices, so every sharding/collective path
runs for real — just not over ICI.
"""

import os

# DLLAMA_TESTS_TPU=1 runs the @pytest.mark.tpu tier on real hardware
# (pytest -m tpu); default is the 8-device virtual CPU mesh.
_TPU_TIER = os.environ.get("DLLAMA_TESTS_TPU") == "1"

if not _TPU_TIER:
    os.environ["JAX_PLATFORMS"] = "cpu"  # force: the suite runs on the CPU mesh
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _TPU_TIER:
    # jax snapshots JAX_PLATFORMS when it is imported; pin the config too, so
    # the CPU mesh holds whatever imported jax first, before any backend
    # initializes.
    jax.config.update("jax_platforms", "cpu")
    # Persistent XLA compile cache for the CPU tier: since plan_scoped_jit
    # (parallel/api.py) scoped trace caches per engine, every engine
    # legitimately compiles its own programs — identical HLO across the
    # suite's hundreds of tiny engines now hits this disk cache instead of
    # recompiling (~30% wall time; keeps the tier-1 run inside its budget).
    # An explicit JAX_COMPILATION_CACHE_DIR env wins; otherwise the
    # checkout's one fixed, git-ignored directory (the same rule as
    # dllama_tpu/compile_cache.py, kept in these few lines so that this file
    # imports nothing of the package).
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        _cache = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".xla_cache")
        try:
            os.makedirs(_cache, exist_ok=True)
            os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
            jax.config.update("jax_compilation_cache_dir", _cache)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.5)
        except OSError:
            pass  # unwritable checkout: run uncached


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "tpu: needs real TPU hardware (run: DLLAMA_TESTS_TPU=1 pytest -m tpu)")


def pytest_collection_modifyitems(config, items):
    """Deselect tpu-marked tests unless the TPU tier is active (they compile
    real Pallas kernels; pointless and slow on the CPU mesh), and everything
    else when it is."""
    import pytest as _pytest

    skip_tpu = _pytest.mark.skip(reason="TPU tier off (set DLLAMA_TESTS_TPU=1)")
    skip_cpu = _pytest.mark.skip(reason="TPU tier on: only -m tpu tests run")
    for item in items:
        has_tpu = "tpu" in item.keywords
        if has_tpu and not _TPU_TIER:
            item.add_marker(skip_tpu)
        elif _TPU_TIER and not has_tpu:
            item.add_marker(skip_cpu)


import pytest as _pt


@_pt.fixture(autouse=True)
def _dllama_env_leak_sentinel():
    """Fail the OFFENDING test when it leaks a DLLAMA_* env knob.

    The quant/serving knobs are read at trace time, so a leaked var flips
    numerics for every later test — the round-5 full-suite incident was 36
    order-dependent golden failures traced to one test's env interplay.
    Autouse + declared first => torn down last, AFTER monkeypatch undo."""
    before = {k: v for k, v in os.environ.items() if k.startswith("DLLAMA_")}
    yield
    after = {k: v for k, v in os.environ.items() if k.startswith("DLLAMA_")}
    assert after == before, (
        "test leaked DLLAMA_* env state: "
        + str({k: (before.get(k), after.get(k))
               for k in set(before) | set(after)
               if before.get(k) != after.get(k)}))


@_pt.fixture(autouse=True)
def _program_store_off_after():
    """``compile_cache.enable()`` (``benchmark/run.py``'s ``main`` and the
    CLI's, which tests call in-process) turns the program store on for the
    process: the next test must not inherit it."""
    yield
    import sys

    cc = sys.modules.get("dllama_tpu.compile_cache")
    if cc is not None:
        cc._programs_dir = None

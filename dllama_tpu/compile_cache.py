"""Where the persistent XLA compilation cache lives — one rule for the CLI,
``chip_smoke.py``, ``benchmark/run.py`` and the tools.

``JAX_COMPILATION_CACHE_DIR``, when set, IS the cache: whoever runs the
program placed it from outside, and nothing in code sets another. When it
is not set, the cache is one fixed, git-ignored directory inside the
checkout — the path is part of a cache entry's key, so a directory that
moves (a tempdir, a per-user home) never hits.

The program store (``runtime/program_store.py``: every served executable,
serialized) lives in ``programs/`` under the same directory and is on exactly
when :func:`enable` turned the cache on in this process: one switch for both.

Stdlib only at import: ``chip_smoke.py``'s parent must be able to ask where the cache is without importing jax.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache")
# ``programs/`` under the directory enable() turned on; None until it did
_programs_dir: str | None = None


def cache_dir() -> str:
    """The directory the rule above names (not created)."""
    return os.environ.get(ENV) or DEFAULT_DIR


def programs_dir() -> str | None:
    """Where the program store reads and writes (not created), or None while
    the persistent cache is not enabled in this process: the store is never
    on without it."""
    return _programs_dir


def enable() -> str | None:
    """Turn the persistent cache on at :func:`cache_dir` for this process
    and its children: creates the directory, exports the env (children
    inherit it) and, when jax is already imported — it snapshots the env at
    import — updates its config too. Returns the directory, or None (with
    one line on stderr) when it cannot be created."""
    global _programs_dir
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        print(f"🚧 compilation cache {path}: {e}; running uncached",
              file=sys.stderr)
        return None
    os.environ[ENV] = path
    # persist what costs a quarter second to compile, not JAX's one second:
    # a prefill bucket whose Q40 matmuls are Pallas kernels compiles in
    # 0.9-1.9 s, and the ones under a second were compiled again at every
    # start (three of qwen3-4b's eight `forward` traces, 4.5 s of a 28 s
    # set-up: PERF.md section 6, PR 35)
    os.environ.setdefault(_MIN_SECS_ENV, "0.25")
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(os.environ[_MIN_SECS_ENV]))
    _programs_dir = os.path.join(path, "programs")
    return path

"""Native (C++) host-runtime components, loaded via ctypes.

The TPU compute path is JAX/XLA/Pallas; the *host* runtime around it — the
quant codecs and the mmap→device weight repack (the data-loader hot loop) —
is native C++, like the reference's (src/nn/nn-quants.cpp, and the weight
slicing half of src/nn/nn-network.cpp:809-854). The library is built on first
use with ``make`` and falls back to the numpy implementations in
:mod:`dllama_tpu.formats.quants` when a toolchain isn't available, so the
package stays importable everywhere. The fallback is never silent: it prints
one line with the reason, and :func:`describe` says which codec this process
runs (the CLI banner prints it).

All entry points are ``extern "C"`` over raw buffers; this module wraps them
with numpy ctypes bindings. Use :func:`get_lib` (returns ``None`` when
unavailable) or the typed wrappers below.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

_DIR = Path(__file__).resolve().parent

_lib: ctypes.CDLL | None = None
_tried = False
# how this process came by its codec (see describe()): "built here", "found
# built", or why numpy serves instead
_how = "not loaded yet"

_c_f32p = ctypes.POINTER(ctypes.c_float)
_c_u8p = ctypes.POINTER(ctypes.c_uint8)
_c_i8p = ctypes.POINTER(ctypes.c_int8)


def default_threads() -> int:
    env = os.environ.get("DLLAMA_NATIVE_THREADS")
    if env:
        return max(1, int(env))
    return max(1, os.cpu_count() or 1)


def _host_signature() -> str:
    """Identity of the CPU the .so was built for: -march=native code moved to
    a different host (shared FS, container image reuse) can SIGILL the whole
    process, which ctypes cannot catch (advisor round-1 finding). The
    signature is EMBEDDED IN THE .so FILENAME, so check-and-load is atomic:
    a foreign host's build has a different name and is simply never opened —
    no tag file to race, no rebuild ping-pong invalidating other hosts'
    builds on a shared FS."""
    import hashlib
    import platform

    parts = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    parts.append(line.strip())
                    break
    except OSError:
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _so_path() -> Path:
    return _DIR / f"libdllama_native.{_host_signature()}.so"


def _stale() -> bool:
    so = _so_path()
    if not so.exists():
        return True
    try:
        return any(src.stat().st_mtime > so.stat().st_mtime
                   for src in _DIR.glob("*.cpp"))
    except OSError:
        return True


def _build() -> str | None:
    """Build to a per-(host, process) temp name and rename into place
    (returns None on success, else the reason it failed):
    concurrent first-use builds (pytest workers, multi-process launches) each
    produce a valid .so and the atomic replace keeps the last one. The host
    signature in the temp name keeps two hosts with colliding pids (pid
    namespaces on a shared volume) from interleaving builds and renaming a
    foreign binary under this host's signed name."""
    tmp = f"libdllama_native.so.tmp.{_host_signature()}.{os.getpid()}"
    try:
        proc = subprocess.run(
            ["make", "-C", str(_DIR), "-s", f"SO={tmp}"],
            capture_output=True, text=True, timeout=120)
        if proc.returncode != 0 or not (_DIR / tmp).exists():
            err = (proc.stderr or proc.stdout or "").strip().splitlines()
            return f"make rc={proc.returncode}" + (f": {err[-1]}" if err else "")
        os.replace(_DIR / tmp, _so_path())
        return None
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    finally:
        (_DIR / tmp).unlink(missing_ok=True)


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, (re)building it on first call when missing
    or older than its source; None if that fails. Only ever dlopens a .so
    whose filename carries THIS host's CPU signature — a build from another
    machine (shared FS) is invisible rather than a SIGILL risk."""
    global _lib, _tried, _how
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("DLLAMA_NO_NATIVE"):
        _how = "numpy (DLLAMA_NO_NATIVE is set)"
        return None
    how = f"native, found built ({_so_path().name})"
    if _stale():
        why = _build()
        if why is None:
            how = f"native, built here from the .cpp ({_so_path().name})"
        elif not _so_path().exists():
            return _fallback(f"build failed: {why}")
    try:
        lib = ctypes.CDLL(str(_so_path()))
    except OSError as e:
        return _fallback(f"dlopen failed: {e}")
    _how = how
    for name, (argtypes, restype) in {
        "q40_quantize": ((_c_f32p, ctypes.c_int64, _c_u8p, ctypes.c_int), None),
        "q40_dequantize": ((_c_u8p, ctypes.c_int64, _c_f32p, ctypes.c_int), None),
        "q80_quantize": ((_c_f32p, ctypes.c_int64, _c_u8p, ctypes.c_int), None),
        "q80_dequantize": ((_c_u8p, ctypes.c_int64, _c_f32p, ctypes.c_int), None),
        "q40_repack_kmajor": ((_c_u8p, ctypes.c_int64, ctypes.c_int64,
                               _c_f32p, _c_i8p, ctypes.c_int), None),
        "bpe_create": ((_c_u8p, ctypes.POINTER(ctypes.c_int64), _c_f32p,
                        ctypes.c_int32, ctypes.c_int32), ctypes.c_void_p),
        "bpe_destroy": ((ctypes.c_void_p,), None),
        "bpe_merge": ((ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
                       ctypes.c_int64), ctypes.c_int64),
    }.items():
        fn = getattr(lib, name)
        fn.argtypes = list(argtypes)
        fn.restype = restype
    _lib = lib
    return _lib


def _fallback(why: str) -> None:
    """Record and SAY that numpy serves instead of the native library."""
    global _how
    _how = f"numpy ({why})"
    print(f"🚧 native codec unavailable, using the numpy codec: {why}",
          file=sys.stderr)
    return None


def available() -> bool:
    return get_lib() is not None


def describe() -> str:
    """Which codec serves this process and how it got here: ``native, built
    here from the .cpp (...)``, ``native, found built (...)`` or ``numpy
    (<reason>)``."""
    get_lib()
    return _how


def _u8(buf) -> np.ndarray:
    a = np.frombuffer(buf, dtype=np.uint8) if not isinstance(buf, np.ndarray) else buf
    return np.ascontiguousarray(a.reshape(-1).view(np.uint8))


def q40_quantize(x: np.ndarray, nthreads: int | None = None) -> bytes | None:
    lib = get_lib()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    out = np.empty((x.size // 32) * 18, dtype=np.uint8)
    lib.q40_quantize(x.ctypes.data_as(_c_f32p), x.size,
                     out.ctypes.data_as(_c_u8p), nthreads or default_threads())
    return out.tobytes()


def q40_dequantize(buf, n: int, nthreads: int | None = None) -> np.ndarray | None:
    lib = get_lib()
    if lib is None:
        return None
    raw = _u8(buf)
    need = (n // 32) * 18
    if raw.size < need:
        raise ValueError(f"q40 buffer too small: {raw.size} < {need} bytes for n={n}")
    out = np.empty(n, dtype=np.float32)
    lib.q40_dequantize(raw.ctypes.data_as(_c_u8p), n,
                       out.ctypes.data_as(_c_f32p), nthreads or default_threads())
    return out


def q80_quantize(x: np.ndarray, nthreads: int | None = None) -> bytes | None:
    lib = get_lib()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    out = np.empty((x.size // 32) * 34, dtype=np.uint8)
    lib.q80_quantize(x.ctypes.data_as(_c_f32p), x.size,
                     out.ctypes.data_as(_c_u8p), nthreads or default_threads())
    return out.tobytes()


def q80_dequantize(buf, n: int, nthreads: int | None = None) -> np.ndarray | None:
    lib = get_lib()
    if lib is None:
        return None
    raw = _u8(buf)
    need = (n // 32) * 34
    if raw.size < need:
        raise ValueError(f"q80 buffer too small: {raw.size} < {need} bytes for n={n}")
    out = np.empty(n, dtype=np.float32)
    lib.q80_dequantize(raw.ctypes.data_as(_c_u8p), n,
                       out.ctypes.data_as(_c_f32p), nthreads or default_threads())
    return out


def q40_repack_kmajor(buf, rows: int, cols: int, nthreads: int | None = None
                      ) -> tuple[np.ndarray, np.ndarray] | None:
    """Disk row-major Q40 [rows, cols] → K-major device planes
    (scales_f32 [cols/32, rows], codes_i8 [cols, rows])."""
    lib = get_lib()
    if lib is None:
        return None
    raw = _u8(buf)
    assert raw.size == rows * (cols // 32) * 18, (raw.size, rows, cols)
    scales = np.empty((cols // 32, rows), dtype=np.float32)
    codes = np.empty((cols, rows), dtype=np.int8)
    lib.q40_repack_kmajor(raw.ctypes.data_as(_c_u8p), rows, cols,
                          scales.ctypes.data_as(_c_f32p),
                          codes.ctypes.data_as(_c_i8p),
                          nthreads or default_threads())
    return scales, codes


class BpeMerger:
    """Handle-holding wrapper over the native BPE merge engine
    (tokenizer.cpp): builds the vocab hash map once, then ``merge`` runs
    allocation-light per call. Construct via :func:`bpe_merger` (None when
    the library is unavailable or handle creation fails)."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._h = handle

    def merge(self, tokens: list[int]) -> list[int] | None:
        """Greedy-merge ``tokens`` (same output as bpe.Tokenizer._merge);
        None signals the caller to fall back (bad ids, dead handle)."""
        if self._h is None:
            return None
        n = len(tokens)
        if n < 2:
            return list(tokens)
        arr = np.asarray(tokens, dtype=np.int32)
        out_n = self._lib.bpe_merge(
            self._h, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n)
        if out_n < 0:
            return None
        return arr[:out_n].tolist()

    def __del__(self):  # noqa: D105 — process-exit teardown may be partial
        try:
            if self._h is not None:
                self._lib.bpe_destroy(self._h)
                self._h = None
        except Exception:  # pragma: no cover — interpreter shutdown
            pass


def bpe_merger(vocab: list[bytes], scores, n_regular: int) -> "BpeMerger | None":
    """Build a native merge engine from the tokenizer tables, or None."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(vocab)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(p) for p in vocab], out=offsets[1:])
    blob = np.frombuffer(b"".join(vocab), dtype=np.uint8) if offsets[n] \
        else np.empty(0, dtype=np.uint8)
    sc = np.ascontiguousarray(scores, dtype=np.float32)
    if sc.size != n:
        return None
    h = lib.bpe_create(blob.ctypes.data_as(_c_u8p),
                       offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                       sc.ctypes.data_as(_c_f32p), n, n_regular)
    if not h:
        return None
    return BpeMerger(lib, h)

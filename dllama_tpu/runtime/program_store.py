"""The program store: every served executable, serialized beside the XLA cache.

The persistent XLA cache (``dllama_tpu/compile_cache.py``) is keyed on the
LOWERED module, so to ask it anything a process must first trace a program in
Python and lower it, Pallas kernels to Mosaic included: a second or more a
prefill bucket at every start, for an executable that then loads in a tenth
of one. This store is keyed on the INPUTS of tracing instead, so a warm start
skips the trace: :class:`runtime.introspection.ObservedJit` asks it the first
time it meets a signature, a hit is
``jax.experimental.serialize_executable.deserialize_and_load``, a miss lowers
and compiles once (through the XLA cache, as ever) and files the result here.

The XLA cache is safe by construction; this one is safe only as far as its key
holds everything a trace reads. :func:`program_key` hashes:

* the bytes of every ``.py`` file of the package (an edit anywhere misses
  everything, which is the point), :func:`source_digest`;
* the ``jax``, ``jaxlib`` and ``libtpu`` versions, the client's
  ``platform_version``, the default backend, the device kind and count;
* the program's name, the jitted function (module and qualified name, and what
  it closes over), its jit options;
* every static argument by a representation that is equal across processes
  (:func:`canonical`: never ``hash()`` or an ``id``);
* the tree structure of the other arguments and every leaf's shape, dtype,
  weak type, sharding, devices and committedness;
* the mesh plan's description;
* every ``DLLAMA_*``, ``JAX_*``, ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``
  environment value (the quant kernel, mode and wire are read at trace time);
* jax's own trace context (x64, default matmul precision: what ``jit`` keys on).

A value that has no such representation makes the program unkeyable, and an
unkeyable program is traced as it always was. Files are pickles (jax's own
format for a serialized executable): they are trusted exactly as far as the
directory is, like the code beside it. Nothing here is fatal: a file that does
not load is removed and the program traced; a directory that cannot be
written is said once on stderr and the program served from its compile.

One backend's quirk is held here: XLA:CPU cannot serialize again an executable
that it loaded from its own persistent cache (the second blob dispatches into
"Function ... not found"), so on the CPU a compile that the XLA cache served is
not filed (:func:`serializes_again`); a TPU's executable is one opaque program
either way.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import hashlib
import os
import pickle
import re
import sys
import threading

FORMAT = 1
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the environment a trace may read: the package's own knobs and the runtime's
_ENV_PREFIXES = ("DLLAMA_", "JAX_")
_ENV_NAMES = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")
# where the caches live says nothing of what a trace computes
_ENV_NOT_READ = ("JAX_COMPILATION_CACHE_DIR",)

_lock = threading.Lock()
_source_digests: dict[str, str] = {}
_said: set = set()   # topics said once: a name, or (what, directory)


class Unkeyable(Exception):
    """A trace-time input with no representation that is equal across
    processes: its program stays outside the store."""


def say(msg: str, *, once=None) -> None:
    """One line on stderr; with ``once``, one line a process and topic."""
    if once is not None:
        with _lock:
            if once in _said:
                return
            _said.add(once)
    print(f"🚧 program store: {msg}", file=sys.stderr, flush=True)


def serializes_again() -> bool:
    """Whether this backend can serialize an executable that its own
    persistent cache served (module docstring): every one but XLA:CPU."""
    import jax

    return jax.default_backend() != "cpu"


# -- the key ---------------------------------------------------------------------


def source_digest(root: str = PACKAGE_ROOT) -> str:
    """sha256 over every ``.py`` file under ``root``, by relative path and
    bytes, in sorted order. Read once a process and root (a few ms)."""
    with _lock:
        got = _source_digests.get(root)
    if got is not None:
        return got
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                body = f.read()
            h.update(os.path.relpath(path, root).encode() + b"\0")
            h.update(len(body).to_bytes(8, "little") + body)
    with _lock:
        _source_digests[root] = h.hexdigest()
    return _source_digests[root]


def environment() -> list[tuple[str, str]]:
    """The environment values the key holds, sorted."""
    return sorted((k, v) for k, v in os.environ.items()
                  if (k.startswith(_ENV_PREFIXES) or k in _ENV_NAMES)
                  and k not in _ENV_NOT_READ)


@functools.lru_cache(maxsize=None)
def _libtpu_version() -> str:
    try:
        from importlib import metadata

        return metadata.version("libtpu")
    except Exception:  # noqa: BLE001 — no libtpu distribution: a CPU or GPU install
        return "none"


def runtime_versions() -> dict[str, str]:
    """What compiled and will run the executable: the jax, jaxlib and libtpu
    versions, the client's ``platform_version``, the default backend and its
    devices' kind and count."""
    import jax
    import jaxlib

    devs = jax.devices()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": _libtpu_version(),
            "platform_version": devs[0].client.platform_version,
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind,
            "device_count": str(len(devs)),
            "process_count": str(jax.process_count())}


def trace_context() -> str:
    """jax's own trace context (what ``jit`` itself keys a trace on: x64, the
    default matmul precision, ...), as text."""
    import jax

    try:
        from jax._src import config as _config

        return repr(_config.trace_context())
    except Exception:  # noqa: BLE001 — a jax without it: the two that matter here
        return repr((jax.config.jax_enable_x64,
                     jax.config.jax_default_matmul_precision))


def canonical(x) -> str:
    """``x`` as text that is equal in every process that holds an equal
    value: never ``hash()``, an ``id`` or a set's order. Raises
    :class:`Unkeyable` for anything it cannot vouch for."""
    if x is None or isinstance(x, (bool, int, float, complex, str, bytes)):
        return repr(x)
    if isinstance(x, enum.Enum):
        return f"{type(x).__qualname__}.{x.name}"
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return (type(x).__qualname__ + "("
                + ",".join(f"{f.name}={canonical(getattr(x, f.name))}"
                           for f in dataclasses.fields(x)) + ")")
    if isinstance(x, (tuple, list)):
        return (type(x).__qualname__ + "("
                + ",".join(canonical(v) for v in x) + ")")
    if isinstance(x, dict):
        return "dict(" + ",".join(sorted(
            f"{canonical(k)}:{canonical(v)}" for k, v in x.items())) + ")"
    if isinstance(x, (set, frozenset)):
        return (type(x).__qualname__ + "("
                + ",".join(sorted(canonical(v) for v in x)) + ")")
    if callable(x) and hasattr(x, "__code__"):
        return function_identity(x)
    if isinstance(x, type):
        return f"{x.__module__}.{x.__qualname__}"
    if type(x).__module__ == "numpy":
        import numpy as np

        if isinstance(x, np.dtype):
            return f"dtype({x.str})"
        if isinstance(x, np.generic):
            return f"{x.dtype.str}({x!r})"
    raise Unkeyable(f"{type(x).__module__}.{type(x).__qualname__}")


def function_identity(fun) -> str:
    """A jitted function by where the package defines it and what it closes
    over. A function defined outside the package (a test's, a tool's) is
    unkeyable: the source digest does not cover its body."""
    module = getattr(fun, "__module__", None) or ""
    if module.split(".")[0] != __name__.split(".")[0]:
        raise Unkeyable(f"function {module}.{getattr(fun, '__qualname__', fun)}"
                        " is defined outside the package")
    cells = [canonical(c.cell_contents) for c in (fun.__closure__ or ())]
    return f"{module}.{fun.__qualname__}[{','.join(cells)}]"


def describe_leaf(x) -> str:
    """One dynamic argument leaf as the compile saw it: shape, dtype, weak
    type, sharding with its devices, committedness. A host value (numpy, a
    Python scalar) has no placement to describe."""
    aval = getattr(x, "aval", None)
    sharding = getattr(x, "sharding", None)
    if aval is not None and sharding is not None:
        ids = sorted(d.id for d in sharding.device_set)
        return (f"{aval.dtype.name}{list(aval.shape)} "
                f"weak={bool(getattr(aval, 'weak_type', False))} "
                f"{sharding!r} on {ids} "
                f"committed={bool(getattr(x, '_committed', True))}")
    shape, dtype = getattr(x, "shape", None), getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"host {dtype}{list(shape)}"
    if isinstance(x, (bool, int, float, complex)):
        return f"host {type(x).__name__} scalar"
    raise Unkeyable(f"argument leaf {type(x).__module__}."
                    f"{type(x).__qualname__}")


def program_key(*, program: str, fun, options: dict, args: tuple,
                static: frozenset, plan: str,
                root: str = PACKAGE_ROOT) -> tuple[str, list[int]]:
    """The store's key for one specialization of one program (hex), and the
    ids of the devices its arguments live on (what a loaded executable is
    bound to). Raises :class:`Unkeyable`."""
    import jax

    statics = [canonical(a) for i, a in enumerate(args) if i in static]
    dynamic = tuple(a for i, a in enumerate(args) if i not in static)
    leaves, tree = jax.tree_util.tree_flatten(dynamic)
    devices: set[int] = set()
    for leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(leaf, "aval"):
            devices.update(d.id for d in sharding.device_set)
    parts = [
        ("format", FORMAT),
        ("source", source_digest(root)),
        ("runtime", sorted(runtime_versions().items())),
        ("program", program),
        ("function", function_identity(fun)),
        ("options", sorted((k, canonical(v)) for k, v in options.items())),
        ("statics", statics),
        ("tree", str(tree)),
        ("leaves", [describe_leaf(x) for x in leaves]),
        ("plan", plan),
        ("environment", environment()),
        ("trace_context", trace_context()),
    ]
    return (hashlib.sha256(repr(parts).encode()).hexdigest(),
            sorted(devices))


# -- the files -------------------------------------------------------------------


def _path(directory: str, program: str, key: str) -> str:
    safe = re.sub(r"[^A-Za-z0-9_.-]", "_", program)[:48]
    return os.path.join(directory, f"{safe}-{key[:40]}.xprog")


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass


def load(directory: str, program: str, key: str, device_ids: list[int]):
    """``(compiled, notes)`` from the store, or None: no such file (a plain
    miss, silent), or a file that does not load (another runtime, truncated,
    another key's bytes, unpicklable): said on stderr, removed, and the
    caller traces as on a miss."""
    path = _path(directory, program, key)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except (FileNotFoundError, NotADirectoryError):
        return None
    except OSError as e:
        say(f"{directory} cannot be read ({e}); programs are traced",
            once=("unreadable", directory))
        return None
    try:
        entry = pickle.loads(blob)
        if entry.get("format") != FORMAT or entry.get("key") != key:
            raise ValueError("the file holds another key's program")
        import jax
        from jax.experimental import serialize_executable

        devices = [d for d in jax.devices() if d.id in device_ids] \
            or jax.devices()[:1]
        compiled = serialize_executable.deserialize_and_load(
            entry["serialized"], entry["in_tree"], entry["out_tree"],
            backend=devices[0].client, execution_devices=devices)
        return compiled, entry.get("notes") or {}
    except Exception as e:  # noqa: BLE001 — never fatal: any bad file is a miss
        say(f"{path} does not load ({type(e).__name__}: {e}); removed, "
            f"tracing {program}")
        _remove(path)
        return None


def save(directory: str, program: str, key: str, compiled, notes: dict) -> bool:
    """File ``compiled`` under ``key`` (a temporary name, then a rename).
    False, with a line on stderr, when it cannot be serialized or the
    directory cannot be written (that said once a directory): the program is
    served from its compile either way."""
    if ("unwritable", directory) in _said:
        return False
    try:
        from jax.experimental import serialize_executable

        serialized, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps({"format": FORMAT, "key": key, "program": program,
                             "serialized": serialized, "in_tree": in_tree,
                             "out_tree": out_tree, "notes": notes},
                            protocol=pickle.HIGHEST_PROTOCOL)
    except Exception as e:  # noqa: BLE001 — never fatal: served from its compile
        say(f"{program} cannot be serialized ({type(e).__name__}: {e}); "
            f"served from its compile")
        return False
    path = _path(directory, program, key)
    tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        os.makedirs(directory, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        return True
    except OSError as e:
        say(f"{directory} cannot be written ({e}); programs are served from "
            f"their compiles and traced again at the next start",
            once=("unwritable", directory))
        _remove(tmp)
        return False

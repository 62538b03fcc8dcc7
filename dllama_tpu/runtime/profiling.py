"""Per-token Eval/Sync split and collective-traffic accounting.

Reference parity target: dllama.cpp prints, for every generated token,
``Eval ms / Sync ms / Sent kB / Recv kB`` (src/dllama.cpp:59-67) from its
executor timers and socket byte counters (src/nn/nn-network.cpp:493-508).
On TPU the whole step is ONE fused XLA program — there is no host-visible
seam between "eval" and "sync" to put a timer on — so the split comes from
the two places it actually exists:

* **time**: a one-off profiler capture of a few steady-state decode steps,
  post-processed here by classifying device-lane events into collective vs
  compute time (``measure_eval_sync``). The measured sync fraction is then
  applied to every token's wall time (the program is identical every step,
  so the fraction is stationary).
* **bytes**: the compiled HLO, where every collective's payload shape is
  static (``collective_traffic``) — per-token wire traffic on TPU is a
  compile-time constant, which is *stronger* accounting than the reference's
  runtime socket counters.

Both are cheap after the first call and neither touches the decode hot path.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import sys
import tempfile
import threading
import time
from dataclasses import dataclass


class CaptureBusyError(RuntimeError):
    """A profiler capture is already running (the profiler supports one
    session per process; ``POST /debug/profile`` maps this to HTTP 409)."""


# THE jax.profiler.trace entry point: the CLI's --profile, the HTTP
# POST /debug/profile window, and measure_eval_sync all come through here,
# so session-at-a-time serialization lives in exactly one place.
_capture_lock = threading.Lock()


@contextlib.contextmanager
def capture(trace_dir: str):
    """Run one profiler session writing xplane traces under ``trace_dir``.
    Raises :class:`CaptureBusyError` instead of the profiler's internal
    error when a session is already active."""
    import jax

    if not _capture_lock.acquire(timeout=0.5):
        raise CaptureBusyError("a profiler capture is already in progress")
    try:
        with jax.profiler.trace(trace_dir):
            yield
    finally:
        _capture_lock.release()

# -- xplane trace parsing ----------------------------------------------------

# Event names that are collective communication (or waiting on it).
# Covers TPU HLO op names (all-reduce.1, all-gather-start.2, ...), the CPU
# backend's jaxpr-derived thunk names (psum.7, ppermute.3), and the CPU
# runtime's cross-device rendezvous machinery.
_SYNC_RE = re.compile(
    r"(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|collective-broadcast|^psum\b|^psum[._]|^ppermute[._]?|^all_gather"
    r"|^all_to_all|^reduce_scatter|rendezvous|^wait\b|^wait:)",
    re.IGNORECASE)

# Runtime bookkeeping events on device lanes that are neither compute nor
# sync (executor scaffolding); excluded from both classes.
_NOISE_RE = re.compile(
    r"(ExecuteHelper|Handle inputs|CreateOutputs|Execute$|::)")

# -- per-op attribution classes ----------------------------------------------
#
# The ROADMAP #2 loop (profile → A/B → promote) classifies device time into
# the op families a decode-step optimization targets. First match wins, so
# order matters: a collective is a collective even when its name mentions a
# dot; attention fusions are named before the generic matmul family; the
# sampler's sort/top-k ops before anything else they could pattern-match.
# Best-effort by construction — on TPU most compute arrives as opaque
# `fusion.N` events, which honestly land in "other" (the tool prints the
# top ops so an operator can still see what a fat fusion contains).
OP_CLASSES = (
    ("collective", _SYNC_RE),
    ("attention", re.compile(r"(attention|attn|flash|softmax)",
                             re.IGNORECASE)),
    ("sampling", re.compile(r"(top_k|top-k|sort|argmax|arg_max|cumsum|"
                            r"categorical|gumbel|threefry|random|rng_bit)",
                            re.IGNORECASE)),
    ("gemv/matmul", re.compile(r"(dot_general|dot\b|dot\.|_dot_|matmul|"
                               r"gemm|gemv|einsum|convolution)",
                               re.IGNORECASE)),
    ("dequant", re.compile(r"(dequant|quantize|convert_element_type|"
                           r"convert\b|bitcast_convert)", re.IGNORECASE)),
)


# A TPU device lane names each event with the WHOLE HLO instruction
# (`%psum.22 = f32[1,1,2048]{2,1,0:T(1,128)S(1)} all-reduce(%bitcast.253),
# ...`, seen on the v5e, PR 22), so a name regex would call any fusion that
# merely consumes `%all-reduce.3` a collective. Such a name is reduced to
# "<instruction> <opcode>" first; the opcode is the first word that follows
# whitespace and precedes "(" (tilings like `T(8,128)` follow ":" or ")").
_HLO_INSTR_RE = re.compile(r"^%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def op_label(name: str) -> str:
    """The event name every classifier below reads: CPU thunk names pass
    through, a TPU lane's full-instruction names become
    ``"psum.22 all-reduce"``."""
    m = _HLO_INSTR_RE.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def classify_op(name: str) -> str:
    """Op-class label for one device event name (see :data:`OP_CLASSES`;
    ``"other"`` for everything unmatched)."""
    for cls, rx in OP_CLASSES:
        if rx.search(name):
            return cls
    return "other"


def empty_attribution(n_steps: int = 0) -> dict:
    """The op-attribution result shape with nothing in it — THE schema
    both :func:`op_attribution` and the idle-window ``?ops=1`` fallback
    build on, so the empty and populated responses can never diverge."""
    return {"n_steps": n_steps, "n_lanes": 0, "lanes": [],
            "device_busy_ms_per_step": 0.0, "classes": {}, "top_ops": [],
            "total_ms_per_step": 0.0, "sum_over_union": 0.0}


def op_attribution(trace_dir: str | None = None, *, xspace=None,
                   n_steps: int = 1, top: int = 25) -> dict:
    """Per-op device-time decomposition of an xplane capture, served
    live via ``POST /debug/profile?ops=1``. Takes either a trace directory (newest
    ``*.xplane.pb`` inside) or an already-parsed ``xspace``.

    Attribution comes from the PRIMARY lane (the device lane with the
    largest interval-union busy time): per-op duration sums, the op-class
    rollup of :data:`OP_CLASSES`, and the top ops by time. The
    sum-vs-union reconcile rides along because summed per-op times can
    double-count nested/overlapping rows — ``sum_over_union`` is the
    primary lane's per-op sum over THAT lane's own union (same lane both
    sides, so a multi-lane capture can't deflate it), and far above 1.0
    means the per-op percentages overstate absolute time.
    ``device_busy_ms_per_step`` is the all-lane union — the honest
    whole-device busy figure. All times are ms, averaged per step with
    ``n_steps``."""
    if xspace is None:
        if trace_dir is None:
            raise ValueError("op_attribution needs trace_dir or xspace")
        pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
        if not pbs:
            raise RuntimeError(f"no xplane.pb under {trace_dir}")
        xspace = _load_xplane(max(pbs, key=os.path.getmtime))

    lanes = []           # per-lane {plane, line, sum_ms, union_ms, n_events}
    all_iv: list[tuple[int, int]] = []
    best = None          # (union_ns, per_op_ns, per_op_count)
    for plane, line in _device_lines(xspace):
        names = {e.id: e.name for e in plane.event_metadata.values()} \
            if hasattr(plane.event_metadata, "values") else {}
        iv, s_ns, n = [], 0, 0
        ops: dict[str, int] = {}
        ops_n: dict[str, int] = {}
        # XEvent.offset_ps is relative to ITS line's timestamp_ns: rebase
        # to absolute ns so the cross-lane union compares real intervals
        base_ns = getattr(line, "timestamp_ns", 0) or 0
        for ev in line.events:
            name = op_label(names.get(ev.metadata_id, str(ev.metadata_id)))
            if _NOISE_RE.search(name):
                continue
            dur = ev.duration_ps // 1000  # -> ns
            start = base_ns + ev.offset_ps // 1000
            iv.append((start, start + dur))
            ops[name] = ops.get(name, 0) + dur
            ops_n[name] = ops_n.get(name, 0) + 1
            s_ns += dur
            n += 1
        u = union_span(iv)
        lanes.append({"plane": plane.name, "line": line.name,
                      "sum_ms": s_ns / 1e6, "union_ms": u / 1e6,
                      "n_events": n})
        all_iv.extend(iv)
        if best is None or u > best[0]:
            best = (u, ops, ops_n)

    steps = max(1, n_steps)
    g_union = union_span(all_iv)
    out = empty_attribution(n_steps)
    out["n_lanes"] = len(lanes)
    out["lanes"] = lanes
    out["device_busy_ms_per_step"] = g_union / 1e6 / steps
    if best is None:
        return out
    best_u, per_op, per_op_n = best
    total_ns = sum(per_op.values())
    out["total_ms_per_step"] = total_ns / 1e6 / steps
    out["sum_over_union"] = round(total_ns / max(1, best_u), 3)
    classes: dict[str, float] = {}
    for name, ns in per_op.items():
        cls = classify_op(name)
        classes[cls] = classes.get(cls, 0.0) + ns
    out["classes"] = {
        cls: {"ms_per_step": round(ns / 1e6 / steps, 4),
              "frac": round(ns / max(1, total_ns), 4)}
        for cls, ns in sorted(classes.items(), key=lambda kv: -kv[1])}
    out["top_ops"] = [
        {"name": name, "class": classify_op(name),
         "ms_per_step": round(ns / 1e6 / steps, 4),
         "count": per_op_n[name], "frac": round(ns / max(1, total_ns), 4)}
        for name, ns in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]
    return out


def union_span(intervals: list[tuple[int, int]]) -> int:
    """Total covered length of possibly-overlapping [start, end] spans, in
    the caller's units — nested profiler events (a rendezvous wait inside a
    psum span) must not double-count. THE one interval-union sweep (the
    Eval/Sync split and op_attribution both use it)."""
    if not intervals:
        return 0
    intervals.sort()
    total = 0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += cur_e - cur_s
    return total


def _union_ms(intervals: list[tuple[int, int]]) -> float:
    """:func:`union_span` over ps spans, in ms."""
    return union_span(intervals) / 1e9


# CPU-backend executor lane families. The naming has changed across jaxlib's
# CPU-runtime rewrites: tf_XLAPjRt* client threads (older), then the thunk
# runtime's tf_XLAEigen* per-device intra-op pools with tf_XLATfrtCpuClient*
# dispatch threads around them. Under jaxlib 0.9 BOTH tf_XLAPjRtCpuClient*
# and tf_XLAEigen* lanes carry thunk-level op events (the client thread runs
# a program's first thunks, the pool the rest, collectives included), so the
# family is chosen by what it carries, not by its name: the one with the
# most op events (this order breaks ties).
_CPU_LANE_FAMILIES = ("tf_XLAPjRt", "tf_XLAEigen", "tf_XLATfrtCpuClient")


def _device_lines(xspace):
    """(plane, line) pairs for lanes that carry per-op device events:
    TPU/GPU ``/device:*`` planes (the line named exactly "XLA Ops": a v5e
    plane also has "XLA Modules", "Async XLA Ops" and "TC Overlay", and
    taking every line whose name CONTAINS "XLA Ops" counted each device as
    two lanes), or the CPU backend's executor lanes. Exactly ONE lane family is used — the one carrying
    the most op events (runtime bookkeeping excluded) — because mixing
    families would inflate the lane count (client dispatch threads are not
    devices) and skew the per-lane average the Eval/Sync split divides by."""
    device: list = []
    families: dict[str, list] = {f: [] for f in _CPU_LANE_FAMILIES}
    for plane in xspace.planes:
        is_dev = "/device:" in plane.name
        for line in plane.lines:
            if is_dev and (line.name == "XLA Ops"
                           or len(plane.lines) == 1):
                device.append((plane, line))
                continue
            for fam in _CPU_LANE_FAMILIES:
                if line.name.startswith(fam):
                    families[fam].append((plane, line))
                    break
    if device:
        return device

    def n_ops(fam: str) -> int:
        return sum(1 for plane, line in families[fam] for ev in line.events
                   if not _NOISE_RE.search(
                       plane.event_metadata[ev.metadata_id].name))

    best = max(_CPU_LANE_FAMILIES, key=n_ops)  # first of the maxima
    return families[best] if n_ops(best) else []


_xplane_pb2 = None


def _load_xplane(path: str):
    """Parse an .xplane.pb via TF's generated proto WITHOUT importing the
    tensorflow package (its __init__ is tens of seconds and half a GB): the
    generated module only needs google.protobuf, so it loads by file path —
    no sys.path mutation, nothing else in the TF tree becomes importable."""
    global _xplane_pb2
    if _xplane_pb2 is None:
        import importlib.util

        pb_py = None
        for p in sys.path:
            cand = os.path.join(p, "tensorflow", "tsl", "profiler",
                                "protobuf", "xplane_pb2.py")
            if os.path.isfile(cand):
                pb_py = cand
                break
        if pb_py is None:
            raise RuntimeError("tensorflow/tsl xplane proto not found")
        spec = importlib.util.spec_from_file_location(
            "dllama_tpu._xplane_pb2", pb_py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _xplane_pb2 = mod

    xs = _xplane_pb2.XSpace()
    with open(path, "rb") as f:
        raw = f.read()
    try:
        xs.ParseFromString(raw)
    except Exception as e:  # proto DecodeError: surface a uniform error
        raise RuntimeError(f"malformed xplane trace {path}: {e}") from e
    return xs


@dataclass
class EvalSyncSplit:
    """Steady-state per-step device-time split, averaged over the profiled
    steps and device lanes."""

    eval_ms: float        # non-collective device time per step per device
    sync_ms: float        # collective + rendezvous time per step per device
    n_steps: int          # steps profiled
    n_lanes: int          # device lanes seen in the trace
    # EXPOSED collective wall: sync lane time NOT covered by concurrent
    # compute on the same lane (union(sync ∪ eval) − union(eval)) — the
    # serialization cost a compute/communication-overlapped program shrinks
    # even when total collective time grows. Published as
    # dllama_comm_exposed_ms by engine.measure_split.
    exposed_ms: float = 0.0

    @property
    def sync_frac(self) -> float:
        tot = self.eval_ms + self.sync_ms
        return self.sync_ms / tot if tot > 0 else 0.0


def split_from_trace(trace_dir: str, n_steps: int) -> EvalSyncSplit:
    """Post-process the newest xplane.pb under ``trace_dir``."""
    pbs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
    if not pbs:
        raise RuntimeError(f"no xplane.pb under {trace_dir}")
    xs = _load_xplane(max(pbs, key=os.path.getmtime))

    sync_ms = eval_ms = exposed_ms = 0.0
    n_lanes = 0
    for plane, line in _device_lines(xs):
        evmeta = plane.event_metadata
        sync_iv: list[tuple[int, int]] = []
        eval_iv: list[tuple[int, int]] = []
        for ev in line.events:
            name = op_label(evmeta[ev.metadata_id].name)
            if _NOISE_RE.search(name):
                continue
            span = (ev.offset_ps, ev.offset_ps + ev.duration_ps)
            (sync_iv if _SYNC_RE.search(name) else eval_iv).append(span)
        if not sync_iv and not eval_iv:
            continue
        n_lanes += 1
        s = _union_ms(sync_iv)
        sync_ms += s
        # compute time nested under / overlapping a sync span counts once,
        # as sync (it is time the lane spent inside the collective)
        both = _union_ms(eval_iv + sync_iv)
        ev_only = _union_ms(eval_iv)
        eval_ms += max(0.0, both - s)
        # exposed = sync wall with no concurrent compute on this lane:
        # union(sync ∪ eval) − union(eval). A collective fully hidden
        # behind compute contributes sync time but zero exposed time.
        exposed_ms += max(0.0, both - ev_only)
    lanes = max(1, n_lanes)
    return EvalSyncSplit(eval_ms=eval_ms / lanes / max(1, n_steps),
                         sync_ms=sync_ms / lanes / max(1, n_steps),
                         n_steps=n_steps, n_lanes=n_lanes,
                         exposed_ms=exposed_ms / lanes / max(1, n_steps))


def measure_eval_sync(step, n_steps: int = 3) -> EvalSyncSplit:
    """Profile ``step()`` (already compiled; must block until ready) for
    ``n_steps`` calls and return the classified device-time split.

    The process's FIRST profiler session initializes tracing lazily and
    misses most thunk-level device events (observed on the CPU backend:
    an almost-empty first capture, a rich second one) — so a throwaway
    warm-up session runs first."""
    with tempfile.TemporaryDirectory(prefix="dllama-prof-") as d:
        with capture(os.path.join(d, "warmup")):
            step()
        with capture(os.path.join(d, "capture")):
            for _ in range(n_steps):
                step()
        return split_from_trace(os.path.join(d, "capture"), n_steps)


def live_split_summary(engine, duration_s: float, *,
                       include_ops: bool = False) -> dict:
    """``POST /debug/profile``: hold a profiler window open over whatever
    decode steps the serving loop dispatches in the next ``duration_s``
    seconds, then classify the captured device time into the Eval/Sync
    split and attach the engine's static collective-traffic accounting.
    Zero live traffic gives a zero split (still parseable), never an error.

    Unlike :func:`measure_eval_sync` this cannot run a warm-up session
    first (the steps are live, not scratch), so the process's very first
    capture may be event-poor — drive traffic and call it twice when the
    first summary comes back empty."""
    from . import telemetry

    reg = telemetry.registry()

    def _steps() -> int:
        return (reg.histogram(telemetry.BATCH_STEP_MS).count()
                + reg.histogram(telemetry.DECODE_STEP_MS).count())

    n0 = _steps()
    ops = None
    with tempfile.TemporaryDirectory(prefix="dllama-live-prof-") as d:
        with capture(d):
            time.sleep(duration_s)
        n = _steps() - n0
        try:
            split = split_from_trace(d, max(1, n))
        except RuntimeError:
            # no xplane written (idle window on some backends): empty split
            split = EvalSyncSplit(eval_ms=0.0, sync_ms=0.0, n_steps=0,
                                  n_lanes=0)
        if include_ops:
            # the per-op view (?ops=1): same capture, decomposed through
            # op_attribution — an idle/empty window yields the empty
            # attribution shape, never an error
            try:
                ops = op_attribution(d, n_steps=max(1, n))
            except RuntimeError:
                ops = empty_attribution()
    out = {
        "duration_ms": duration_s * 1000.0,
        "n_steps": n,
        "eval_ms": split.eval_ms,
        "sync_ms": split.sync_ms,
        "sync_frac": split.sync_frac,
        "n_lanes": split.n_lanes,
        "collective_traffic": None,
    }
    if ops is not None:
        out["op_attribution"] = ops
    try:
        tr = engine.collect_traffic()
        out["collective_traffic"] = {
            "sent_kb_per_token": tr.sent_kb, "recv_kb_per_token": tr.recv_kb,
            "n_collectives": tr.n_collectives, "by_kind": tr.by_kind}
    except Exception as e:  # noqa: BLE001 — traffic is additive; say why
        out["collective_traffic_error"] = f"{type(e).__name__}: {e}"
    return out


# -- static collective-traffic accounting ------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

# Matches only the DEFINING instruction: the opcode must come directly after
# the `= <type>[shape]` result (possibly a (tuple,...) for async -start ops)
# and be followed by its `(` operand list — consumer lines that merely
# reference `%all-reduce.3` as an operand never match, and the -done half of
# an async start/done pair is skipped so each collective counts once.
# The layout after the shape is skipped as one `{...}` group: the TPU
# compiler writes tilings with parentheses there (`{2,1,0:T(1,128)S(1)}`),
# which a "no `(` before the opcode" rule read as "not a collective" — on
# the chip every tp program reported zero collectives (PR 22).
_COLL_RE = re.compile(
    r"=\s*\(?\s*([a-z0-9]+)\[([0-9,]*)\](?:\{[^}]*\})?"
    r"(?:,\s*[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)*\)?\s"
    r"((?:all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(?:-start|-done)?)\(")

# group size from the instruction's replica_groups: `{{0,1},{2,3}}` (explicit
# lists -> size of the first group) or iota v2 `[4,2]<=[8]` (groups x size)
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]<=")


@dataclass
class TrafficStats:
    """Per-device, per-step collective wire traffic from the compiled HLO.

    ``sent_kb``/``recv_kb`` use the standard ring-algorithm byte model over
    each collective's OWN replica group (parsed from the instruction; the
    global device count is only the fallback). With group size ``n`` and the
    op's result bytes ``R``: all-reduce moves ``2(n-1)/n × R`` per device,
    reduce-scatter ``(n-1) × R`` (its result is the 1/n shard), everything
    else ``(n-1)/n × R``. Collectives inside a while-loop body (the layer
    ``lax.scan`` compiles to one) appear ONCE in the HLO but execute once per
    iteration — the caller supplies ``loop_multiplier`` (= n_layers for a
    decode step) to scale them. The reference reports measured socket bytes
    (nn-network.cpp:493-508); on TPU the program — and therefore the traffic
    — is a compile-time constant, so this accounting is exact in shape and
    model-based only in the ring factor."""

    sent_kb: float
    recv_kb: float
    n_collectives: int
    by_kind: dict

    def __bool__(self) -> bool:
        return self.n_collectives > 0


_COMP_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")


def collective_traffic(hlo_text: str, n_devices: int,
                       loop_multiplier: int = 1) -> TrafficStats:
    body_names = set(_WHILE_BODY_RE.findall(hlo_text))
    by_kind: dict[str, float] = {}
    n = 0
    total_kb = 0.0
    current_comp = None
    for line in hlo_text.splitlines():
        hm = _COMP_HEADER_RE.match(line)
        if hm is not None:
            current_comp = hm.group(1)
            continue
        m = _COLL_RE.search(line)
        if m is None:
            continue
        mult = loop_multiplier if current_comp in body_names else 1
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if kind.endswith("-done"):
            continue  # the -start half already counted this collective
        kind = kind.removesuffix("-start")
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue
        gm = _GROUPS_LIST_RE.search(line)
        if gm is not None:
            group = gm.group(1).count(",") + 1  # {{0}} -> 1 -> moves nothing
        else:
            gm = _GROUPS_IOTA_RE.search(line)
            # iota form, or `replica_groups={}` = all participants
            group = int(gm.group(1)) if gm else n_devices
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        payload_kb = numel * nbytes / 1024.0
        if kind == "all-reduce":
            moved = 2.0 * payload_kb * (group - 1) / group
        elif kind == "reduce-scatter":
            moved = payload_kb * (group - 1)  # result is the 1/group shard
        else:
            moved = payload_kb * (group - 1) / group
        moved *= mult
        by_kind[kind] = by_kind.get(kind, 0.0) + moved
        total_kb += moved
        n += mult
    return TrafficStats(sent_kb=total_kb, recv_kb=total_kb,
                        n_collectives=n, by_kind=by_kind)

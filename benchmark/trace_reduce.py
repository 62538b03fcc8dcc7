"""From a profiler trace (``.xplane.pb``) to numbers: device busy union, idle
share, collective share, time per device operation of each program, the idle
gaps by what the host was doing, and per-program (XLA module) durations.

The benchmark's own copy of the idea in ``dllama_tpu/runtime/profiling.py``
(``_device_lines``, ``op_label``, ``split_from_trace``), so that a later PR
cannot move the yardstick. Reads the file with ``jax.profiler.ProfileData``
alone. Checked against ``fixtures/tiny.xplane.pb`` by the self-test.
"""

from __future__ import annotations

import bisect
import re
from collections.abc import Callable

# a v5e lane names each event with the whole HLO instruction:
#   %all-reduce.3 = f32[1,1,2048]{2,1,0:T(1,128)S(1)} all-reduce(...)
_HLO_INSTR = re.compile(r"^%([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_COLLECTIVE = re.compile(r"(^|[\s%])(all-reduce|all-gather|reduce-scatter|collective-permute|"
                         r"all-to-all|psum|ppermute)")
_CPU_LANES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
_NOISE = re.compile(r"^(ThreadpoolListener|ExecuteHelper|PjitFunction)")
_CONTAINERS = (" while", " conditional", " call")   # their bodies' ops are events of their own
SPAN_PREFIX = "bench."
MIN_GAP_S = 50e-6


def op_label(name: str) -> str:
    """``"all-reduce.3 all-reduce"`` for a TPU lane's full-instruction name;
    any other name passes through."""
    m = _HLO_INSTR.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def is_collective(label: str) -> bool:
    return bool(_COLLECTIVE.search(label))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _lanes(pd):
    """Per device: (name, op events, module events), events as (label, start_s,
    end_s). On a TPU plane the line named exactly "XLA Ops" carries the ops
    and "XLA Modules" the programs. Without device planes (a CPU rehearsal)
    the executor threads stand in as one lane with no modules."""
    lanes, cpu = [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        if "/device:" in plane.name:
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or (lines if len(lines) == 1 else [])
            mods = [ln for ln in lines if ln.name == "XLA Modules"]
            if ops:
                lanes.append((plane.name, _events(ops), _events(mods)))
        else:
            cpu.extend(ln for ln in lines if ln.name.startswith(_CPU_LANES))
    if not lanes and cpu:
        lanes.append(("/host:CPU executor", _events(cpu), []))
    return lanes


def _events(lines) -> list[tuple[str, float, float]]:
    out = []
    for ln in lines:
        for ev in ln.events:
            if ev.duration_ns > 0 and not _NOISE.match(ev.name):
                out.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def _host_spans(pd) -> list[tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if "/device:" in plane.name:
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9))
    return spans


def idle_stretches(busy: list[tuple[float, float]], phases) -> list[tuple[float, float]]:
    """The stretches in which no op ran: between ``busy``'s merged intervals,
    and from the first tick phase to the first op and from the last op to the
    last phase's end (a slice that begins on an empty server begins idle; the
    same hull as ``program_spans.idle_by_phase``)."""
    lo = min([busy[0][0]] + [s for _n, s, _e in phases])
    hi = max([busy[-1][1]] + [e for _n, _s, e in phases])
    return ([(lo, busy[0][0])] + [(b0, a1) for (_a0, b0), (a1, _b1) in zip(busy, busy[1:])]
            + [(busy[-1][1], hi)])


def _overlaps(a: float, b: float, spans) -> dict[str, float]:
    """Seconds of [a, b] under each span name."""
    out: dict[str, float] = {}
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > 0:
            out[name] = out.get(name, 0.0) + ov
    return out


def _label_gap(a: float, b: float, phases, spans) -> dict[str, float]:
    """What the host was doing in the idle stretch [a, b], in seconds by label.
    The program's own spans first: each ``dllama.tick.<phase>`` over the
    stretch gets its part of it (the loop thread feeds the device, and the
    stretch between two steps lies under several phases). What no phase
    covers goes to the benchmark's span over most of the whole stretch, the
    scheduler's callbacks first (they run on that same thread), then the
    generator's sleep."""
    out = _overlaps(a, b, phases)
    rest = (b - a) - sum(out.values())
    if rest < MIN_GAP_S:
        return out
    best = _overlaps(a, b, spans)
    label = next((n for n in ("bench.on_token", "bench.submit", "bench.sleep") if best.get(n, 0.0) > 0.5 * (b - a)),
                 "no span")
    out[label] = out.get(label, 0.0) + rest
    return out


def _tick_phases(pd) -> list[tuple[str, float, float]]:
    """The program's ``dllama.tick.<phase>`` spans as (name, start_s, end_s),
    through ``program_spans``' own grouping (imported here: that module
    imports this one)."""
    import program_spans

    return [(f"{program_spans.ROOT_SPAN}.{phase}", s, e)
            for t in program_spans.group_ticks(program_spans._tick_events(pd))
            for phase, s, e, _stats in t["children"]]


_MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def _module_of(mods) -> Callable[[float], str | None]:
    """start_s -> the name of the program (XLA module) running then, without
    ``jit_`` and the run's id, or ``None``. A fusion's name says nothing of
    its program: ``dynamic-slice_convert_fusion.8`` is one fusion of the decode
    step and another of the prefill chunk."""
    mods = sorted((s, e, _MODULE_NAME.match(n).group(1)) for n, s, e in mods)
    starts = [m[0] for m in mods]

    def at(t: float) -> str | None:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else None

    return at


def reduce(path: str, window_s: float | None = None) -> dict:
    """The trace's numbers. ``window_s`` is the traced window's length on the
    host's clock; without it, the span of the device events is used."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lanes = _lanes(pd)
    if not lanes:
        raise ValueError(f"{path}: no device lane in the trace")
    spans, phases = _host_spans(pd), _tick_phases(pd)
    busy, coll = [], []
    for _name, ops, _mods in lanes:
        busy.append(total(union([(s, e) for _n, s, e in ops])))
        coll.append(total(union([(s, e) for n, s, e in ops if is_collective(op_label(n))])))
    name0, ops0, mods0 = lanes[0]
    if window_s is None:
        window_s = max(e for _n, _s, e in ops0) - min(s for _n, s, _e in ops0)
    per_op: dict[str, float] = {}
    module_at = _module_of(mods0)
    for n, s, e in ops0:
        lab = op_label(n)
        if lab.endswith(_CONTAINERS):
            continue
        mod = module_at(s)
        lab = f"{mod}/{lab}" if mod else lab
        per_op[lab] = per_op.get(lab, 0.0) + (e - s)
    gaps: dict[str, float] = {}
    for a, b in idle_stretches(union([(s, e) for _n, s, e in ops0]), phases):
        if b - a >= MIN_GAP_S:
            for lab, secs in _label_gap(a, b, phases, spans).items():
                gaps[lab] = gaps.get(lab, 0.0) + secs
    modules: dict[str, list[float]] = {}
    for n, s, e in mods0:
        modules.setdefault(n, []).append(e - s)
    busy_s = sum(busy) / len(busy)
    return {
        "n_devices": len(lanes), "n_events": sum(len(ops) for _n, ops, _m in lanes),
        "busy_s": busy_s, "window_s": float(window_s),
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "collective_s": sum(coll) / len(coll),
        "device_ops": [[k, v] for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])],
        "modules": modules,
    }

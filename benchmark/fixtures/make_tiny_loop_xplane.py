#!/usr/bin/env python3
"""Regenerates ``tiny_loop.xplane.pb``, the hand-computable trace that
``readers/loop_life.py`` is checked against (``tests/test_benchmark_loop_life.py``):
the loop's life OUTSIDE its phases, which ``tiny_spans.xplane.pb`` (a program
before PR 56) does not hold. Like ``make_tiny_spans_xplane.py`` it needs JAX
alone. All times in milliseconds.

``/host:CPU`` line "python" holds four ``dllama.tick`` spans, each with
``cpu_us``, and a ``dllama.loop.between_ticks`` span between every two:

* tick 1 [0, 5], cpu 900 us: step_dispatch [0, 1], step_wait [1, 4.8]; nothing
  covers [4.8, 5];
* between ticks [5, 5.5];
* tick 2 [5.5, 10], cpu 1500 us: step_dispatch [5.5, 6.5], step_wait [6.5, 10];
* between ticks [10, 12];
* tick 3 [12, 15], idle, cpu 100 us: idle_wait [12, 15];
* between ticks [15, 15.2];
* tick 4 [15.2, 20], cpu 2100 us: step_dispatch [15.2, 16], step_wait [16, 20].

``/device:TPU:0`` "XLA Ops" is busy [0.5, 4.9], [6, 10.4], [16, 20]: 12.8 of
the 20 ms hull, idle 7.2 ms in [0, 0.5], [4.9, 6] and [10.4, 16].

* under a between-ticks span: [5, 5.5] 0.5 (idle all through), [10, 12] less the
  op's [10, 10.4] 1.6, [15, 15.2] 0.2: 2.3 ms, 11.5% of the hull;
* under no ``dllama.tick.<phase>`` span (what ``idle_unspanned_share`` reads):
  those 2.3 and [4.9, 5] inside tick 1: 2.4 ms;
* work-carrying ticks 1, 2, 4: cpu 0.9, 1.5, 2.1 ms, median 1.5 (tick 3 slept).
"""

import os

from jax.profiler import ProfileData

MS = 10 ** 9          # picoseconds
STATS = {"tick": 1, "n_active": 2, "cpu_us": 3}
GAP = "dllama.loop.between_ticks"


def plane(name, lines):
    meta, out, mid = [], [], 0
    for line_name, events in lines:
        evs = []
        for ev_name, start, end, *stats in events:
            mid += 1
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{ev_name}" }} }}')
            st = " ".join(f"stats {{ metadata_id: {STATS[k]} int64_value: {v} }}"
                          for k, v in (stats[0] if stats else {}).items())
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {round(start * MS)} "
                       f"duration_ps: {round((end - start) * MS)} {st} }}")
        out.append(f'lines {{ name: "{line_name}" {" ".join(evs)} }}')
    smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}' for k, i in STATS.items())
    return f'planes {{ name: "{name}" {" ".join(out)} {" ".join(meta)} {smeta} }}'


def tick(n, start, end, n_active, cpu_us, phases):
    return [("dllama.tick", start, end, {"tick": n, "n_active": n_active, "cpu_us": cpu_us})] + [
        ("dllama.tick." + p[0], *p[1:]) for p in phases]


FUSION = "%fusion.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput"
STEP = "jit_paged_sampled_step_guarded(1)"
TEXT = "\n".join([
    plane("/device:TPU:0", [
        ("XLA Ops", [(FUSION, 0.5, 4.9), (FUSION, 6, 10.4), (FUSION, 16, 20)]),
        ("XLA Modules", [(STEP, 0.5, 4.9), (STEP, 6, 10.4), (STEP, 16, 20)])]),
    plane("/host:CPU", [
        ("python", (
            tick(1, 0, 5, 2, 900, [("step_dispatch", 0, 1), ("step_wait", 1, 4.8)])
            + [(GAP, 5, 5.5)]
            + tick(2, 5.5, 10, 2, 1500, [("step_dispatch", 5.5, 6.5), ("step_wait", 6.5, 10)])
            + [(GAP, 10, 12)]
            + tick(3, 12, 15, 0, 100, [("idle_wait", 12, 15)])
            + [(GAP, 15, 15.2)]
            + tick(4, 15.2, 20, 2, 2100, [("step_dispatch", 15.2, 16), ("step_wait", 16, 20)]))),
        ("generator", [("bench.sleep", 0, 20)])]),
])

if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_loop.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(path, os.path.getsize(path), "bytes")

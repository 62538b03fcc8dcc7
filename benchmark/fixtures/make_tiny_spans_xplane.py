#!/usr/bin/env python3
"""Regenerates ``tiny_spans.xplane.pb``, the hand-computable trace that
``program_spans.py`` and its readers are checked against
(``tests/test_benchmark_program_spans.py``). Like ``make_tiny_xplane.py`` it
needs JAX alone. All times in milliseconds.

``/host:CPU`` line "python" holds four ``dllama.tick`` spans with their phases:

* tick 7 [0, 6], chunk-free: deadlines [0, .1], admit_begin [.1, .2] (admitted=0),
  step_prepare [.2, .5], step_dispatch [.5, 1], step_wait [1, 5], emit [5, 5.8],
  bookkeeping [5.8, 5.9]; nothing covers [5.9, 6]. Children cover 5.9 of 6.
* tick 8 [6, 16], a chunk tick: deadlines [6, 6.1], admit_begin [6.1, 7.1]
  (admitted=1), prefill_dispatch [7.1, 7.6], step_prepare [7.6, 7.8],
  step_dispatch [7.8, 8], step_wait [8, 15], emit [15, 15.6], bookkeeping
  [15.6, 16]. Children cover all of it.
* tick 9 [16, 19], idle: deadlines [16, 16.1], admit_begin [16.1, 16.2]
  (admitted=0), idle_wait [16.2, 19].
* no span at all over [19, 19.5].
* tick 10 [19.5, 24], chunk-free: step_dispatch [19.5, 20], step_wait [20, 24].

``/device:TPU:0`` "XLA Ops" is busy [0, 5], [7.5, 10.5], [10.5, 15], [20.2, 24]:
16.3 of the 24 ms hull, idle 7.7 ms in two stretches.

* [5, 7.5]: emit .8, bookkeeping .1, nothing .1, deadlines .1, admit_begin 1.0,
  prefill_dispatch .4.
* [15, 20.2]: emit .6, bookkeeping .4, deadlines .1, admit_begin .1, idle_wait
  2.8, nothing .5, step_dispatch .5, step_wait .2.

So idle under a host phase 4.3 ms (emit 1.4, admit_begin 1.1, bookkeeping .5,
step_dispatch .5, prefill_dispatch .4, deadlines .2, step_wait .2), under
``idle_wait`` 2.8 ms, under no span .6 ms. Work-carrying ticks 7, 8, 10: wall less
step_wait 2.0, 3.0, .5 ms (median 2.0); the admitting admit_begin takes 1.0 ms;
chunk-free step_wait 4.0 and 4.0 ms (the chunk tick's 7.0 is left out).
"""

import os

from jax.profiler import ProfileData

MS = 10 ** 9          # picoseconds
STATS = {"tick": 1, "n_active": 2, "admitted": 3}


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


def tick(n, start, end, n_active, phases):
    return [("dllama.tick", start, end, {"tick": n, "n_active": n_active})] + [
        ("dllama.tick." + p[0], *p[1:]) for p in phases]


FUSION = "%fusion.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput"
TEXT = "\n".join([
    plane("/device:TPU:0", [
        ("XLA Ops", [(FUSION, 0, 5), (FUSION, 7.5, 10.5), (FUSION, 10.5, 15), (FUSION, 20.2, 24)]),
        ("XLA Modules", [("jit_paged_sampled_step_guarded(1)", 0, 5), ("jit_forward(2)", 7.5, 10.5),
                         ("jit_paged_sampled_step_guarded(1)", 10.5, 15),
                         ("jit_paged_sampled_step_guarded(1)", 20.2, 24)])]),
    plane("/host:CPU", [
        ("python", (
            tick(7, 0, 6, 2, [("deadlines", 0, .1), ("admit_begin", .1, .2, {"admitted": 0}),
                              ("step_prepare", .2, .5), ("step_dispatch", .5, 1), ("step_wait", 1, 5),
                              ("emit", 5, 5.8), ("bookkeeping", 5.8, 5.9)])
            + [("bench.on_token", 5.1, 5.7)]
            + tick(8, 6, 16, 2, [("deadlines", 6, 6.1), ("admit_begin", 6.1, 7.1, {"admitted": 1}),
                                 ("prefill_dispatch", 7.1, 7.6), ("step_prepare", 7.6, 7.8),
                                 ("step_dispatch", 7.8, 8), ("step_wait", 8, 15), ("emit", 15, 15.6),
                                 ("bookkeeping", 15.6, 16)])
            + tick(9, 16, 19, 0, [("deadlines", 16, 16.1), ("admit_begin", 16.1, 16.2, {"admitted": 0}),
                                  ("idle_wait", 16.2, 19)])
            + tick(10, 19.5, 24, 3, [("step_dispatch", 19.5, 20), ("step_wait", 20, 24)]))),
        ("generator", [("bench.sleep", 0, 24), ("PjitFunction(forward)", 7.2, 7.5)])]),
])

if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_spans.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(path, os.path.getsize(path), "bytes")

#!/usr/bin/env python3
"""Regenerates ``tiny_step.xplane.pb`` and ``tiny_step_parent.xplane.pb``, the
hand-computable traces that ``readers/step_account.py`` and ``readers/phase_ms.py``
are checked against (``tests/test_benchmark_step_account.py``). Like
``make_tiny_spans_xplane.py`` it needs JAX alone. All times in milliseconds.

``/host:CPU`` line "python" of ``tiny_step`` holds three ``dllama.tick`` spans:

* tick 1 [0, 10], chunk-free: step_prepare [0, .3], step_upload [.3, 1.3]
  (arrays=9, bytes=1348), step_dispatch [1.3, 2], step_wait [2, 9], emit [9, 9.8],
  bookkeeping [9.8, 10]. Fetches: tokens [2, 8.4], nonfinite [8.5, 8.8].
  Device: the step's program M [2.5, 8] with ops [2.5, 4] and [4.4, 7.9]; after
  it a scatter's program and its one op [9.2, 9.5].
  lag 2.5 - 2 = .5, module 5.5, tail 9 - 8 = 1.0 (.5 + 5.5 + 1.0 = 7 = step_wait);
  inner 5.5 - (1.5 + 3.5) = .5; the first fetch starts before M ends and is no
  copy, the second starts after: copy .3; ops outside M .3.
* tick 2 [10, 19], chunk-free, the device ahead of the call: step_prepare
  [10, 10.2], step_upload [10.2, 11], step_dispatch [11, 12.5], step_wait
  [12.5, 18], emit [18, 19]. Fetches: accepted [12.5, 17.2], tokens [17.25, 17.4],
  nonfinite [17.45, 17.5]. Device: M [12, 17] with ops [12, 14] and [14.25, 17].
  lag 12 - 12.5 = -.5, module 5.0, tail 1.0 (-.5 + 5 + 1 = 5.5 = step_wait);
  inner .25; copy .15 + .05 = .2; nothing outside M.
* tick 3 [19, 32], a chunk tick: admit_begin [19, 19.5] (admitted=1, rids "7"),
  prefill_dispatch [19.5, 20.5] (rid=7, tokens=20, bucket=32), step_upload
  [20.5, 21], step_dispatch [21, 21.5], step_wait [21.5, 31], emit [31, 32].
  Device: ``jit_forward`` [20.6, 25], then M [25, 30.5]. It has a
  ``prefill_dispatch`` and is left out of the account.

So over ticks 1 and 2 the medians are lag 0.0, module 5.25, tail 1.0, inner .375,
copy .25, outside .15; over the three work-carrying ticks ``step_upload`` is
1.0, .8, .5 (median .8) and ``step_dispatch`` .7, 1.5, .5 (median .7).

``tiny_step_parent`` is the same slice as a program from before PR 40 writes it:
no ``step_upload`` (``step_dispatch`` runs from where it began), no fetch span.
Both readers give ``None`` for it.
"""

import os

from jax.profiler import ProfileData

MS = 10 ** 9          # picoseconds
STATS = ("tick", "n_active", "admitted", "rids", "rid", "tokens", "bucket", "arrays", "bytes", "what")


def plane(name, lines):
    meta, out, mid = [], [], 0
    for line_name, events in lines:
        evs = []
        for ev_name, start, end, *stats in events:
            mid += 1
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{ev_name}" }} }}')
            st = " ".join(f"stats {{ metadata_id: {STATS.index(k) + 1} "
                          + (f'str_value: "{v}"' if isinstance(v, str) else f"int64_value: {v}") + " }"
                          for k, v in (stats[0] if stats else {}).items())
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {round(start * MS)} "
                       f"duration_ps: {round((end - start) * MS)} {st} }}")
        out.append(f'lines {{ name: "{line_name}" {" ".join(evs)} }}')
    smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
                     for i, k in enumerate(STATS, start=1))
    return f'planes {{ name: "{name}" {" ".join(out)} {" ".join(meta)} {smeta} }}'


def tick(n, start, end, n_active, phases, fetches=()):
    return ([("dllama.tick", start, end, {"tick": n, "n_active": n_active})]
            + [("dllama.tick." + p[0], *p[1:]) for p in phases]
            + [("dllama.step.fetch", s, e, {"what": what}) for what, s, e in fetches])


FUSION = "%fusion.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput"
STEP, FORWARD, SCATTER = "jit_paged_sampled_step_guarded(1)", "jit_forward(2)", "jit_scatter_kv_blocks(3)"
DEVICE = plane("/device:TPU:0", [
    ("XLA Ops", [(FUSION, 2.5, 4), (FUSION, 4.4, 7.9), (FUSION, 9.2, 9.5), (FUSION, 12, 14), (FUSION, 14.25, 17),
                 (FUSION, 20.6, 25), (FUSION, 25, 30.5)]),
    ("XLA Modules", [(STEP, 2.5, 8), (SCATTER, 9.2, 9.5), (STEP, 12, 17), (FORWARD, 20.6, 25), (STEP, 25, 30.5)])])


def host(split: bool) -> str:
    """The loop thread's line; ``split`` False is the parent's shape."""
    def head(prepare_end, upload_end, call_end, stats):
        if split:
            return [("step_upload", prepare_end, upload_end, stats), ("step_dispatch", upload_end, call_end)]
        return [("step_dispatch", prepare_end, call_end)]

    fetches = (lambda *f: f) if split else (lambda *f: ())
    return plane("/host:CPU", [("python", (
        tick(1, 0, 10, 1, [("step_prepare", 0, .3), *head(.3, 1.3, 2, {"arrays": 9, "bytes": 1348}),
                           ("step_wait", 2, 9), ("emit", 9, 9.8), ("bookkeeping", 9.8, 10)],
             fetches(("tokens", 2, 8.4), ("nonfinite", 8.5, 8.8)))
        + tick(2, 10, 19, 1, [("step_prepare", 10, 10.2), *head(10.2, 11, 12.5, {"arrays": 9, "bytes": 1348}),
                              ("step_wait", 12.5, 18), ("emit", 18, 19)],
               fetches(("accepted", 12.5, 17.2), ("tokens", 17.25, 17.4), ("nonfinite", 17.45, 17.5)))
        + tick(3, 19, 32, 2, [("admit_begin", 19, 19.5, {"admitted": 1, "rids": "7"} if split else {"admitted": 1}),
                              ("prefill_dispatch", 19.5, 20.5, {"rid": 7, "tokens": 20, "bucket": 32} if split else {}),
                              *head(20.5, 21, 21.5, {"arrays": 9, "bytes": 1348}),
                              ("step_wait", 21.5, 31), ("emit", 31, 32)],
               fetches(("tokens", 21.5, 30.7), ("nonfinite", 30.8, 30.9)))))])


TEXTS = {"tiny_step.xplane.pb": "\n".join([DEVICE, host(True)]),
         "tiny_step_parent.xplane.pb": "\n".join([DEVICE, host(False)])}

if __name__ == "__main__":
    for name, text in TEXTS.items():
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
        with open(path, "wb") as f:
            f.write(ProfileData.text_proto_to_serialized_xspace(text))
        print(path, os.path.getsize(path), "bytes")

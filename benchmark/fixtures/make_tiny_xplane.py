#!/usr/bin/env python3
"""Regenerates ``tiny.xplane.pb``, the hand-computable trace the self-test
reduces (the repo's ``tools/make_xplane_fixture.py`` shows the same idea with
the TensorFlow proto; this one needs JAX alone). All times in picoseconds;
1 ms = 1e9 ps. What it holds, and what the reduction must find:

* ``/device:TPU:0`` "XLA Ops": a fusion [0, 4] ms; an all-reduce [4, 6] ms with
  a nested wait [4.5, 5.5] ms that must not count twice; a fusion [5, 7] ms
  overlapping it; idle [7, 9] ms; a fusion [9, 10] ms. Busy union 8 ms,
  collective 2 ms. "XLA Modules": one ``jit_paged_sampled_step_guarded`` of
  7 ms and two ``jit_forward`` programs (1 ms and 3 ms: the widest is 3 ms).
  A line "Async XLA Ops" that must be ignored.
* ``/device:TPU:1`` "XLA Ops": a fusion [0, 3] ms and a psum-named
  all-reduce [3, 4] ms. Busy 4 ms, collective 1 ms.
* ``/host:CPU``: ``bench.on_token`` [7.0, 8.5] ms covers most of device 0's
  gap; ``bench.sleep`` [0, 10] ms; an unrelated event that must be ignored.

Over a 10 ms window: busy_s = (8 + 4) / 2 = 6 ms, idle share 0.4,
collective_s = (2 + 1) / 2 = 1.5 ms.
"""

import os

from jax.profiler import ProfileData

MS = 10 ** 9


def plane(name, lines):
    meta, out, mid = [], [], 0
    for line_name, events in lines:
        evs = []
        for ev_name, start, dur in events:
            mid += 1
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{ev_name}" }} }}')
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {int(start * MS)} duration_ps: {int(dur * MS)} }}")
        out.append(f'lines {{ name: "{line_name}" {" ".join(evs)} }}')
    return f'planes {{ name: "{name}" {" ".join(out)} {" ".join(meta)} }}'


TEXT = "\n".join([
    plane("/device:TPU:0", [
        ("XLA Ops", [
            ("%fusion.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput", 0, 4),
            ("%all-reduce.3 = f32[1,1,2048]{2,1,0:T(1,128)S(1)} all-reduce(%fusion.1), replica_groups={}", 4, 2),
            ("%wait.1 = () custom-call(%all-reduce.3)", 4.5, 1),
            ("%fusion.2 = bf16[16,4096]{1,0} fusion(%all-reduce.3), kind=kLoop", 5, 2),
            ("%fusion.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%p0, %p1), kind=kOutput", 9, 1)]),
        ("XLA Modules", [
            ("jit_paged_sampled_step_guarded(123)", 0, 7),
            ("jit_forward(77)", 7, 1), ("jit_forward(78)", 8, 3)]),
        ("Async XLA Ops", [("%copy-start.1 = (f32[8]) copy-start(%p2)", 0, 10)])]),
    plane("/device:TPU:1", [
        ("XLA Ops", [
            ("%fusion.3 = bf16[16,4096]{1,0} fusion(%p0), kind=kLoop", 0, 3),
            ("%psum.9 = f32[4096]{0} all-reduce(%fusion.3), to_apply=%add", 3, 1)])]),
    plane("/host:CPU", [
        ("python", [("bench.sleep", 0, 10), ("bench.on_token", 7.0, 1.5),
                    ("PjitFunction(forward)", 0, 50)])]),
])

if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(TEXT))
    print(path, os.path.getsize(path), "bytes")

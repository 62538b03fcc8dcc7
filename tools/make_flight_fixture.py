#!/usr/bin/env python
"""Regenerate ``tests/goldens/flight_dump.json`` — the golden
flight-recorder dump behind the Chrome-trace fixture test.

The fixture is a deterministic mini-run recorded through the REAL
:class:`runtime.flightrec.FlightRecorder` API (injected fake clock, no
jax): three requests stream through two slots with admissions, an
interleaved prefill, a budget preemption, retirements for three
different reasons, paged block-pool occupancy on every tick, and each
tick divided into ``telemetry.TICK_PHASES`` spans (``phases`` /
``phase_spans``, rendered as nested slices on the scheduler track). The
span ring entries are derived from the recorded event timeline, so
spans and ticks share one clock — exactly what a live dump looks like.

Run from the repo root::

    python tools/make_flight_fixture.py

and commit the regenerated golden together with whatever recorder
change made it necessary (tests/test_flightrec.py validates the
conversion, not byte equality, so regeneration is rarely needed).
"""

from __future__ import annotations

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dllama_tpu.runtime import flightrec  # noqa: E402

OUT = REPO / "tests" / "goldens" / "flight_dump.json"

_T0 = 1_000_000_000  # ns
_STEP = 250_000      # 0.25 ms per clock read — every timestamp distinct


class _Clock:
    def __init__(self):
        self.t = _T0

    def __call__(self) -> int:
        self.t += _STEP
        return self.t


def record() -> dict:
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    blocks = {"total": 30, "used": 0, "shared": 0, "reserved": 0}

    def tick(body, slots, used, shared):
        rec.begin_tick(queue_depth=body.pop("queue_depth", 0),
                       n_admissions=body.pop("n_admissions", 0))
        body["run"]()
        blocks.update(used=used, shared=shared)
        rec.end_tick(blocks=dict(blocks), slots=slots, prefill_budget=256)

    for rid, n_prompt in ((0, 24), (1, 9), (2, 17)):
        rec.note("submit", rid, n_prompt=n_prompt, max_tokens=8)

    def t1():
        with rec.tick_phase("admit_begin"):
            rec.note("admit", 0, slot=0, reused=0, n_prompt=24)
            rec.note("admit", 1, slot=1, reused=0, n_prompt=9)
        with rec.tick_phase("prefill_dispatch"):
            rec.note_prefill(0, 2.0, 23)
        with rec.tick_phase("prefill_dispatch"):
            rec.note_prefill(1, 0.9, 8)
        with rec.tick_phase("admit_commit"):
            rec.note("decode_armed", 1, slot=1, pos=8, reused=0)

    tick({"queue_depth": 3, "n_admissions": 0, "run": t1},
         [None, None], 4, 0)

    def step(fn):
        """One decode step's phases around ``fn`` (its emit-side notes)."""
        with rec.tick_phase("step_prepare"):
            pass
        with rec.tick_phase("step_upload") as ph:
            ph.next_phase("step_dispatch")
            ph.next_phase("step_wait")
        with rec.tick_phase("emit"):
            fn()
        with rec.tick_phase("bookkeeping"):
            pass

    def t2():
        rec.note("preempt", 0, reason="prefill_budget")
        with rec.tick_phase("admit_commit"):
            rec.note("decode_armed", 0, slot=0, pos=23, reused=0)

        def emit():
            rec.note_dispatch(1.5, 2, 2)
            rec.note("first_token", 0, slot=0)
            rec.note("first_token", 1, slot=1)

        step(emit)

    tick({"queue_depth": 1, "n_admissions": 1, "run": t2}, [0, 1], 4, 0)

    def t3():
        def emit():
            rec.note_dispatch(1.4, 2, 2)
            rec.note("retire", 1, reason="eos", slot=1, n_tokens=3)

        step(emit)
        with rec.tick_phase("admit_begin"):
            rec.note("admit", 2, slot=1, reused=8, n_prompt=17)
        with rec.tick_phase("prefill_dispatch"):
            rec.note_prefill(2, 0.8, 8)
        with rec.tick_phase("admit_commit"):
            rec.note("decode_armed", 2, slot=1, pos=16, reused=8)

    tick({"queue_depth": 1, "n_admissions": 0, "run": t3}, [0, None], 5, 1)

    def t4():
        def emit():
            rec.note_dispatch(1.6, 2, 2)
            rec.note("first_token", 2, slot=1)
            rec.note("retire", 0, reason="max_tokens", slot=0, n_tokens=8)

        step(emit)

    tick({"queue_depth": 0, "n_admissions": 0, "run": t4}, [None, 2], 5, 1)

    def t5():
        def emit():
            rec.note_dispatch(1.3, 1, 1)
            rec.note("retire", 2, reason="max_tokens", slot=1, n_tokens=8)

        step(emit)

    tick({"queue_depth": 0, "n_admissions": 0, "run": t5}, [None, None], 2, 0)

    # span ring entries derived from the recorded event timeline, so the
    # trace's request tracks line up with the scheduler tick track
    events = rec.snapshot()["events"]

    def at(rid, event):
        return next(e for e in events
                    if e["rid"] == rid and e["event"] == event)

    spans = []
    for rid in (0, 1, 2):
        sub = at(rid, "submit")["t_ns"]
        adm = at(rid, "admit")
        armed = at(rid, "decode_armed")["t_ns"]
        ret = at(rid, "retire")
        slot = adm["slot"]
        spans.append({"request_id": rid, "phase": "queue",
                      "start_ns": sub, "end_ns": adm["t_ns"],
                      "slot": slot, "n_tokens": 0})
        spans.append({"request_id": rid, "phase": "admit",
                      "start_ns": adm["t_ns"] - 100_000,
                      "end_ns": adm["t_ns"], "slot": slot,
                      "n_tokens": adm["reused"]})
        spans.append({"request_id": rid, "phase": "prefill_chunk",
                      "start_ns": adm["t_ns"],
                      "end_ns": adm["t_ns"] + 150_000, "slot": slot,
                      "n_tokens": adm["n_prompt"] - 1 - adm["reused"]})
        spans.append({"request_id": rid, "phase": "prefill",
                      "start_ns": adm["t_ns"], "end_ns": armed,
                      "slot": slot,
                      "n_tokens": adm["n_prompt"] - 1 - adm["reused"]})
        spans.append({"request_id": rid, "phase": "decode",
                      "start_ns": armed, "end_ns": ret["t_ns"],
                      "slot": slot, "n_tokens": ret["n_tokens"]})
    spans.sort(key=lambda s: (s["start_ns"], s["end_ns"]))

    doc = rec.payload("fixture", victims=[],
                      info={"generator": "tools/make_flight_fixture.py"},
                      spans=spans, requests=[])
    doc["pid"] = 0  # byte-stable regeneration
    return doc


def main() -> int:
    doc = record()
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n",
                   encoding="utf-8")
    print(f"✅ wrote {OUT} ({len(doc['ticks'])} ticks, "
          f"{len(doc['events'])} events, {len(doc['spans'])} spans)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

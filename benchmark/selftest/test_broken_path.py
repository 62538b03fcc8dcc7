"""``correct`` with the TIMED path broken underneath: the whole command on the
CPU at the self-test's tiny sizes (the configurations name their device
``cpu``, so the look for a chip passes), with the program altering a token
where it produces it. The negative controls break the reference; this breaks
the program, and ``correct`` must come out false for the dense decoders'
reference and for a reference a configuration brought (``tiny-qwen3-moe``)
alike. ``python3 -m pytest benchmark/selftest/test_broken_path.py``
(``selftest.py`` runs it too)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

sys.path.insert(0, BENCH)
import run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest.json")
with open(MANIFEST, encoding="utf-8") as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"] if w["chips"] == 1 and w["traffic"] == "closed"]


def _line(capsys, workload: str) -> dict:
    assert run.main(["--workload", workload, "--seed", "3000000017", "--seconds", "1", "--manifest", MANIFEST]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_a_token_altered_where_it_is_produced_turns_correct_false(workload, capsys, monkeypatch):
    from dllama_tpu.runtime import serving

    honest = _line(capsys, workload)
    assert honest["correct"] is True and honest["failed"] == 0

    emit = serving._GeneratorCore._emit_run
    seen = [0]

    def altered(self, i, tokens):
        """Every eighth token the scheduler emits is the next id up: fed back as
        the slot's next input, streamed and recorded like any other."""
        out = []
        for t in tokens:
            seen[0] += 1
            out.append((t + 1) % self.cfg.vocab_size if seen[0] % 8 == 0 else t)
        return emit(self, i, out)

    monkeypatch.setattr(serving._GeneratorCore, "_emit_run", altered)
    broken = _line(capsys, workload)
    assert seen[0] > 64
    assert broken["correct"] is False and broken["failed"] == 0
    assert broken["gap"]["max"] > 3 * broken["gap"]["tolerance"] and honest["gap"]["max"] < broken["gap"]["tolerance"] / 5
    assert all(broken["gap"]["invariants"].values())      # nothing else told: the gap alone caught it

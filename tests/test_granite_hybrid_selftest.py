"""``benchmark/run.py`` with ``granite-4.0-h-small``'s modules at the tiny preset,
from a manifest of its own (``benchmark/granite_hybrid/selftest/manifest.json``),
under every control: a file of its own beside ``tests/test_granite_hybrid.py`` so
that the two run on two workers. ``benchmark/selftest/selftest.py`` reads its own
manifest alone, which a PR that adds a configuration may not edit: this is
where that manifest's controls run."""

import hashlib
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GH = os.path.join(BENCH, "granite_hybrid")
MANIFEST = os.path.join(GH, "selftest", "manifest.json")
# what the gap cannot see in this equation, and why (``gap_tolerance.json`` says the same): no positions to shift; the
# gap is a ratio of logit differences, so a scale on every logit drops out of it
NOT_CAUGHT = ("shift", "nologitscale")

sys.path.insert(0, BENCH)           # as run.py puts it, and as benchmark/selftest/test_*.py do
import run as bench_run  # noqa: E402


@pytest.fixture(autouse=True)
def _engine_loader_put_back():
    """The weights module's seam replaces the engine's tensor-reading call
    for the process: every test here hands it back as it found it, and
    starts from a registry at zero."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.runtime import telemetry

    # the command holds the PROCESS's non-finite counter to zero (``tripwire_quiet``), and a worker that ran a
    # chaos or numerics file before this one has counted there
    telemetry.registry().reset()
    yield
    engine_mod.load_params_from_mfile = load_params_from_mfile


def _controls():
    with open(MANIFEST, encoding="utf-8") as f:
        return json.load(f)["workloads"][0]["selftest"]["controls"]


CAUGHT = ("droplayer", "dropblock", "noresmult", "noembmult", "sqrtscale", "rope", "bf16state", "bf16router",
          "softmaxall", "misroute", "noshared", "dropstate", "secondhalf")


@pytest.mark.parametrize("control, correct", [("none", True)] + [(c, True) for c in NOT_CAUGHT]
                         + [(c, False) for c in CAUGHT])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under every
    control its manifest lists: each multiplier's, the score's scale, a state
    or a router below float32 among them. ``shift`` and ``nologitscale`` are the
    two the gap cannot see (``gap_tolerance.json`` names them; ``rope`` shows the
    reference tells a rotary embedding from none, and
    ``tests/test_granite_hybrid.py`` holds the logits themselves to the scale)."""
    assert control == "none" or control in NOT_CAUGHT or control in _controls()
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-granite-hybrid.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "4", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]


def test_the_manifest_lists_every_control_the_tolerance_file_calls_caught():
    with open(os.path.join(GH, "gap_tolerance.json"), encoding="utf-8") as f:
        limits = json.load(f)
    spec = importlib.util.spec_from_file_location("granite_hybrid_reference", os.path.join(GH, "reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    controls = set(reference.CONTROLS) - {"none"}
    assert set(_controls()) == set(CAUGHT) == controls - set(NOT_CAUGHT)
    # what the bfloat16 cell on the chip does not part from an honest run is named with its readings; the two the
    # gap cannot see at any precision are among them
    assert set(NOT_CAUGHT) <= set(limits["not_caught"]) <= controls
    assert set(limits["not_caught"]) == {"shift", "nologitscale", "dropblock", "softmaxall", "rope"}
    assert limits["tolerance"] == {"bfloat16": 0.3, "float32": 0.01}
    assert set(limits["tolerance"]) == {"bfloat16", "float32"}

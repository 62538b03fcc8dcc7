"""``benchmark/run.py`` with ``nemotron-3-super-120b-a12b``'s modules at the
tiny preset, from a manifest of its own
(``benchmark/nemotron_h/selftest/manifest.json``), under every control: a file
of its own beside ``tests/test_nemotron_h.py`` so that the two run on two
workers. ``benchmark/selftest/selftest.py`` reads its own manifest alone, which a
PR that adds a configuration may not edit: this is where that manifest's
controls run."""

import hashlib
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
NH = os.path.join(BENCH, "nemotron_h")
MANIFEST = os.path.join(NH, "selftest", "manifest.json")

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


@pytest.mark.parametrize("control, correct", [("none", True), ("shift", True)] + [(c, False) for c in (
    "droplayer", "dropblock", "dropstate", "nodecay", "bf16state", "misroute", "noshared", "bf16router", "nolatent",
    "gated", "nobias", "rope")])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under every
    control its manifest lists. ``shift`` is the one that cannot be caught:
    the model has no positions to shift (``gap_tolerance.json`` names it, and
    ``rope`` shows the reference tells a rotary embedding from none)."""
    assert control in ("none", "shift") or control in _controls()
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-nemotron-h.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "4", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]


def test_the_manifest_lists_every_control_the_tolerance_file_calls_caught():
    with open(os.path.join(NH, "gap_tolerance.json"), encoding="utf-8") as f:
        limits = json.load(f)
    spec = importlib.util.spec_from_file_location("nemotron_h_reference", os.path.join(NH, "reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    controls = set(reference.CONTROLS) - {"none"}
    # ``dropblock`` is caught in float32 at the tiny size and not in bfloat16 at the cell's (2 attention layers of 22)
    assert set(limits["not_caught"]) == {"shift", "dropblock"} and set(_controls()) == controls - {"shift"}
    assert {"dropstate", "nodecay", "bf16state", "misroute", "noshared", "bf16router", "nolatent", "gated", "nobias",
            "rope", "shift", "droplayer", "dropblock"} == controls
    for key in ("tolerance", "share_over", "share_tolerance", "near_tie"):
        assert set(limits[key]) == {"bfloat16", "float32"}

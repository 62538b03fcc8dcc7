"""``benchmark/run.py`` with ``solar-open2-250b``'s modules at the tiny preset,
from a manifest of its own (``benchmark/solar_open2/selftest/manifest.json``),
under every control: a file of its own beside ``tests/test_solar_open2.py`` so
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
SO = os.path.join(BENCH, "solar_open2")
MANIFEST = os.path.join(SO, "selftest", "manifest.json")
# what the gap cannot see in this equation, and why (``gap_tolerance.json`` says the same): no positions to shift
NOT_CAUGHT = ("shift",)
# what the TINY preset cannot show and the cell does: three of eight scores have no near-ties for a bfloat16 to close
# (the rehearsal's largest gap reads 0.0018 of the 0.01 allowed), where the eighth and ninth of 320 lie 9e-5 apart. The
# reference's own logits move under it (``tests/test_solar_open2.py``, the variants) and on the chip the share fails
TINY_BLIND = ("bf16router",)

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


CAUGHT = ("droplayer", "dropblock", "scalardecay", "nonegeig", "nogate", "misroute", "noshared", "state16")


@pytest.mark.parametrize("control, correct", [("none", True)] + [(c, True) for c in NOT_CAUGHT + TINY_BLIND]
                         + [(c, False) for c in CAUGHT])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under every
    control its manifest lists: a head's decay given its channels' mean, ``beta``
    not doubled, the full layers' gate left out, a state below float32 among
    them. ``shift`` is the one the gap cannot see (no layer reads a position)."""
    assert control == "none" or control in NOT_CAUGHT + TINY_BLIND or control in _controls()
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-solar-open2.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "4", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]


def test_the_manifest_lists_every_control_the_tolerance_file_calls_caught():
    with open(os.path.join(SO, "gap_tolerance.json"), encoding="utf-8") as f:
        limits = json.load(f)
    spec = importlib.util.spec_from_file_location("solar_open2_reference", os.path.join(SO, "reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    controls = set(reference.CONTROLS) - {"none"}
    assert set(_controls()) == set(CAUGHT) == controls - set(NOT_CAUGHT) - set(TINY_BLIND)
    # what the bfloat16 cell on the chip does not part from an honest run is named with its readings; the one the gap
    # cannot see at any precision is among them
    assert set(NOT_CAUGHT) <= set(limits["not_caught"]) <= controls
    assert set(limits["tolerance"]) == {"bfloat16", "float32"}

"""``benchmark/run.py`` with ``mellum2-12b-a2.5b``'s modules at the tiny preset,
from a manifest of its own (``benchmark/mellum/selftest/manifest.json``), under
every control: a file of its own beside ``tests/test_mellum.py`` so that the two
run on two workers. ``benchmark/selftest/selftest.py`` reads its own manifest
alone, which a PR that adds a configuration may not edit: this is where that
manifest's controls run. The rehearsal's traffic is the cell's in small:
sessions of three turns behind a shared prompt, so the later turns are admitted
behind a match in both pools and the post-window check holds one of them."""

import hashlib
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MELLUM = os.path.join(BENCH, "mellum")
MANIFEST = os.path.join(MELLUM, "selftest", "manifest.json")
CELL = "tiny-mellum.sessions"

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


CAUGHT = ("shift", "droplayer", "dropwindowblock", "nowindow", "ropeswap", "noqknorm", "rawtopk", "bf16router")


@pytest.mark.parametrize("control, correct", [("none", True)] + [(c, False) for c in CAUGHT])
def test_whole_command_rehearsal(control, correct, capsys):
    """``correct`` true, and false under every control the manifest lists; the
    honest run matched prefixes in BOTH pools, at a session's previous prompt
    and at the shared prompt's end, and compiled nothing in its window."""
    assert control == "none" or control in _controls()
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", CELL, "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "4", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]
    if control == "none":
        from dllama_tpu.runtime import telemetry

        assert line["metrics"]["window_compiles"]["value"] == 0
        reused = telemetry.registry().counter(telemetry.PREFIX_REUSE_TOKENS).total()
        assert reused > 96 * 4          # more than the shared prompt once a client: turns matched their sessions too


def test_the_manifest_lists_every_control_of_the_reference():
    spec = importlib.util.spec_from_file_location("mellum_reference", os.path.join(MELLUM, "reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    assert set(_controls()) == set(CAUGHT) == set(reference.CONTROLS) - {"none"}
    with open(os.path.join(MELLUM, "gap_tolerance.json"), encoding="utf-8") as f:
        why = json.load(f)["why"]
    assert all(c in why for c in CAUGHT)


def test_the_counts_are_the_frozen_ones():
    """The configuration's counts module, to the byte, at the cell's size:
    rows in the pattern of ``benchmark/selftest/counts_frozen.json``."""
    spec = importlib.util.spec_from_file_location("mellum_counts", os.path.join(MELLUM, "counts.py"))
    counts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(counts)
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    with open(os.path.join(MELLUM, "selftest", "counts_frozen.json"), encoding="utf-8") as f:
        rows = json.load(f)["rows"]
    assert len(rows) >= 12
    for row in rows:
        got = getattr(counts, row["fn"])(model, **row["args"])
        assert got == row["value"], row

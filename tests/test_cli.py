"""CLI-mode tests driven in-process (reference flows: dllama.cpp
inference/chat). The API and worker modes have their own test files; this
covers the inference printout contract and the chat REPL loop (template
render → prefill → sampled decode → EOS/seq-len stop) end to end."""

import io
import os

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.serve import cli

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

LLAMA3_SNIPPET = (
    "{% set content = '<|start_header_id|>' + message['role'] + "
    "'<|end_header_id|>\n\n' + message['content'] | trim + '<|eot_id|>' %}")


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("cli")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(77)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=192), rng)
    data = byte_vocab_tokenizer()
    data.chat_template = LLAMA3_SNIPPET  # autodetects as llama3
    tfile.write_tfile(tpath, data)
    return str(mpath), str(tpath)


def test_inference_mode_prints_reference_style_stats(model_files, capsys):
    m, t = model_files
    rc = cli.main(["inference", "--model", m, "--tokenizer", t,
                   "--prompt", "hello world", "--steps", "16",
                   "--temperature", "0.0", "--seed", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Evaluation" in out and "Prediction" in out
    assert "tokens/s" in out and "nTokens" in out


def test_inference_requires_prompt_and_steps(model_files):
    m, t = model_files
    with pytest.raises(SystemExit):
        cli.main(["inference", "--model", m, "--tokenizer", t, "--steps", "4"])
    with pytest.raises(SystemExit):
        cli.main(["inference", "--model", m, "--tokenizer", t,
                  "--prompt", "hi"])


def test_chat_mode_replies_and_exits_on_eof(model_files, capsys, monkeypatch):
    """One user turn through the real REPL: template render, prefill, fused
    sampled decode, stream until EOS or the context cap, clean EOF exit
    (reference: dllama.cpp:174-258)."""
    m, t = model_files
    monkeypatch.setattr("sys.stdin", io.StringIO("hello\n"))
    rc = cli.main(["chat", "--model", m, "--tokenizer", t,
                   "--temperature", "0.8", "--seed", "3",
                   "--max-seq-len", "128"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "🤖" in out  # the assistant turn streamed something
    assert "context is full" not in out.split("🤖")[0]  # prompt fit


@pytest.mark.parametrize("mode", ["turbo", "turbo16", "bananas"])
def test_unknown_quant_mode_env_refused_at_engine_construction(
        model_files, monkeypatch, mode):
    """An exported DLLAMA_TPU_QUANT_MODE this build does not know is
    refused before the load, naming the valid values: falling through to
    ``auto`` would serve the operator other numerics without a word."""
    from dllama_tpu.runtime.engine import InferenceEngine

    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", mode)
    with pytest.raises(ValueError) as exc:
        InferenceEngine(*model_files, compute_dtype="bfloat16")
    msg = str(exc.value)
    assert repr(mode) in msg
    assert all(valid in msg for valid in ("auto", "exact", "fast"))


def test_quant_mode_flag_has_three_choices(model_files, capsys):
    with pytest.raises(SystemExit) as exc:
        cli.build_parser().parse_args(
            ["inference", "--model", model_files[0],
             "--tokenizer", model_files[1], "--quant-mode", "turbo"])
    assert exc.value.code == 2
    assert "choose from auto, exact, fast" in capsys.readouterr().err.replace("'", "")


@pytest.mark.parametrize("exported", [None, "exact"])
def test_make_engine_restores_quant_mode_env(model_files, exported):
    """--quant-mode fast writes DLLAMA_TPU_QUANT_MODE for the engine it
    builds; a later make_engine with the default ``auto`` in the same
    process puts back what the user had (nothing, or their own export),
    so the whole environment reads as it was found."""
    # managed by hand, not monkeypatch.setenv: make_engine writes the
    # variable by design, and monkeypatch would re-instate at teardown
    # whatever value it saw first
    prev_qm = os.environ.pop("DLLAMA_TPU_QUANT_MODE", None)
    if exported is not None:
        os.environ["DLLAMA_TPU_QUANT_MODE"] = exported
    found = dict(os.environ)
    base = ["inference", "--model", model_files[0],
            "--tokenizer", model_files[1], "--compute-dtype", "bf16",
            "--temperature", "0"]
    try:
        eng = cli.make_engine(cli.build_parser().parse_args(
            base + ["--quant-mode", "fast"]))
        assert os.environ["DLLAMA_TPU_QUANT_MODE"] == "fast"
        assert eng.params.layers.wq.scales.dtype == "bfloat16"
        eng.close()
        eng2 = cli.make_engine(cli.build_parser().parse_args(base))
        eng2.close()
        assert dict(os.environ) == found
    finally:
        cli._cli_wrote_quant_mode = False
        cli._env_quant_before_cli = None
        if prev_qm is None:
            os.environ.pop("DLLAMA_TPU_QUANT_MODE", None)
        else:
            os.environ["DLLAMA_TPU_QUANT_MODE"] = prev_qm

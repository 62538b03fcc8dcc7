"""InferenceEngine tests: chunked prefill parity, generation determinism,
seq-len guards, perplexity (reference flows: dllama.cpp inference/perplexity)."""

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime.engine import InferenceEngine

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("engine")
    mpath = d / "m.m"
    tpath = d / "t.t"
    rng = np.random.default_rng(123)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=48), rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


def make_engine(model_files, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("seed", 7)
    return InferenceEngine(model_files[0], model_files[1], **kw)


def test_generate_greedy_deterministic(model_files):
    e1 = make_engine(model_files)
    r1 = e1.generate("hello world", 8, stop_on_eos=False)
    e2 = make_engine(model_files)
    r2 = e2.generate("hello world", 8, stop_on_eos=False)
    assert r1.tokens == r2.tokens
    assert len(r1.tokens) == 8
    assert r1.prompt_tokens > 1
    assert any(s.kind == "eval" for s in r1.steps)
    assert sum(s.n_tokens for s in r1.steps if s.kind == "pred") == 8


def test_prefill_chunking_invariant(model_files):
    """nbatches=2 vs nbatches=32 must produce identical generations —
    the reference's positions-as-batch semantics (SURVEY.md §2.2)."""
    small = make_engine(model_files, n_batches=2)
    big = make_engine(model_files, n_batches=32)
    rs = small.generate("hello world hello world", 6, stop_on_eos=False)
    rb = big.generate("hello world hello world", 6, stop_on_eos=False)
    assert rs.tokens == rb.tokens


def test_continuation_matches_fresh_longer_prompt(model_files):
    """generate → continue == the cache holds exactly the generated tokens."""
    e = make_engine(model_files)
    r1 = e.generate("hello world", 4, stop_on_eos=False)
    r2 = e.generate([r1.tokens[-1]] if False else r1.tokens[-1:], 3, stop_on_eos=False)

    f = make_engine(model_files)
    prompt_ids = f.tokenizer.encode("hello world") + r1.tokens
    rf = f.generate(prompt_ids, 3, stop_on_eos=False)
    assert r2.tokens == rf.tokens


def test_seq_len_guard(model_files):
    e = make_engine(model_files, max_seq_len=8)
    assert e.cfg.seq_len == 8
    with pytest.raises(ValueError):
        e.prefill(list(range(9)))
    r = e.generate("hello", 100, stop_on_eos=False)  # capped at seq_len
    assert e.pos <= 8


def test_generation_caps_at_seq_len(model_files):
    e = make_engine(model_files, max_seq_len=10)
    r = e.generate("hello world", 100, stop_on_eos=False)
    assert e.pos == 10


def test_perplexity_prefers_repetition(model_files):
    e = make_engine(model_files)
    ids = e.tokenizer.encode("hello world hello world hello world")
    ppl_rep = e.perplexity(ids)
    assert np.isfinite(ppl_rep) and ppl_rep > 0
    rng = np.random.default_rng(0)
    rand_ids = [int(x) for x in rng.integers(0, 256, size=len(ids))]
    ppl_rand = e.perplexity(rand_ids)
    assert np.isfinite(ppl_rand)


def test_tp_engine_matches_single(model_files):
    base = make_engine(model_files, tp=1)
    rb = base.generate("hello world", 6, stop_on_eos=False)
    tp = make_engine(model_files, tp=4)
    rt = tp.generate("hello world", 6, stop_on_eos=False)
    assert rb.tokens == rt.tokens


def test_prefill_tail_padding_does_not_corrupt_history(model_files):
    """Regression: a padded chunk near seq_len must not clamp-and-overwrite
    older KV entries (dynamic_update_slice clamps start indices)."""
    # seq_len=48, n_batches=32: prompt of 40 once triggered a 32-wide padded
    # chunk at pos 32 spanning past 48 → clamped to 16, corrupting history.
    e = make_engine(model_files, n_batches=32)
    ids = [int(x) for x in np.random.default_rng(1).integers(1, 200, size=40)]
    e.prefill(ids)
    logits_a = e.decode_step(5)

    f = make_engine(model_files, n_batches=8)  # 8 divides 40: no tail padding
    f.prefill(ids)
    logits_b = f.decode_step(5)
    np.testing.assert_allclose(logits_a, logits_b, rtol=2e-4, atol=2e-5)


def test_sync_q80_parity_mode_changes_logits(model_files):
    """--buffer-float-type q80 must actually fake-quantize in-graph."""
    from dllama_tpu.formats.quants import Q80

    e32 = make_engine(model_files)
    eq = make_engine(model_files, sync_type=Q80)
    assert eq.cfg.sync_q80 and not e32.cfg.sync_q80
    ids = e32.tokenizer.encode("hello world")
    la, _ = e32.prefill(ids)
    lb, _ = eq.prefill(ids)
    assert not np.allclose(la, lb)  # quantization must have an effect
    assert np.abs(la - lb).max() < 0.5  # but a small one


def test_bf16_compute_mode(model_files):
    """Serving mode: bf16 activations + bf16 KV cache generate sane tokens
    (not token-identical to f32 — different arithmetic — but deterministic)."""
    import jax.numpy as jnp

    e = make_engine(model_files, compute_dtype="bfloat16")
    assert e.kv.k.dtype == jnp.bfloat16
    r1 = e.generate("hello world", 6, stop_on_eos=False)
    e2 = make_engine(model_files, compute_dtype="bfloat16")
    r2 = e2.generate("hello world", 6, stop_on_eos=False)
    assert r1.tokens == r2.tokens and len(r1.tokens) == 6
    assert all(0 <= t < e.cfg.vocab_size for t in r1.tokens)


def test_prefill_bucket_selection(model_files):
    """Default nbatches -> adaptive TPU-sized buckets; explicit -> pinned."""
    e = make_engine(model_files)  # seq_len 48: only the 32 bucket fits
    assert e.prefill_buckets == (32,)
    assert e._prefill_chunk_size(100) == 32
    e2 = make_engine(model_files, n_batches=16)
    assert e2.prefill_buckets == (16,)


def test_prefill_bucketed_matches_fixed(tmp_path):
    """Adaptive bucketing (128+64+32 chunks) must generate exactly what a
    fixed-chunk engine does — positions-as-batch semantics are chunk-size
    invariant (same property the reference relies on, SURVEY.md §4)."""
    from dllama_tpu.formats import tfile as _tfile
    from helpers import byte_vocab_tokenizer as _bv, tiny_header_params as _hp
    from helpers import write_tiny_model as _wm

    mpath, tpath = tmp_path / "m.m", tmp_path / "t.t"
    rng = np.random.default_rng(321)
    _wm(mpath, _hp(vocab_size=268, seq_len=192), rng)
    _tfile.write_tfile(tpath, _bv())

    adaptive = InferenceEngine(str(mpath), str(tpath), temperature=0.0, seed=7)
    assert adaptive.prefill_buckets == (128, 64, 32)
    fixed = InferenceEngine(str(mpath), str(tpath), temperature=0.0, seed=7,
                            n_batches=8)
    prompt = [int(t) for t in rng.integers(4, 260, size=150)]
    ra = adaptive.generate(prompt, 6, stop_on_eos=False)
    rf = fixed.generate(prompt, 6, stop_on_eos=False)
    assert ra.tokens == rf.tokens
    # 149 prompt-eval tokens (last seeds decode): 128 + 21 = two dispatches
    assert sum(1 for s in ra.steps if s.kind == "eval") == 2


def test_quant_mode_flip_after_load_fails_loudly(model_files, monkeypatch):
    """Flipping DLLAMA_TPU_QUANT_MODE after load must raise, not silently run
    one mode's math over the other mode's stored weights (bf16 scales and the
    logits head are baked in at load)."""
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "fast")
    e = make_engine(model_files, compute_dtype="bfloat16")
    e.generate("ab", 2, stop_on_eos=False)  # sanity: matching env serves
    # same RESOLUTION under a different spelling (auto on bf16 == fast):
    # must NOT trip the guard — only genuine numerics changes do
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "auto")
    e.generate("ab", 2, stop_on_eos=False)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    with pytest.raises(RuntimeError, match="changed after load"):
        e.generate("ab", 2, stop_on_eos=False)

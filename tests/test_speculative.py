"""Prompt-lookup speculative decode: exactness and acceptance.

Speculative greedy must be BIT-IDENTICAL to plain greedy on every input —
the verify step accepts exactly the prefix the model itself would have
produced (models.llama.verify_step_guarded) — while a self-repeating prompt must
show real multi-token acceptance (fewer dispatches than tokens). The
reference has no speculative path (one token per step, dllama.cpp:88-99);
this is a TPU-economics feature: decode is HBM-bound, so tokens per weight
read is the lever.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import quants, tfile
from dllama_tpu.models import ModelConfig, init_random_params
from dllama_tpu.models.llama import greedy_step_guarded, verify_step_guarded
from dllama_tpu.runtime import KVCache
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.speculative import NgramProposer

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


# -- proposer ---------------------------------------------------------------


def test_proposer_drafts_previous_continuation():
    p = NgramProposer(3)
    p.extend([1, 2, 3, 4, 9, 1, 2])  # trailing bigram (1,2) seen before at ..3,4
    assert p.draft() == [3, 4, 9]


def test_proposer_pads_short_continuation():
    p = NgramProposer(4)
    p.extend([1, 2, 3, 1, 2])  # earlier (1,2) is followed only by [3, 1, 2]
    assert p.draft() == [3, 1, 2, 2]


def test_proposer_no_signal_repeats_last():
    p = NgramProposer(2)
    p.extend([5, 6, 7])
    assert p.draft() == [7, 7]
    assert NgramProposer(2).draft() == [0, 0]


def test_proposer_self_overlap():
    p = NgramProposer(3)
    p.extend([8, 8, 8, 8])  # overlapping (8,8): drafts self-extension
    assert p.draft() == [8, 8, 8]


def test_proposer_trigram_beats_bigram():
    """Two continuations of the bigram (1,2) exist; the trailing TRIGRAM
    (9,1,2) disambiguates to the second one."""
    p = NgramProposer(2)
    p.extend([0, 1, 2, 7, 7,    # (1,2) -> 7,7  (bigram candidate)
              9, 1, 2, 5, 5,    # (9,1,2) -> 5,5 (trigram match)
              9, 1, 2])
    assert p.draft() == [5, 5]


def test_proposer_bigram_fallback_when_trigram_unseen():
    p = NgramProposer(2)
    p.extend([4, 1, 2, 7, 7, 3, 1, 2])  # trailing trigram (3,1,2) unseen
    assert p.draft() == [7, 7]


# -- verify_step_guarded vs sequential greedy -------------------------------

CLEAN = np.float32(0.0)          # the tripwire's poison selector outside a chaos run


def _cfg():
    from dllama_tpu.formats import mfile

    return ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256, seq_len=64,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA)


@pytest.mark.parametrize("trial", range(3))
def test_verify_matches_sequential_greedy(trial):
    cfg = _cfg()
    params = init_random_params(cfg, seed=trial)
    rng = np.random.default_rng(trial)
    token = int(rng.integers(0, cfg.vocab_size))
    drafts = [int(t) for t in rng.integers(0, cfg.vocab_size, 4)]
    pos = 0

    # sequential oracle
    kv = KVCache.create(cfg)
    step = jax.jit(greedy_step_guarded, static_argnums=1)
    seq = []
    t = token
    for i in range(len(drafts) + 1):
        (nxt, nf), kv = step(params, cfg, jnp.asarray([[t]]), jnp.int32(pos + i), kv, CLEAN)
        assert int(nf[0]) == 0
        seq.append(int(nxt[0]))
        t = seq[-1]

    # one verify dispatch
    kv2 = KVCache.create(cfg)
    ver = jax.jit(verify_step_guarded, static_argnums=1)
    (n_acc, preds, nf), _ = ver(params, cfg,
                                jnp.asarray([[token, *drafts]], jnp.int32),
                                jnp.int32(pos), kv2, CLEAN)
    assert int(nf[0]) == 0
    n_acc = int(n_acc[0])
    preds = np.asarray(preds)[0]

    # the accepted run equals the sequential transcript prefix
    assert [int(x) for x in preds[: n_acc + 1]] == seq[: n_acc + 1]
    # acceptance is exactly the longest draft prefix matching the oracle
    expect_acc = 0
    for i, d in enumerate(drafts):
        if d == seq[i]:
            expect_acc += 1
        else:
            break
    assert n_acc == expect_acc


# -- engine end-to-end ------------------------------------------------------


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("spec")
    tok = byte_vocab_tokenizer()
    hdr = tiny_header_params(vocab_size=tok.vocab_size, seq_len=128,
                             weight_type=quants.Q40)
    write_tiny_model(d / "m.m", hdr, np.random.default_rng(11))
    tfile.write_tfile(d / "t.t", tok)
    return str(d / "m.m"), str(d / "t.t")


def _gen(model_files, prompt, steps, **kw):
    m, t = model_files
    eng = InferenceEngine(m, t, temperature=0.0, **kw)
    try:
        out = eng.generate(prompt, steps, stop_on_eos=False)
    finally:
        eng.close()
    return out


@pytest.mark.parametrize("prompt", ["the quick brown fox", "ababababababab"])
def test_speculative_identical_to_plain_greedy(model_files, prompt):
    plain = _gen(model_files, prompt, 48)
    spec = _gen(model_files, prompt, 48, spec_lookup=4)
    assert spec.tokens == plain.tokens
    assert spec.text == plain.text


def test_speculative_accepts_on_repetitive_output(model_files):
    """Greedy decode on a tiny random model degenerates into a cycle; the
    proposer must exploit it: strictly fewer dispatches than tokens."""
    spec = _gen(model_files, "hello hello hello hello", 64, spec_lookup=4)
    pred_steps = [s for s in spec.steps if s.kind == "pred"]
    n_tokens = sum(s.n_tokens for s in pred_steps)
    assert n_tokens == len(spec.tokens)
    assert len(pred_steps) < n_tokens, (
        f"no acceptance: {len(pred_steps)} dispatches for {n_tokens} tokens")


def test_spec_and_chunk_are_exclusive(model_files):
    m, t = model_files
    with pytest.raises(ValueError, match="exclusive"):
        InferenceEngine(m, t, temperature=0.0, spec_lookup=4, decode_chunk=8)


def test_spec_ignored_at_temperature(model_files):
    """temperature>0 keeps the sampled path (speculative is greedy-only)."""
    m, t = model_files
    eng = InferenceEngine(m, t, temperature=0.9, seed=7, spec_lookup=4)
    try:
        a = eng.generate("the quick", 24, stop_on_eos=False).tokens
    finally:
        eng.close()
    eng2 = InferenceEngine(m, t, temperature=0.9, seed=7)
    try:
        b = eng2.generate("the quick", 24, stop_on_eos=False).tokens
    finally:
        eng2.close()
    assert a == b


def test_ragged_verify_matches_per_row_oracles():
    """ragged_verify_step_guarded row-by-row: greedy rows equal a solo
    verify_step_guarded at that row's position; sampled rows equal
    sampled_token on the position-0 logits with n_acc forced to 0; no row
    counts a non-finite logit."""
    from dllama_tpu.models.llama import ragged_verify_step_guarded
    from dllama_tpu.ops.sampling import sampled_token

    cfg = _cfg()
    params = init_random_params(cfg, seed=5)
    rng = np.random.default_rng(5)
    B, K = 3, 3
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, K + 1)), jnp.int32)
    pos = jnp.asarray([4, 0, 9], jnp.int32)
    temps = jnp.asarray([0.0, 0.8, 0.0], jnp.float32)
    topps = jnp.full((B,), 0.9, jnp.float32)
    coins = jnp.asarray([0.0, 0.37, 0.0], jnp.float32)

    kv = KVCache.create(cfg, batch_size=B)
    (n_acc, preds, nf), _ = jax.jit(ragged_verify_step_guarded, static_argnums=1)(
        params, cfg, toks, pos, kv, temps, topps, coins, CLEAN)
    n_acc, preds = np.asarray(n_acc), np.asarray(preds)
    assert (np.asarray(nf) == 0).all()

    for b in (0, 2):  # greedy rows: equal a solo single-row verify
        kv1 = KVCache.create(cfg)
        (na1, p1, nf1), _ = jax.jit(verify_step_guarded, static_argnums=1)(
            params, cfg, toks[b:b + 1], pos[b], kv1, CLEAN)
        assert int(na1[0]) == n_acc[b] and int(nf1[0]) == 0
        np.testing.assert_array_equal(np.asarray(p1)[0], preds[b])

    # sampled row: n_acc 0 and first token from the row's own coin
    assert n_acc[1] == 0
    from dllama_tpu.models import forward

    kv1 = KVCache.create(cfg)
    logits, _ = jax.jit(forward, static_argnums=1)(
        params, cfg, toks[1:2], pos[1], kv1)
    want = sampled_token(logits[:, 0], jnp.float32(0.8), jnp.float32(0.9),
                         jnp.float32(0.37))
    assert int(want[0]) == preds[1, 0]


def test_speculative_on_moe_model(tmp_path):
    """verify_step_guarded is forward-based, so speculation rides MoE models too:
    identical to plain greedy."""
    m, t = tmp_path / "m.m", tmp_path / "t.t"
    write_tiny_model(m, tiny_header_params(vocab_size=268, seq_len=96,
                                           n_experts=4, n_active_experts=2),
                     np.random.default_rng(11))
    tfile.write_tfile(t, byte_vocab_tokenizer())
    plain = InferenceEngine(str(m), str(t), temperature=0.0)
    want = plain.generate("hello hello", 20, stop_on_eos=False).tokens
    plain.close()
    spec = InferenceEngine(str(m), str(t), temperature=0.0, spec_lookup=3)
    got = spec.generate("hello hello", 20, stop_on_eos=False).tokens
    spec.close()
    assert got == want


@pytest.mark.parametrize("tp", [1, 2])
def test_speculative_under_sp_matches_plain(model_files, tp):
    """Speculation composes with sequence parallelism (verify rides the ring
    attention path at T=K+1): identical to plain greedy under sp=2."""
    m, t = model_files
    plain = InferenceEngine(m, t, sp=2, tp=tp, temperature=0.0)
    want = plain.generate("hello hello hello", 12, stop_on_eos=False).tokens
    plain.close()
    spec = InferenceEngine(m, t, sp=2, tp=tp, temperature=0.0, spec_lookup=2)
    got = spec.generate("hello hello hello", 12, stop_on_eos=False).tokens
    spec.close()
    assert got == want


# -- rejection sampling (runtime/speculative.spec_decide) --------------------


def test_spec_decide_zero_draft_is_plain_sampled_step():
    """A zero-length draft degrades to the plain sampled decode step
    BIT-exactly: position 0's sample runs ops.sampling.sampled_token on
    the position-0 logits with position 0's coin (``acoins[:, 0]`` — the
    next draw of the request's sequential coin stream, the same draw
    the non-speculative step would consume)."""
    from dllama_tpu.ops.sampling import sampled_token
    from dllama_tpu.runtime.speculative import spec_decide

    rng = np.random.default_rng(3)
    B, K, V = 4, 3, 64
    logits = jnp.asarray(rng.standard_normal((B, K + 1, V)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, V, (B, K + 1)), jnp.int32)
    temps = jnp.asarray([0.6, 0.9, 1.3, 0.8], jnp.float32)
    topps = jnp.asarray([0.9, 0.5, 1.0, 0.95], jnp.float32)  # incl. topp=1
    acoins = jnp.asarray(rng.random((B, K)), jnp.float32)
    n_acc, out = jax.jit(spec_decide)(
        logits, tokens, jnp.zeros(B, jnp.int32), temps, topps,
        acoins, jnp.asarray(rng.random(B), jnp.float32))
    np.testing.assert_array_equal(np.asarray(n_acc), 0)
    want = sampled_token(logits[:, 0], temps, topps, acoins[:, 0])
    np.testing.assert_array_equal(np.asarray(out)[:, 0], np.asarray(want))


def test_spec_decide_greedy_rows_match_exact_prefix_rule():
    """Greedy rows (temp <= 0) keep the exact-match acceptance capped at
    the row's draft length, and emit the model's own argmax run."""
    from dllama_tpu.runtime.speculative import spec_decide

    rng = np.random.default_rng(7)
    B, K, V = 3, 4, 32
    logits = jnp.asarray(rng.standard_normal((B, K + 1, V)), jnp.float32)
    preds = np.argmax(np.asarray(logits), -1)
    # row 0: drafts equal the model's own predictions (full acceptance up
    # to lens); row 1: first draft wrong; row 2: lens caps acceptance
    tokens = np.zeros((B, K + 1), np.int32)
    tokens[:, 1:] = preds[:, :-1]
    tokens[1, 1] = (preds[1, 0] + 1) % V
    lens = jnp.asarray([K, K, 2], jnp.int32)
    n_acc, out = jax.jit(spec_decide)(
        logits, jnp.asarray(tokens), lens,
        jnp.zeros(B, jnp.float32), jnp.full((B,), 0.9, jnp.float32),
        jnp.zeros((B, K), jnp.float32), jnp.zeros(B, jnp.float32))
    assert list(np.asarray(n_acc)) == [K, 0, 2]
    np.testing.assert_array_equal(np.asarray(out), preds)


def test_spec_decide_distribution_preserved_tv_bound():
    """The satellite's statistical acceptance: the emitted next-token
    distribution of spec-sampled decode equals non-spec sampling within
    a total-variation bound on a toy model (fixed seeds). Exact-match
    verify emits the plain-decode sample at every position, so the
    marginal IS p_target by construction (and the accept rate equals
    p_target(draft)); the empirical TV distance over N draws
    concentrates within ~sqrt(V/N)."""
    from dllama_tpu.ops.sampling import sampled_token
    from dllama_tpu.runtime.speculative import spec_decide

    rng = np.random.default_rng(17)
    V, N, draft = 16, 20000, 3
    logits = jnp.asarray(rng.standard_normal((1, 2, V)) * 2.0, jnp.float32)
    toks = jnp.asarray([[0, draft]], jnp.int32)
    lens = jnp.asarray([1], jnp.int32)
    temps = jnp.asarray([0.8], jnp.float32)
    topps = jnp.asarray([0.9], jnp.float32)

    def one(ac, fc):
        return spec_decide(logits, toks, lens, temps, topps,
                           ac[None, None], fc[None])

    acs = jnp.asarray(rng.random(N), jnp.float32)
    fcs = jnp.asarray(rng.random(N), jnp.float32)
    n_accs, outs = jax.jit(jax.vmap(one))(acs, fcs)
    n_accs, outs = np.asarray(n_accs)[:, 0], np.asarray(outs)[:, 0]
    first = np.where(n_accs >= 1, draft, outs[:, 0])

    plain = jax.jit(jax.vmap(
        lambda c: sampled_token(logits[:, 0], temps, topps, c)))(
        jnp.asarray(rng.random(N), jnp.float32))
    plain = np.asarray(plain)[:, 0]

    p_spec = np.bincount(first, minlength=V) / N
    p_plain = np.bincount(plain, minlength=V) / N
    tv = 0.5 * np.abs(p_spec - p_plain).sum()
    assert tv < 0.03, f"TV distance {tv:.4f} — distribution not preserved"
    # and the accept rate itself matches the drafted token's target prob
    from dllama_tpu.runtime.speculative import target_sampling_probs

    p_d = float(target_sampling_probs(logits[:, 0], temps, topps)[0, draft])
    assert abs(float((n_accs >= 1).mean()) - p_d) < 0.02


def test_spec_coins_consumed_rule():
    """The host commit rule: one coin per EMITTED token (n_acc accepted
    drafts + the position-n_acc sample), independent of draft length —
    the stream-position invariant resume fast-forwards on."""
    from dllama_tpu.runtime.speculative import spec_coins_consumed

    assert spec_coins_consumed(0, 0) == 1   # no draft: plain decode's coin
    assert spec_coins_consumed(0, 4) == 1   # first draft wrong: 1 emitted
    assert spec_coins_consumed(2, 4) == 3   # 2 accepted + the sample
    assert spec_coins_consumed(4, 4) == 5   # all accepted + bonus


def test_speculative_identical_under_fast(model_files):
    """Speculation composes with fast numerics (bf16 engines, mode
    ``auto``): a [B, K+1] verify and a [B, 1] decode dispatch pick the
    same greedy tokens (asserted exactly here on CPU)."""
    plain = _gen(model_files, "the quick brown fox", 32,
                 compute_dtype="bfloat16")
    spec = _gen(model_files, "the quick brown fox", 32, spec_lookup=4,
                compute_dtype="bfloat16")
    assert spec.tokens == plain.tokens

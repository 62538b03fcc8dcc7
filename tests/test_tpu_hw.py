"""Real-TPU kernel tier (@pytest.mark.tpu) — run in the bench window:

    DLLAMA_TESTS_TPU=1 python -m pytest tests/ -m tpu -q

Makes the Pallas-kernel error-bound claims (ops/quant_matmul.py module doc:
~2e-5 abs error at Precision.HIGHEST) reproducible artifacts instead of
builder folklore (VERDICT round-1 weak #7), and exercises the fused greedy
decode + sharded kernels on actual hardware. Every test here skips cleanly
when the backend isn't a TPU.
"""

from __future__ import annotations

import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def tpu_backend():
    import jax

    devs = jax.devices()
    if jax.default_backend() != "tpu":  # the repo's one rule: parallel.api.on_tpu
        pytest.skip(f"no TPU backend (devices: {devs})")
    return devs


def test_quant_matmul_error_bound_on_hw(tpu_backend):
    """Kernel vs exact float64 host oracle: abs error ~2e-5 at HIGHEST."""
    import jax.numpy as jnp

    from dllama_tpu.ops.linear import dequantize_weight, quantize_weight_q40
    from dllama_tpu.ops.quant_matmul import quant_matmul

    rng = np.random.default_rng(7)
    w = quantize_weight_q40((rng.standard_normal((512, 1024)) * 0.1).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)

    got = np.asarray(quant_matmul(x, w))
    wd = np.asarray(dequantize_weight(w)).astype(np.float64)
    want = np.asarray(x, np.float64) @ wd
    err = np.abs(got - want).max()
    assert err < 5e-5, f"max abs error {err}"


def test_fused_decode_kernel_error_bound_on_hw(tpu_backend):
    """The decode-shaped fused dequant-GEMV (DLLAMA_TPU_QUANT_KERNEL=fused
    candidate) compiled by Mosaic: exact mode vs the float64 host oracle at
    the tiled kernel's error bound; fast mode within bf16-rounding drift of
    exact (the serving-mode contract)."""
    import jax.numpy as jnp

    from dllama_tpu.ops.linear import dequantize_weight, quantize_weight_q40
    from dllama_tpu.ops.quant_matmul import quant_matmul, supports_decode

    rng = np.random.default_rng(17)
    w = quantize_weight_q40(
        (rng.standard_normal((512, 2048)) * 0.1).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((1, 2048)), jnp.float32)
    assert supports_decode((1, 2048), w)

    got = np.asarray(quant_matmul(x, w, fused=True))
    wd = np.asarray(dequantize_weight(w)).astype(np.float64)
    want = np.asarray(x, np.float64) @ wd
    assert np.abs(got - want).max() < 5e-5

    fast = np.asarray(quant_matmul(x, w, fused=True, fast=True))
    rms = float(np.sqrt(np.mean(got ** 2)))
    assert np.abs(fast - got).max() / rms < 2e-2


def test_flash_attention_parity_on_hw(tpu_backend):
    """Kernel vs XLA oracle on the MXU. At default matmul precision the MXU
    runs one bf16 pass per f32 dot, so kernel-vs-oracle differences are
    accumulation-order noise at bf16 scale (~2.5e-3 measured on v5e) — assert
    a gross-error bound there. Under HIGHEST (3-pass f32 emulation) both
    paths are f32-exact and agree to float epsilon."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops.attention import attention
    from dllama_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(11)
    B, T, H, KV, D, S = 1, 4, 8, 2, 64, 256
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    start = jnp.int32(17)
    positions = start + jnp.arange(T, dtype=jnp.int32)[None, :]

    with jax.default_matmul_precision("highest"):
        got = np.asarray(flash_attention(q, k, v, start, D))
        want = np.asarray(attention(q, k, v, positions, D))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    got_d = np.asarray(flash_attention(q, k, v, start, D))
    want_d = np.asarray(attention(q, k, v, positions, D))
    assert np.abs(got_d - want_d).max() < 2e-2


def test_fused_greedy_decode_on_hw(tpu_backend):
    """The production decode step compiles and steps on hardware, quantized
    params + donated KV, token never leaving the device."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig, init_random_params
    from dllama_tpu.models.llama import greedy_step_guarded
    from dllama_tpu.runtime import KVCache

    cfg = ModelConfig(
        arch=ArchType.LLAMA, dim=256, hidden_dim=512, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=64, vocab_size=2048, seq_len=256,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=RopeType.LLAMA,
        compute_dtype="bfloat16")
    params = init_random_params(cfg, seed=3, quantized=True)
    kv = KVCache.create(cfg, dtype=jnp.bfloat16)
    greedy = jax.jit(greedy_step_guarded, static_argnums=1, donate_argnums=(4,))
    clean = jnp.float32(0.0)            # the tripwire's poison selector outside a chaos run

    token = jnp.zeros((1, 1), jnp.int32)
    toks = []
    for pos in range(4):
        (nxt, nf), kv = greedy(params, cfg, token, jnp.int32(pos), kv, clean)
        assert int(nf[0]) == 0
        token = nxt[:, None]
        toks.append(int(nxt[0]))
    assert all(0 <= t < cfg.vocab_size for t in toks)
    # determinism: same inputs, fresh cache -> same tokens
    kv2 = KVCache.create(cfg, dtype=jnp.bfloat16)
    token = jnp.zeros((1, 1), jnp.int32)
    toks2 = []
    for pos in range(4):
        (nxt, _), kv2 = greedy(params, cfg, token, jnp.int32(pos), kv2, clean)
        token = nxt[:, None]
        toks2.append(int(nxt[0]))
    assert toks == toks2


def test_sharded_quant_matmul_on_hw(tpu_backend):
    """TP shard_map kernel path on hardware (single chip = tp 1 mesh still
    routes through quant_matmul_sharded's shard_map)."""
    import jax.numpy as jnp

    from dllama_tpu.ops.linear import linear, quantize_weight_q40
    from dllama_tpu.ops.quant_matmul import quant_matmul_sharded
    from dllama_tpu.parallel.api import make_tp_mesh

    rng = np.random.default_rng(13)
    w = quantize_weight_q40((rng.standard_normal((256, 512)) * 0.1).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((1, 8, 512)), jnp.float32)
    plan = make_tp_mesh(len(tpu_backend))
    got = quant_matmul_sharded(plan, x, w, out_axis="hidden")
    assert got is not None
    want = linear(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_f8_kv_flash_on_hw(tpu_backend):
    """float8_e4m3 cache through the real Mosaic-lowered flash kernel: f8
    loads + upcast must match the XLA oracle reading the same stored cache."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops.attention import attention
    from dllama_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(41)
    B, T, H, KV, D, S = 1, 4, 8, 2, 64, 256
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k8 = jnp.asarray(rng.standard_normal((B, KV, S, D)),
                     jnp.float32).astype(jnp.float8_e4m3fn)
    v8 = jnp.asarray(rng.standard_normal((B, KV, S, D)),
                     jnp.float32).astype(jnp.float8_e4m3fn)
    start = jnp.int32(17)
    positions = start + jnp.arange(T, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(flash_attention(q, k8, v8, start, D))
        want = np.asarray(attention(q, k8, v8, positions, D))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ragged_serving_programs_on_hw(tpu_backend):
    """The batched-serving dispatches on real hardware: one ragged mixed
    greedy/sampled step and one ragged speculative verify, per-row
    positions, donated KV."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig, init_random_params
    from dllama_tpu.models.llama import ragged_verify_step_guarded, sampled_step_guarded
    from dllama_tpu.runtime import KVCache

    cfg = ModelConfig(
        arch=ArchType.LLAMA, dim=256, hidden_dim=512, n_layers=2,
        n_heads=4, n_kv_heads=2, head_dim=64, vocab_size=2048, seq_len=256,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=RopeType.LLAMA,
        compute_dtype="bfloat16")
    params = init_random_params(cfg, seed=9, quantized=True)
    n_slots = 4
    kv = KVCache.create(cfg, batch_size=n_slots, dtype=jnp.bfloat16)
    step = jax.jit(sampled_step_guarded, static_argnums=1, donate_argnums=(4,))
    verify = jax.jit(ragged_verify_step_guarded, static_argnums=1, donate_argnums=(4,))
    clean = jnp.float32(0.0)            # the tripwire's poison selector outside a chaos run

    pos = jnp.asarray([3, 0, 9, 5], jnp.int32)
    temps = jnp.asarray([0.0, 0.8, 0.0, 1.2], jnp.float32)
    topps = jnp.full((n_slots,), 0.9, jnp.float32)
    coins = jnp.full((n_slots,), 0.4, jnp.float32)
    toks = jnp.ones((n_slots, 1), jnp.int32)
    (nxt, nf), kv = step(params, cfg, toks, pos, kv, temps, topps, coins, clean)
    assert nxt.shape == (n_slots,) and (np.asarray(nf) == 0).all()
    draft = jnp.tile(nxt[:, None], (1, 5))
    (n_acc, preds, nf), kv = verify(params, cfg, draft, pos + 1, kv,
                                    temps, topps, coins, clean)
    n_acc, preds = np.asarray(n_acc), np.asarray(preds)
    assert (np.asarray(nf) == 0).all()
    assert preds.shape == (n_slots, 5)
    sampled_rows = np.asarray(temps) > 0
    assert (n_acc[sampled_rows] == 0).all()  # sampled rows accept nothing


@pytest.mark.parametrize("arch", ["dense", "hybrid"])
def test_the_compiled_paged_step_holds_no_second_pool_on_hw(tpu_backend, arch):
    """The twin of ``tests/test_kvblocks.py`` / ``tests/test_olmo_hybrid.py``'s
    structural test on the REAL lowering, where the Mosaic kernels stand in
    the layer scan as custom calls (a slice in front of one is materialized,
    and XLA may copy a carry whose layout a custom call does not accept):
    bf16 over Q40 planes, heads of 128 lanes so ``paged_ragged_attention``
    is compiled in, the cache donated. The step's temporaries stay under
    half of ONE pool (k: 67 MB dense, 17 MB hybrid); as the scan's stacked
    output the pool was a temporary of both pools."""
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig
    from dllama_tpu.runtime.introspection import mosaic_kernels
    from helpers import compile_paged_step, param_shapes

    hybrid = dict(arch=ArchType.OLMO_HYBRID, layer_period=4, lin_heads=2,
                  lin_key_dim=64, lin_value_dim=128, lin_conv_kernel=4)
    cfg = ModelConfig(
        **(hybrid if arch == "hybrid" else dict(arch=ArchType.LLAMA)),
        dim=256, hidden_dim=512, n_layers=8, n_heads=2, n_kv_heads=2,
        head_dim=128, vocab_size=2048, seq_len=4096, norm_epsilon=1e-5,
        rope_theta=10000.0, rope_type=RopeType.LLAMA,
        compute_dtype="bfloat16")
    compiled, pool = compile_paged_step(
        cfg, param_shapes(cfg, jnp.bfloat16), n_slots=4, n_blocks=1 + 4 * 256,
        block_size=16, table_width=256, pool_dtype=jnp.bfloat16)
    kernels = mosaic_kernels(compiled.as_text())
    assert kernels.get("paged_ragged_attention") == 1, kernels
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool // 2, (temp, pool)


def test_spec_transcript_identity_on_hw(tpu_backend):
    """--spec-lookup vs plain greedy transcript identity ON HARDWARE
    (ADVICE r3 #1): the claim 'exact by construction' rides on logits being
    bit-equal between the [1, K+1] verify dispatch and the [1, 1] decode
    dispatch — exactly the dispatch-shape ulp hazard golden_assets documents.
    CPU asserts it in test_speculative.py; this asserts it where it can
    actually break. A mismatch here would demote speculation from 'exact'
    to 'approximate' and must fail loudly."""
    import numpy as np

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime.engine import InferenceEngine
    from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

    import tempfile, os
    d = tempfile.mkdtemp(prefix="dllama-hw-spec-")
    m, t = os.path.join(d, "m.m"), os.path.join(d, "t.t")
    rng = np.random.default_rng(17)
    write_tiny_model(m, tiny_header_params(vocab_size=268, seq_len=160), rng)
    tfile.write_tfile(t, byte_vocab_tokenizer())

    plain = InferenceEngine(m, t, temperature=0.0, seed=5,
                            compute_dtype="bfloat16")
    r_plain = plain.generate("hello world hello world", 24, stop_on_eos=False)
    spec = InferenceEngine(m, t, temperature=0.0, seed=5,
                           compute_dtype="bfloat16", spec_lookup=4)
    r_spec = spec.generate("hello world hello world", 24, stop_on_eos=False)
    assert r_spec.tokens == r_plain.tokens
    # speculation actually engaged: fewer dispatches than tokens
    n_disp = sum(1 for s in r_spec.steps if s.kind == "pred")
    assert n_disp < len(r_spec.tokens)


def test_fast_mode_quant_matmul_drift_on_hw(tpu_backend):
    """Exact-vs-fast drift on the REAL MXU (the CPU interpret-mode drift
    test can't see Mosaic's actual bf16 pass): fast mode must stay within
    bf16-rounding distance of the exact kernel, and the model-level argmax
    (greedy token) must be stable at these shapes."""
    import jax.numpy as jnp

    from dllama_tpu.ops.linear import quantize_weight_q40
    from dllama_tpu.ops.quant_matmul import quant_matmul

    rng = np.random.default_rng(23)
    w = quantize_weight_q40(
        (rng.standard_normal((512, 1024)) * 0.1).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)

    exact = np.asarray(quant_matmul(x, w))
    fast = np.asarray(quant_matmul(x, w, fast=True))
    rms = float(np.sqrt(np.mean(exact ** 2)))
    drift = float(np.abs(fast - exact).max()) / rms
    assert drift < 2e-2, drift
    # row argmax (the greedy-token proxy) unchanged — asserted only where
    # the top-2 gap exceeds twice the tolerated drift, so a legal rounding
    # difference on a near-tie can't flake the test across TPU generations
    top2 = np.sort(exact, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > 2 * 2e-2 * rms
    assert decisive.any()
    np.testing.assert_array_equal(exact.argmax(-1)[decisive],
                                  fast.argmax(-1)[decisive])


def test_decode_rate_physically_sane_on_hw(tpu_backend):
    """Fetch-forced decode rate sits inside its physical window.

    Two regression classes this guards (both happened in round 4):
    * timing that doesn't force execution (a block_until_ready that
      returns early) reports ENQUEUE rates far ABOVE the HBM roofline;
    * a quant-matmul dispatch regression (e.g. back to the ~130 GB/s
      custom-call path) drops the rate far BELOW the fused-dequant band.
    Bounds are generous (roofline/6 .. roofline*1.3) so chip generations
    and host jitter can't flake them; the 2026-07-31 capture measured
    ~roofline/3.
    """
    import time

    import jax
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig, init_random_params
    from dllama_tpu.models.llama import greedy_step_guarded
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.runtime import KVCache

    cfg = ModelConfig(
        arch=ArchType.LLAMA, dim=2048, hidden_dim=8192, n_layers=8,
        n_heads=16, n_kv_heads=8, head_dim=128, vocab_size=32000,
        seq_len=512, norm_epsilon=1e-5, rope_theta=500000.0,
        rope_type=RopeType.LLAMA, compute_dtype="bfloat16")
    params = init_random_params(cfg, seed=5, quantized=True)
    kv = KVCache.create(cfg, dtype=jnp.bfloat16)
    greedy = jax.jit(greedy_step_guarded, static_argnums=1, donate_argnums=(4,))
    clean = jnp.float32(0.0)            # the tripwire's poison selector outside a chaos run

    def fetch(x):
        jax.device_get(jnp.ravel(x)[0])

    token = jnp.zeros((1,), jnp.int32)
    (token, nf), kv = greedy(params, cfg, token[:, None], jnp.int32(0), kv, clean)
    assert int(nf[0]) == 0
    fetch(token)
    (token, _), kv = greedy(params, cfg, token[:, None], jnp.int32(1), kv, clean)
    fetch(token)  # throwaway: first post-compile dispatch absorbs backlog
    probe = jax.jit(lambda x: x + 1)(jnp.zeros((8,), jnp.int32))
    fetch(probe)
    t0 = time.perf_counter()
    fetch(probe)
    rtt = time.perf_counter() - t0

    steps = 24
    t0 = time.perf_counter()
    for i in range(steps):
        (token, _), kv = greedy(params, cfg, token[:, None], jnp.int32(2 + i), kv, clean)
    fetch(token)
    ms = 1e3 * max(1e-9, time.perf_counter() - t0 - rtt) / steps

    # bytes a decode step must stream: the layer stacks + the head
    # (embedding excluded: one gathered row per step)
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(
            (params.layers, params.logits),
            is_leaf=lambda x: isinstance(x, QuantizedWeight)):
        if isinstance(leaf, QuantizedWeight):
            nbytes += leaf.codes.nbytes + leaf.scales.nbytes
        elif hasattr(leaf, "nbytes"):
            nbytes += leaf.nbytes  # dense head / norms
    from dllama_tpu.runtime.roofline import nameplate_ceilings

    gbps = nameplate_ceilings(jax.devices()[0].device_kind).hbm_gbps
    roofline_ms = 1e3 * nbytes / (gbps * 1e9)
    assert ms < 6 * roofline_ms, (
        f"decode {ms:.2f} ms/step is >6x the {roofline_ms:.2f} ms HBM "
        f"roofline — quant-matmul dispatch regression?")
    assert ms > 0.77 * roofline_ms, (
        f"decode {ms:.2f} ms/step is above the physical roofline "
        f"({roofline_ms:.2f} ms) — timing is not forcing execution")


def test_macbeth_transcript_on_hw(tpu_backend):
    """The macbeth-scale determinism chain ON CHIP (VERDICT r4 next #8): the
    reference's strongest test drives 2048+ greedy steps and diffs the
    transcript (examples/macbeth.sh:5,192); here the committed
    reference-binary golden (2049-step transcript from the rebuilt C++
    dllama) replays through the real-TPU engine in exact numerics. This is
    the longest cross-implementation chain in the suite — accumulation-order
    or dispatch-shape drift anywhere in 2k steps breaks it.

    Uses --decode-chunk to keep the per-fetch host round trip off the critical
    path (chunked decode is bit-identical by construction,
    tests/test_decode_chunk.py)."""
    from pathlib import Path
    import tempfile

    import golden_assets
    from dllama_tpu.formats.quants import F32
    from dllama_tpu.runtime.engine import InferenceEngine

    variant = "llama_macbeth_f32"
    golden = golden_assets.load_golden(variant)
    if golden is None:
        pytest.skip("no macbeth golden (run tools/golden_reference.py)")
    tmp = Path(tempfile.mkdtemp(prefix="dllama-hw-macbeth-"))
    m, t, m_sha, t_sha = golden_assets.build_assets(variant, tmp)
    if m_sha != golden["m_sha256"] or t_sha != golden["t_sha256"]:
        pytest.skip("synthetic assets no longer match the golden's hashes")

    eng = InferenceEngine(
        str(m), str(t), sync_type=F32, compute_dtype="float32",
        temperature=golden["temperature"], seed=golden["sampler_seed"],
        decode_chunk=32)
    try:
        got, r = golden_assets.replay_reference_driver(eng, golden)
        want = golden["pieces"]
        assert len(r.tokens) == len(want) >= 2000
        mismatches = [i for i in range(len(want)) if got[i] != want[i]]
        assert not mismatches, (mismatches[:5], len(want))
    finally:
        eng.close()

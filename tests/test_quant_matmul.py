"""Pallas Q40 matmul kernel vs the XLA dequant+dot oracle (the parity
methodology of nn-vulkan-test.cpp: accelerated op vs reference semantics)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.ops.linear import linear, quantize_weight_q40
from dllama_tpu.ops.quant_matmul import quant_matmul, supports


def _mk(out, in_, seed=0):
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((out, in_)) * 0.1).astype(np.float32)
    return quantize_weight_q40(w)


@pytest.mark.parametrize("m,n,k", [
    (1, 256, 512),     # decode step
    (8, 512, 1024),    # small prefill
    (32, 128, 256),    # reference nBatches
    (16, 64, 128),     # kv-proj-like narrow output
])
def test_kernel_matches_xla_oracle(m, n, k):
    w = _mk(n, k, seed=n + k)
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, k)), jnp.float32)
    want = linear(x, w)
    got = quant_matmul(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_q80_planes():
    """Q80 weights land in the same (scales, int8-codes) planes — the kernel
    consumes them unchanged (codes*scales; nothing 4-bit-specific). The
    codes span the full int8 range here, unlike Q40's [-8, 7]."""
    from dllama_tpu.formats.quants import quantize_q80, unpack_q80

    rng = np.random.default_rng(5)
    w = (rng.standard_normal((256, 512)) * 0.1).astype(np.float32)
    scales, codes = unpack_q80(quantize_q80(w.reshape(-1)), w.size)
    from dllama_tpu.ops.linear import QuantizedWeight

    qw = QuantizedWeight(
        scales=jnp.asarray(scales.reshape(256, 16).T.astype(np.float32)),
        codes=jnp.asarray(np.ascontiguousarray(codes.reshape(256, 512).T)))
    assert int(np.abs(np.asarray(qw.codes)).max()) > 8  # genuinely 8-bit
    x = jnp.asarray(rng.standard_normal((4, 512)), jnp.float32)
    want = linear(x, qw)
    got = quant_matmul(x, qw, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_3d_batch():
    w = _mk(256, 512, seed=1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 3, 512)), jnp.float32)
    want = linear(x, w)
    got = quant_matmul(x, w, interpret=True)
    assert got.shape == (2, 3, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_supports_predicate():
    assert supports((1, 512), _mk(256, 512))
    assert supports((1, 96), _mk(256, 96))  # K=96: whole-K block (÷32)
    assert supports((1, 512), _mk(96, 512))  # N=96: whole-N block
    # K mismatch between x and w is never dispatched to the kernel
    assert not supports((1, 256), _mk(96, 512))
    # oversized batch falls back to XLA (VMEM bound on the un-tiled M axis)
    assert not supports((2048, 512), _mk(96, 512))
    # stacked (3D) weights fall back to XLA
    from dllama_tpu.ops.linear import QuantizedWeight

    w = _mk(96, 512)
    stacked = QuantizedWeight(scales=w.scales[None], codes=w.codes[None])
    assert not supports((1, 512), stacked)


# ---------------------------------------------------------------------------
# sharded kernel (shard_map wrapper) vs the auto-sharded XLA path
# ---------------------------------------------------------------------------

from dllama_tpu.parallel.api import make_mesh, make_tp_mesh, use_plan  # noqa: E402
from dllama_tpu.ops.quant_matmul import quant_matmul_sharded  # noqa: E402


def _x3(b, t, k, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, t, k)), jnp.float32)


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_row_split_matches_oracle(tp):
    plan = make_tp_mesh(tp)
    w = _mk(256, 512, seed=9)
    x = _x3(1, 8, 512)
    want = linear(x, w)
    got = quant_matmul_sharded(plan, x, w, out_axis="hidden", interpret=True)
    assert got is not None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_col_split_matches_oracle(tp):
    plan = make_tp_mesh(tp)
    w = _mk(256, 512, seed=10)
    x = _x3(1, 8, 512)
    want = linear(x, w)
    got = quant_matmul_sharded(plan, x, w, in_axis="hidden", interpret=True)
    assert got is not None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sharded_replicated_fallback_runs_kernel():
    """Non-divisible shard dim (KV replication case): kernel runs replicated."""
    plan = make_tp_mesh(4)
    w = _mk(96, 512, seed=11)  # 96 % 4 != 0 at lane granularity... 96/4=24, divisible
    # use an axis name the mesh doesn't carry to force replication instead
    got = quant_matmul_sharded(plan, _x3(1, 4, 512), w, out_axis="experts",
                               interpret=True)
    assert got is not None
    want = linear(_x3(1, 4, 512), w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_sharded_with_dp_batch():
    plan = make_mesh({"dp": 2, "tp": 2})
    w = _mk(256, 512, seed=12)
    x = _x3(4, 2, 512)
    want = linear(x, w)
    got = quant_matmul_sharded(plan, x, w, out_axis="hidden", interpret=True)
    assert got is not None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fast mode (bf16 dequant, one MXU pass) vs exact mode — SURVEY §7.4's
# exact/fast split; drift bound is the deliverable (VERDICT r3 next #2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,k", [(1, 256, 512), (8, 512, 1024)])
def test_fast_mode_drift_bounded(m, n, k):
    """Fast-mode output drifts from the exact kernel only by bf16 rounding of
    the weights/activations: relative error stays under ~1%, typical ~0.3%.
    The accumulator is f32, so error does NOT grow with K."""
    w = _mk(n, k, seed=n + k + 1)
    x = jnp.asarray(np.random.default_rng(m + 7).standard_normal((m, k)),
                    jnp.float32)
    exact = np.asarray(quant_matmul(x, w, interpret=True))
    fast = np.asarray(quant_matmul(x, w, interpret=True, fast=True))
    rel = np.abs(fast - exact) / np.maximum(np.abs(exact), 1e-3)
    assert float(np.median(rel)) < 3e-3, float(np.median(rel))
    # elementwise max-rel explodes where the exact output cancels to ~0, so
    # the worst-case bound is error relative to the output's RMS magnitude
    rms = float(np.sqrt(np.mean(exact ** 2)))
    assert float(np.abs(fast - exact).max()) / rms < 2e-2, \
        (float(np.abs(fast - exact).max()), rms)


def test_fast_mode_env_knob_xla_path(monkeypatch):
    """DLLAMA_TPU_QUANT_MODE=fast flips the XLA fallback to bf16 dequant; the
    output dtype still matches the caller's activation dtype."""
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    w = _mk(256, 512, seed=21)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((4, 512)),
                    jnp.float32)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    exact = linear(x, w)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "fast")
    fast = linear(x, w)
    assert fast.dtype == x.dtype
    denom = np.maximum(np.abs(np.asarray(exact)), 1e-3)
    rel = np.abs(np.asarray(fast) - np.asarray(exact)) / denom
    assert float(np.median(rel)) < 5e-3, float(np.median(rel))


def test_fast_mode_auto_keys_off_bf16_activations(monkeypatch):
    """Unit-tests the mode predicate: auto resolves to fast iff activations
    are bf16; explicit exact/fast override the dtype. (The numerics each mode
    produces are covered by the drift tests above.)"""
    from dllama_tpu.ops.linear import _fast_mode

    monkeypatch.delenv("DLLAMA_TPU_QUANT_MODE", raising=False)
    assert _fast_mode(jnp.zeros((1, 4), jnp.bfloat16)) is True
    assert _fast_mode(jnp.zeros((1, 4), jnp.float32)) is False
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    assert _fast_mode(jnp.zeros((1, 4), jnp.bfloat16)) is False
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "fast")
    assert _fast_mode(jnp.zeros((1, 4), jnp.float32)) is True


def test_fast_mode_sharded_matches_plain_fast():
    """The shard_map-wrapped fast kernel reproduces the single-device fast
    kernel (row and col splits)."""
    plan = make_tp_mesh(2)
    w = _mk(256, 512, seed=22)
    x = _x3(1, 8, 512, seed=23)
    want = np.asarray(quant_matmul(x.reshape(8, 512), w, interpret=True,
                                   fast=True)).reshape(1, 8, 256)
    for kw in ({"out_axis": "hidden"}, {"in_axis": "hidden"}):
        got = quant_matmul_sharded(plan, x, w, interpret=True, fast=True, **kw)
        assert got is not None
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2, atol=1e-3)


def test_fast_mode_model_logit_drift(monkeypatch):
    """End-to-end logit drift of fast-mode numerics on a full (tiny) model
    forward — the quantified exact-vs-fast deliverable at the level users see.
    Drift is bf16-rounding-sized; argmax (greedy token) is stable here."""
    from dllama_tpu.formats import mfile
    from dllama_tpu.models import ModelConfig, forward, init_random_params
    from dllama_tpu.runtime import KVCache

    cfg = ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=8, vocab_size=128, seq_len=32,
        norm_epsilon=1e-5, rope_theta=10000.0,
        rope_type=mfile.RopeType.LLAMA)
    params = init_random_params(cfg, seed=31, quantized=True)
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], dtype=jnp.int32)

    # fresh lambdas per mode: jit wrappers around the SAME function object
    # share the global pjit executable cache, which would reuse the exact
    # program for the fast run and make this test vacuous
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    exact, _ = jax.jit(lambda p, c, t, s, k: forward(p, c, t, s, k),
                       static_argnums=1)(
        params, cfg, tokens, jnp.int32(0), KVCache.create(cfg))
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "fast")
    fast, _ = jax.jit(lambda p, c, t, s, k: forward(p, c, t, s, k),
                      static_argnums=1)(
        params, cfg, tokens, jnp.int32(0), KVCache.create(cfg))

    e = np.asarray(exact, np.float32)
    f = np.asarray(fast, np.float32)
    assert not np.array_equal(e, f)  # the mode switch actually engaged
    rms = float(np.sqrt(np.mean(e ** 2)))
    drift = float(np.abs(f - e).max()) / rms
    assert drift < 5e-2, drift
    np.testing.assert_array_equal(e.argmax(-1), f.argmax(-1))


# ---------------------------------------------------------------------------
# decode-shaped FUSED dequant-GEMV kernel (DLLAMA_TPU_QUANT_KERNEL=fused):
# one full-K pass per N stripe, dequant in-register — BIT-PARITY with the
# XLA fused-dequant reference in exact mode (the single full-K dot keeps
# the reference's reduction structure; the tiled kernel's blocked
# k-accumulation cannot make this claim)
# ---------------------------------------------------------------------------

from dllama_tpu.ops.linear import dequantize_weight  # noqa: E402
from dllama_tpu.ops.quant_matmul import supports_decode  # noqa: E402


def _assert_bit_parity(got, want):
    """Fused kernel vs the XLA fused-dequant reference, to the last few
    bits. Written as bitwise equality; under jaxlib 0.9 the CPU backend picks
    its GEMV/GEMM blocking by host (the one-row cases differ in the last
    bits on some hosts and not on others: max abs 4.3e-6 on values of order
    5, PR 22), so the interpret-mode claim a CPU can check is "a few ulps".
    Whether the COMPILED kernel is bit-identical is the chip's to say."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4e-6, atol=8e-6)


def _xla_fused_dequant(x, w, fast=False):
    """The XLA fused-dequant reference linear() falls back to — computed
    with the same ops, so the kernel's parity target is the real thing."""
    wd = dequantize_weight(w, dtype=jnp.bfloat16 if fast else x.dtype)
    xr = x.astype(jnp.bfloat16) if fast else x
    return jax.lax.dot_general(
        xr, wd, dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@pytest.mark.parametrize("m,n,k", [
    (1, 256, 512),     # decode step
    (16, 512, 1024),   # FUSED_MAX_M edge (verify width)
    (4, 96, 96),       # whole-N block, tiny-K
    (2, 128, 2048),    # multi-chunk scale expansion (bk_e < K)
])
def test_fused_kernel_bit_parity_q40(m, n, k):
    w = _mk(n, k, seed=n + k)
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, k)),
                    jnp.float32)
    assert supports_decode((m, k), w)
    got = quant_matmul(x, w, interpret=True, fused=True)
    _assert_bit_parity(got, _xla_fused_dequant(x, w))


def test_fused_kernel_bit_parity_q80_planes():
    """Q80 weights land in the same (scales, int8-codes) planes; the fused
    kernel consumes them unchanged and stays bit-parity."""
    from dllama_tpu.formats.quants import quantize_q80, unpack_q80
    from dllama_tpu.ops.linear import QuantizedWeight

    rng = np.random.default_rng(5)
    w = (rng.standard_normal((256, 512)) * 0.1).astype(np.float32)
    scales, codes = unpack_q80(quantize_q80(w.reshape(-1)), w.size)
    qw = QuantizedWeight(
        scales=jnp.asarray(scales.reshape(256, 16).T.astype(np.float32)),
        codes=jnp.asarray(np.ascontiguousarray(codes.reshape(256, 512).T)))
    assert int(np.abs(np.asarray(qw.codes)).max()) > 8  # genuinely 8-bit
    x = jnp.asarray(rng.standard_normal((1, 512)), jnp.float32)
    got = quant_matmul(x, qw, interpret=True, fused=True)
    _assert_bit_parity(got, _xla_fused_dequant(x, qw))


def test_fused_kernel_fast_mode_drift_bounded():
    """Fast mode (bf16 dequant, one MXU pass, f32 accumulation): the XLA
    reference's in-jaxpr fusion may elide the bf16 rounding of the dequant
    transient, so fast parity is drift-bounded (bf16-rounding-sized), not
    bitwise — same contract as the tiled kernel's fast mode."""
    w = _mk(256, 2048, seed=77)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((1, 2048)),
                    jnp.float32)
    fast = np.asarray(quant_matmul(x, w, interpret=True, fused=True,
                                   fast=True))
    exact = np.asarray(quant_matmul(x, w, interpret=True, fused=True))
    rel = np.abs(fast - exact) / np.maximum(np.abs(exact), 1e-3)
    assert float(np.median(rel)) < 3e-3, float(np.median(rel))
    rms = float(np.sqrt(np.mean(exact ** 2)))
    assert float(np.abs(fast - exact).max()) / rms < 2e-2


def test_fused_exact_bf16_graph_mirrors_reference_dequant():
    """An exact-mode bf16 activation graph: the kernel dequantizes at
    bf16 like the XLA reference (dequant-at-activation-dtype rule), so
    xla↔fused drift is bf16-rounding-sized — NOT bitwise (XLA fusion may
    elide the bf16 rounding on either side; the bitwise claim is scoped
    to f32 graphs)."""
    w = _mk(256, 512, seed=61)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 512)),
                    jnp.bfloat16)
    got = np.asarray(quant_matmul(x, w, interpret=True, fused=True),
                     np.float32)
    want = np.asarray(_xla_fused_dequant(x.astype(jnp.float32), w),
                      np.float32)
    rms = float(np.sqrt(np.mean(want ** 2)))
    assert float(np.abs(got - want).max()) / rms < 2e-2


def test_fused_falls_back_to_tiled_for_prefill_widths():
    """fused=True on an M > FUSED_MAX_M dispatch silently takes the tiled
    kernel — a fused-mode engine never fails on its prefill chunks."""
    from dllama_tpu.ops.quant_matmul import FUSED_MAX_M

    m = FUSED_MAX_M * 2
    w = _mk(256, 512, seed=31)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((m, 512)),
                    jnp.float32)
    assert not supports_decode((m, 512), w)
    got = quant_matmul(x, w, interpret=True, fused=True)
    want = quant_matmul(x, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_mode_gate(monkeypatch):
    """DLLAMA_TPU_QUANT_KERNEL=fused resolves through pallas_mode_gate
    (the ONE gate): fused kwargs off-TPU carry interpret=True. ``auto``
    resolves to the fused kernel from what the dispatch shows — fast mode,
    a TPU, no plan, a decode-shaped 2-D dispatch — and to nothing off a
    TPU (the truth table below has the rest)."""
    from dllama_tpu.ops import quant_matmul as qm

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    for fast in (False, True):
        kw = qm.pallas_mode_gate(fast)
        assert kw is not None and kw["fused"] is True
        assert kw["interpret"] is True  # off-TPU test path
    monkeypatch.delenv("DLLAMA_TPU_QUANT_KERNEL", raising=False)
    w = _w_shapes(512, 256)
    assert qm.pallas_mode_gate(True, (4, 512), w) is None   # not a TPU
    monkeypatch.setattr(qm, "on_tpu", lambda: True)
    assert qm.pallas_mode_gate(True, (4, 512), w) == {"interpret": False,
                                                      "fused": True}
    assert qm.pallas_mode_gate(True) is None    # shown no shape: no kernel
    kw = qm.pallas_mode_gate(False, (4, 512), w)
    assert kw == {"interpret": False}           # exact mode: tiled, as ever


def _w_shapes(k, n, lead=(), scales=jnp.float32):
    """A Q40 weight as shapes only (what the gates look at)."""
    from dllama_tpu.ops.linear import QuantizedWeight

    return QuantizedWeight(
        scales=jax.ShapeDtypeStruct(lead + (k // 32, n), scales),
        codes=jax.ShapeDtypeStruct(lead + (k, n), jnp.int8))


def _gate_oracle(mode, fast, tpu, m, ndim, plan):
    """The rule, written out again: (kernel, interpret) or None."""
    if mode == "xla":
        return None
    if mode == "fused":
        return ("fused", not tpu)
    if mode == "pallas":
        return ("tiled", not tpu)
    if not tpu:
        return None
    if not fast:
        return ("tiled", False)
    if 1 <= m <= 320 and ndim == 2 and not plan:
        return ("fused", False)
    return None


def _path_oracle(fast, m, ndim):
    """Which path a gate result that wants the fused kernel is counted
    under (None: quant_matmul runs the tiled kernel, or nothing does)."""
    if ndim != 2:
        return None
    if 1 <= m <= 16:
        return "fused"
    return "chunk" if fast and m <= 320 else None


@pytest.mark.parametrize("m", [1, 2, 4, 8, 16, 17, 32, 64, 128, 256, 257,
                               272, 320, 321, 512])
@pytest.mark.parametrize("plan", [False, True], ids=["noplan", "plan"])
@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
@pytest.mark.parametrize("fast", [False, True], ids=["exact", "fast"])
@pytest.mark.parametrize("mode", ["auto", "xla", "pallas", "fused"])
def test_gate_truth_table(monkeypatch, mode, fast, tpu, plan, m):
    """mode x fast x platform x M x 2-D/3-D weight x plan/no plan. The rows
    that matter: fast ``auto`` on a TPU is the fused kernel at EVERY M from
    1 to 16 (no lower bound: 2 and 4 engage like 16), counted ``fused``,
    and at every M from 17 to 320 (a prefill chunk of up to 256 rows,
    alone or with a tick's decode rows joined to it), counted ``chunk``;
    nothing at 321 or 512, under a plan or over a stack of experts — and
    never the tiled kernel by this rule. Exact mode has no chunk regime."""
    from contextlib import nullcontext

    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.ops.linear import _fused_path, _pallas_wanted
    from dllama_tpu.parallel.api import make_tp_mesh, use_plan

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", mode)
    monkeypatch.setattr(qm, "on_tpu", lambda: tpu)
    x = jax.ShapeDtypeStruct((m, 512), jnp.bfloat16 if fast else jnp.float32)
    with (use_plan(make_tp_mesh(2)) if plan else nullcontext()):
        for ndim, w in ((2, _w_shapes(512, 256)),
                        (3, _w_shapes(512, 256, lead=(4,)))):
            want = _gate_oracle(mode, fast, tpu, m, ndim, plan)
            kw = qm.pallas_mode_gate(fast, x.shape, w)
            got = None if kw is None else (
                "fused" if qm.wants_fused(kw) else "tiled", kw["interpret"])
            assert got == want, (ndim, kw)
            if want is not None and want[0] == "fused":
                assert _fused_path(kw, x, w, fast) == _path_oracle(
                    fast, m, ndim)
            else:
                assert _fused_path(kw, x, w, fast) is None
            # what linear()'s plain path makes of it: a kernel only where a
            # kernel covers the shape, and never tiled for fast auto
            plain = _pallas_wanted(x, w, fast)
            if mode == "auto" and fast:
                assert (plain is not None) == (want is not None)
                assert plain is None or qm.wants_fused(plain)
            if ndim == 3:
                assert plain is None    # MoE-style stacks keep the XLA path


def test_auto_has_no_row_floor():
    """No constant keeps a row count between 1 and FUSED_MAX_M off the
    kernel: the only bounds in the module are the upper one and VMEM."""
    from dllama_tpu.ops import quant_matmul as qm

    assert not hasattr(qm, "FUSED_MIN_M")
    w = _w_shapes(4096, 14336, scales=jnp.bfloat16)
    assert all(qm.supports_decode((m, 4096), w, True)
               for m in range(1, qm.FUSED_MAX_M + 1))
    assert not qm.supports_decode((qm.FUSED_MAX_M + 1, 4096), w, True)
    # ... and none between FUSED_MAX_M and CHUNK_MAX_M off the chunk regime
    assert not hasattr(qm, "CHUNK_MIN_M")
    assert all(qm.fused_path((m, 4096), w, True) == "chunk"
               for m in range(qm.FUSED_MAX_M + 1, qm.CHUNK_MAX_M + 1))
    assert qm.fused_path((qm.CHUNK_MAX_M + 1, 4096), w, True) is None
    assert qm.fused_path((qm.FUSED_MAX_M + 1, 4096), w, False) is None


def _stack(n_layers, out, in_, seed):
    from dllama_tpu.ops.linear import QuantizedWeight

    ws = [_mk(out, in_, seed=seed + l) for l in range(n_layers)]
    return ws, QuantizedWeight(scales=jnp.stack([w.scales for w in ws]),
                               codes=jnp.stack([w.codes for w in ws]))


@pytest.mark.parametrize("fast", [False, True], ids=["exact", "fast"])
@pytest.mark.parametrize("m", [1, 2, 4, 16])
def test_stack_and_index_equals_per_layer_bitwise(m, fast):
    """The decode kernel handed the layer stack and an index computes what
    it computes handed that layer's planes: bitwise, for every index, with
    f32 and with bf16 (fast-load) scales."""
    from dllama_tpu.ops.linear import QuantizedWeight

    ws, stack = _stack(3, 256, 512, seed=90)
    if fast:
        cast = lambda w: QuantizedWeight(w.scales.astype(jnp.bfloat16), w.codes)
        ws, stack = [cast(w) for w in ws], cast(stack)
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, 512)),
                    jnp.float32)
    call = jax.jit(lambda x, s, l: quant_matmul(
        x, s, interpret=True, fused=True, fast=fast, layer=l))
    for l, w in enumerate(ws):
        want = quant_matmul(x, w, interpret=True, fused=True, fast=fast)
        got = call(x, stack, jnp.int32(l))   # traced index: one program
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert call._cache_size() == 1


def test_stack_entry_refuses_what_the_decode_kernel_cannot_take():
    """``layer`` has no tiled twin: a prefill-wide dispatch or a forced
    tile raises instead of silently reading layer 0."""
    _, stack = _stack(2, 256, 512, seed=95)
    x = jnp.zeros((32, 512), jnp.float32)
    with pytest.raises(ValueError, match="layer-stack entry"):
        quant_matmul(x, stack, interpret=True, fused=True, layer=jnp.int32(1))
    with pytest.raises(ValueError, match="layer-stack entry"):
        quant_matmul(x[:1], stack, interpret=True, layer=jnp.int32(1))


@pytest.mark.parametrize("mode,path", [("xla", "xla"), ("pallas", "tiled"),
                                       ("fused", "fused")])
def test_linear_layer_slice_matches_the_plain_slice(monkeypatch, mode, path):
    """linear() over a LayerSlice: the fused mode reads the stack through
    the index, every other mode takes the slice — same values as linear()
    on that layer's own planes, and the path is noted for the program."""
    from dllama_tpu.ops.linear import LayerSlice
    from dllama_tpu.runtime import introspection

    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", mode)
    ws, stack = _stack(3, 256, 512, seed=70)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((2, 2, 512)),
                    jnp.float32)
    scope = f"layer-slice-{mode}"
    f = introspection.observe(
        jax.jit(lambda x, s, l: linear(x, LayerSlice(s, l))),
        scope=scope, program="p")
    for l, w in enumerate(ws):
        np.testing.assert_array_equal(
            np.asarray(f(x, stack, jnp.int32(l))), np.asarray(linear(x, w)))
    counts = introspection.ledger().q40_paths(scope)["p"]
    assert counts == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 0, path: 1}


def test_layer_slice_under_a_plan_takes_the_slice(monkeypatch):
    """The stack entry has no sharded twin: under a mesh plan a LayerSlice
    is sliced and dispatched like any 2-D weight."""
    from dllama_tpu.ops.linear import LayerSlice

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    ws, stack = _stack(2, 256, 512, seed=75)
    x = _x3(1, 4, 512, seed=76)
    with use_plan(make_tp_mesh(2)):
        got = linear(x, LayerSlice(stack, jnp.int32(1)), out_axis="hidden")
    np.testing.assert_allclose(np.asarray(got), np.asarray(linear(x, ws[1])),
                               rtol=1e-5, atol=1e-5)


def test_fused_mode_linear_end_to_end(monkeypatch):
    """linear() under DLLAMA_TPU_QUANT_KERNEL=fused dispatches the decode
    kernel for a decode-shaped activation and matches the XLA reference
    bitwise (exact numerics)."""
    monkeypatch.setenv("DLLAMA_TPU_QUANT_MODE", "exact")
    w = _mk(256, 512, seed=41)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 512)),
                    jnp.float32)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    want = linear(x, w)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    got = linear(x, w)
    _assert_bit_parity(got, want)


def test_fused_sharded_col_split_matches_oracle():
    """The shard_map-wrapped fused kernel under a tp mesh (col-split: the
    decode hot path's wo/w2 merges)."""
    plan = make_tp_mesh(2)
    w = _mk(256, 512, seed=51)
    x = _x3(1, 4, 512, seed=52)
    want = linear(x, w)
    got = quant_matmul_sharded(plan, x, w, in_axis="hidden",
                               interpret=True, fused=True)
    assert got is not None
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_linear_dispatches_sharded_kernel_under_plan(monkeypatch):
    """linear() no longer bypasses the kernel under a mesh plan
    (VERDICT round-1 weak #2): DLLAMA_TPU_QUANT_KERNEL=pallas + plan routes
    through quant_matmul_sharded in interpret mode off-TPU."""
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    plan = make_tp_mesh(2)
    w = _mk(256, 512, seed=13)
    x = _x3(1, 4, 512)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    want = linear(x, w)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    with use_plan(plan):
        got = linear(x, w, out_axis="hidden")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,path", [(1, "fused"), (4, "fused"), (32, "tiled")])
def test_dense_forward_scans_the_layer_index_for_decode_shapes(monkeypatch, t,
                                                               path):
    """``forward`` (the dense slot pool: ``inference``, ``greedy_step``)
    walks the layer index for a decode-shaped dispatch, so its Q40 planes
    reach linear() as stack + index and the forced fused mode reads them
    in place; a chunk-wide dispatch walks the index too, and in EXACT mode
    (this f32 graph) the fused kernel has no regime for it, so forced
    ``fused`` falls to the tiled kernel on the slice. Same logits and
    cache as the XLA mode either way."""
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig, init_random_params
    from dllama_tpu.models.llama import forward
    from dllama_tpu.runtime import introspection
    from dllama_tpu.runtime.kvcache import KVCache

    cfg = ModelConfig(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=3,
                      n_heads=8, n_kv_heads=2, head_dim=8, vocab_size=128,
                      seq_len=64, norm_epsilon=1e-5, rope_theta=10000.0,
                      rope_type=RopeType.LLAMA)
    params = init_random_params(cfg, seed=11, quantized=True)
    tokens = jnp.asarray(
        np.random.default_rng(t).integers(1, 127, (1, t)).astype(np.int32))

    def run(mode):
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", mode)
        scope = f"dense-forward-{t}-{mode}"
        f = introspection.observe(
            jax.jit(lambda p, c, tk, s, kv: forward(p, c, tk, s, kv),
                    static_argnums=1), scope=scope, program="forward")
        logits, kv = f(params, cfg, tokens, jnp.int32(3), KVCache.create(cfg))
        return logits, kv, introspection.ledger().q40_paths(scope)["forward"]

    want, kv_x, paths_x = run("xla")
    got, kv_k, paths_k = run("fused")
    assert paths_x == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 8}
    assert paths_k == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 0, path: 8}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kv_k.k), np.asarray(kv_x.k),
                               rtol=1e-5, atol=1e-6)


# --- the chunk regime of the fused kernel (PR 35): 17..256 rows, fast mode;
# --- to 320 since PR 47 (the widest bucket and a tick's decode rows) ---------

# reduced copies of the benchmark configurations' plane shapes [K, N]: the
# widths cut by 8 (or so) and kept on the 128-lane grid, the oddities kept:
# K = 640 / 480 / 1376 are 20 / 15 / 43 Q40 blocks (512 divides none of
# them), and the hybrid's packed q k v z plane keeps its own 17280 columns
# (135 x 128: only a 128-wide stripe divides it)
CHUNK_SHAPES = {
    "mistral-wq": (512, 512), "mistral-wk": (512, 128),
    "mistral-w1": (512, 1792), "mistral-w2": (1792, 512),
    "qwen3-wq": (640, 1024), "qwen3-wo": (1024, 640),
    "qwen3-w2": (2432, 640),
    "hybrid-qkvz": (480, 17280), "hybrid-w2": (1376, 384),
}


def _planes(k, n, seed, lead=()):
    """Random Q40 planes as a fast-mode load holds them (bf16 scales),
    made directly: quantizing a dense [17280, 480] on the host is slow."""
    from dllama_tpu.ops.linear import QuantizedWeight

    rng = np.random.default_rng(seed)
    return QuantizedWeight(
        scales=jnp.asarray(rng.uniform(0.001, 0.011, lead + (k // 32, n)),
                           jnp.bfloat16),
        codes=jnp.asarray(rng.integers(-8, 8, lead + (k, n)), jnp.int8))


@pytest.mark.parametrize("stacked", [False, True], ids=["plane", "stack"])
@pytest.mark.parametrize("m", [17, 32, 64, 128, 256, 272, 320])
@pytest.mark.parametrize("shape", sorted(CHUNK_SHAPES))
def test_chunk_kernel_matches_dequant_then_dot(shape, m, stacked):
    """The chunk regime against ``dequantize_weight`` + ``dot_general``
    (what linear() falls back to) at every bucket width, one off the
    buckets and the joined widths (the widest bucket with 16 slots' decode
    rows, and the regime's upper edge), handed a plane pair or the layer
    stack and a traced index."""
    from dllama_tpu.ops import quant_matmul as qm

    k, n = CHUNK_SHAPES[shape]
    x = jnp.asarray(np.random.default_rng(m).standard_normal((m, k)),
                    jnp.bfloat16)
    if stacked:
        stack = _planes(k, n, seed=k + n, lead=(3,))
        w = type(stack)(*(p[2] for p in stack))
        assert qm.fused_path((m, k), w, True) == "chunk"
        got = jax.jit(lambda x, s, l: qm._decode_call(
            x, s, interpret=True, fast=True, layer=l))(x, stack, jnp.int32(2))
    else:
        w = _planes(k, n, seed=k + n)
        assert qm.fused_path((m, k), w, True) == "chunk"
        got = qm._decode_call(x, w, interpret=True, fast=True)
    assert got.shape == (m, n) and got.dtype == jnp.float32
    _assert_bit_parity(got, _xla_fused_dequant(x, w, fast=True))


@pytest.mark.parametrize("scales", [jnp.bfloat16, jnp.float32],
                         ids=["bf16-scales", "f32-scales"])
def test_chunk_kernel_dequantizes_the_tile_bit_for_bit(scales):
    """An identity activation reads the ``wd`` scratch out: every entry is
    one product and 255 zeros, so the f32 output IS the dequantized tile,
    and it equals ``dequantize_weight(w, bfloat16)`` bit for bit (the scale
    rounded to bf16 first, the product rounded once)."""
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.ops.linear import QuantizedWeight

    k, n = 256, 384
    w = _mk(n, k, seed=5)
    w = QuantizedWeight(w.scales.astype(scales), w.codes)
    got = qm._decode_call(jnp.eye(k, dtype=jnp.bfloat16), w, interpret=True,
                          fast=True)
    want = dequantize_weight(w, jnp.bfloat16)
    assert np.any(np.asarray(want, np.float32) != 0)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want, np.float32))


def test_chunk_regime_is_fast_modes_alone_and_ends_at_320_rows():
    """quant_matmul(fused=True) past either bound runs the tiled kernel
    (exact mode keeps what the goldens were taken with), and the stack
    entry refuses such a dispatch instead of reading layer 0."""
    w = _mk(256, 512, seed=31)
    _, stack = _stack(2, 256, 512, seed=95)
    for m, fast in ((32, False), (321, True)):
        x = jnp.asarray(np.random.default_rng(m).standard_normal((m, 512)),
                        jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(quant_matmul(x, w, interpret=True, fused=True,
                                    fast=fast)),
            np.asarray(quant_matmul(x, w, interpret=True, fast=fast)))
        with pytest.raises(ValueError, match="layer-stack entry"):
            quant_matmul(x, stack, interpret=True, fused=True, fast=fast,
                         layer=jnp.int32(1))


@pytest.mark.parametrize("mode,path", [("xla", "xla"), ("fused", "chunk")])
def test_linear_layer_slice_at_chunk_width(monkeypatch, mode, path):
    """linear() over a LayerSlice of 64 bf16 rows: the fused mode reads the
    stack through the index and counts the dispatch as ``chunk``."""
    from dllama_tpu.ops.linear import LayerSlice, QuantizedWeight
    from dllama_tpu.runtime import introspection

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", mode)
    stack = _planes(512, 256, seed=3, lead=(3,))
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 64, 512)),
                    jnp.bfloat16)
    scope = f"layer-slice-chunk-{mode}"
    f = introspection.observe(
        jax.jit(lambda x, s, l: linear(x, LayerSlice(s, l))),
        scope=scope, program="p")
    for l in range(3):
        w = QuantizedWeight(*(p[l] for p in stack))
        want = _xla_fused_dequant(x, w, fast=True).astype(jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(f(x, stack, jnp.int32(l)), np.float32),
            np.asarray(want, np.float32))
    counts = introspection.ledger().q40_paths(scope)["p"]
    assert counts == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 0, path: 1}


@pytest.mark.parametrize("t", [64, 256])
def test_dense_forward_takes_the_chunk_kernel_at_bucket_widths(monkeypatch, t):
    """``forward`` over a bf16 graph at a 64- and a 256-row bucket: it walks
    the layer index, every Q40 dispatch (seven a layer and this toy's
    quantized head) reaches the fused kernel's chunk regime through the
    stack entry, none falls back to XLA, and the logits and the cache are
    the XLA mode's to bf16 rounding."""
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig, init_random_params
    from dllama_tpu.models.llama import forward
    from dllama_tpu.runtime import introspection
    from dllama_tpu.runtime.kvcache import KVCache

    cfg = ModelConfig(arch=ArchType.LLAMA, dim=128, hidden_dim=256,
                      n_layers=3, n_heads=8, n_kv_heads=2, head_dim=16,
                      vocab_size=256, seq_len=320, norm_epsilon=1e-5,
                      rope_theta=10000.0, rope_type=RopeType.LLAMA,
                      compute_dtype="bfloat16")
    params = init_random_params(cfg, seed=11, quantized=True)
    tokens = jnp.asarray(
        np.random.default_rng(t).integers(1, 255, (1, t)).astype(np.int32))

    def run(mode):
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", mode)
        scope = f"dense-forward-chunk-{t}-{mode}"
        f = introspection.observe(
            jax.jit(lambda p, c, tk, s, kv: forward(p, c, tk, s, kv),
                    static_argnums=1), scope=scope, program="forward")
        logits, kv = f(params, cfg, tokens, jnp.int32(3), KVCache.create(cfg))
        return logits, kv, introspection.ledger().q40_paths(scope)["forward"]

    want, kv_x, paths_x = run("xla")
    got, kv_k, paths_k = run("fused")
    assert paths_x == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 8}
    assert paths_k == {"chunk": 8, "fused": 0, "tiled": 0, "grouped": 0, "xla": 0}
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2 * scale)
    np.testing.assert_allclose(np.asarray(kv_k.k, np.float32),
                               np.asarray(kv_x.k, np.float32), atol=3e-2)

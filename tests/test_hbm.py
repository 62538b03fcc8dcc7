"""Pre-staging HBM budget guard (runtime.hbm) — the reference prints its
required-memory estimate before loading (nn-core.cpp:162-176); here a misfit
must refuse cleanly instead of OOM-wedging the TPU backend (VERDICT r3 #7)."""

import pytest

from dllama_tpu.formats import mfile
from dllama_tpu.models import ModelConfig
from dllama_tpu.runtime.hbm import (
    check_budget,
    device_memory_bytes,
    estimate_device_bytes,
    matmul_weight_count,
)


def _cfg(**kw):
    base = dict(
        arch=mfile.ArchType.LLAMA, dim=4096, hidden_dim=14336, n_layers=32,
        n_heads=32, n_kv_heads=8, head_dim=128, vocab_size=128256,
        seq_len=1024, norm_epsilon=1e-5, rope_theta=500000.0,
        rope_type=mfile.RopeType.LLAMA)
    base.update(kw)
    return ModelConfig(**base)


def test_8b_q40_fits_16gb_chip():
    """The north-star config (8B Q40, one v5e 16 GB chip) must fit by
    construction — the guard exists to stop misfits, not the headline run."""
    est = estimate_device_bytes(_cfg(), weight_repr="q40", kv_dtype_bytes=2)
    assert est["need_per_device"] < 16 * 1024 ** 3
    # and the estimate is in the right ballpark: ~8B params * 1.125 B
    assert 7e9 < matmul_weight_count(_cfg()) < 9e9
    assert est["weights_bytes"] > 8e9


def test_8b_f32_refuses_16gb(monkeypatch):
    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(16 * 1024 ** 3))
    est = estimate_device_bytes(_cfg(), weight_repr="f32", kv_dtype_bytes=2)
    with pytest.raises(RuntimeError, match="refusing to stage"):
        check_budget(est["need_per_device"], "test model")


def test_skip_env_bypasses(monkeypatch):
    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(16 * 1024 ** 3))
    monkeypatch.setenv("DLLAMA_SKIP_HBM_CHECK", "1")
    est = estimate_device_bytes(_cfg(), weight_repr="f32", kv_dtype_bytes=2)
    assert check_budget(est["need_per_device"], "test model") is None


def test_sharding_and_offload_shrink_need():
    c = _cfg()
    full = estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=2)
    tp8 = estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=2,
                                n_shards=8)
    off = estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=2,
                                offload=True)
    assert tp8["need_per_device"] < full["need_per_device"] / 4
    assert off["need_per_device"] < full["need_per_device"] / 2


def test_70b_single_chip_refuses(monkeypatch):
    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(16 * 1024 ** 3))
    c = _cfg(dim=8192, hidden_dim=28672, n_layers=80, n_heads=64)
    est = estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=2)
    with pytest.raises(RuntimeError):
        check_budget(est["need_per_device"], "70B")
    # but offload over 8 shards fits
    est8 = estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=2,
                                 n_shards=8, offload=True)
    assert check_budget(est8["need_per_device"], "70B offload") is not None


def test_device_memory_env_override(monkeypatch):
    monkeypatch.setenv("DLLAMA_HBM_BYTES", "123456")
    assert device_memory_bytes() == 123456


def test_engine_records_estimate(tmp_path):
    import numpy as np
    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime.engine import InferenceEngine
    from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

    mpath, tpath = tmp_path / "m.m", tmp_path / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=48),
                     np.random.default_rng(1))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    e = InferenceEngine(str(mpath), str(tpath))
    assert e.hbm_estimate["need_per_device"] > 0


# -- HBM admission guard (ISSUE 4) --------------------------------------------


def test_fit_batch_slots_degrades_in_dp_steps(monkeypatch):
    from dllama_tpu.runtime.hbm import fit_batch_slots

    c = _cfg(dim=512, hidden_dim=1024, n_layers=4, vocab_size=2048,
             n_heads=8, n_kv_heads=4, head_dim=64, seq_len=512)
    # dp=2: n slots -> batch n/2+1, so 8->b5, 6->b4, 4->b3. A limit
    # between need(b3) and need(b4) fits only the 4-slot pool.
    mid = (estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=4,
                                 batch=3)["need_per_device"]
           + estimate_device_bytes(c, weight_repr="q40", kv_dtype_bytes=4,
                                   batch=4)["need_per_device"]) // 2
    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(mid))
    n, est = fit_batch_slots(c, 8, weight_repr="q40", kv_dtype_bytes=4,
                             dp=2)
    assert n == 4 and n % 2 == 0
    assert est["need_per_device"] <= mid
    # nothing fits -> 0 (caller refuses)
    monkeypatch.setenv("DLLAMA_HBM_BYTES", "1000")
    n, _ = fit_batch_slots(c, 8, weight_repr="q40", kv_dtype_bytes=4, dp=2)
    assert n == 0
    # unknown limit / explicit skip -> untouched
    monkeypatch.delenv("DLLAMA_HBM_BYTES")
    n, _ = fit_batch_slots(c, 8, weight_repr="q40", kv_dtype_bytes=4, dp=2)
    assert n == 8
    monkeypatch.setenv("DLLAMA_HBM_BYTES", "1000")
    monkeypatch.setenv("DLLAMA_SKIP_HBM_CHECK", "1")
    n, _ = fit_batch_slots(c, 8, weight_repr="q40", kv_dtype_bytes=4, dp=2)
    assert n == 8


def test_admission_check_uses_measured_bytes_and_uncompiled_extra(monkeypatch):
    from dllama_tpu.runtime.hbm import admission_check

    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(1_000_000))
    ok, _ = admission_check(need_bytes=400_000, measured_bytes={},
                            extra_bytes=0, what="x")
    assert ok
    # measured evidence RAISES the estimate past the limit
    ok, reason = admission_check(need_bytes=400_000,
                                 measured_bytes={"forward": 1_200_000},
                                 extra_bytes=0, what="x")
    assert not ok and "measured" in reason
    # uncompiled-program workspace pushes a borderline admission over
    ok, reason = admission_check(need_bytes=900_000, measured_bytes={},
                                 extra_bytes=200_000, what="x")
    assert not ok and "uncompiled" in reason
    # the guard stands down when the limit is unknown
    monkeypatch.delenv("DLLAMA_HBM_BYTES")
    ok, _ = admission_check(need_bytes=10**15, measured_bytes={},
                            extra_bytes=0, what="x")
    assert ok


def test_estimate_prefill_temp_bytes_scales_with_tokens():
    from dllama_tpu.runtime.hbm import estimate_prefill_temp_bytes

    c = _cfg()
    small = estimate_prefill_temp_bytes(c, 32)
    big = estimate_prefill_temp_bytes(c, 256)
    assert big == small * 8 and small > 0


# -- the admission columns (PR 60) -------------------------------------------------


def _mellum_cfg(rows=1280, seq_len=11776):
    """Mellum2-12B-A2.5B's sixteen held layers as the engine reads them."""
    return ModelConfig(
        arch=mfile.ArchType.MELLUM, dim=2304, hidden_dim=896, n_layers=16, n_heads=32, n_kv_heads=4, head_dim=128,
        vocab_size=98304, seq_len=seq_len, norm_epsilon=1e-6, rope_theta=500000.0, rope_type=mfile.RopeType.YARN,
        n_experts=64, n_active_experts=8, moe_router_width=64, layer_period=4, full_layer_at=3, sliding_window=1024,
        n_heads_sliding=32, rope_theta_sliding=500000.0, rope_dim=128, compute_dtype="bfloat16",
        window_column_rows=rows)


def test_an_admission_column_is_priced_at_the_familys_shape():
    """The full layers dense at the slot's length and the sliding layers' buffer
    of the window and the widest chunk: 127 MB where a column dense over all
    sixteen layers would be 386 MB; a dense decoder's column is its view."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.hbm import admission_column_bytes

    row = 2 * 4 * 128 * 2                                 # K and V of 4 heads of 128 in bfloat16
    stats = admission_column_bytes(_mellum_cfg(), jnp.bfloat16) - row * (4 * 11776 + 12 * 1280)
    assert 0 < stats < 1024                               # the routing counters and the buffer's position
    assert admission_column_bytes(_mellum_cfg(rows=0), jnp.bfloat16) - stats == row * 16 * 11776
    dense = _cfg(seq_len=1024)
    assert admission_column_bytes(dense, jnp.bfloat16) == 32 * 2 * 8 * 128 * 1024 * 2


@pytest.mark.parametrize("limit_gib, fits", [(15.75, "whole"), (14.5, "degraded"), (12.5, "refused")])
def test_columns_that_do_not_fit_degrade_or_refuse_the_pool_at_construction(monkeypatch, limit_gib, fits):
    """``slots`` columns are charged whole beside the blocks: the cell's
    sixteen (2.0 GB) fit a 16 GB chip beside 9 GB of weights and both pools;
    on a smaller budget the pool shrinks by what they take, and where even one
    sequence's blocks do not fit beside them the construction is refused."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.hbm import admission_column_bytes, fit_block_pool

    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(int(limit_gib * 2 ** 30)))
    cfg, slots = _mellum_cfg(), 16
    want = slots * 736 + 1
    window_pool = 2 * 12 * (2 * slots * 66 + 1) * 4 * 128 * 16 * 2
    kw = dict(block_size=16, min_blocks=737, weight_repr="q40", kv_dtype_bytes=2, state_bytes=window_pool)
    columns = slots * admission_column_bytes(cfg, jnp.bfloat16)
    bare, _ = fit_block_pool(cfg, want, **kw)
    n, est = fit_block_pool(cfg, want, column_bytes=columns, **kw)
    assert est["admission_columns_bytes"] == columns and 2.0e9 < columns < 2.1e9
    if fits == "whole":
        assert n == bare == want
    elif fits == "degraded":
        assert 737 <= n < bare <= want
    else:
        assert n == 0 and bare > 0

"""Quantized-wire collectives (parallel/qcollectives.py) — the reference's
Q80 sync pipes (llm.cpp:167: each node ships its quantized partial,
OP_MERGE_ADD after dequant; report fig. 6 wire volume) realized as XLA
collectives.

All manual-SPMD entry goes through the version-compat shim
(``parallel.api.shard_map``) — raw ``jax.shard_map`` does not exist on
0.4.x jax and ``jax.experimental.shard_map`` is gone on ≥0.5, so a direct
call can never trace on one of the two; dlint rule shard-map-shim
keeps this closed-world."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dllama_tpu.ops.linear import fake_quant_q80
from dllama_tpu.parallel.api import shard_map
from dllama_tpu.parallel.qcollectives import psum_q80_wire, wire_psum


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


@pytest.mark.parametrize("n", [2, 4, 8])
def test_psum_q80_wire_equals_sum_of_fake_quant_partials(n):
    """The wire collective's numerics ARE the reference's merge: bit-equal
    to summing fake_quant_q80'd partials (quantize-each-partial-then-add,
    llm.cpp OP_MERGE_ADD semantics) — NOT quantize-after-sum."""
    rng = np.random.default_rng(5)
    parts = rng.standard_normal((n, 3, 64)).astype(np.float32)
    want = np.sum(np.asarray(jax.vmap(fake_quant_q80)(jnp.asarray(parts))),
                  axis=0)

    fn = jax.jit(shard_map(
        lambda x: psum_q80_wire(x[0], "tp"), mesh=_mesh(n),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    np.testing.assert_array_equal(got, want)


def test_psum_q80_wire_close_to_f32_psum():
    rng = np.random.default_rng(6)
    parts = rng.standard_normal((4, 2, 128)).astype(np.float32)
    exact = parts.sum(axis=0)
    fn = jax.jit(shard_map(
        lambda x: psum_q80_wire(x[0], "tp"), mesh=_mesh(4),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    # per-partial q80 rounding: ~|x|max/127 per term
    assert np.abs(got - exact).max() < 4 * np.abs(parts).max() / 127 + 1e-6


def test_wire_psum_dispatch(monkeypatch):
    """wire_psum routes by env knob and block divisibility."""
    rng = np.random.default_rng(7)
    parts = rng.standard_normal((2, 1, 64)).astype(np.float32)

    def run():
        fn = jax.jit(shard_map(
            lambda x: wire_psum(x[0], "tp"), mesh=_mesh(2),
            in_specs=P("tp"), out_specs=P(), check_vma=False))
        return np.asarray(fn(jnp.asarray(parts)))

    monkeypatch.delenv("DLLAMA_TPU_WIRE", raising=False)
    f32 = run()
    np.testing.assert_allclose(f32, parts.sum(axis=0), rtol=1e-6)
    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    q80 = run()
    assert not np.array_equal(q80, f32)  # quantization engaged
    np.testing.assert_allclose(q80, f32, atol=4 * np.abs(parts).max() / 127)
    # non-divisible trailing axis falls back to full precision
    odd = rng.standard_normal((2, 1, 48)).astype(np.float32)
    fn = jax.jit(shard_map(
        lambda x: wire_psum(x[0], "tp"), mesh=_mesh(2),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(jnp.asarray(odd))),
                               odd.sum(axis=0), rtol=1e-6)


def test_q80_wire_forward_drift_bounded(monkeypatch):
    """End-to-end: a tp=2 forward with --wire q80 on the Pallas col-split
    path stays close to the f32-wire logits (the wo/w2 partial merges are
    the only thing that changed)."""
    from dllama_tpu.formats import mfile
    from dllama_tpu.models import forward, init_random_params
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.parallel.api import make_tp_mesh, use_plan
    from dllama_tpu.parallel.sharding import kv_cache_sharding, shard_params
    from dllama_tpu.runtime import KVCache

    cfg = ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=8, vocab_size=128, seq_len=32,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA)
    params = init_random_params(cfg, seed=41, quantized=True)
    tokens = jnp.asarray([[3, 1, 4, 1, 5]], dtype=jnp.int32)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")

    plan = make_tp_mesh(2)
    sharded = shard_params(plan, params)

    def run():
        kv0 = KVCache.create(cfg)
        kv = jax.device_put(kv0, kv_cache_sharding(plan, kv0))
        with use_plan(plan):
            # fresh lambda per run: jit wrappers around the SAME function
            # object share the global pjit executable cache, which would
            # silently reuse the first run's program and hide the env knob
            logits, _ = jax.jit(
                lambda p, c, t, s, k: forward(p, c, t, s, k),
                static_argnums=1)(sharded, cfg, tokens, jnp.int32(0), kv)
        return np.asarray(logits, np.float32)

    monkeypatch.delenv("DLLAMA_TPU_WIRE", raising=False)
    base = run()
    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    wired = run()
    assert not np.array_equal(wired, base)  # the wire really quantized
    rms = float(np.sqrt(np.mean(base ** 2)))
    assert float(np.abs(wired - base).max()) / rms < 5e-2


def test_q80_wire_shrinks_collective_traffic(monkeypatch):
    """The point of the feature, measured by the compiled HLO: the q80-wire
    program's collective bytes are a fraction of the f32-wire program's
    (int8 codes + f16 scales vs f32 values)."""
    from dllama_tpu.runtime.profiling import collective_traffic

    def compiled_kb(env):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        fn = jax.jit(shard_map(
            lambda x: wire_psum(x, "tp"), mesh=_mesh(4),
            in_specs=P(None, "tp"), out_specs=P(), check_vma=False))
        x = jnp.ones((8, 4 * 512), jnp.float32)
        txt = fn.lower(x).compile().as_text()
        return collective_traffic(txt, 4).sent_kb

    monkeypatch.delenv("DLLAMA_TPU_WIRE", raising=False)
    f32_kb = compiled_kb({})
    q80_kb = compiled_kb({"DLLAMA_TPU_WIRE": "q80"})
    assert f32_kb > 0 and q80_kb > 0
    # vs XLA's ring all-reduce (2(n-1)/n · 4B) the quantized all-gather
    # ((n-1)/n · n · 1.0625B) wins 8/(1.0625n)x — ~1.9x at n=4 (the full
    # ~3.8x of report fig. 6 is vs the reference's own all-gather+merge
    # formulation; see the qcollectives docstring for the crossover)
    assert q80_kb < f32_kb * 0.6, (q80_kb, f32_kb)


def test_wire_psum_crossover_guard(monkeypatch):
    """Past the all-gather crossover (n_parts > 7) the quantized wire would
    MOVE MORE bytes than the f32 ring all-reduce — wire_psum must fall back
    to full precision there."""
    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    rng = np.random.default_rng(8)
    parts = rng.standard_normal((8, 1, 64)).astype(np.float32)
    fn = jax.jit(shard_map(
        lambda x: wire_psum(x[0], "tp", n_parts=8), mesh=_mesh(8),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    # exact f32 sum — no quantization happened
    np.testing.assert_allclose(got, parts.sum(axis=0), rtol=1e-6)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_psum_q80_ring_close_to_f32(n):
    """The past-crossover ring variant: per-hop requantization error grows
    ~linearly in n but stays rounding-scale; result within n quantization
    steps of the exact sum, and every device sees the full vector."""
    rng = np.random.default_rng(13)
    parts = rng.standard_normal((n, 2, n * 64)).astype(np.float32)
    exact = parts.sum(axis=0)
    from dllama_tpu.parallel.qcollectives import psum_q80_ring

    fn = jax.jit(shard_map(
        lambda x: psum_q80_ring(x[0], "tp", n)[None], mesh=_mesh(n),
        in_specs=P("tp"), out_specs=P("tp", None, None), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))  # [n, ...]: per-device results
    for dev in range(n):
        assert np.abs(got[dev] - exact).max() < \
            (2 * n) * np.abs(parts).max() / 127 + 1e-6, dev
    # all devices agree exactly (the all-gather hops are deterministic)
    for dev in range(1, n):
        np.testing.assert_array_equal(got[dev], got[0])


def test_wire_psum_routes_ring_past_crossover(monkeypatch):
    """n_parts > crossover with a ring-splittable axis routes to the ring
    (quantized — differs from exact), not the f32 fallback."""
    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    rng = np.random.default_rng(14)
    parts = rng.standard_normal((8, 1, 8 * 32)).astype(np.float32)
    fn = jax.jit(shard_map(
        lambda x: wire_psum(x[0], "tp", n_parts=8), mesh=_mesh(8),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    exact = parts.sum(axis=0)
    assert not np.array_equal(got, exact)  # quantized path taken
    assert np.abs(got - exact).max() < 16 * np.abs(parts).max() / 127 + 1e-6


def test_wire_psum_unwraps_single_axis_tuple(monkeypatch):
    """The MoE caller passes red_axes as a 1-tuple — past the crossover it
    must still reach the quantized ring, not silently fall back to f32."""
    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    rng = np.random.default_rng(15)
    parts = rng.standard_normal((8, 1, 8 * 32)).astype(np.float32)
    fn = jax.jit(shard_map(
        lambda x: wire_psum(x[0], ("tp",), n_parts=8), mesh=_mesh(8),
        in_specs=P("tp"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    assert not np.array_equal(got, parts.sum(axis=0))  # quantized ring ran


def test_wire_psum_multi_axis_past_crossover_decomposes(monkeypatch):
    """A 2-axis reduction whose PRODUCT exceeds the crossover (4x2=8) must
    decompose into sequential per-axis quantized reductions, not silently
    pay f32 wire (the large-mesh MoE ep x hidden regime)."""
    from jax.sharding import Mesh as _Mesh

    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    mesh = _Mesh(np.array(jax.devices()).reshape(4, 2), ("a", "b"))
    rng = np.random.default_rng(16)
    parts = rng.standard_normal((4, 2, 1, 64)).astype(np.float32)

    fn = jax.jit(shard_map(
        lambda x: wire_psum(x[0, 0], ("a", "b"), (4, 2)), mesh=mesh,
        in_specs=P("a", "b"), out_specs=P(), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts)))
    exact = parts.sum(axis=(0, 1))
    assert not np.array_equal(got, exact)  # quantized stages ran
    # two-stage quantization error: bounded by a few rounding steps of the
    # partial magnitudes
    assert np.abs(got - exact).max() < 12 * np.abs(parts).max() / 127 + 1e-6


# -- overlapped (TokenWeave-shaped) ring reductions (ISSUE 8) ----------------


def _ring(fn_body, n, parts, out_specs=None):
    """Run ``fn_body(local_parts)`` under an n-way tp shard_map."""
    fn = jax.jit(shard_map(
        fn_body, mesh=_mesh(n), in_specs=P("tp"),
        out_specs=P() if out_specs is None else out_specs, check_vma=False))
    return np.asarray(fn(jnp.asarray(parts)))


@pytest.mark.parametrize("n_chunks", [2, 4])
def test_overlapped_f32_bitwise_equals_unchunked(n_chunks):
    """Chunking the trailing axis is elementwise-invariant: the overlapped
    merge must be BIT-identical to the single ring (n_chunks=1) — the
    invariant that makes --comm-overlap promotable without new goldens."""
    from dllama_tpu.parallel.qcollectives import (overlapped_wire_psum,
                                                  ring_wire_psum)

    rng = np.random.default_rng(21)
    parts = rng.standard_normal((4, 2, 256)).astype(np.float32)
    whole = _ring(lambda x: ring_wire_psum(x[0], "tp", 4), 4, parts)
    chunked = _ring(
        lambda x: overlapped_wire_psum(x[0], "tp", 4, n_chunks), 4, parts)
    np.testing.assert_array_equal(chunked, whole)
    # and the ring itself is an all-reduce: allclose to the exact f32 sum
    # (rank-order summation may differ from XLA's psum in the last ulp)
    np.testing.assert_allclose(whole, parts.sum(axis=0), rtol=1e-5,
                               atol=1e-5)


def test_ring_q80_bitwise_equals_reference_merge():
    """The quantized ring ships each partial's Q80 planes unchanged, so its
    result is BIT-identical to the reference's all-gather merge
    (psum_q80_wire == sum of fake_quant_q80 partials in rank order) —
    goldens and error bounds transfer to the overlapped path."""
    from dllama_tpu.parallel.qcollectives import _ring_rank_order_sum

    rng = np.random.default_rng(22)
    parts = rng.standard_normal((4, 2, 128)).astype(np.float32)
    got = _ring(
        lambda x: _ring_rank_order_sum(x[0], "tp", 4, quantized=True),
        4, parts)
    want = np.sum(np.asarray(jax.vmap(fake_quant_q80)(jnp.asarray(parts))),
                  axis=0)
    np.testing.assert_array_equal(got, want)
    ref = _ring(lambda x: psum_q80_wire(x[0], "tp"), 4, parts)
    np.testing.assert_array_equal(got, ref)


def test_ring_replicas_bit_identical_per_device():
    """Every device must compute the identical rank-order sum (fp addition
    is non-associative; replica drift would desync downstream SPMD
    decisions). Asserted for both wire formats."""
    from dllama_tpu.parallel.qcollectives import _ring_rank_order_sum

    rng = np.random.default_rng(23)
    parts = rng.standard_normal((8, 1, 64)).astype(np.float32)
    for quant in (False, True):
        per_dev = _ring(
            lambda x: _ring_rank_order_sum(x[0], "tp", 8,
                                           quantized=quant)[None],
            8, parts, out_specs=P("tp", None, None))
        for d in range(1, 8):
            np.testing.assert_array_equal(per_dev[d], per_dev[0])


def test_overlapped_q80_error_bounded_by_per_partial_roundtrip():
    """q80-wire error of the overlapped merge is the SUM of each partial's
    one quantization roundtrip — bounded by n x the per-partial Q80 step
    (absmax/127 per 32-block), the same bound the reference merge holds."""
    from dllama_tpu.parallel.qcollectives import overlapped_wire_psum

    rng = np.random.default_rng(24)
    parts = rng.standard_normal((4, 2, 256)).astype(np.float32)
    import os

    os.environ["DLLAMA_TPU_WIRE"] = "q80"
    try:
        got = _ring(
            lambda x: overlapped_wire_psum(x[0], "tp", 4, 4), 4, parts)
    finally:
        os.environ.pop("DLLAMA_TPU_WIRE", None)
    exact = parts.sum(axis=0)
    bound = 4 * (np.abs(parts).max() / 127.0) * 0.5 + 1e-6  # round-to-even
    assert np.abs(got - exact).max() <= 4 * bound
    # and it is exactly the fake-quant merge, not merely close
    want = np.sum(np.asarray(jax.vmap(fake_quant_q80)(jnp.asarray(parts))),
                  axis=0)
    np.testing.assert_array_equal(got, want)


def test_ring_wire_psum_routes_requantizing_ring_past_crossover(monkeypatch):
    """Past the all-gather crossover with a ring-splittable chunk the
    overlapped path delegates to psum_q80_ring (constant wire win) — the
    result then differs from the one-quantization-per-partial merge."""
    from dllama_tpu.parallel.qcollectives import ring_wire_psum

    monkeypatch.setenv("DLLAMA_TPU_WIRE", "q80")
    rng = np.random.default_rng(25)
    parts = rng.standard_normal((8, 1, 8 * 32)).astype(np.float32)
    got = _ring(lambda x: ring_wire_psum(x[0], "tp", 8), 8, parts)
    want_ref = np.sum(np.asarray(
        jax.vmap(fake_quant_q80)(jnp.asarray(parts))), axis=0)
    assert not np.array_equal(got, want_ref)  # requantizing ring ran
    np.testing.assert_allclose(got, parts.sum(axis=0),
                               atol=10 * np.abs(parts).max() / 127)


# -- overlap_chunks resolution (the --comm-overlap grammar) ------------------


def test_overlap_chunks_resolution_properties():
    from dllama_tpu.parallel.qcollectives import overlap_chunks

    # off spellings
    for off in (0, "0", "off", None, ""):
        assert overlap_chunks(off, 4096) == 0
    # auto: largest candidate <= 4 whose chunks stay Q80-block-divisible
    assert overlap_chunks("auto", 4096) == 4
    assert overlap_chunks("auto", 256) == 4      # 64-wide chunks, 32 | 64
    assert overlap_chunks("auto", 64) == 2       # 4 -> 16-wide (not 32|) -> 2
    assert overlap_chunks("auto", 33) == 0       # nothing fits: degrade
    # explicit N must divide; < 2 and non-dividing refuse loudly
    assert overlap_chunks(8, 4096) == 8
    assert overlap_chunks("8", 4096) == 8
    with pytest.raises(ValueError):
        overlap_chunks(3, 4096)
    with pytest.raises(ValueError):
        overlap_chunks(1, 4096)


def test_wire_traffic_model_prices_every_path():
    from dllama_tpu.parallel.qcollectives import wire_traffic_model

    dim, n = 4096, 4
    assert wire_traffic_model(dim, 1, 0, False) == []  # no wire, no bytes
    [(op, wire, b)] = wire_traffic_model(dim, n, 0, False)
    assert (op, wire) == ("all_reduce", "f32")
    assert b == pytest.approx(2 * (n - 1) / n * 4.0 * dim)
    [(op, wire, b)] = wire_traffic_model(dim, n, 4, False)
    assert (op, wire) == ("ppermute", "f32")
    assert b == pytest.approx((n - 1) * 4.0 * dim)
    [(op, wire, bq)] = wire_traffic_model(dim, n, 4, True)
    assert (op, wire) == ("ppermute", "q80")
    assert bq == pytest.approx((n - 1) * (1 + 2 / 32) * dim)
    assert b / bq == pytest.approx(4 / (1 + 2 / 32))  # the ~3.76x shrink
    # past the crossover with ring-splittable chunks: reduce-scatter halves
    [(op, wire, br)] = wire_traffic_model(8 * 32 * 8, 8, 1, True)
    assert (op, wire) == ("ppermute", "q80")
    assert br == pytest.approx(2 * 7 / 8 * (1 + 2 / 32) * 8 * 32 * 8)


# -- the `wire` failpoint's in-graph injection site --------------------------


def test_wire_poison_scope_poisons_row0_of_shipped_partial():
    """Inside a poison scope with code >= 3 the ring merge's row 0 goes
    non-finite on every device while other rows stay exact; codes < 3
    (the `logits` site's range) pass through clean. Outside any scope the
    injection code is never traced at all."""
    from dllama_tpu.parallel.qcollectives import (_maybe_poison_partial,
                                                  ring_wire_psum,
                                                  wire_poison_scope)

    rng = np.random.default_rng(26)
    parts = rng.standard_normal((2, 3, 2, 64)).astype(np.float32)

    def run(code):
        def body(x, p):
            with wire_poison_scope(p[0]):
                return ring_wire_psum(x[0], "tp", 2)
        fn = jax.jit(shard_map(
            body, mesh=_mesh(2), in_specs=(P("tp"), P()),
            out_specs=P(), check_vma=False))
        return np.asarray(fn(jnp.asarray(parts),
                             jnp.asarray([code], jnp.float32)))

    clean = run(0.0)
    np.testing.assert_allclose(clean, parts.sum(axis=0), rtol=1e-5,
                               atol=1e-5)
    for code in (1.0, 2.0):  # logits-site codes: wire stays clean
        np.testing.assert_array_equal(run(code), clean)
    nan_hit = run(3.0)
    assert np.all(np.isnan(nan_hit[0]))        # row 0 poisoned
    np.testing.assert_array_equal(nan_hit[1:], clean[1:])  # bystanders exact
    inf_hit = run(4.0)
    assert np.all(np.isinf(inf_hit[0]))
    np.testing.assert_array_equal(inf_hit[1:], clean[1:])
    # outside any scope: passthrough, no selector in the graph
    x = jnp.asarray(parts[0])
    assert _maybe_poison_partial(x) is x


def test_wire_traffic_model_q80_explicit_colsplit_pricing():
    """Overlap-off pricing must mirror what actually merges: the GSPMD
    psum is f32, but the EXPLICIT col-split (sharded Pallas kernel →
    wire_psum) ships q80 — all-gather below the crossover, the
    requantizing ring past it."""
    from dllama_tpu.parallel.qcollectives import wire_traffic_model

    dim = 4096
    [(op, wire, b)] = wire_traffic_model(dim, 4, 0, True, q80_explicit=True)
    assert (op, wire) == ("all_gather", "q80")
    assert b == pytest.approx(3 * (1 + 2 / 32) * dim)
    [(op, wire, b)] = wire_traffic_model(8 * 32 * 8, 8, 0, True,
                                         q80_explicit=True)
    assert (op, wire) == ("ppermute", "q80")  # past crossover: ring
    # q80 off, or a GSPMD merge, keeps the f32 all-reduce pricing
    [(op, wire, _)] = wire_traffic_model(dim, 4, 0, False, q80_explicit=True)
    assert (op, wire) == ("all_reduce", "f32")
    [(op, wire, _)] = wire_traffic_model(dim, 4, 0, True, q80_explicit=False)
    assert (op, wire) == ("all_reduce", "f32")


def test_overlap_chunks_rejects_garbage_with_grammar():
    from dllama_tpu.parallel.qcollectives import overlap_chunks

    with pytest.raises(ValueError, match="off.*auto.*integer"):
        overlap_chunks("bananas", 4096)


def test_wire_poison_dp_scope_pins_global_row0():
    """Under dp the shard-local row 0 exists once per dp group: with the
    dp axis named, only dp group 0's row 0 is poisoned — the global blast
    radius stays ONE request."""
    from jax.sharding import Mesh as _Mesh

    from dllama_tpu.parallel.qcollectives import (ring_wire_psum,
                                                  wire_poison_dp_scope,
                                                  wire_poison_scope)

    mesh = _Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    rng = np.random.default_rng(27)
    # global batch 4 over dp=2 (2 rows per shard), tp partials on axis 0
    parts = rng.standard_normal((2, 4, 1, 64)).astype(np.float32)

    def body(x, p):
        with wire_poison_scope(p[0]), wire_poison_dp_scope("dp"):
            return ring_wire_psum(x[0], "tp", 2)

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("tp", "dp"), P()),
        out_specs=P("dp"), check_vma=False))
    got = np.asarray(fn(jnp.asarray(parts),
                        jnp.asarray([3.0], jnp.float32)))
    assert np.all(np.isnan(got[0]))              # global row 0: poisoned
    assert np.all(np.isfinite(got[1:]))          # rows 1-3 (incl. dp
    # group 1's local row 0, global row 2) untouched
    np.testing.assert_allclose(got[1:], parts.sum(axis=0)[1:], rtol=1e-5,
                               atol=1e-5)


def test_wire_poison_covers_requantizing_ring_past_crossover(monkeypatch):
    """The `wire` failpoint must also bite on the past-crossover route
    (psum_q80_ring): a fired fault that injects nothing would let chaos
    report coverage the large-mesh configs don't have."""
    from dllama_tpu.parallel.qcollectives import (psum_q80_ring,
                                                  wire_poison_scope)

    rng = np.random.default_rng(28)
    parts = rng.standard_normal((8, 2, 1, 8 * 32)).astype(np.float32)

    def body(x, p):
        with wire_poison_scope(p[0]):
            return psum_q80_ring(x[0], "tp", 8)

    fn = jax.jit(shard_map(
        body, mesh=_mesh(8), in_specs=(P("tp"), P()),
        out_specs=P(), check_vma=False))
    hit = np.asarray(fn(jnp.asarray(parts), jnp.asarray([3.0], jnp.float32)))
    assert not np.all(np.isfinite(hit[0]))       # row 0 poisoned
    assert np.all(np.isfinite(hit[1:]))          # bystander rows intact

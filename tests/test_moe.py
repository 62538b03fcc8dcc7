"""Mixture-of-experts: format walk, router math, forward vs numpy golden,
expert parallelism, converter plan.

All of this is NEW capability: the reference parses N_EXPERTS and its
converter can emit expert weights, but its graph builder never reads
nExperts — an MoE model cannot run there at all (SURVEY.md §2.2). The .m MoE
layout here matches the reference converter's expert order (w3/w1/w2 per
expert) and adds the missing router tensor (block_moe_gate).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.formats import mfile, quants
from dllama_tpu.models import ModelConfig, forward, init_random_params, load_params_from_mfile
from dllama_tpu.parallel import use_plan
from dllama_tpu.parallel.api import make_mesh
from dllama_tpu.parallel.sharding import kv_cache_sharding, shard_params, validate_ep
from dllama_tpu.runtime import KVCache

from helpers import tiny_header_params, write_tiny_model

E, K = 4, 2  # experts / active experts for the tiny configs


def _moe_params(arch=mfile.ArchType.LLAMA, **kw):
    return tiny_header_params(arch=arch, n_experts=E, n_active_experts=K,
                              weight_type=quants.F32, **kw)


def _golden_moe_ffn(cfg: ModelConfig, h: np.ndarray, gate_w: np.ndarray,
                    we1, we2, we3) -> np.ndarray:
    """Per-token loop reimplementation of the MoE FFN (no shared code)."""
    B, T, _ = h.shape
    y = np.zeros_like(h)
    logits = h @ gate_w.T  # [B,T,E]
    for b in range(B):
        for t in range(T):
            lg = logits[b, t]
            p = np.exp(lg - lg.max())
            p /= p.sum()
            idx = np.argsort(-p)[: cfg.n_active_experts]
            w = p[idx] / p[idx].sum() if cfg.moe_norm_topk else p[idx]
            acc = np.zeros(cfg.dim, np.float32)
            for wi, ei in zip(w, idx):
                g = h[b, t] @ we1[ei].T
                g = g / (1.0 + np.exp(-g))  # silu
                u = h[b, t] @ we3[ei].T
                acc += wi * ((g * u) @ we2[ei].T)
            y[b, t] = acc
    return y


def _golden_moe_forward(dense, cfg: ModelConfig, tokens: np.ndarray):
    """Full-model golden with the MoE FFN; attention mirrors
    test_model.golden_forward's math."""
    from test_model import golden_forward

    # run the dense golden with zeroed FFN contribution by giving it zero
    # w1/w3 (silu(0)*u = 0), then add MoE contributions layer by layer — not
    # possible layerwise from outside, so instead: reimplement inline.
    B, T = tokens.shape
    hd = cfg.head_dim
    x = dense["embedding"][tokens].astype(np.float32)

    def rms(v, w):
        inv = 1.0 / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + cfg.norm_epsilon)
        return v * inv * w

    def rope(v, positions):
        half = hd // 2
        freqs = 1.0 / cfg.rope_theta ** (2.0 * np.arange(half, dtype=np.float32) / hd)
        ang = positions[..., None] * freqs
        c, s = np.cos(ang)[:, :, None, :], np.sin(ang)[:, :, None, :]
        out = v.copy()
        a, b = v[..., 0::2], v[..., 1::2]
        out[..., 0::2] = a * c - b * s
        out[..., 1::2] = a * s + b * c
        return out

    positions = np.arange(T)[None, :] + np.zeros((B, 1), np.int32)
    for l in range(cfg.n_layers):
        h = rms(x, dense[f"block_norm_0.{l}"])
        q = (h @ dense[f"block_matmul_q.{l}"].T).reshape(B, T, cfg.n_heads, hd)
        k = (h @ dense[f"block_matmul_k.{l}"].T).reshape(B, T, cfg.n_kv_heads, hd)
        v = (h @ dense[f"block_matmul_v.{l}"].T).reshape(B, T, cfg.n_kv_heads, hd)
        q, k = rope(q, positions), rope(k, positions)
        att = np.zeros((B, T, cfg.n_heads, hd), np.float32)
        for hh in range(cfg.n_heads):
            kv_h = hh // (cfg.n_heads // cfg.n_kv_heads)
            for b in range(B):
                for t in range(T):
                    scores = np.einsum("sh,h->s", k[b, : t + 1, kv_h], q[b, t, hh]) / np.sqrt(hd)
                    e = np.exp(scores - scores.max())
                    p = e / e.sum()
                    att[b, t, hh] = p @ v[b, : t + 1, kv_h]
        x = x + att.reshape(B, T, -1) @ dense[f"block_matmul_wo.{l}"].T
        h = rms(x, dense[f"block_norm_1.{l}"])
        we1 = np.stack([dense[f"block_expert_w1.{l}.{e}"] for e in range(E)])
        we2 = np.stack([dense[f"block_expert_w2.{l}.{e}"] for e in range(E)])
        we3 = np.stack([dense[f"block_expert_w3.{l}.{e}"] for e in range(E)])
        x = x + _golden_moe_ffn(cfg, h, dense[f"block_moe_gate.{l}"], we1, we2, we3)
    x = rms(x, dense["final_norm"])
    return x @ dense["final_matmul_logits"].T


def test_mfile_walk_moe(tmp_path):
    p = _moe_params()
    write_tiny_model(tmp_path / "moe.m", p, np.random.default_rng(0))
    with mfile.ModelFile.open(tmp_path / "moe.m") as mf:
        assert mf.header.n_experts == E and mf.has_moe_router
        assert "block_moe_gate.0" in mf.tensors
        assert f"block_expert_w2.1.{E-1}" in mf.tensors
        assert "block_matmul_w1.0" not in mf.tensors
        # disk order within a layer: gate then w3/w1/w2 per expert
        o = mf.tensors
        assert (o["block_moe_gate.0"].offset < o["block_expert_w3.0.0"].offset
                < o["block_expert_w1.0.0"].offset < o["block_expert_w2.0.0"].offset
                < o["block_expert_w3.0.1"].offset)


def test_mfile_routerless_moe_file_detected(tmp_path):
    """A reference-converter-style MoE file (no router) parses with
    has_moe_router=False and refuses to load params."""
    p = _moe_params()
    # write with router, then excise the router bytes to fake the reference layout
    write_tiny_model(tmp_path / "a.m", p, np.random.default_rng(0))
    with mfile.ModelFile.open(tmp_path / "a.m") as mf:
        spans = sorted(
            (r.offset, r.n_bytes) for k, r in mf.tensors.items()
            if r.name == "block_moe_gate")
        raw = open(tmp_path / "a.m", "rb").read()
    out = bytearray()
    prev = 0
    for off, nb in spans:
        out += raw[prev:off]
        prev = off + nb
    out += raw[prev:]
    (tmp_path / "b.m").write_bytes(out)

    with mfile.ModelFile.open(tmp_path / "b.m") as mf:
        assert not mf.has_moe_router
        cfg = ModelConfig.from_header(mf.header)
        with pytest.raises(ValueError, match="router"):
            load_params_from_mfile(mf, cfg)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_forward_matches_golden(tmp_path, norm_topk):
    p = _moe_params()
    dense = write_tiny_model(tmp_path / "moe.m", p, np.random.default_rng(7))
    tokens = np.asarray([[5, 9, 2, 11, 3]], dtype=np.int32)

    from dataclasses import replace

    with mfile.ModelFile.open(tmp_path / "moe.m") as mf:
        cfg = replace(ModelConfig.from_header(mf.header), moe_norm_topk=norm_topk)
        assert cfg.is_moe
        params = load_params_from_mfile(mf, cfg)

    want = _golden_moe_forward(dense, cfg, tokens)
    logits, _ = jax.jit(forward, static_argnums=1)(
        params, cfg, jnp.asarray(tokens), jnp.int32(0), KVCache.create(cfg))
    np.testing.assert_allclose(np.asarray(logits)[0], want[0], rtol=2e-4, atol=2e-4)


def test_norm_topk_changes_outputs(tmp_path):
    """Renormalized vs raw top-k router weights genuinely differ (the only
    behavioral router knob: softmax-then-topk-renorm equals topk-then-softmax,
    so an arch-based 'flavor' would be a no-op)."""
    from dataclasses import replace

    write_tiny_model(tmp_path / "m.m", _moe_params(), np.random.default_rng(3))
    tokens = jnp.asarray([[1, 2, 3]], dtype=jnp.int32)
    with mfile.ModelFile.open(tmp_path / "m.m") as mf:
        cfg_norm = ModelConfig.from_header(mf.header)
        assert cfg_norm.moe_norm_topk  # header default
        params = load_params_from_mfile(mf, cfg_norm)
    cfg_raw = replace(cfg_norm, moe_norm_topk=False)
    a, _ = jax.jit(forward, static_argnums=1)(
        params, cfg_norm, tokens, jnp.int32(0), KVCache.create(cfg_norm))
    b, _ = jax.jit(forward, static_argnums=1)(
        params, cfg_raw, tokens, jnp.int32(0), KVCache.create(cfg_raw))
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_moe_norm_topk_header_round_trip(tmp_path):
    p = _moe_params()
    p["moe_norm_topk"] = 0
    write_tiny_model(tmp_path / "m.m", p, np.random.default_rng(1))
    with mfile.ModelFile.open(tmp_path / "m.m") as mf:
        assert mf.header.moe_norm_topk == 0
        assert not ModelConfig.from_header(mf.header).moe_norm_topk


@pytest.mark.parametrize("mesh_axes", [
    {"ep": 4},
    {"ep": 2, "tp": 2},
    {"dp": 2, "ep": 2, "tp": 2},
    {"tp": 4},  # hidden-sharded, no ep axis: sparse col-split path
])
def test_ep_sharded_forward_matches_unsharded(mesh_axes):
    cfg = ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
        n_heads=8, n_kv_heads=4, head_dim=8, vocab_size=128, seq_len=32,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA,
        n_experts=E, n_active_experts=K)
    B = 2 if "dp" in mesh_axes else 1
    params = init_random_params(cfg, seed=31)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (B, 6)), dtype=jnp.int32)

    ref, _ = jax.jit(forward, static_argnums=1)(
        params, cfg, tokens, jnp.int32(0), KVCache.create(cfg, batch_size=B))

    plan = make_mesh(mesh_axes)
    validate_ep(cfg, plan.axis_size("ep"))
    sharded = shard_params(plan, params)
    if "ep" in mesh_axes:
        assert sharded.layers.we1.sharding.spec[1] == "ep"
    kv0 = KVCache.create(cfg, batch_size=B)
    kv = jax.device_put(kv0, kv_cache_sharding(plan, kv0))
    with use_plan(plan):
        got, _ = jax.jit(forward, static_argnums=1)(
            sharded, cfg, tokens, jnp.int32(0), kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_validate_ep():
    cfg = ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=8, hidden_dim=16, n_layers=1,
        n_heads=2, n_kv_heads=2, head_dim=4, vocab_size=32, seq_len=8,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA,
        n_experts=6, n_active_experts=2)
    validate_ep(cfg, 3)
    with pytest.raises(ValueError):
        validate_ep(cfg, 4)
    from dataclasses import replace
    with pytest.raises(ValueError):
        validate_ep(replace(cfg, n_experts=0, n_active_experts=0), 2)


def test_hf_plan_includes_router_and_dual_names():
    from dllama_tpu.convert.hf import hf_tensor_plan

    p = tiny_header_params(n_experts=2, n_active_experts=1)
    p["weight_float_type"] = quants.Q40
    plan = hf_tensor_plan(p)
    keys = [it.keys for it in plan]
    assert ("model.layers.0.block_sparse_moe.gate.weight",
            "model.layers.0.mlp.gate.weight") in keys
    assert ("model.layers.0.block_sparse_moe.experts.0.w3.weight",
            "model.layers.0.mlp.experts.0.up_proj.weight") in keys
    # dense mlp keys absent for MoE
    assert not any("mlp.gate_proj" in k for ks in keys for k in ks)


def test_hf_config_qwen3_moe_mapping(tmp_path):
    import json

    from dllama_tpu.convert.hf import load_hf_config

    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "qwen3_moe", "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 48,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "max_position_embeddings": 128,
        "vocab_size": 100, "num_experts": 8, "num_experts_per_tok": 2,
        "rope_theta": 10000, "rms_norm_eps": 1e-6, "head_dim": 16,
    }))
    params = load_hf_config(tmp_path, quants.Q40)
    assert params["n_experts"] == 8 and params["n_active_experts"] == 2
    assert params["hidden_dim"] == 48  # moe_intermediate_size wins
    assert params["moe_norm_topk"] == 0  # HF Qwen3MoeConfig default: False


# ---------------------------------------------------------------------------
# sparse (ragged_dot) dispatch vs the dense all-experts oracle
# ---------------------------------------------------------------------------

from dataclasses import replace as _replace

from dllama_tpu.models.llama import _moe_ffn, init_random_params
from dllama_tpu.parallel.api import make_mesh, use_plan
from dllama_tpu.parallel.sharding import shard_params


def _sparse_dense_cfg(**kw):
    base = dict(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=1,
        n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=128, seq_len=32,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA,
        n_experts=8, n_active_experts=2)
    base.update(kw)
    return ModelConfig(**base)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_sparse_matches_dense_oracle(norm_topk):
    cfg = _sparse_dense_cfg(moe_norm_topk=norm_topk)
    params = init_random_params(cfg, seed=21)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((2, 5, cfg.dim)), jnp.float32)

    dense = _moe_ffn(_replace(cfg, moe_impl="dense"), h, lp)
    sparse = _moe_ffn(_replace(cfg, moe_impl="sparse"), h, lp)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_sparse_ep_sharded_matches_dense():
    """Sparse dispatch under an ep mesh (shard_map + psum combine)."""
    cfg = _sparse_dense_cfg()
    params = init_random_params(cfg, seed=22)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((1, 6, cfg.dim)), jnp.float32)

    dense = _moe_ffn(_replace(cfg, moe_impl="dense"), h, lp)
    plan = make_mesh({"ep": 4})
    with use_plan(plan):
        sparse = jax.jit(
            lambda hh: _moe_ffn(_replace(cfg, moe_impl="sparse"), hh, lp))(h)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_sparse_flops_scale_with_k_not_E():
    """The point of sparse dispatch: FFN cost ~ k/E of dense (VERDICT #6).
    Measured on the decode-sized gather path, which is O(k) on every backend
    (ragged_dot's CPU fallback lowering is a masked dense over all groups, so
    the prefill path's savings only materialize on TPU)."""
    cfg = _sparse_dense_cfg(dim=128, hidden_dim=256, n_experts=8,
                            n_active_experts=2)
    params = init_random_params(cfg, seed=23)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    h = jnp.ones((1, 8, cfg.dim), jnp.float32)  # N*k = 16 -> gather path

    def flops(impl):
        from dllama_tpu.runtime.introspection import cost_analysis_dict

        fn = jax.jit(lambda hh: _moe_ffn(_replace(cfg, moe_impl=impl), hh, lp))
        # cost_analysis() returns [dict] on this jax, a dict on newer —
        # the shared version-compat accessor owns that decision
        return cost_analysis_dict(fn.lower(h).compile())["flops"]

    dense, sparse = flops("dense"), flops("sparse")
    # dense FFN ~ N*E*3*D*H; sparse ~ N*k*3*D*H (+ routing/gather overhead).
    # E/k = 4 here; require at least 2x measured reduction.
    assert sparse < dense / 2, (sparse, dense)


def test_sparse_ragged_path_matches_dense():
    """Prefill-sized inputs take the sort+ragged_dot branch; same oracle."""
    cfg = _sparse_dense_cfg()
    params = init_random_params(cfg, seed=24)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.standard_normal((1, 40, cfg.dim)), jnp.float32)  # N*k=80

    dense = _moe_ffn(_replace(cfg, moe_impl="dense"), h, lp)
    sparse = _moe_ffn(_replace(cfg, moe_impl="sparse"), h, lp)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("axes", [{"tp": 4}, {"ep": 2, "tp": 2}, {"tp": 8}])
def test_sparse_hidden_sharded_matches_dense(axes, monkeypatch):
    """tp shards the expert-hidden axis: the sparse path must RUN (col-split
    H-partials psum'd, composed with ep) rather than silently paying the
    dense all-experts O(E) fallback (VERDICT r3 weak #3). The dense impl is
    poisoned to prove which path executed; hidden_dim=96 divides by 2/4/8."""
    import dllama_tpu.models.llama as M

    cfg = _sparse_dense_cfg()
    params = init_random_params(cfg, seed=31)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((1, 6, cfg.dim)), jnp.float32)

    dense = _moe_ffn(_replace(cfg, moe_impl="dense"), h, lp)

    def _poisoned(*a, **k):
        raise AssertionError("dense fallback taken under a sharded mesh")

    monkeypatch.setattr(M, "_moe_ffn_dense", _poisoned)
    plan = make_mesh(axes)
    with use_plan(plan):
        sparse = jax.jit(
            lambda hh: _moe_ffn(_replace(cfg, moe_impl="auto"), hh, lp))(h)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


def test_sparse_hidden_sharded_ragged_branch_matches_dense():
    """Same property on the prefill-sized sort+ragged_dot branch."""
    cfg = _sparse_dense_cfg()
    params = init_random_params(cfg, seed=32)
    lp = jax.tree.map(lambda a: None if a is None else a[0], params.layers,
                      is_leaf=lambda x: x is None)
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.standard_normal((1, 40, cfg.dim)), jnp.float32)

    dense = _moe_ffn(_replace(cfg, moe_impl="dense"), h, lp)
    plan = make_mesh({"ep": 2, "tp": 4})
    with use_plan(plan):
        sparse = jax.jit(
            lambda hh: _moe_ffn(_replace(cfg, moe_impl="sparse"), hh, lp))(h)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# quantized expert planes (VERDICT r4 next #5): Q40/Q80 MoE files keep their
# expert weights quantized on device — 1 B/weight resident — with the dequant
# fused into the consuming dot (gather regime) or expanded per local slice
# (ragged regime).
# ---------------------------------------------------------------------------


def _q40_moe_file(tmp_path, seed=7, **kw):
    p = tiny_header_params(n_experts=E, n_active_experts=K,
                           weight_type=quants.Q40, **kw)
    write_tiny_model(tmp_path / "moe_q40.m", p, np.random.default_rng(seed))
    return tmp_path / "moe_q40.m"


def _logits(params, cfg, tokens, plan=None):
    kv = KVCache.create(cfg, batch_size=tokens.shape[0])
    if plan is not None:
        kv = jax.device_put(kv, kv_cache_sharding(plan, kv))
    ctx = use_plan(plan) if plan is not None else None
    if ctx is not None:
        with ctx:
            out, _ = jax.jit(forward, static_argnums=1)(
                params, cfg, jnp.asarray(tokens), jnp.int32(0), kv)
    else:
        out, _ = jax.jit(forward, static_argnums=1)(
            params, cfg, jnp.asarray(tokens), jnp.int32(0), kv)
    return np.asarray(out)


@pytest.mark.parametrize("n_tokens", [5, 1])  # ragged regime / gather regime
def test_q40_experts_match_dense_load(tmp_path, n_tokens):
    """Quantized expert planes produce the same logits as dense-loading the
    SAME Q40 file (identical dequant values, different residency): both
    sparse regimes — ragged grouped matmul (prefill) and per-row gather
    (decode)."""
    from dllama_tpu.ops.linear import QuantizedWeight

    path = _q40_moe_file(tmp_path)
    tokens = np.asarray([[5, 9, 2, 11, 3][:n_tokens]], dtype=np.int32)
    with mfile.ModelFile.open(path) as mf:
        cfg = ModelConfig.from_header(mf.header)
        pq = load_params_from_mfile(mf, cfg, weight_mode="auto")
        pd = load_params_from_mfile(mf, cfg, weight_mode="f32")
    assert isinstance(pq.layers.we1, QuantizedWeight)
    assert isinstance(pq.layers.we2, QuantizedWeight)
    assert pq.layers.we1.codes.shape == (2, E, cfg.dim, cfg.hidden_dim)
    assert not isinstance(pd.layers.we1, QuantizedWeight)
    lq = _logits(pq, cfg, tokens)
    ld = _logits(pd, cfg, tokens)
    np.testing.assert_allclose(lq, ld, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mesh_axes", [
    {"ep": 4},
    {"ep": 2, "tp": 2},
    {"tp": 4},  # hidden-sharded quantized planes: scale K/32 axis splits
])
def test_q40_experts_sharded_matches_unsharded(tmp_path, mesh_axes):
    path = _q40_moe_file(tmp_path, hidden_dim=128)  # 128/32=4 scale rows
    tokens = np.asarray([[5, 9, 2, 11, 3]], dtype=np.int32)
    with mfile.ModelFile.open(path) as mf:
        cfg = ModelConfig.from_header(mf.header)
        ref_params = load_params_from_mfile(mf, cfg)
        plan = make_mesh(mesh_axes)
        validate_ep(cfg, plan.axis_size("ep"))
        sharded = load_params_from_mfile(mf, cfg, plan=plan)
    if "ep" in mesh_axes:
        assert sharded.layers.we1.codes.sharding.spec[1] == "ep"
    ref = _logits(ref_params, cfg, tokens)
    got = _logits(sharded, cfg, tokens, plan=plan)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_q40_expert_hbm_estimate_charges_quantized(tmp_path):
    """The budget estimator's q40 charge (1.125 B/weight) now matches what
    the loader actually keeps resident for expert planes."""
    from dllama_tpu.runtime.hbm import estimate_device_bytes, matmul_weight_count

    path = _q40_moe_file(tmp_path)
    with mfile.ModelFile.open(path) as mf:
        cfg = ModelConfig.from_header(mf.header)
        est_q = estimate_device_bytes(cfg, weight_repr="q40", kv_dtype_bytes=4)
        est_d = estimate_device_bytes(cfg, weight_repr="bf16", kv_dtype_bytes=4)
        params = load_params_from_mfile(mf, cfg)
    n_expert_w = 3 * cfg.n_layers * cfg.n_experts * cfg.dim * cfg.hidden_dim
    resident = (params.layers.we1.codes.nbytes + params.layers.we1.scales.nbytes
                + params.layers.we2.codes.nbytes + params.layers.we2.scales.nbytes
                + params.layers.we3.codes.nbytes + params.layers.we3.scales.nbytes)
    # loader keeps ~1.125 B/weight (codes + scales) for the expert planes
    assert resident <= n_expert_w * 1.5
    assert est_q["need_per_device"] < est_d["need_per_device"]


# -- the decode form's kernel: expert_gemv against its XLA oracle -------------


def _expert_stack(rng, L, E, K, N, scale_dtype=jnp.float32):
    from dllama_tpu.ops.linear import QuantizedWeight

    codes = rng.integers(-7, 8, size=(L, E, K, N)).astype(np.int8)
    scales = rng.uniform(0.5, 1.5, size=(L, E, K // 32, N)) / (4.2 * K ** 0.5)
    return QuantizedWeight(scales=jnp.asarray(scales, scale_dtype), codes=jnp.asarray(codes))


@pytest.mark.parametrize("n_pairs", [0, 1, 7, 12])
@pytest.mark.parametrize("K,N", [(64, 128), (128, 256)])
def test_expert_gemv_is_its_xla_oracle(n_pairs, K, N):
    """``expert_gemv`` (interpret mode off a TPU) against the gather form: each
    of the first ``n_pairs`` pairs is one row times ONE expert's planes, read
    out of layer ``layer`` of the stack where it lies; the pairs behind them
    (an absent expert's, a dead row's) are not computed and read zero, whatever
    their expert index says. float32 graphs: the same dequantized values and
    one dot over the whole contraction on both sides, so 1e-5."""
    from dllama_tpu.ops import expert_gemv as eg

    rng = np.random.default_rng(K + n_pairs)
    L, E, P = 3, 4, 12
    stack = _expert_stack(rng, L, E, K, N)
    x = jnp.asarray(rng.standard_normal((P, K)), jnp.float32)
    experts = rng.integers(0, E, size=P).astype(np.int32)
    experts[n_pairs:] = 10_000            # behind the count: never read
    for layer in (0, 2):
        got = np.asarray(eg.expert_gemv(x, stack, jnp.int32(layer), jnp.asarray(experts),
                                        jnp.int32(n_pairs), interpret=True))
        want = np.asarray(eg.expert_gemv_xla(x, stack, jnp.int32(layer), jnp.asarray(experts),
                                             jnp.int32(n_pairs)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert np.all(got[n_pairs:] == 0)
        if n_pairs:
            # against the planes themselves, so the oracle is not its own witness
            e0 = int(experts[0])
            w = np.asarray(stack.codes[layer, e0], np.float32) \
                * np.repeat(np.asarray(stack.scales[layer, e0]), 32, axis=0)
            np.testing.assert_allclose(got[0], np.asarray(x[0]) @ w, rtol=2e-4, atol=2e-4)


def test_expert_gemv_gate_routes_through_the_one_gate(monkeypatch):
    from dllama_tpu.ops import expert_gemv as eg

    stack = _expert_stack(np.random.default_rng(0), 1, 2, 64, 128)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    assert eg.kernel_choice(8, stack, False) is None
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    assert eg.kernel_choice(8, stack, False) == {"interpret": True, "fast": False}
    monkeypatch.delenv("DLLAMA_TPU_QUANT_KERNEL")
    assert eg.kernel_choice(8, stack, False) is None      # auto, off a TPU


# -- the chunk form's kernel: expert_chunk against its pairs and the every-row form --


def _plane(stack, layer, e):
    return np.asarray(stack.codes[layer, e], np.float32) * np.repeat(np.asarray(stack.scales[layer, e], np.float32), 32, axis=0)


def _share_case(case, rng):
    """``(held, k, N, local [N k])`` of one shape of routing: ``local`` reads
    ``held`` where a pair's expert is absent or its row dead or padding. ``N``
    is two tiles and a quarter."""
    from dllama_tpu.ops.expert_chunk import TILE_ROWS as tm

    held, k, N = 4, 2, 2 * tm + 8
    if case == "no held pair":
        local = np.full(N * k, held)
    elif case == "every pair on one expert":                # a run of every row: three tiles, the last of 8 rows
        local = np.where(np.arange(N * k) % k == 0, 2, held)
    elif case == "at the static bound":                     # every pair held, the whole layer (held = width)
        local = np.stack([rng.permutation(held)[:k] for _ in range(N)]).reshape(-1)
    elif case == "dead and padding rows":                   # rows 3, 17 dead, the last 11 padding; a third of the rest absent
        local = np.stack([rng.permutation(held + 2)[:k] for _ in range(N)])
        local[[3, 17]] = held
        local[N - 11:] = held
        local = np.minimum(local, held).reshape(-1)
    elif case == "a run ends on a tile edge, the next one past it":   # a tile of pairs on expert 0, one more on expert 1, 1 on 3
        local = np.full((N, k), held)
        local[:tm, 0], local[:tm + 1, 1], local[N - 1, 0] = 0, 1, 3
        local = local.reshape(-1)
    else:                                                   # "the whole layer, eight a token"
        held, k, N = 8, 8, 24
        local = np.stack([rng.permutation(held) for _ in range(N)]).reshape(-1)
    return held, k, N, local.astype(np.int32)


_SHARE_CASES = ["no held pair", "every pair on one expert", "at the static bound", "dead and padding rows",
                "a run ends on a tile edge, the next one past it", "the whole layer, eight a token"]


@pytest.mark.parametrize("striped", [False, True], ids=["whole plane", "striped"])
@pytest.mark.parametrize("case", _SHARE_CASES)
def test_expert_chunk_is_its_pairs_one_at_a_time(case, striped):
    """``expert_chunk`` (interpret mode off a TPU), both ends, against each held
    pair computed alone from the planes themselves: the gather end writes pair
    ``p`` of run ``j`` to row ``tile0[j] tm + (p - pair0[j])`` of the fed
    layout, the scatter end adds each pair's weighted row to its token's. A
    plane whole, and in two stripes of its output columns (reduced shapes with
    the real ones' divisibility: K a multiple of 32, N of lane tiles)."""
    import types

    from dllama_tpu.models import share
    from dllama_tpu.ops import expert_chunk as ec

    rng = np.random.default_rng(len(case))
    held, k, N, local = _share_case(case, rng)
    D, H, tm = 64, 256, ec.TILE_ROWS
    cfg = types.SimpleNamespace(n_experts=held)
    we1, we2 = _expert_stack(rng, 2, held, D, H), _expert_stack(rng, 2, held, H, D)
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(N, k)), jnp.float32)
    rows, experts, w, n_held = share._sorted_pairs(cfg, jnp.asarray(local), weights)
    runs, fed = share._runs(cfg, jnp.asarray(local), tm)
    counts = np.bincount(local, minlength=held + 1)[:held]
    assert int(fed) == int((-(-counts // tm)).sum()) * tm and int(runs[0]) == int((counts > 0).sum())
    F = ec.fed_rows(N * min(k, held), held)
    assert int(fed) <= F and int(n_held) <= N * min(k, held)
    if case == "at the static bound":
        assert int(n_held) == N * min(k, held)
    m, rows, w = jnp.int32(1), np.asarray(rows), np.asarray(w)
    kw = {"interpret": True, "tn": 128 if striped else None}
    h = np.asarray(ec.expert_chunk(x, we1, m, runs, jnp.asarray(rows), rows_out=F, **kw))
    a = rng.standard_normal((F, H)).astype(np.float32)       # the scatter end's input: anything, in the fed layout
    # the scatter end reads a pair's weight where the router left it: the pair's place in the flat [N k] weights
    at = jnp.argsort(jnp.asarray(local), stable=True)
    y = np.asarray(ec.expert_chunk(jnp.asarray(a), we2, m, runs, jnp.asarray(rows), (at, weights), rows_out=N, **{**kw, "tn": 32 if striped else None}))
    want, p = np.zeros((N, D), np.float32), 0
    tile0 = np.cumsum(-(-counts // tm)) - -(-counts // tm)
    for e in range(held):
        for r in range(counts[e]):
            q = tile0[e] * tm + r
            np.testing.assert_allclose(h[q], np.asarray(x[rows[p]]) @ _plane(we1, 1, e), rtol=2e-4, atol=2e-4)
            want[rows[p]] += w[p] * (a[q] @ _plane(we2, 1, e))
            p += 1
    assert p == int(n_held)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)
    untouched = np.setdiff1d(np.arange(N), rows[:p])
    assert not y[untouched].any()                            # a row no held pair names stays zero


@pytest.mark.parametrize("case", _SHARE_CASES)
def test_the_grouped_chunk_form_is_the_every_row_form(case, monkeypatch):
    """``share._experts_chunk`` with the kernel forced (interpret mode) against
    ``_experts_chunk_xla``, the every-row form through ``linear`` that stays
    the form off a TPU: the same pairs, the same arithmetic a pair, the same
    order of a token's experts, so float32 graphs agree to rounding; and the
    rows each says it fed its planes."""
    import types

    from dllama_tpu.formats.mfile import HiddenAct
    from dllama_tpu.models import share
    from dllama_tpu.ops import expert_chunk as ec

    rng = np.random.default_rng(len(case))
    held, k, N, local = _share_case(case, rng)
    D, H = 64, 128
    cfg = types.SimpleNamespace(n_experts=held, hidden_act=HiddenAct.SILU)
    lp = types.SimpleNamespace(we1=_expert_stack(rng, 2, held, D, H), we2=_expert_stack(rng, 2, held, H, D),
                               we3=_expert_stack(rng, 2, held, D, H))
    x = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(N, k)), jnp.float32)
    args = (cfg, x, jnp.asarray(local), weights, jnp.int32(1), lp)
    want, every = share._experts_chunk_xla(*args)
    assert share._experts_chunk(*args)[1] == every            # auto, off a TPU: the every-row form
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    got, fed = jax.jit(lambda *a: share._experts_chunk(cfg, *a, lp))(*args[1:5])
    counts = np.bincount(local, minlength=held + 1)[:held]
    assert int(fed) == int((-(-counts // ec.TILE_ROWS)).sum()) * ec.TILE_ROWS
    assert int(every) == N * int((counts > 0).sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    if counts.sum():
        assert float(jnp.abs(want).max()) > 0.05


def test_expert_chunk_gate_routes_through_the_one_gate(monkeypatch):
    from dllama_tpu.ops import expert_chunk as ec

    stack = _expert_stack(np.random.default_rng(0), 1, 2, 64, 128)
    F = ec.fed_rows(80, 2)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    assert ec.kernel_choice(40, F, stack, False, False) is None
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    assert ec.kernel_choice(40, F, stack, False, True) == {"interpret": True, "fast": False}
    monkeypatch.delenv("DLLAMA_TPU_QUANT_KERNEL")
    assert ec.kernel_choice(40, F, stack, False, False) is None      # auto, off a TPU
    # the VMEM predicate: a stripe for a plane too wide to land whole twice, none for a width off the lane grid
    assert ec.stripe(256, ec.fed_rows(2048, 12), 7168, 2048, True, False) in (512, 1024)
    assert ec.stripe(256, ec.fed_rows(2560, 32), 3072, 1024, True, False) == 1024
    assert ec.stripe(256, F, 64, 100, True, False) is None

"""Functional transformer forward for Llama 2/3/3.x and Qwen3.

This replaces the reference's per-node op-graph builder (reference:
buildLlmNet, src/llm.cpp:142-490) with a single SPMD program: the graph that
the reference assembles as [merge_add, inv_rms, rms_norm, cast, matmul_q/k/v,
(qwen3 q/k norms), rope, shift, multihead_att, cast, matmul_wo, cast, SYNC] +
[merge_add, inv_rms, rms_norm, cast, w1/w3, silu, mul, cast, w2, cast, SYNC]
per layer (llm.cpp:226-443) is expressed directly in jnp; tensor-parallel
synchronization (the two all-reduces per layer) is carried by sharding
annotations + XLA collectives instead of explicit SYNC steps.

Design choices (TPU-first, not a translation):

* **Stacked layer parameters + ``lax.scan``** — one compiled layer body
  regardless of depth; keeps compile time O(1) in ``n_layers`` and lets XLA
  pipeline HBM prefetch of the next layer's weights.
* Batch dimension is ``[B, T]`` *sequences × positions* — the reference's
  positions-as-batch prefill (nBatches, SURVEY.md §2.2) is the ``B=1`` case.
* Activations carry logical axis names via
  :func:`dllama_tpu.parallel.constrain` so the same code runs single-chip or
  sharded over any mesh.
"""

from __future__ import annotations

from typing import Any, NamedTuple


import jax
import jax.numpy as jnp
import numpy as np

from ..formats.mfile import ArchType, HiddenAct, ModelFile, RopeType
from ..formats.quants import Q40
from ..ops import flash_attention as _fa
from ..ops.attention import attention
from ..ops.flash_attention import flash_attention
from ..ops.linear import (
    LayerSlice,
    QuantizedWeight,
    Weight,
    fake_quant_q80,
    linear,
    quantize_weight_q40,
)
from ..ops.norms import rms_norm, rms_norm_per_head
from ..parallel.api import constrain, on_tpu, shard_map
from ..parallel.api import current_plan as _current_plan
from ..runtime import numerics as _numerics
from ..runtime.kvcache import KVCache, update_layer
from .config import ModelConfig
from .family import Family, family_of, layer_kinds
from .rope import apply_rope, build_rope_cache


class LayerParams(NamedTuple):
    """Per-layer weights; every leaf carries a leading ``[n_layers]`` axis."""

    wq: Weight  # [L, q_dim, dim]
    wk: Weight  # [L, kv_dim, dim]
    wv: Weight  # [L, kv_dim, dim]
    wo: Weight  # [L, dim, q_dim]
    w1: Weight | None  # [L, hidden_dim, dim]   (gate; None for MoE layers)
    w2: Weight | None  # [L, dim, hidden_dim]   (down)
    w3: Weight | None  # [L, hidden_dim, dim]   (up)
    norm_att: jax.Array  # [L, dim]
    norm_ffn: jax.Array  # [L, dim]
    norm_q: jax.Array | None  # [L, head_dim] (qwen3) or None
    norm_k: jax.Array | None
    # MoE (None for dense models). Expert weights carry any Weight repr:
    # dense (compute dtype) or stacked QuantizedWeight (Q40/Q80 planes — 1
    # B/weight resident, dequant fused into the consuming dot). Layout is
    # IN-major
    # ("[.., in, out]") so ``lax.ragged_dot``'s grouped matmul consumes the
    # dense planes with no per-step transpose (its rhs contracts axis 1).
    moe_gate: jax.Array | None = None  # [L, E, dim] router
    we1: Weight | None = None          # [L, E, dim, hidden_dim] (gate)
    we2: Weight | None = None          # [L, E, hidden_dim, dim] (down)
    we3: Weight | None = None          # [L, E, dim, hidden_dim] (up)


# the per-layer 2-D matmul planes: the leaves whose leading axis a layer scan
# may hand to linear() as stack + index (LayerSlice) instead of slicing
_LAYER_MATMULS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3")


def _layer_at(layers: LayerParams, l: jax.Array) -> LayerParams:
    """Layer ``l`` of the stacked weights, for a scan that closes over the
    stack and walks the index. A scan over ``layers`` as ``xs`` hands the
    body a slice, and XLA materializes that slice in front of a custom call:
    the fused dequant-GEMV would read a fresh copy of every plane it was
    built to read once. So the Q40 matmul planes stay whole and go to
    :func:`linear` as a :class:`LayerSlice` — this is the place that knows
    their leading axis is the layer; MoE expert stacks (``[L, E, ..]``) and
    everything small are sliced as a scan would slice them."""
    return _stack_at(layers, l, _LAYER_MATMULS)


def _stack_at(layers, l: jax.Array, matmuls: tuple[str, ...]):
    """:func:`_layer_at` for any stacked NamedTuple of layer weights whose
    2-D matmul planes are the fields ``matmuls`` (the hybrid decoder's
    linear-attention stack has its own, models/hybrid.py)."""
    def at(name: str, leaf):
        if name in matmuls and isinstance(leaf, QuantizedWeight):
            return LayerSlice(leaf, l)
        return jax.tree.map(lambda a: _at(a, l), leaf)

    return type(layers)(*(at(n, getattr(layers, n)) for n in layers._fields))


def _at(a: jax.Array, l) -> jax.Array:
    """Layer ``l`` of a stacked leaf ``[L, ...]`` (a column's, a pool's, a
    weight stack's)."""
    return jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)


def _put(a: jax.Array, a_l: jax.Array, l) -> jax.Array:
    """:func:`_at` undone: ``a`` with its layer ``l`` replaced by ``a_l``."""
    return jax.lax.dynamic_update_index_in_dim(a, a_l, l, 0)


def _scan_by_index(cfg: ModelConfig, rows: int) -> bool:
    """Whether a layer scan walks the layer INDEX with the weight stack
    closed over (:func:`_layer_at`) instead of scanning the stack: a
    dispatch the fused kernel has a regime for (``rows`` = B x T
    flattened: a decode step's 1..16, a prefill chunk's up to 256, a
    chunk with a tick's decode rows joined to it) on one device. Wider
    dispatches, mesh plans and offloaded weights scan the stack itself, as
    ever. Platform and kernel mode do not enter: where
    no kernel takes the stack, linear() slices it, which is what the scan
    did."""
    from ..ops.quant_matmul import CHUNK_MAX_M

    return (_current_plan() is None and not cfg.offload
            and rows <= CHUNK_MAX_M)


def _layer_indices(cfg: ModelConfig) -> jax.Array:
    return jnp.arange(cfg.n_layers, dtype=jnp.int32)


class Params(NamedTuple):
    embedding: jax.Array  # [vocab, dim]
    layers: LayerParams
    final_norm: jax.Array  # [dim]
    logits: Weight  # [vocab, dim]


def _use_flash(cfg: ModelConfig, q_shape, kv_shape) -> bool:
    """Trace-time choice of the single-device attention kernel. Under a mesh
    plan the auto-sharder cannot partition a pallas_call — the TP path wraps
    the kernel in shard_map (flash_attention_sharded) and the SP path has its
    own kernels (parallel/ring.py). Exception: a PURE-pp mesh — inside the
    manual pp shard_map with no other mesh axes every stage's arrays are
    fully local, so the plain kernel applies per stage."""
    from ..parallel.api import current_plan

    if cfg.attn_impl not in ("auto", "xla", "flash"):
        raise ValueError(f"attn_impl must be auto|xla|flash, got {cfg.attn_impl!r}")
    if cfg.attn_impl == "xla":
        return False
    plan = current_plan()
    plan_ok = plan is None or (
        plan.axis_size("pp") > 1
        and all(plan.axis_size(a) == 1 for a in ("tp", "sp", "dp", "ep")))
    n_kv, s = kv_shape[1], kv_shape[2]
    ok = _fa.supports(q_shape, n_kv, s)
    if cfg.attn_impl == "flash":
        if not ok:
            raise ValueError(f"flash attention unsupported for q={q_shape}, S={s}")
        if plan is not None and plan.axis_size("pp") > 1 and not plan_ok:
            # direct-forward pp meshes with extra axes never pass through
            # validate_pp: a forced kernel must still fail loudly here, not
            # silently run the oracle
            raise ValueError(
                "attn_impl='flash' under pp×(tp|dp|sp|ep) is unsupported "
                "(the Pallas kernel can't nest inside the manual pp "
                "shard_map with auto axes); use 'auto' or 'xla', or pure pp")
        return plan_ok
    return ok and on_tpu() and plan_ok


def _sharded_flash(cfg: ModelConfig, plan, q, k_cache, v_cache, start_pos):
    """TP-path Pallas attention via shard_map; None → caller uses the oracle.

    ``attn_impl='flash'`` forces it (interpret mode off-TPU, for tests) and
    FAILS LOUDLY when the plan/shape can't take the kernel — a forced mode
    silently falling back to the oracle hid exactly the configurations the
    user asked to exercise (advisor round-1 finding); ``'auto'`` enables it
    on TPU backends only."""
    if cfg.attn_impl == "xla":
        return None
    if plan.axis_size("pp") > 1:
        # inside the manual pp shard_map a nested pallas shard_map can't
        # partition; per-stage attention uses the XLA oracle when other
        # axes are in play (validate_pp rejects forced 'flash' for
        # pp×(tp|dp|sp); PURE pp runs the plain kernel via _use_flash)
        return None
    if plan.axis_size("sp") > 1:
        # sp attention is owned by the ring path (parallel/ring.py); landing
        # here means sp_attention declined the geometry (S % sp != 0, an
        # irregular head split, or B % dp != 0) and the oracle serves the
        # fallback — which a forced 'flash' must surface, not paper over
        if cfg.attn_impl == "flash":
            raise ValueError(
                f"attn_impl='flash' forced but the sp ring path declined "
                f"this geometry (plan axes "
                f"{dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))}, "
                f"q={q.shape}, kv={k_cache.shape}; needs S % sp == 0, a "
                f"regular head split, and B % dp == 0) — drop attn_impl or "
                f"use 'auto'")
        return None
    force = cfg.attn_impl == "flash"
    if not force and not on_tpu():
        return None
    res = _fa.flash_attention_sharded(
        plan, q, k_cache, v_cache, start_pos, cfg.head_dim,
        interpret=force and not on_tpu())
    if res is None and force:
        raise ValueError(
            f"attn_impl='flash' forced but the sharded kernel does not apply "
            f"(plan axes {dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))}, "
            f"q={q.shape}, kv={k_cache.shape}; irregular q-head/kv-group "
            f"splits (tp % n_kv != 0 with n_kv % tp != 0) use the XLA "
            f"oracle — drop attn_impl or use 'auto')")
    return res


def _hidden_act(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.hidden_act == HiddenAct.SILU:
        return jax.nn.silu(x)
    if cfg.hidden_act == HiddenAct.RELU2:
        return jnp.square(jax.nn.relu(x))
    if cfg.hidden_act == HiddenAct.GELU:
        # tanh-approx gelu (reference: gelu_F32, nn-cpu-ops.cpp:1133-1142)
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown hidden activation {cfg.hidden_act!r}")


def _moe_router(cfg: ModelConfig, h: jax.Array, gate: jax.Array):
    """Top-k routing (shared by both MoE impls): softmax over all expert
    logits, top-k, then either renormalize the selected weights to sum to 1
    (cfg.moe_norm_topk — Mixtral semantics; renormalizing equals softmaxing
    the selected logits) or keep the raw probabilities (Qwen3-MoE with HF
    norm_topk_prob false). Returns ``(weights [.., k], idx [.., k])``."""
    logits = jnp.einsum("...d,ed->...e", h.astype(jnp.float32),
                        gate.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.n_active_experts)
    if cfg.moe_norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx


def _experts_dense(we, x: jax.Array, rows: jax.Array | None = None) -> jax.Array:
    """Dense ``[..., in, out]`` planes of an expert-stack weight (inside the
    layer scan: ``[E, in, out]``), optionally gathered at ``rows`` along the
    leading expert axis first (gathering the QUANTIZED planes keeps the HBM
    read at 1 B/weight — the dequant expansion happens on the k gathered
    slices only, and XLA fuses it into the consuming dot, the same fused-
    dequant fast path ops.linear uses)."""
    from ..ops.linear import QuantizedWeight, _fast_mode, dequantize_weight

    if isinstance(we, QuantizedWeight):
        if rows is not None:
            we = QuantizedWeight(scales=we.scales[rows], codes=we.codes[rows])
        fast = _fast_mode(x) or we.scales.dtype == jnp.bfloat16
        return dequantize_weight(we, dtype=jnp.bfloat16 if fast else x.dtype)
    return we if rows is None else we[rows]


def _expert_gather_dot(x: jax.Array, we, rows: jax.Array) -> jax.Array:
    """``y[n] = x[n] @ plane(rows[n])`` — the decode-regime per-row expert
    dot. ``x [N, D]``, result f32 ``[N, out]``: gather, then dequant via
    :func:`_experts_dense`."""
    w = _experts_dense(we, x, rows)
    return jnp.einsum("nd,ndh->nh", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _moe_ffn_dense(cfg: ModelConfig, h: jax.Array, lp: LayerParams) -> jax.Array:
    """All-experts einsum, gate-weighted — O(E) FLOPs but exact and simple;
    the oracle the sparse path is tested against, and the fallback when the
    mesh shards the expert-hidden axis over tp."""
    E = cfg.n_experts
    weights, idx = _moe_router(cfg, h, lp.moe_gate)
    one_hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # [B,T,k,E]
    gates = jnp.einsum("btke,btk->bte", one_hot, weights)    # sparse rows
    gates = constrain(gates, "batch", None, "experts")

    ht = h.astype(cfg.compute_dtype)
    we1 = _experts_dense(lp.we1, ht)
    we2 = _experts_dense(lp.we2, ht)
    we3 = _experts_dense(lp.we3, ht)
    h1 = jnp.einsum("btd,edh->bteh", ht, we1)
    h3 = jnp.einsum("btd,edh->bteh", ht, we3)
    a = _hidden_act(cfg, h1) * h3
    a = constrain(a, "batch", None, "experts", "hidden")
    y = jnp.einsum("bteh,ehd,bte->btd", a, we2,
                   gates.astype(cfg.compute_dtype))
    return y.astype(h.dtype)


# Below this many (token, expert) rows the sparse path gathers per-row expert
# weights instead of sorting into ragged groups: at decode (N·k ~ a few) the
# gathered weights are tiny and the compute is exactly O(k) on EVERY backend,
# whereas ragged_dot's fallback lowering is a masked dense over all groups.
_MOE_GATHER_MAX_ROWS = 32


def _moe_sparse_local(cfg: ModelConfig, x: jax.Array, idx: jax.Array,
                      weights: jax.Array, we1, we2, we3,
                      e_lo: jax.Array, e_local: int) -> jax.Array:
    """Sparse MoE over this device's expert slice ``[e_lo, e_lo+e_local)``.

    ``x [N, D]``, ``idx/weights [N, k]``. Rows routed to non-local experts are
    clamped to expert 0 with weight 0 (computed-then-discarded — N·k rows per
    device keeps shapes static; still O(k), not O(E), work per token).

    Two regimes: decode-sized inputs gather the k experts' weight slices per
    row (true O(k) FLOPs, small transient); prefill-sized inputs sort rows by
    expert and run one ``lax.ragged_dot`` grouped matmul per projection.
    """
    N, k = idx.shape
    flat_e = idx.reshape(N * k) - e_lo
    valid = (flat_e >= 0) & (flat_e < e_local)
    flat_e = jnp.where(valid, flat_e, 0)
    flat_w = jnp.where(valid, weights.reshape(N * k), 0.0)
    x_rep = x[jnp.arange(N * k, dtype=jnp.int32) // k]  # row per (token, k)

    if N * k <= _MOE_GATHER_MAX_ROWS:
        h1 = _expert_gather_dot(x_rep, we1, flat_e)
        h3 = _expert_gather_dot(x_rep, we3, flat_e)
        a = (_hidden_act(cfg, h1) * h3).astype(x.dtype)
        y = _expert_gather_dot(a, we2, flat_e)
        y = y * flat_w[:, None]
    else:
        order = jnp.argsort(flat_e)                    # group rows by expert
        xs = x_rep[order]
        group_sizes = jnp.bincount(flat_e, length=e_local).astype(jnp.int32)
        # ragged_dot needs a dense rhs: quantized planes expand to a
        # bf16 transient of this device's local expert slice here (prefill
        # regime — MXU-bound, so the extra HBM of the expansion is paid
        # where it is cheapest; decode takes the gather regime above)
        d1 = _experts_dense(we1, xs)
        d2 = _experts_dense(we2, xs)
        d3 = _experts_dense(we3, xs)

        h1 = jax.lax.ragged_dot(xs.astype(d1.dtype), d1, group_sizes,
                                preferred_element_type=jnp.float32)
        h3 = jax.lax.ragged_dot(xs.astype(d3.dtype), d3, group_sizes,
                                preferred_element_type=jnp.float32)
        a = (_hidden_act(cfg, h1) * h3).astype(d2.dtype)
        y = jax.lax.ragged_dot(a, d2, group_sizes,
                               preferred_element_type=jnp.float32)
        y = y[jnp.argsort(order)] * flat_w[:, None]    # unsort to [N*k]
    return jnp.sum(y.reshape(N, k, -1), axis=1).astype(x.dtype)


def _moe_ffn_sparse(cfg: ModelConfig, h: jax.Array, lp: LayerParams) -> jax.Array:
    """Sparse top-k dispatch: tokens sorted by expert, one ``lax.ragged_dot``
    per projection — O(k/E) of the dense path's FFN FLOPs (the whole point of
    MoE; beyond-reference capability, SURVEY.md §2.2). Runs inside shard_map
    under a mesh: experts shard over ``ep`` (each device computes its local
    expert groups, psum combines), batch shards over ``dp``."""
    B, T, D = h.shape
    weights, idx = _moe_router(cfg, h, lp.moe_gate)
    x = h.astype(cfg.compute_dtype).reshape(B * T, D)
    idx2 = idx.reshape(B * T, cfg.n_active_experts)
    w2 = weights.astype(cfg.compute_dtype).reshape(B * T, cfg.n_active_experts)

    plan = _current_plan()
    if plan is None or plan.axis_size("pp") > 1:
        # no mesh, or already inside the manual pp shard_map (nesting another
        # shard_map is unsupported): run the sparse path stage-locally with
        # the full expert set
        y = _moe_sparse_local(cfg, x, idx2, w2, lp.we1, lp.we2, lp.we3,
                              jnp.int32(0), cfg.n_experts)
        return y.reshape(B, T, D).astype(h.dtype)

    from jax.sharding import PartitionSpec as P

    ep_ax = plan.resolve("experts")
    if ep_ax is not None and cfg.n_experts % plan._axis_size(ep_ax) != 0:
        ep_ax = None
    # tp shards the expert-hidden axis (param_shardings lays we1/we3 out as
    # [E(ep), D, H(tp)] and we2 as [E(ep), H(tp), D]): each device runs the
    # sparse dispatch over its H-slice — SiLU/GELU are elementwise over H, so
    # the act(h1)*h3 product is exact per-shard — and the we2 contraction's
    # H-partials psum together with the ep partials. This is col-split FFN
    # semantics (reference sliceColMatmul, nn-core.cpp:219-230) composed with
    # expert parallelism; previously a hidden-sharded mesh silently paid the
    # dense all-experts O(E) fallback (VERDICT r3 weak #3).
    from ..ops.linear import QuantizedWeight

    hid_ax = plan.resolve("hidden")
    if hid_ax is not None and (plan._axis_size(hid_ax) == 1
                               or cfg.hidden_dim % plan._axis_size(hid_ax) != 0):
        hid_ax = None
    from ..formats.quants import QUANT_BLOCK_SIZE

    if (hid_ax is not None and isinstance(lp.we2, QuantizedWeight)
            and (cfg.hidden_dim // QUANT_BLOCK_SIZE)
            % plan._axis_size(hid_ax) != 0):
        # we2's scale plane is [E, H/32, D]: an H-shard must also divide the
        # 32-element block axis or the scales can't split with the codes
        hid_ax = None
    e_local = cfg.n_experts // (plan._axis_size(ep_ax) if ep_ax else 1)
    red_axes = tuple(a for a in (ep_ax, hid_ax) if a is not None)

    from ..parallel.qcollectives import wire_psum

    ax_sizes = tuple(plan._axis_size(a) for a in red_axes)

    def local(x_l, idx_l, w_l, we1, we2, we3):
        e_lo = (jax.lax.axis_index(ep_ax) * e_local) if ep_ax else jnp.int32(0)
        y = _moe_sparse_local(cfg, x_l, idx_l, w_l, we1, we2, we3, e_lo, e_local)
        return wire_psum(y, red_axes, ax_sizes) if red_axes else y

    def we_spec(we, *, hid_on_out: bool):
        """Per-leaf PartitionSpecs for one expert-stack weight [E, in, out]:
        the per-repr plane layout comes from the ONE place that defines it
        (parallel.sharding.map_expert_weight), with the logical "hidden"
        axis resolved to this mesh's hid_ax."""
        from ..parallel.sharding import map_expert_weight

        in_ax, out_ax = (None, "hidden") if hid_on_out else ("hidden", None)
        return map_expert_weight(
            we, in_ax, out_ax,
            lambda _leaf, axes: P(ep_ax, *(hid_ax if a == "hidden" else None
                                           for a in axes)))

    fn = shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(), P(), P(),
                  we_spec(lp.we1, hid_on_out=True),
                  we_spec(lp.we2, hid_on_out=False),
                  we_spec(lp.we3, hid_on_out=True)),
        out_specs=P(),
        check_vma=False)
    y = fn(x, idx2, w2, lp.we1, lp.we2, lp.we3)
    return y.reshape(B, T, D).astype(h.dtype)


def _moe_ffn(cfg: ModelConfig, h: jax.Array, lp: LayerParams) -> jax.Array:
    """Mixture-of-experts SwiGLU FFN — new capability (the reference parses
    N_EXPERTS but its graph builder never emits expert ops, SURVEY.md §2.2).

    cfg.moe_impl picks the compute: "sparse" (grouped ragged_dot, default) or
    "dense" (all-experts oracle). The sparse path shards experts over ep AND
    the expert-hidden axis over tp (col-split partials, psum-combined); only
    a non-divisible hidden shard degrades to dense, whose einsums tolerate
    the replicated layout sharding_for falls back to.
    """
    impl = cfg.moe_impl
    plan = _current_plan()
    if impl == "auto":
        impl = "sparse"
    if impl == "sparse" and plan is not None:
        hid_ax = plan.resolve("hidden")
        if hid_ax is not None and plan._axis_size(hid_ax) > 1 \
                and cfg.hidden_dim % plan._axis_size(hid_ax) != 0:
            impl = "dense"
    if impl == "sparse":
        return _moe_ffn_sparse(cfg, h, lp)
    return _moe_ffn_dense(cfg, h, lp)


def _tap_stat(x: jax.Array) -> dict[str, jax.Array]:
    """Activation stats for one numerics-observatory tap site (all f32/i32
    scalars, cheap reductions XLA fuses into the producing op's epilogue):
    rms and abs-max over FINITE lanes (a NaN must poison the non-finite
    count, not the statistics), the non-finite lane count, and the Q80
    roundtrip error the sync/wire quantization would apply at this
    boundary (0 when the trailing axis isn't block-divisible)."""
    from ..formats.quants import Q80_BLOCK_SIZE
    from ..parallel.qcollectives import q80_roundtrip_error

    xf = x.astype(jnp.float32)
    finite = jnp.isfinite(xf)
    nf = jnp.sum(jnp.logical_not(finite).astype(jnp.int32))
    xz = jnp.where(finite, xf, 0.0)
    rms = jnp.sqrt(jnp.mean(jnp.square(xz)))
    absmax = jnp.max(jnp.abs(xz))
    q80e = (q80_roundtrip_error(xz) if x.shape[-1] % Q80_BLOCK_SIZE == 0
            else jnp.float32(0.0))
    return {"rms": rms, "absmax": absmax, "nonfinite": nf, "q80_err": q80e}


# Widest dispatch that still counts as the decode regime for the overlapped
# merges: single steps (T=1), fused-chunk scan bodies (T=1), and speculative
# verifies (T=K+1, small) ride the ring; prefill chunks (T >= 32) keep the
# monolithic GSPMD psum — they are MXU-bound, so chunking their merge would
# add launch overhead where there is no exposed collective wall to hide.
_OVERLAP_MAX_WIDTH = 16


def _overlapped_col_linear(cfg: ModelConfig, x: jax.Array, w,
                           in_logical: str):
    """TokenWeave-shaped col-split projection: the local partial matmul and
    a CHUNKED ring merge inside one shard_map, so XLA can schedule chunk
    i's ``ppermute`` hops concurrently with chunk j's dequant/accumulate
    compute (parallel/qcollectives.overlapped_wire_psum; the q80 wire rides
    the same hops when ``--wire q80``). Returns None when this geometry
    keeps the monolithic GSPMD path: no plan / no tp resolution for
    ``in_logical`` / non-divisible shapes / sp-pp meshes (their manual
    regions can't nest another shard_map) / prefill-wide dispatches."""
    from jax.sharding import PartitionSpec as P

    from ..formats.quants import Q40_BLOCK_SIZE
    from ..ops.linear import _fast_mode, dequantize_weight
    from ..parallel.qcollectives import overlapped_wire_psum

    plan = _current_plan()
    if (cfg.comm_overlap <= 1 or plan is None or x.ndim != 3
            or x.shape[1] > _OVERLAP_MAX_WIDTH
            or any(plan.axis_size(a) > 1 for a in ("sp", "pp"))):
        return None
    B, T, K = x.shape
    k_ax = plan.resolve(in_logical)
    if k_ax is None or K % plan._axis_size(k_ax) != 0:
        return None
    n = plan._axis_size(k_ax)
    if n <= 1 or cfg.dim % cfg.comm_overlap != 0:
        return None
    dp_ax = plan.resolve("batch")
    if dp_ax is not None and B % plan._axis_size(dp_ax) != 0:
        dp_ax = None
    quant = isinstance(w, QuantizedWeight)
    if quant and (K // n) % Q40_BLOCK_SIZE != 0:
        return None  # the scale plane's block rows can't split with codes
    fast = quant and (_fast_mode(x) or w.scales.dtype == jnp.bfloat16)
    out_dtype = x.dtype

    def local(xl, *wl):
        # f32 partials so the cross-device reduction doesn't round in bf16
        # (same rule as quant_matmul_sharded's col-split merge)
        if quant:
            from ..ops.quant_matmul import pallas_local_choice, quant_matmul

            sc, cd = wl
            lw = QuantizedWeight(scales=sc, codes=cd)
            # the ONE shared kernel rule (quant_matmul.pallas_local_choice)
            # — flipping --comm-overlap never silently swaps the local
            # matmul's numerics
            kernel = pallas_local_choice(tuple(xl.shape), lw, fast)
            if kernel is not None:
                part = quant_matmul(xl.astype(jnp.float32), lw,
                                    fast=fast, **kernel)
            else:
                wd = dequantize_weight(
                    lw, dtype=jnp.bfloat16 if fast else xl.dtype)
                part = jax.lax.dot_general(
                    xl.astype(wd.dtype), wd,
                    dimension_numbers=(((2,), (0,)), ((), ())),  # [K, D]
                    preferred_element_type=jnp.float32)
        else:
            wd = wl[0].astype(xl.dtype)
            part = jax.lax.dot_general(
                xl, wd,
                dimension_numbers=(((2,), (1,)), ((), ())),  # dense [D, K]
                preferred_element_type=jnp.float32)
        merged = overlapped_wire_psum(part, k_ax, n, cfg.comm_overlap)
        return merged.astype(out_dtype)

    if quant:
        w_specs = (P(k_ax, None), P(k_ax, None))  # scales, codes shard K
        w_leaves = (w.scales, w.codes)
    else:
        w_specs = (P(None, k_ax),)  # dense [out, in] shards the in dim
        w_leaves = (w,)
    fn = shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(dp_ax, None, k_ax), *w_specs),
        out_specs=P(dp_ax, None, None), check_vma=False)
    from ..parallel.qcollectives import wire_poison_dp_scope

    # under dp the shard-local "row 0" exists per dp group: name the axis
    # so the wire poison site can pin the GLOBAL row 0 (one request)
    with wire_poison_dp_scope(dp_ax):
        return fn(x, *w_leaves)


def _merge_linear(cfg: ModelConfig, x: jax.Array, w, in_logical: str):
    """One col-split partial merge (wo or w2): the overlapped ring path
    when ``--comm-overlap`` resolved chunks for this geometry, else the
    plain :func:`linear` col-split (GSPMD psum / sharded Pallas kernel)."""
    y = _overlapped_col_linear(cfg, x, w, in_logical)
    if y is not None:
        return y
    return linear(x, w, in_axis=in_logical)


def _attn_qkv(cfg: ModelConfig, x: jax.Array, lp: LayerParams,
              cos: jax.Array, sin: jax.Array, positions: jax.Array, fq):
    """Attention prologue shared by the dense and paged layer steps:
    pre-norm, QKV projections, optional qk-norm, rope. Returns post-rope
    ``q [B, T, n_heads, hd]`` and ``k/v [B, T, n_kv, hd]``."""
    B, T, _ = x.shape
    h = fq(rms_norm(x, lp.norm_att, cfg.norm_epsilon))
    q = linear(h, lp.wq, out_axis="heads").reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = linear(h, lp.wk, out_axis="kv_heads").reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = linear(h, lp.wv, out_axis="kv_heads").reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)

    if cfg.uses_qk_norm:
        q = rms_norm_per_head(q, lp.norm_q, cfg.norm_epsilon)
        k = rms_norm_per_head(k, lp.norm_k, cfg.norm_epsilon)

    q = apply_rope(q, cos, sin, positions, cfg.rope_type)
    k = apply_rope(k, cos, sin, positions, cfg.rope_type)
    return q, k, v


def _attn_out_and_ffn(cfg: ModelConfig, x: jax.Array, att: jax.Array,
                      lp: LayerParams, fq, taps: bool):
    """Layer epilogue shared by the dense and paged layer steps: output
    projection + residual, then the ffn half. Returns ``(x, stats|None)``."""
    B, T, _ = x.shape
    x = x + fq(_merge_linear(cfg, fq(att.reshape(B, T, cfg.q_dim)), lp.wo,
                             "heads"))
    x = constrain(x, "batch", None, None)
    attn_stat = _tap_stat(x) if taps else None

    # -- ffn half (reference ff segment, llm.cpp:369-439; MoE is new) ------
    h = fq(rms_norm(x, lp.norm_ffn, cfg.norm_epsilon))
    if cfg.is_moe:
        x = x + fq(_moe_ffn(cfg, h, lp))
    else:
        gate = _hidden_act(cfg, linear(h, lp.w1, out_axis="hidden"))
        up = linear(h, lp.w3, out_axis="hidden")
        hidden = constrain(fq(gate * up), "batch", None, "hidden")
        x = x + fq(_merge_linear(cfg, hidden, lp.w2, "hidden"))
    x = constrain(x, "batch", None, None)
    if taps:
        return x, {"attn_out": attn_stat, "mlp_out": _tap_stat(x)}
    return x, None


def _layer_step(cfg: ModelConfig, x: jax.Array, lp: LayerParams,
                k_cache: jax.Array, v_cache: jax.Array,
                cos: jax.Array, sin: jax.Array, start_pos: jax.Array,
                positions: jax.Array, taps: bool = False):
    """One transformer block. ``x: [B, T, dim]``; caches are head-major
    ``[B, n_kv, S, hd]`` (see runtime.kvcache). With ``taps`` (a
    trace-time bool — the numerics observatory's activation taps) the
    return gains a per-site stats dict: ``attn_out`` after the attention
    residual, ``mlp_out`` after the ffn residual."""
    B, T, _ = x.shape

    # Q80 sync-parity: fake-quantize at the reference's cast points — matmul
    # inputs (X→Q80 casts) and the partial-sum outputs that cross the wire
    # (ZQ pipe casts, llm.cpp:258-265, 360-365, 433-438).
    fq = fake_quant_q80 if cfg.sync_q80 else (lambda a: a)

    # -- attention half (reference att segment, llm.cpp:226-366) -----------
    q, k, v = _attn_qkv(cfg, x, lp, cos, sin, positions, fq)

    att, k_cache, v_cache = _attend_dense(cfg, q, k, v, k_cache, v_cache,
                                          start_pos, positions)
    x, stats = _attn_out_and_ffn(cfg, x, att, lp, fq, taps)
    if taps:
        return x, k_cache, v_cache, stats
    return x, k_cache, v_cache


def _attend_dense(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                  k_cache: jax.Array, v_cache: jax.Array,
                  start_pos: jax.Array, positions: jax.Array):
    """Append the new rows ``k, v [B, T, n_kv, hd]`` to one layer's dense
    cache and attend ``q [B, T, n_heads, hd]`` over it: the sp ring, the
    sharded or plain flash kernel, or the XLA oracle, as plan and shapes
    resolve. Returns ``(att, k_cache, v_cache)``. Shared by
    :func:`_layer_step` and the hybrid decoder's full layers
    (models/hybrid.py). The score's scale is ``cfg.score_dim ** -0.5``: the
    head's width unless the header states the scale."""
    sp_res = None
    plan = _current_plan()
    if plan is not None and plan.axis_size("sp") > 1 \
            and plan.axis_size("pp") == 1:  # sp×pp nesting unsupported
        from ..parallel.ring import sp_attention

        # ragged rides the same ring/merge paths: positions are affine
        # WITHIN each batch row, which is all the per-row kernel pos table
        # and the [B, T] masks assume; the per-slot append depths shard
        # with the batch rows
        sp_res = sp_attention(plan, q, k_cache, v_cache, k, v, positions,
                              start_pos, cfg.head_dim, attn_impl=cfg.attn_impl)
    if sp_res is not None:
        att, k_cache, v_cache = sp_res
    else:
        k_cache, v_cache = update_layer(k_cache, v_cache, k, v, start_pos)
        # ragged (per-row positions) rides the same kernels: the flash
        # kernel's position table is blocked per batch row
        att = (_sharded_flash(cfg, plan, q, k_cache, v_cache, start_pos)
               if plan is not None else None)
        if att is None:
            if _use_flash(cfg, q.shape, k_cache.shape):
                # forced 'flash' off-TPU runs the kernel in interpret mode
                # (the test path, same rule _sharded_flash applies)
                att = flash_attention(
                    q, k_cache, v_cache, start_pos, cfg.score_dim,
                    interpret=(cfg.attn_impl == "flash"
                               and not on_tpu()))
            else:
                att = attention(q, k_cache, v_cache, positions, cfg.score_dim)
    att = constrain(att, "batch", None, "heads", None)
    return att, k_cache, v_cache


def _write_kv_rows(pool: jax.Array, l: jax.Array, new: jax.Array,
                   blk: jax.Array, off: jax.Array) -> jax.Array:
    """Write ``new [B, T, n_kv, hd]`` into layer ``l`` of the whole pool
    ``[L, n_blocks, n_kv, bs, hd]`` at the cells ``(l, blk[b, t], :,
    off[b, t], :)``, in place where the pool is a scan's carry: nothing of
    the pool is sliced out or put back.

    The decode step (T == 1, static) writes its B cells row by row; wider
    dispatches (the verify step, a paged prefill) scatter. Same cells, same
    bytes — but beside a Pallas matmul in the scanned body the scatter kept
    the pool slices out of VMEM and paged attention went from 0.29 to 1.0 ms
    a layer on the chip (PERF.md section 6, PR 26 / PR 28)."""
    new = new.astype(pool.dtype)
    if new.shape[1] != 1:
        # advanced (blk, off) indices around the head slice address each
        # row's [n_kv, hd] cell of layer l
        return pool.at[l, blk, :, off, :].set(new)
    cells = jnp.swapaxes(new, 1, 2)[:, None, None]     # [B, 1, 1, n_kv, 1, hd]
    for b in range(new.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, cells[b], (l, blk[b, 0], 0, off[b, 0], 0))
    return pool


def _paged_layer_step(cfg: ModelConfig, x: jax.Array, lp: LayerParams,
                      k_pool: jax.Array, v_pool: jax.Array, l: jax.Array,
                      cos: jax.Array, sin: jax.Array,
                      positions: jax.Array, tables: jax.Array,
                      write_lens: jax.Array | None = None):
    """Transformer block ``l`` over the PAGED cache (runtime/kvblocks.py).

    ``k_pool/v_pool: [L, n_blocks, n_kv, block_size, hd]`` is the WHOLE
    block pool, given back whole with layer ``l``'s new rows written;
    ``tables [B, max_blocks]`` maps each row's logical block index to a
    physical block (0 = the null block). New K/V rows scatter into their
    physical (layer, block, offset) cell, then the row's logical cache is
    gathered out of layer ``l`` to the dense head-major view and
    attended by the XLA oracle — value-identical to the dense slot-pool
    layer step on the same context (the gather materializes exactly the
    rows ``update_layer`` would have produced; rows behind unallocated
    table entries read the null block and are position-masked). The
    TPU-native ragged-paged-attention kernel (ops/paged_attention.py,
    PAPERS.md "Ragged Paged Attention") replaces the gather+oracle pair
    whenever its gate resolves — same callers, same program names, zero
    extra compiles; the oracle's arithmetic in another reduction order
    (float32 ulps), bounded by each row's length, zeros on a row whose
    table starts with the null block."""
    fq = fake_quant_q80 if cfg.sync_q80 else (lambda a: a)
    q, k, v = _attn_qkv(cfg, x, lp, cos, sin, positions, fq)
    att, k_pool, v_pool = _attend_paged(cfg, q, k, v, k_pool, v_pool, l,
                                        positions, tables, write_lens)
    x, _ = _attn_out_and_ffn(cfg, x, att, lp, fq, taps=False)
    return x, k_pool, v_pool


def _attend_paged(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                  k_pool: jax.Array, v_pool: jax.Array, l: jax.Array,
                  positions: jax.Array, tables: jax.Array,
                  write_lens: jax.Array | None = None, *, window: int = 0):
    """Write the new rows ``k, v [B, T, n_kv, hd]`` into layer ``l`` of the
    whole block pool ``[L, n_blocks, n_kv, bs, hd]`` and attend ``q``
    through the block ``tables``: the ragged paged kernel where its gate
    resolves, the gather + XLA oracle otherwise (:func:`_paged_layer_step`
    has the contract). Both are handed the whole pool and the index (a
    slice in front of a custom call is materialized, the lesson of
    :func:`_layer_at`), so a layer scan that carries the pool moves none of
    it. Returns ``(att, k_pool, v_pool)``, the pools whole. Shared with the
    hybrid decoder's full layers, whose ``l`` is the period, and with
    models/laguna.py's two pools. ``window`` > 0 is a sliding-window layer:
    the query sees the newest ``window`` keys, and table entries behind
    them may be null (their blocks went back to the free list)."""
    from ..ops import paged_attention as _pa

    B, T = q.shape[:2]
    bs = k_pool.shape[3]
    n_blocks_seq = tables.shape[1]
    brow = jnp.arange(B, dtype=jnp.int32)[:, None]
    blk = tables[brow, positions // bs]                      # [B, T]
    off = positions % bs
    if write_lens is not None:
        # ragged verify (paged_verify_step_guarded): lane t of row b is a
        # real input only while t <= write_lens[b] — lanes past the row's
        # draft length carry padding whose writes must not consume (or
        # corrupt) cells the host never allocated blocks for. Redirect
        # them to the null block; traced, so varying per-slot draft
        # lengths never retrace.
        lane = jnp.arange(T, dtype=jnp.int32)[None, :]
        blk = jnp.where(lane <= write_lens[:, None], blk, 0)
    # inactive rows carry all-null tables, so their ride-along writes land
    # in the null block
    k_pool = _write_kv_rows(k_pool, l, k, blk, off)
    v_pool = _write_kv_rows(v_pool, l, v, blk, off)

    kernel = _pa.kernel_choice(tuple(q.shape), cfg.n_kv_heads,
                               n_blocks_seq, bs)
    if kernel is not None:
        # walk the block table in-kernel, as far as each row is long: the
        # dense logical cache never materializes in HBM, and a dead row
        # (an all-null table, whatever its stale position) costs nothing
        att = _pa.paged_ragged_attention(q, k_pool, v_pool, l, tables,
                                         positions, cfg.score_dim,
                                         window=window, **kernel)
    else:
        def view(pool):
            gathered = pool[l, tables]           # [B, M, n_kv, bs, hd]
            return jnp.moveaxis(gathered, 2, 1).reshape(
                B, cfg.n_kv_heads, n_blocks_seq * bs, pool.shape[-1])

        att = attention(q, view(k_pool), view(v_pool), positions,
                        cfg.score_dim, window=window)
    att = constrain(att, "batch", None, "heads", None)
    return att, k_pool, v_pool


def _exact_f32_dots(fn):
    """Trace an f32 ("exact") graph with every dot at HIGHEST precision.

    On a TPU the MXU's default for an f32 dot is ONE bf16 pass (~1e-3
    relative): only the Pallas quantized matmuls asked for HIGHEST, so the
    attention dots, dense (F32/F16-file) planes and the XLA dequant fallback
    of the parity mode were bf16-grade, and the reference-binary transcript
    replayed on the chip flipped its first token at a 1.3e-3 logit margin
    (tests/test_tpu_hw.py::test_macbeth_transcript_on_hw, PR 22). The
    precision context is read when a dot is traced — Pallas kernels
    included — so wrapping the forward covers the whole program family.
    bf16 (fast) graphs are untouched; the CPU backend computes f32 dots in
    f32 whatever the precision says."""
    import functools

    @functools.wraps(fn)
    def wrapped(params, cfg, *args, **kwargs):
        if jnp.dtype(cfg.compute_dtype) != jnp.float32:
            return fn(params, cfg, *args, **kwargs)
        with jax.default_matmul_precision("highest"):
            return fn(params, cfg, *args, **kwargs)

    return wrapped


@_exact_f32_dots
def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, kv: KVCache,
            n_valid: jax.Array | None = None) -> tuple[jax.Array, KVCache]:
    """Full forward: ``tokens [B, T]`` at absolute ``start_pos`` → logits.

    ``n_valid`` belongs to the decoders with a recurrent state
    (models/hybrid.py, models/falcon_h1.py, where ``kv`` is a
    :class:`~dllama_tpu.runtime.kvblocks.StateColumn`): how many of the
    chunk's ``T`` positions are real. K/V rows written for padding are
    overwritten later; a recurrent state would keep them, so padded
    positions leave it untouched. The dense decoders pad freely and never
    pass it.

    Returns float32 logits ``[B, T, vocab]`` and the updated cache. Jittable;
    ``start_pos`` is a traced scalar (all rows at one position) or a ``[B]``
    vector — per-row positions for ragged batched serving
    (runtime/serving.py), where each slot of the batch is its own sequence
    at its own depth. One compilation per ``T`` either way.
    """
    return family_of(cfg).forward(params, cfg, tokens, start_pos, kv, n_valid)


def _dense_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                   start_pos: jax.Array, kv: KVCache, n_valid=None):
    """:func:`forward` of the dense decoders (the Llama and the Qwen3
    equations): one scan over the stacked layers, the cache's layers as
    xs/ys. ``n_valid`` is not theirs."""
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    ragged = start_pos.ndim > 0
    # numerics observatory taps (runtime/numerics): a TRACE-TIME flag, so
    # the default (off) trace is byte-identical — no tap code exists in it
    collect = _numerics.taps_active()
    plan = _current_plan()
    if plan is not None and plan.axis_size("pp") > 1:
        if collect:
            # the manual pp schedule owns its own shard_map region; tap
            # stats can't thread through it — fail at trace time rather
            # than silently returning an empty pytree
            raise ValueError("numerics taps are unsupported under "
                             "pipeline parallelism (pp > 1)")
        # pipeline parallelism: layer stack sharded over pp, stages hand the
        # activation along the ring (parallel/pipeline.py — new capability).
        # Ragged [B] start_pos (batched serving) rides along: each stage's
        # _layer_step gets the per-row depths.
        from ..parallel.pipeline import pp_forward

        return pp_forward(plan, cfg, params, tokens, start_pos, kv)

    B, T = tokens.shape
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    x = constrain(x, "batch", None, None)

    cos, sin = build_rope_cache(cfg)
    arange = jnp.arange(T, dtype=jnp.int32)[None, :]
    positions = (start_pos[:, None] if ragged else start_pos) + arange
    positions = jnp.broadcast_to(positions, (B, T))

    by_index = _scan_by_index(cfg, B * T) and not collect

    def body(carry, xs):
        x = carry
        lp, k_l, v_l = xs
        if by_index:
            lp = _layer_at(params.layers, lp)
        elif cfg.offload:
            # weights stream host → device per layer; XLA prefetches the next
            # layer's transfer while this layer computes (cfg.offload docs)
            lp = jax.device_put(lp, jax.memory.Space.Device)
        if collect:
            x, k_l, v_l, st = _layer_step(cfg, x, lp, k_l, v_l, cos, sin,
                                          start_pos, positions, taps=True)
            return x, (k_l, v_l, st)
        x, k_l, v_l = _layer_step(cfg, x, lp, k_l, v_l, cos, sin,
                                  start_pos, positions)
        return x, (k_l, v_l)

    # scan over the stacked layer axis; caches ride along as per-layer xs/ys.
    layers = _layer_indices(cfg) if by_index else params.layers
    x, ys = jax.lax.scan(body, x, (layers, kv.k, kv.v))
    if collect:
        new_k, new_v, layer_taps = ys  # stacked [L] leaves per site
    else:
        new_k, new_v = ys

    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    final_stat = _tap_stat(x) if collect else None
    if cfg.sync_q80:  # final cast before the logits matmul (llm.cpp:445-486)
        x = fake_quant_q80(x)
    logits = linear(x, params.logits, out_axis="vocab").astype(jnp.float32)
    logits = constrain(logits, "batch", None, "vocab")
    if collect:
        taps = dict(layer_taps)
        taps["final_norm"] = final_stat
        taps["logits"] = _tap_stat(logits)
        return logits, KVCache(k=new_k, v=new_v), taps
    return logits, KVCache(k=new_k, v=new_v)


def forward_with_taps(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      start_pos: jax.Array, kv: KVCache):
    """:func:`forward` with the numerics observatory's activation taps
    collected: returns ``((logits, taps), kv)`` where ``taps`` is the
    per-site stats pytree (``attn_out``/``mlp_out`` carry stacked ``[L]``
    leaves from the layer scan; ``final_norm``/``logits`` scalars — see
    :func:`_tap_stat`). A separate entry point (not a flag argument) so
    the plain program's trace stays byte-identical and the tapped one is
    only ever jitted when an engine opts in (``--numerics-taps``)."""
    with _numerics.collecting_taps():
        logits, kv, taps = forward(params, cfg, tokens, start_pos, kv)
    return (logits, taps), kv


def nll_from_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Fused log-softmax-gather: per-position negative log-likelihood.

    ``logits [B, T, vocab]`` (float32), ``targets [B, T]`` int32 →
    ``nll [B, T]`` float32 where ``nll[b, t] = logsumexp(logits[b, t]) -
    logits[b, t, targets[b, t]]`` (always >= 0). The reduction is the
    whole point: jitted as the epilogue of :func:`prefill_nll`, the
    program's output is ``[B, T]``, so full-vocab logits for a long eval
    chunk never round-trip through HBM as a program result the host then
    downloads — the quality observatory scores 8k-token sequences at
    prefill bandwidth.
    """
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    picked = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return lse - picked


def prefill_nll(params: Params, cfg: ModelConfig, tokens: jax.Array,
                targets: jax.Array, start_pos: jax.Array,
                kv: KVCache) -> tuple[jax.Array, KVCache]:
    """Teacher-forced prefill twin of :func:`forward` for the quality
    observatory (runtime/evalharness.py): same body, but the epilogue is
    the fused :func:`nll_from_logits` reduction instead of returning
    full-vocab logits. ``tokens [B, T]`` at ``start_pos`` with next-token
    ``targets [B, T]`` → per-position ``nll [B, T]`` float32 plus the
    updated cache, so an eval sequence's chunks double as its prefill.
    Padding rows (token 0 / target 0 past the chunk's valid length)
    compute garbage NLL the caller slices off — exactly the padding
    discipline of the serving prefill chunks, which is what makes the
    batched path bit-identical to the engine oracle.
    """
    logits, kv = forward(params, cfg, tokens, start_pos, kv)
    nll = constrain(nll_from_logits(logits, targets), "batch", None)
    return nll, kv


# ---------------------------------------------------------------------------
# Decode steps, the non-finite tripwire (runtime/numerics) in every one
# ---------------------------------------------------------------------------
#
# Every engine/serving decode dispatch is one of the programs below: the
# fused step plus (a) an in-graph poison selector (a traced f32 scalar driven
# by the `logits` failpoint: 0.0 outside a chaos run, so arming chaos never
# recompiles) and (b) a fused per-row count of non-finite decode-step logits
# beside the picked token. There is no step without the tripwire (dlint's
# guarded-twin rule refuses one): a caller that wants no injection passes
# poison 0.0. The `_guarded` suffix names the XLA modules the benchmark's
# readers key on; the compile ledger's names are the engine's (`greedy_step`).


def _poison_logits(logits: jax.Array, poison: jax.Array) -> jax.Array:
    """Inject the failpoint's poison into the logits in-graph: 0 = clean
    passthrough, 1 = NaN, 2 = +Inf (numerics.POISON_CODES). Codes >= 3
    belong to the ``wire`` failpoint site (numerics.WIRE_POISON_CODES,
    injected into the ring collectives' shipped partials by
    parallel/qcollectives) and pass through clean here."""
    val = jnp.where(poison >= 2.0, jnp.float32(jnp.inf),
                    jnp.float32(jnp.nan))
    hit = jnp.logical_and(poison > 0.0, poison < 3.0)
    return jnp.where(hit, val.astype(logits.dtype), logits)


def _guarded_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     start_pos: jax.Array, kv, poison: jax.Array,
                     fwd=None):
    """The decode programs' forward: runs under ``wire_poison_scope`` so
    the overlapped wire collectives (when the trace contains them) carry the
    SAME traced poison scalar the logits site uses: codes 1-2 poison logits,
    3-4 poison this device's shipped ring partial (batch row 0 only). One
    traced selector, so arming either chaos site never recompiles. Prefill
    programs never enter the scope and trace no injection code at all.
    ``fwd`` stands in for :func:`forward` (same signature):
    ``parallel.multihost.replicated`` hands in the forward whose logits
    every process holds whole."""
    from ..parallel.qcollectives import wire_poison_scope

    with wire_poison_scope(poison):
        return (fwd or forward)(params, cfg, tokens, start_pos, kv)


def _nonfinite_rows(logits: jax.Array) -> jax.Array:
    """Per-row count of non-finite lanes: ``[B, ...] -> [B] int32``."""
    bad = jnp.logical_not(jnp.isfinite(logits)).astype(jnp.int32)
    return jnp.sum(bad, axis=tuple(range(1, logits.ndim)))


def greedy_step_guarded(params: Params, cfg: ModelConfig, tokens: jax.Array,
                        start_pos: jax.Array, kv: KVCache,
                        poison: jax.Array, fwd=None):
    """Fused forward + argmax of the last position, the single-dispatch
    greedy decode step (SURVEY.md §7.4 "single fused jitted step"), with the
    tripwire: returns ``((token, nonfinite), kv)`` where ``nonfinite [B]``
    counts non-finite lanes of the decode-step logits, the one row every
    emitted token is derived from."""
    logits, kv = _guarded_forward(params, cfg, tokens, start_pos, kv, poison,
                                  fwd)
    last = _poison_logits(logits[:, -1, :], poison)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return (tok, _nonfinite_rows(last)), kv


def sampled_step_guarded(params: Params, cfg: ModelConfig, tokens: jax.Array,
                         start_pos: jax.Array, kv: KVCache,
                         temperature: jax.Array, topp: jax.Array,
                         coin: jax.Array, poison: jax.Array, fwd=None):
    """Fused forward + temperature/top-p sample of the last position, the
    temperature>0 twin of :func:`greedy_step_guarded`: one dispatch and a
    4-byte transfer per sampled token instead of a vocab-row download
    (reference samples on host after the logits gather,
    src/tokenizer.cpp:480-510). ``temperature``/``topp``/``coin`` are traced
    f32 scalars (the host steps its xorshift* RNG and passes the coin in),
    so per-request sampling knobs never trigger a recompile.

    Also the ragged batched-serving step: everything broadcasts over rows,
    and ``nonfinite [B]`` is per slot, so a poisoned request can be failed
    without touching the rest of the batch. Returns ``((token, nonfinite),
    kv)``."""
    from ..ops.sampling import sampled_token

    logits, kv = _guarded_forward(params, cfg, tokens, start_pos, kv, poison,
                                  fwd)
    last = _poison_logits(logits[:, -1, :], poison)
    return (sampled_token(last, temperature, topp, coin),
            _nonfinite_rows(last)), kv


def _scan_decode_guarded(step1, token: jax.Array, start_pos: jax.Array,
                         kv: KVCache, n_steps: int,
                         coins: jax.Array | None = None):
    """The one multi-step decode scan shared by every chunked variant
    (greedy/sampled, plain/replicated): feeds each picked token into the
    next forward on device. ``step1(tokens_2d, pos, kv[, coin])`` is the
    single-step function and returns ``((tok, nf), kv)``; the per-row
    non-finite counts accumulate over the chunk's scan carry, one fused
    count per dispatch, exactly like the tokens themselves. Returns
    ``((tokens [B, n_steps], nonfinite [B]), kv)``."""

    def body(carry, xs):
        token, kv, nf = carry
        if coins is None:
            (nxt, nf_i), kv = step1(token[:, None], start_pos + xs, kv)
        else:
            i, coin = xs
            (nxt, nf_i), kv = step1(token[:, None], start_pos + i, kv, coin)
        return (nxt, kv, nf + nf_i), nxt

    xs = jnp.arange(n_steps, dtype=jnp.int32)
    nf0 = jnp.zeros(token.shape, dtype=jnp.int32)
    (_, kv, nf), toks = jax.lax.scan(
        body, (token, kv, nf0), xs if coins is None else (xs, coins))
    return (jnp.moveaxis(toks, 0, 1), nf), kv  # ([B, n_steps], [B])


def greedy_steps_guarded(params: Params, cfg: ModelConfig, token: jax.Array,
                         start_pos: jax.Array, kv: KVCache, n_steps: int,
                         poison: jax.Array, fwd=None):
    """``n_steps`` fused greedy decode steps in ONE dispatch: one dispatch
    + one ``4·n_steps``-byte transfer per CHUNK instead of per token. Output
    is bit-identical to ``n_steps`` single :func:`greedy_step_guarded` calls
    (greedy is deterministic); the caller truncates at EOS: tokens past it
    are discarded work, not divergence. ``token: [B]`` seeds the chunk.
    Returns ``((tokens, nonfinite), kv)``."""
    return _scan_decode_guarded(
        lambda t, p, kv: greedy_step_guarded(params, cfg, t, p, kv, poison,
                                             fwd),
        token, start_pos, kv, n_steps)


def sampled_steps_guarded(params: Params, cfg: ModelConfig, token: jax.Array,
                          start_pos: jax.Array, kv: KVCache,
                          temperature: jax.Array, topp: jax.Array,
                          coins: jax.Array, n_steps: int,
                          poison: jax.Array, fwd=None):
    """The temperature>0 twin of :func:`greedy_steps_guarded`: ``coins
    [n_steps]`` are the host xorshift draws for the whole chunk (the host
    rewinds its RNG to the number of tokens actually kept after EOS
    truncation, so the stream stays bit-identical to single-step decode).

    Also the RAGGED chunked step for batched serving (BatchedGenerator
    .step_chunk): everything broadcasts over rows: ``token/start_pos [B]``,
    vector ``temperature/topp [B]`` (temp<=0 rows take argmax), and ``coins
    [n_steps, B]`` (scan consumes axis 0), so K fused steps run over the
    whole slot pool in one dispatch."""
    return _scan_decode_guarded(
        lambda t, p, kv, c: sampled_step_guarded(params, cfg, t, p, kv,
                                                 temperature, topp, c,
                                                 poison, fwd),
        token, start_pos, kv, n_steps, coins=coins)


def verify_step_guarded(params: Params, cfg: ModelConfig, tokens: jax.Array,
                        start_pos: jax.Array, kv: KVCache,
                        poison: jax.Array, fwd=None):
    """Speculative greedy verify: ONE forward over ``tokens [B, K+1]`` (the
    real next input followed by K drafted tokens) at positions
    ``start_pos..start_pos+K``; ``preds[:, t]`` is the greedy argmax after
    consuming ``tokens[:, :t+1]`` and ``n_acc`` is the longest draft prefix
    the model agrees with (``tokens[:, i+1] == preds[:, i]``). The caller
    emits ``preds[:, :n_acc+1]``: exactly what n_acc+1 sequential
    :func:`greedy_step_guarded` calls would produce, for one dispatch whose
    HBM cost is a single decode step (weights dominate; the K extra rows
    ride the same weight reads on the MXU). The tripwire counts over all
    K+1 verify positions (every one of them can become an emitted token):
    ``((n_acc, preds, nonfinite), kv)``.

    KV safety is the decode-chunk argument (engine module docstring): rows
    written for rejected drafts sit at positions > the committed point,
    invisible to the causal mask, and the next dispatch's K+1 writes start
    exactly where the stale region starts. No reference analogue: the
    reference decodes strictly one token per step (dllama.cpp:88-99)."""
    logits, kv = _guarded_forward(params, cfg, tokens, start_pos, kv, poison,
                                  fwd)
    logits = _poison_logits(logits, poison)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
    ok = (tokens[:, 1:] == preds[:, :-1]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(ok, axis=-1), axis=-1)
    return (n_acc, preds, _nonfinite_rows(logits)), kv


def ragged_verify_step_guarded(params: Params, cfg: ModelConfig,
                               tokens: jax.Array, pos_vec: jax.Array,
                               kv: KVCache, temps: jax.Array,
                               topps: jax.Array, coins: jax.Array,
                               poison: jax.Array, fwd=None):
    """Batched-serving twin of :func:`verify_step_guarded`: one verify
    dispatch over ragged rows ``tokens [B, K+1]`` at per-row positions
    ``pos_vec [B]``. Greedy rows (temp <= 0) accept the longest draft prefix
    exactly as the single-sequence path does; sampled rows consume their one
    coin on the position-0 logits and accept nothing: their token/coin
    streams are bit-identical to the plain ragged step, so per-request
    determinism (the serving invariant) survives speculation joining the
    batch. Returns ``((n_acc, preds, nonfinite), kv)`` with per-row counts,
    so batched serving fails only the poisoned slot."""
    from ..ops.sampling import sampled_token

    logits, kv = _guarded_forward(params, cfg, tokens, pos_vec, kv, poison,
                                  fwd)
    logits = _poison_logits(logits, poison)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]
    ok = (tokens[:, 1:] == preds[:, :-1]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(ok, axis=-1), axis=-1)
    greedy_row = jnp.asarray(temps) <= 0.0
    n_acc = jnp.where(greedy_row, n_acc, 0)
    first = sampled_token(logits[:, 0], temps, topps, coins)
    preds = preds.at[:, 0].set(first)  # greedy rows: first == argmax already
    return (n_acc, preds, _nonfinite_rows(logits)), kv


# ---------------------------------------------------------------------------
# Paged program family — block-table KV (runtime/kvblocks.py)
# ---------------------------------------------------------------------------
#
# The paged twins of the ragged serving programs: KV lives in a block pool
# [L, n_blocks, n_kv, block_size, hd] and every row of the batch addresses
# its context through a block table. Shapes are static per pool geometry
# (n_blocks, block_size, batch width, table width), so the whole family
# jits once per geometry and the compile ledger stays quiet across
# admissions/retirements — the continuous-batching requirement.


@_exact_f32_dots
def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, pkv, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """Full forward over the paged pool: ``tokens [B, T]`` at per-row
    ``pos_vec [B]`` with block ``tables [B, max_blocks]``. Returns float32
    logits ``[B, T, vocab]`` and the updated pool (a
    :class:`~dllama_tpu.runtime.kvblocks.PagedKVCache`). The program owns
    ONE pool: ``pkv.k / pkv.v [L, n_blocks, n_kv, bs, hd]`` ride the layer
    scan's carry whole, layer ``l`` writes its ``B x T`` rows in place and
    attends through the whole pool and ``l``; no layer's slice is cut out,
    nothing is stacked back, and with the pool donated (the serving
    wrappers do) what comes back is the caller's buffer. Always ragged —
    the paged path exists for continuous batching only. ``write_lens``
    (speculative verify: per-row valid input width minus one, i.e. the
    row's draft length) masks KV writes for lanes past it to the null
    block — see :func:`_paged_layer_step`."""
    return family_of(cfg).paged_forward(params, cfg, tokens, pos_vec, pkv,
                                        tables, write_lens)


def _dense_paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                         pos_vec: jax.Array, pkv, tables: jax.Array,
                         write_lens: jax.Array | None = None):
    """:func:`paged_forward` of the dense decoders: one scan over the layer
    index, the pool's two planes whole in its carry."""
    from ..runtime.kvblocks import PagedKVCache

    if _numerics.taps_active():
        raise ValueError("numerics taps are unsupported on the paged KV "
                         "path (use the dense slot pool for tap sessions)")
    plan = _current_plan()
    if plan is not None and plan.axis_size("pp") > 1:
        raise ValueError("paged KV is unsupported under pipeline "
                         "parallelism (pp > 1)")
    pos_vec = jnp.asarray(pos_vec, dtype=jnp.int32)
    B, T = tokens.shape
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    x = constrain(x, "batch", None, None)

    cos, sin = build_rope_cache(cfg)
    arange = jnp.arange(T, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(pos_vec[:, None] + arange, (B, T))

    by_index = _scan_by_index(cfg, B * T)

    def body(carry, xs):
        x, k_pool, v_pool = carry
        l, lp = xs
        if by_index:
            lp = _layer_at(params.layers, l)
        elif cfg.offload:
            lp = jax.device_put(lp, jax.memory.Space.Device)
        return _paged_layer_step(cfg, x, lp, k_pool, v_pool, l, cos, sin,
                                 positions, tables, write_lens), None

    # the pool in the carry, not as xs/ys: there every layer's slice was
    # copied out and back and the stacked output was a second pool, half of
    # a decode step's device time (PERF.md section 6, PR 33)
    xs = (_layer_indices(cfg), None if by_index else params.layers)
    (x, new_k, new_v), _ = jax.lax.scan(body, (x, pkv.k, pkv.v), xs)
    logits = constrain(_head(params, cfg, x), "batch", None, "vocab")
    return logits, PagedKVCache(k=new_k, v=new_v)


def paged_sampled_step_guarded(params: Params, cfg: ModelConfig,
                               tokens: jax.Array, pos_vec: jax.Array,
                               pkv, tables: jax.Array, temps: jax.Array,
                               topps: jax.Array, coins: jax.Array,
                               poison: jax.Array):
    """The paged ragged decode step, the block-table twin of
    :func:`sampled_step_guarded`: one dispatch samples every row
    (temp <= 0 rows take argmax), ``nonfinite [B]`` is per row so a
    poisoned request fails without touching the rest of the batch.
    Returns ``((token, nonfinite), pkv)``."""
    from ..ops.sampling import sampled_token
    from ..parallel.qcollectives import wire_poison_scope

    with wire_poison_scope(poison):
        logits, pkv = paged_forward(params, cfg, tokens, pos_vec, pkv, tables)
    last = _poison_logits(logits[:, -1, :], poison)
    return (sampled_token(last, temps, topps, coins),
            _nonfinite_rows(last)), pkv


# ---------------------------------------------------------------------------
# A tick that carries a chunk: the joined rows, the attend split, the epilogue
# ---------------------------------------------------------------------------
#
# What every family's ``forward_and_step`` (``Family.tick``) shares, said
# once: how a prefill chunk ``[1, T]`` and the tick's decode rows ``[R, 1]``
# lie joined ``[1, T + R]``, which rows are dead, how ONE layer's attention
# tells the two apart, and what is done to the decode rows behind the last
# layer. A family's tick program is its mixer's closure over these, its
# carry's pairs and its return tuple. Each family calls them where its own
# lines stood (an embedding row gathered between the tokens' join and the
# positions', a padding mask in front of the dead-row rule), so every program
# lowers to the text it lowered to (tests/goldens/program_hlo_sha256.json).


def _join_tokens(chunk: jax.Array, tokens: jax.Array) -> jax.Array:
    """The tick's token ids as one row: the chunk's ``[1, T]`` and then the
    decode rows' ``[R, 1]``, one a slot: ``[T + R]``."""
    return jnp.concatenate([chunk[0], tokens[:, 0]])


def _join_positions(chunk_pos: jax.Array, pos_vec: jax.Array, T: int):
    """Where the joined rows stand: ``(cpos [1, T], rpos [R, 1], positions
    [1, T + R])``, the chunk's from ``chunk_pos`` on, then each decode row's
    own; ``cpos`` and ``rpos`` are what the two attention forms take."""
    cpos = (chunk_pos + jnp.arange(T, dtype=jnp.int32))[None, :]
    rpos = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    return cpos, rpos, jnp.concatenate([cpos, rpos.T], axis=1)


def _live_rows(tables: jax.Array) -> jax.Array:
    """The dead-row rule: ``[R] bool``, False for a row whose block table
    ``tables [R, M]`` starts with the null block: an inactive slot of a step
    or a tick, whose writes land in the null block and whose state is the
    pool's null row."""
    return tables[:, 0] != 0


def _state_rows(live: jax.Array) -> jax.Array:
    """Row ``b``'s row of the state pool: slot ``b``'s, ``b + 1``, or
    ``StatePool.NULL`` while the row is dead (:func:`_live_rows`)."""
    from ..runtime.kvblocks import StatePool

    return jnp.where(live, jnp.arange(1, live.shape[0] + 1, dtype=jnp.int32),
                     StatePool.NULL)


def _by_row(a: jax.Array, T: int) -> jax.Array:
    """The decode rows behind a chunk's ``T``, one a batch row: ``[1, T + R,
    ...] -> [R, 1, ...]``."""
    return jnp.swapaxes(a[:, T:], 0, 1)


def _join(c: jax.Array, r: jax.Array) -> jax.Array:
    """:func:`_by_row` undone: ``[1, T, ...]`` and ``[R, 1, ...]`` as ``[1,
    T + R, ...]``."""
    return jnp.concatenate([c, jnp.swapaxes(r, 0, 1)], axis=1)


def _attend_split(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
                  T: int, column, k_pool: jax.Array, v_pool: jax.Array, l,
                  chunk_pos: jax.Array, cpos: jax.Array, rpos: jax.Array,
                  tables: jax.Array):
    """ONE layer's attention over the joined rows ``q, k, v [1, T + R, ..]``:
    the chunk's ``T`` rows append to the column's layer and attend over it
    (:func:`_attend_dense`), the decode rows, one a batch row, write into
    layer ``l`` of the pools in place and attend through their ``tables``
    (:func:`_attend_paged`). Returns ``(att [1, T + R, ..], k_l, v_l, k_pool,
    v_pool)``. ``column() -> (k_l, v_l)`` fetches the column's layer: a call,
    so that a family whose column rides its scan's carry slices it out BEHIND
    the chunk's rows, where its own lines did; ``l`` is whatever indexes the
    pools (a layer, an attention ordinal, a period)."""
    att_c, k_l, v_l = _attend_dense(cfg, q[:, :T], k[:, :T], v[:, :T],
                                    *column(), chunk_pos, cpos)
    att_r, k_pool, v_pool = _attend_paged(
        cfg, _by_row(q, T), _by_row(k, T), _by_row(v, T), k_pool, v_pool, l,
        rpos, tables)
    return _join(att_c, att_r), k_l, v_l, k_pool, v_pool


def _pick_rows(head, params: Params, cfg: ModelConfig, x: jax.Array, T: int,
               poison: jax.Array):
    """A tick program's epilogue over the hidden rows ``x [1, T + R, dim]``
    behind the last layer: ``head(params, cfg, rows) -> logits`` (the
    family's) for the ``R`` decode rows ALONE (the serving prefill throws a
    chunk's logits away), then the poison, the argmax and the non-finite
    count, as :func:`paged_sampled_step_guarded` has them. Returns ``(greedy
    [R], nonfinite [R], last [R, V])``: each row's ARGMAX, which is what the
    step's sampler gives a batch in which no row samples, and the rows'
    float32 logits, poisoned as the step's are, for
    ``ops.sampling.sampled_token`` where a row does."""
    last = _poison_logits(head(params, cfg, _by_row(x, T))[:, -1, :], poison)
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return greedy, _nonfinite_rows(last), last


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Float32 logits of the hidden rows ``x``: the final norm, the cast in
    front of the logits matmul where the sync buffers are Q80
    (llm.cpp:445-486), the head."""
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    if cfg.sync_q80:
        x = fake_quant_q80(x)
    return linear(x, params.logits, out_axis="vocab").astype(jnp.float32)


@_exact_f32_dots
def forward_and_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     pos_vec: jax.Array, cache, tables: jax.Array,
                     chunk: jax.Array, chunk_pos: jax.Array,
                     poison: jax.Array):
    """A tick that carries a prefill chunk, as ONE program: :func:`forward`
    over ``chunk [1, T]`` at ``chunk_pos`` into an admission's column AND
    :func:`paged_sampled_step_guarded`'s layers and head over the tick's
    decode rows (``tokens [R, 1]`` at ``pos_vec`` through ``tables``), so
    that every layer's seven planes are read once for both. ``cache`` is
    ``(column, pool)``, both given back (and both donated where the server
    jits this).

    One layer scan carries the joined activations ``[1, T + R, D]`` and the
    whole pool; the column's layers ride as the scan's xs/ys, as
    :func:`forward` has them. Everything a layer does a row at a time (the
    norms, rope, the seven matmuls, the residuals) runs once over the
    ``T + R`` rows, which to ``ops.quant_matmul`` is a chunk: one fetch and
    one dequant of each stripe. Only attention tells the rows apart
    (:func:`_attend_split`); a row with an all-null table is dead, as an
    inactive slot of a step is, and every row may be.

    After the scan :func:`_pick_rows`. Returns ``((token, nonfinite,
    logits), (column, pool))``. The sampler is not in here because it is 7.3
    of the step's 12.8 MB of executable (its two vocabulary-wide sorts),
    this program exists once a prefill bucket, and a start pays for every
    byte it loads (PERF.md section 6, PR 47). The dense decoders' program,
    with no mesh plan: another family brings a tick program of its own
    (``models/falcon_h1.py``, ...) or keeps its ``forward`` /
    ``paged_forward`` pair (``Family.tick`` None)."""
    from ..runtime.kvblocks import PagedKVCache

    if cfg.paged_only or _current_plan() is not None:
        raise ValueError("forward_and_step is the dense decoders' program "
                         "on one device")
    col, pkv = cache
    chunk_pos = jnp.asarray(chunk_pos, dtype=jnp.int32)
    T, R = chunk.shape[1], tokens.shape[0]
    x = params.embedding[_join_tokens(chunk, tokens)].astype(
        cfg.compute_dtype)[None]
    cos, sin = build_rope_cache(cfg)
    cpos, rpos, positions = _join_positions(chunk_pos, pos_vec, T)
    fq = fake_quant_q80 if cfg.sync_q80 else (lambda a: a)
    by_index = _scan_by_index(cfg, T + R)

    def body(carry, xs):
        x, k_pool, v_pool = carry
        l, lp, k_l, v_l = xs
        if by_index:
            lp = _layer_at(params.layers, l)
        elif cfg.offload:
            lp = jax.device_put(lp, jax.memory.Space.Device)
        q, k, v = _attn_qkv(cfg, x, lp, cos, sin, positions, fq)
        att, k_l, v_l, k_pool, v_pool = _attend_split(
            cfg, q, k, v, T, lambda: (k_l, v_l), k_pool, v_pool, l,
            chunk_pos, cpos, rpos, tables)
        x, _ = _attn_out_and_ffn(cfg, x, att, lp, fq, taps=False)
        return (x, k_pool, v_pool), (k_l, v_l)

    xs = (_layer_indices(cfg), None if by_index else params.layers,
          col.k, col.v)
    (x, pool_k, pool_v), (col_k, col_v) = jax.lax.scan(
        body, (x, pkv.k, pkv.v), xs)
    return (_pick_rows(_head, params, cfg, x, T, poison),
            (KVCache(k=col_k, v=col_v), PagedKVCache(k=pool_k, v=pool_v)))


def paged_verify_step_guarded(params: Params, cfg: ModelConfig,
                              tokens: jax.Array, pos_vec: jax.Array,
                              pkv, tables: jax.Array, lens: jax.Array,
                              temps: jax.Array, topps: jax.Array,
                              acoins: jax.Array, fcoins: jax.Array,
                              poison: jax.Array):
    """The paged speculative verify step: the block-table twin of
    :func:`ragged_verify_step_guarded`, widened to speculative *sampling*.

    One forward over ``tokens [B, K+1]`` (each row: its committed next
    token followed by its proposer's drafts, padded past the row's
    ``lens [B]`` draft length) at per-row ``pos_vec``, KV scattered
    through the block ``tables`` with writes masked past ``lens``
    (:func:`paged_forward` ``write_lens``: the host only allocates
    blocks covering ``pos..pos+lens``). The logits epilogue is
    :func:`runtime.speculative.spec_decide`: greedy rows accept the
    longest model-matching draft prefix exactly as the dense path does;
    sampled rows run rejection-sampling acceptance with the residual
    resample / ``sampled_token`` bonus, so their emitted distribution is
    exactly the non-speculative sampling distribution. The tripwire counts
    over all K+1 verify positions (every one can become an emitted token).
    Returns ``((n_acc [B], out [B, K+1], nonfinite [B]), pkv)``, the counts
    per row so that batched serving fails only the poisoned slot; the caller
    emits ``out[b, : n_acc[b] + 1]``.

    KV safety is the verify-step argument one level up: every write
    lands at/above the row's committed ``pos`` in refcount-1 blocks the
    slot owns (shared prefix blocks are never a write target:
    ``__debug__``-asserted by the generator), so rejected lanes need no
    device rollback: the table/pos bookkeeping alone rolls them back,
    and the next dispatch's writes start exactly where the stale region
    starts. Jitted once per pool geometry (``K+1``, table width, batch
    width are static; ``lens``/coins/knobs traced), so varying per-slot
    draft lengths and admit/retire churn never retrace."""
    from ..parallel.qcollectives import wire_poison_scope
    from ..runtime.speculative import spec_decide

    with wire_poison_scope(poison):
        logits, pkv = paged_forward(params, cfg, tokens, pos_vec, pkv,
                                    tables, write_lens=lens)
    logits = _poison_logits(logits, poison)
    n_acc, out = spec_decide(logits, tokens, lens, temps, topps,
                             acoins, fcoins)
    return (n_acc, out, _nonfinite_rows(logits)), pkv


def gather_kv_blocks(pkv, ids: jax.Array):
    """Device side of a KV-tier SPILL (runtime/kvblocks.HostKVMirror):
    gather ``len(ids)`` physical blocks out of the pool as one contiguous
    chunk ``(k, v)`` each ``[L, K, n_kv, bs, hd]`` — ONE batched read per
    spill, then a single ``device_put`` moves the chunk to pinned host
    memory. ``ids`` is traced (fixed K = kvblocks.SPILL_BATCH, short
    batches padded with the null block), so tier pressure never retraces.
    Plan-independent data movement — jitted raw at the call site, same
    argument as PagedGenerator's take/put/copy programs."""
    return pkv.k[:, ids], pkv.v[:, ids]


def scatter_kv_blocks(pkv, chunk_k: jax.Array, chunk_v: jax.Array,
                      ids: jax.Array):
    """Device side of a KV-tier PAGE-IN: scatter a host chunk (moved back
    device-side by ``device_put``) into the pool at physical blocks
    ``ids``. Lanes the page-in does not want target the null block (id 0)
    — its contents are value-invisible garbage by the pool's contract, so
    a partial chunk restore is the same one program. Returns the updated
    pool (donated at the jit wrapper)."""
    from ..runtime.kvblocks import PagedKVCache

    return PagedKVCache(k=pkv.k.at[:, ids].set(chunk_k.astype(pkv.k.dtype)),
                        v=pkv.v.at[:, ids].set(chunk_v.astype(pkv.v.dtype)))


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


def _stack_weights(ws: list[Any]) -> Any:
    if isinstance(ws[0], QuantizedWeight):
        return QuantizedWeight(
            scales=jnp.stack([w.scales for w in ws]),
            codes=jnp.stack([w.codes for w in ws]),
        )
    return jnp.stack(ws)


def load_params_from_mfile(mf: ModelFile, cfg: ModelConfig,
                           weight_mode: str = "auto", plan=None) -> Params:
    """Build device params from a .m file via the streaming loader.

    ``weight_mode``: ``"auto"`` keeps Q40 files quantized on device (planes),
    ``"f32"``/``"bf16"`` dequantize to dense. With ``plan`` the params come
    back fully sharded — each device shard's bytes are read directly from the
    mmap (runtime.weights), replacing the reference's root-to-worker weight
    streaming (NnRootWeightLoader, SURVEY.md §2 #12) with bounded host memory.
    """
    from ..runtime.weights import load_params

    return load_params(mf, cfg, weight_mode, plan)


def init_random_params(cfg: ModelConfig, seed: int = 0, scale: float = 0.02,
                       quantized: bool = False, dtype=jnp.float32) -> Params:
    """Random params for tests/benchmarks (shape-identical to a loaded model)."""
    rng = np.random.default_rng(seed)

    def rand(*shape):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    def mk(out, in_) -> Weight:
        w = rand(cfg.n_layers, out, in_)
        if quantized:
            return _stack_weights([quantize_weight_q40(w[l]) for l in range(cfg.n_layers)])
        return jnp.asarray(w, dtype=dtype)

    def mk_experts(out, in_) -> Weight:
        if quantized:
            w = rand(cfg.n_layers, cfg.n_experts, out, in_)
            return _stack_weights([
                _stack_weights([quantize_weight_q40(w[l, e])
                                for e in range(cfg.n_experts)])
                for l in range(cfg.n_layers)])
        # dense experts store IN-major (ragged_dot rhs layout)
        return jnp.asarray(rand(cfg.n_layers, cfg.n_experts, in_, out),
                           dtype=cfg.compute_dtype)

    qwen3 = cfg.arch == ArchType.QWEN3
    moe = cfg.is_moe
    layers = LayerParams(
        wq=mk(cfg.q_dim, cfg.dim),
        wk=mk(cfg.kv_dim, cfg.dim),
        wv=mk(cfg.kv_dim, cfg.dim),
        wo=mk(cfg.dim, cfg.q_dim),
        w1=None if moe else mk(cfg.hidden_dim, cfg.dim),
        w2=None if moe else mk(cfg.dim, cfg.hidden_dim),
        w3=None if moe else mk(cfg.hidden_dim, cfg.dim),
        norm_att=jnp.asarray(1.0 + rand(cfg.n_layers, cfg.dim)),
        norm_ffn=jnp.asarray(1.0 + rand(cfg.n_layers, cfg.dim)),
        norm_q=jnp.asarray(1.0 + rand(cfg.n_layers, cfg.head_dim)) if qwen3 else None,
        norm_k=jnp.asarray(1.0 + rand(cfg.n_layers, cfg.head_dim)) if qwen3 else None,
        moe_gate=(jnp.asarray(rand(cfg.n_layers, cfg.n_experts, cfg.dim))
                  if moe else None),
        # in-major expert layout (see LayerParams); quantized=True mirrors
        # the loader's Q40 expert planes ([L, E]-stacked QuantizedWeight)
        we1=mk_experts(cfg.hidden_dim, cfg.dim) if moe else None,
        we2=mk_experts(cfg.dim, cfg.hidden_dim) if moe else None,
        we3=mk_experts(cfg.hidden_dim, cfg.dim) if moe else None,
    )
    logits = rand(cfg.vocab_size, cfg.dim)
    return Params(
        embedding=jnp.asarray(rand(cfg.vocab_size, cfg.dim)),
        layers=layers,
        final_norm=jnp.asarray(1.0 + rand(cfg.dim)),
        logits=(quantize_weight_q40(logits) if quantized
                else jnp.asarray(logits, dtype=dtype)),
    )


# ---------------------------------------------------------------------------
# The dense decoders as a family (models/family.py)
# ---------------------------------------------------------------------------


def _load_params(ld, cfg: ModelConfig) -> Params:
    """The dense decoders' one stack from the tensors ``mfile``'s walk
    names, through the streaming loader (runtime/weights.py)."""
    h = ld.h
    moe = h.n_experts > 0
    qk_norm = cfg.uses_qk_norm
    # Under offload only the per-layer stacks go host-side: they are the
    # O(model) bytes and stream through the scan; embedding / final norm /
    # logits are used outside it and stay resident in device memory.
    ld.host_scope = True
    layers = LayerParams(
        wq=ld.matmul("block_matmul_q", h.q_dim, h.dim, stacked=True,
                     out_axis="heads", in_axis=None),
        wk=ld.matmul("block_matmul_k", h.kv_dim, h.dim, stacked=True,
                     out_axis="kv_heads", in_axis=None),
        wv=ld.matmul("block_matmul_v", h.kv_dim, h.dim, stacked=True,
                     out_axis="kv_heads", in_axis=None),
        wo=ld.matmul("block_matmul_wo", h.dim, h.q_dim, stacked=True,
                     out_axis=None, in_axis="heads"),
        w1=None if moe else ld.matmul("block_matmul_w1", h.hidden_dim, h.dim,
                                      stacked=True, out_axis="hidden", in_axis=None),
        w2=None if moe else ld.matmul("block_matmul_w2", h.dim, h.hidden_dim,
                                      stacked=True, out_axis=None, in_axis="hidden"),
        w3=None if moe else ld.matmul("block_matmul_w3", h.hidden_dim, h.dim,
                                      stacked=True, out_axis="hidden", in_axis=None),
        norm_att=ld.stacked_f32("block_norm_0", h.dim),
        norm_ffn=ld.stacked_f32("block_norm_1", h.dim),
        norm_q=ld.stacked_f32("block_norm_q", h.head_dim) if qk_norm else None,
        norm_k=ld.stacked_f32("block_norm_k", h.head_dim) if qk_norm else None,
        moe_gate=ld.stacked_f32("block_moe_gate", h.n_experts, h.dim) if moe else None,
        we1=(ld.expert_stack("block_expert_w1", h.hidden_dim, h.dim,
                             "hidden", None) if moe else None),
        we2=(ld.expert_stack("block_expert_w2", h.dim, h.hidden_dim,
                             None, "hidden") if moe else None),
        we3=(ld.expert_stack("block_expert_w3", h.hidden_dim, h.dim,
                             "hidden", None) if moe else None),
    )
    ld.host_scope = False
    return ld.params(layers)


def _matmul_weight_count(cfg: ModelConfig) -> int:
    per_layer = (cfg.dim * cfg.q_dim + 2 * cfg.dim * cfg.kv_dim
                 + cfg.q_dim * cfg.dim)
    if cfg.is_moe:
        per_layer += (3 * cfg.dim * cfg.hidden_dim * cfg.n_experts
                      + cfg.dim * cfg.n_experts)
    else:
        per_layer += 3 * cfg.dim * cfg.hidden_dim
    return cfg.n_layers * per_layer + cfg.dim * cfg.vocab_size  # + lm head


FAMILY = Family(
    forward=_dense_forward,
    paged_forward=_dense_paged_forward,
    tick=forward_and_step,
    column=lambda cfg, k, v: KVCache(k=k, v=v),
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(full=cfg.n_layers),
    describe=lambda cfg, engine: "",
    refusal=None)

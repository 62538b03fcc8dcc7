"""Parameter sharding plans — the SPMD analogue of the reference's slicers.

Maps every model parameter to a NamedSharding under a :class:`MeshPlan`:

* row-split matmuls (wq/wk/wv/w1/w3/logits — reference sliceRowMatmul,
  nn-core.cpp:207-217): shard the OUTPUT dim over ``tp``;
* col-split matmuls (wo/w2 — reference sliceColMatmul, nn-core.cpp:219-230):
  shard the INPUT dim over ``tp``; their partial-sum outputs are what XLA
  all-reduces (the reference's SYNC_NODE_SLICES + OP_MERGE_ADD pair);
* norms and the embedding stay replicated (the embedding broadcast is the
  reference's SYNC_WITH_ROOT, free under replication);
* KV cache shards over kv-heads like sliceKvCache (nn-core.cpp:198-205).

The reference's divisibility constraints (asserts in the slicers; README's
2^n nodes ≤ nKvHeads rule) become :func:`validate_tp` here — with the
extension that ``n_heads % tp == 0`` may hold while ``n_kv_heads < tp``
requires KV replication, a capability the reference lacks (SURVEY.md §7.4).
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax

from ..ops.linear import QuantizedWeight
from .api import MeshPlan

if TYPE_CHECKING:  # imported lazily at runtime (models imports parallel.api)
    from ..models.config import ModelConfig
    from ..models.llama import Params
    from ..runtime.kvcache import KVCache


def _weight_sharding(plan: MeshPlan, w, out_axis: str | None, in_axis: str | None,
                     stacked: bool):
    """Sharding for one matmul weight: dense ``[L?, out, in]`` or K-major Q40
    planes ``[L?, in, out]`` / ``[L?, in/32, out]``. The stacked layer axis
    maps to the ``pp`` pipeline axis when the mesh has one."""
    lead = ("layers",) if stacked else ()
    if isinstance(w, QuantizedWeight):
        return QuantizedWeight(
            scales=plan.sharding_for(tuple(w.scales.shape), *lead, in_axis, out_axis),
            codes=plan.sharding_for(tuple(w.codes.shape), *lead, in_axis, out_axis),
        )
    return plan.sharding_for(tuple(w.shape), *lead, out_axis, in_axis)


def map_expert_weight(we, in_axis, out_axis, f):
    """Rebuild an expert-stack weight by applying ``f(leaf, plane_axes)`` to
    each leaf, where ``plane_axes`` are the logical axis names of the leaf's
    PLANE dims (the leading ``[L?, E]`` axes are the caller's concern).

    THE single statement of per-repr expert plane layout — quantized scale
    planes shard like their codes (the K/32 block axis follows the in
    axis) — consumed by both the NamedSharding
    builder below and the shard_map in_specs in models.llama, so the two
    can't drift apart."""
    if isinstance(we, QuantizedWeight):
        return QuantizedWeight(scales=f(we.scales, (in_axis, out_axis)),
                               codes=f(we.codes, (in_axis, out_axis)))
    return f(we, (in_axis, out_axis))


def _expert_sharding(plan: MeshPlan, we, in_axis, out_axis):
    """Shardings for one [L, E, in, out] expert-stack weight, any repr."""
    return map_expert_weight(
        we, in_axis, out_axis,
        lambda leaf, axes: plan.sharding_for(
            tuple(leaf.shape), "layers", "experts", *axes))


def param_shardings(plan: MeshPlan, params: "Params") -> "Params":
    """A Params-shaped tree of NamedShardings."""
    from ..models.llama import LayerParams, Params

    lp = params.layers
    layers = LayerParams(
        wq=_weight_sharding(plan, lp.wq, "heads", None, True),
        wk=_weight_sharding(plan, lp.wk, "kv_heads", None, True),
        wv=_weight_sharding(plan, lp.wv, "kv_heads", None, True),
        wo=_weight_sharding(plan, lp.wo, None, "heads", True),
        w1=None if lp.w1 is None else _weight_sharding(plan, lp.w1, "hidden", None, True),
        w2=None if lp.w2 is None else _weight_sharding(plan, lp.w2, None, "hidden", True),
        w3=None if lp.w3 is None else _weight_sharding(plan, lp.w3, "hidden", None, True),
        norm_att=plan.sharding_for(tuple(lp.norm_att.shape), "layers", None),
        norm_ffn=plan.sharding_for(tuple(lp.norm_ffn.shape), "layers", None),
        norm_q=None if lp.norm_q is None else plan.sharding_for(
            tuple(lp.norm_q.shape), "layers", None),
        norm_k=None if lp.norm_k is None else plan.sharding_for(
            tuple(lp.norm_k.shape), "layers", None),
        # MoE: experts over ep, expert-hidden over tp (new capability; the
        # reference has no runtime MoE, SURVEY.md §2.2). Expert weights are
        # in-major (ragged_dot layout, see LayerParams): we1/we3 [L,E,D,H],
        # we2 [L,E,H,D] — any Weight repr (dense / quantized).
        moe_gate=None if lp.moe_gate is None else plan.sharding_for(
            tuple(lp.moe_gate.shape), "layers", "experts", None),
        we1=None if lp.we1 is None else _expert_sharding(
            plan, lp.we1, None, "hidden"),
        we2=None if lp.we2 is None else _expert_sharding(
            plan, lp.we2, "hidden", None),
        we3=None if lp.we3 is None else _expert_sharding(
            plan, lp.we3, None, "hidden"),
    )
    return Params(
        embedding=plan.sharding(None, None),
        layers=layers,
        final_norm=plan.sharding(None),
        logits=_weight_sharding(plan, params.logits, "vocab", None, False),
    )


def kv_cache_sharding(plan: MeshPlan, kv: "KVCache") -> "KVCache":
    """[L, B, n_kv, S, hd] — kv-heads over tp, batch over dp, and the seq dim
    over sp when the mesh has one (the ring-attention path in parallel/ring.py
    consumes the seq-sharded layout; on tp/dp-only meshes "seq" resolves to
    nothing and stays replicated).

    When tp > n_kv_heads the kv-head dim is replicated (KV replication
    groups; the reference instead caps nodes at nKvHeads)."""
    from ..runtime.kvcache import KVCache

    s = plan.sharding_for(tuple(kv.k.shape), "layers", "batch", "kv_heads", "seq", None)
    return KVCache(k=s, v=s)


def paged_kv_sharding(plan: MeshPlan, pkv):
    """Paged block pool ``[L, n_blocks, n_kv, block_size, hd]`` — kv-heads
    over tp like the dense cache; the block and row axes stay replicated
    (block-table gathers index the unsharded block axis)."""
    from ..runtime.kvblocks import PagedKVCache

    s = plan.sharding_for(tuple(pkv.k.shape), "layers", None, "kv_heads",
                          None, None)
    return PagedKVCache(k=s, v=s)


def shard_params(plan: MeshPlan, params: "Params") -> "Params":
    """Place params on the mesh with the TP shardings."""
    shardings = param_shardings(plan, params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s) if s is not None else x,
        params, shardings,
        is_leaf=lambda x: x is None,
    )


def validate_tp(cfg: "ModelConfig", tp: int) -> None:
    """TP divisibility rules (reference: asserts nn-core.cpp:200-221 and the
    n_nodes ≤ n_kv_heads cap, app.cpp:232-234)."""
    if cfg.n_heads % tp != 0:
        raise ValueError(f"n_heads {cfg.n_heads} not divisible by tp={tp}")
    if cfg.hidden_dim % tp != 0:
        raise ValueError(f"hidden_dim {cfg.hidden_dim} not divisible by tp={tp}")
    if cfg.vocab_size % tp != 0:
        raise ValueError(f"vocab_size {cfg.vocab_size} not divisible by tp={tp}")
    if cfg.n_kv_heads % tp != 0 and tp % cfg.n_kv_heads != 0:
        raise ValueError(
            f"tp={tp} incompatible with n_kv_heads={cfg.n_kv_heads}: needs "
            f"either n_kv_heads % tp == 0 or tp % n_kv_heads == 0 (replication)")


def validate_ep(cfg: "ModelConfig", ep: int) -> None:
    """Expert-parallel divisibility (new capability; no reference analogue)."""
    if not cfg.is_moe:
        raise ValueError("ep axis requires an MoE model (n_experts > 0)")
    if cfg.n_experts % ep != 0:
        raise ValueError(f"n_experts {cfg.n_experts} not divisible by ep={ep}")

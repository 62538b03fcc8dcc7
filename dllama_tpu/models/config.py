"""Model configuration — the runtime view of a .m header.

Carries everything the graph builder needs (reference: LlmHeader,
src/llm.hpp:42-71) plus TPU-side execution choices (compute dtype, weight
layout) that have no reference equivalent.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

from ..formats.mfile import ArchType, HiddenAct, ModelHeader, RopeType


@dataclass(frozen=True)
class ModelConfig:
    arch: ArchType
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    seq_len: int
    norm_epsilon: float
    rope_theta: float
    rope_type: RopeType
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    n_experts: int = 0
    n_active_experts: int = 0
    # Renormalize the selected top-k router weights to sum to 1 (HF
    # norm_topk_prob; Mixtral semantics and Qwen3-MoE with norm_topk_prob
    # true). False keeps the raw softmax probabilities (sum < 1). Note:
    # softmax-then-topk-renorm and topk-then-softmax are the same function —
    # only the renorm-vs-raw choice changes behavior.
    moe_norm_topk: bool = True
    # Hybrid decoders (ArchType.OLMO_HYBRID, models/hybrid.py): the layer
    # pattern as its period (0 = one homogeneous stack; P = each period is
    # P-1 gated delta-rule layers, then one full softmax-attention layer),
    # and the mixer's sizes. The arch implies the rest, as QWEN3 implies its
    # per-head q/k norm: block norms on a sublayer's OUTPUT (x + norm(f(x))),
    # a q/k norm over the whole projection before the heads are split, no
    # rotary embedding (rope_type and rope_theta are not read).
    layer_period: int = 0
    lin_heads: int = 0
    lin_key_dim: int = 0
    lin_value_dim: int = 0
    lin_conv_kernel: int = 0
    lin_neg_eigval: bool = False

    # TPU execution choices (no reference equivalent):
    compute_dtype: str = "float32"  # "float32" for parity, "bfloat16" for speed
    # attention implementation: "auto" = Pallas flash kernel on TPU when the
    # shapes fit (single-device graph), XLA oracle otherwise; "xla"/"flash"
    # force one. The TP/SP paths pick their own kernels inside shard_map.
    attn_impl: str = "auto"
    # Q80 activation-sync parity: reproduce the reference's Q80 cast points
    # in-graph (llm.cpp:258-265 casts; wire pipes SURVEY.md §2 #10) via
    # fake-quantization. Costs throughput; off for pure-TPU serving.
    sync_q80: bool = False
    # MoE compute: "sparse" = sort-by-expert + lax.ragged_dot grouped matmul
    # (O(k) experts per token); "dense" = all-experts einsum, gate-weighted
    # (O(E), exact and simple — the test oracle); "auto" = sparse.
    moe_impl: str = "auto"
    # Host-DRAM weight offload (70B/405B, BASELINE config 5): per-layer
    # weights live in pinned host memory and stream to device memory inside
    # the scan (layer ℓ+1's transfer overlaps layer ℓ's compute under XLA's
    # latency-hiding scheduler). Set via --weight-mode offload; the loader
    # places the layer stack host-side to match. No reference equivalent —
    # the reference keeps shards resident (SURVEY.md §7.4).
    offload: bool = False
    # Compute/communication overlap for the two per-layer tp partial merges
    # (wo and w2 — the reference's SYNC steps): > 0 splits each merge's
    # model-dim into this many chunks reduced by independent ppermute ring
    # chains (parallel/qcollectives.overlapped_wire_psum) so chunk i's hops
    # overlap chunk i+1's compute under XLA's latency-hiding scheduler
    # (TokenWeave shape, PAPERS.md). 0 keeps the monolithic GSPMD psum.
    # Resolved by the engine from --comm-overlap {off,auto,N}; static trace
    # config, so it is part of the multihost cluster fingerprint.
    comm_overlap: int = 0

    @property
    def q_dim(self) -> int:
        return self.head_dim * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_dim * self.n_kv_heads

    @property
    def kv_mul(self) -> int:
        """GQA group size (reference: multiheadAtt_F32 kvMul, nn-cpu-ops.cpp:756)."""
        return self.n_heads // self.n_kv_heads

    @property
    def uses_qk_norm(self) -> bool:
        """Qwen3 applies per-head RMS norm to q/k before rope (llm.cpp:285-309)."""
        return self.arch == ArchType.QWEN3

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_hybrid(self) -> bool:
        """Recurrent (linear-attention) layers beside the full ones: a
        slot's context is K/V blocks AND a state row (runtime/kvblocks)."""
        return self.layer_period > 0

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.layer_period if self.is_hybrid else 0

    @property
    def n_linear_layers(self) -> int:
        return self.n_periods * (self.layer_period - 1)

    @property
    def n_kv_layers(self) -> int:
        """Layers that hold a K/V cache: every one, or a hybrid's full ones."""
        return self.n_periods if self.is_hybrid else self.n_layers

    @property
    def lin_conv_dim(self) -> int:
        """Channels of the mixer's causal convolution: q~, k~, v~."""
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def lin_in_dim(self) -> int:
        """Width of the mixer's packed input projection: q~ k~ v~ z."""
        return self.lin_conv_dim + self.lin_heads * self.lin_value_dim

    @classmethod
    def from_header(cls, h: ModelHeader, compute_dtype: str = "float32") -> "ModelConfig":
        from ..formats.quants import Q80

        hybrid = {}
        if h.arch_type == ArchType.OLMO_HYBRID:
            if h.linear_n_key_heads != h.linear_n_value_heads:
                raise ValueError(
                    f"hybrid model: {h.linear_n_key_heads} key heads against "
                    f"{h.linear_n_value_heads} value heads; the mixer here "
                    f"pairs them one to one")
            hybrid = dict(
                layer_period=h.layer_period, lin_heads=h.linear_n_value_heads,
                lin_key_dim=h.linear_key_head_dim,
                lin_value_dim=h.linear_value_head_dim,
                lin_conv_kernel=h.linear_conv_kernel,
                lin_neg_eigval=bool(h.linear_neg_eigval))
        return cls(
            **hybrid,
            sync_q80=h.sync_type == Q80,
            arch=h.arch_type,
            dim=h.dim,
            hidden_dim=h.hidden_dim,
            n_layers=h.n_layers,
            n_heads=h.n_heads,
            n_kv_heads=h.n_kv_heads,
            head_dim=h.head_dim,
            vocab_size=h.vocab_size,
            seq_len=h.seq_len,
            norm_epsilon=h.norm_epsilon,
            rope_theta=h.rope_theta,
            rope_type=h.rope_type,
            rope_scaling_factor=h.rope_scaling_factor,
            rope_scaling_low_freq_factor=h.rope_scaling_low_freq_factor,
            rope_scaling_high_freq_factor=h.rope_scaling_high_freq_factor,
            rope_scaling_orig_max_seq_len=h.rope_scaling_orig_max_seq_len,
            hidden_act=h.hidden_act,
            n_experts=h.n_experts,
            n_active_experts=h.n_active_experts,
            moe_norm_topk=bool(h.moe_norm_topk),
            compute_dtype=compute_dtype,
        )

    def with_seq_len(self, seq_len: int) -> "ModelConfig":
        return replace(self, seq_len=seq_len)

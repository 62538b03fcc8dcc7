"""Model configuration — the runtime view of a .m header.

Carries everything the graph builder needs (reference: LlmHeader,
src/llm.hpp:42-71) plus TPU-side execution choices (compute dtype, weight
layout) that have no reference equivalent.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple

from ..formats.mfile import (PATTERN_ARCHS, ArchType, HiddenAct, ModelHeader,
                             RopeType)


class Multipliers(NamedTuple):
    """The fixed scalars of ``ArchType.FALCON_H1``'s layer equation
    (models/falcon_h1.py says where each is applied): the published
    ``embedding_multiplier``, ``lm_head_multiplier``, ``attention_in/out_
    multiplier``, ``key_multiplier``, ``ssm_in/out_multiplier``,
    ``mlp_multipliers`` (gate, down) and ``ssm_multipliers`` (over the z, x,
    B, C and dt lanes of the mixer's in-projection); and
    ``ArchType.GRANITE_HYBRID``'s ``residual_multiplier`` on every block's
    output where it joins the stream (models/nemotron_h.py's walk; its
    ``embedding_multiplier`` is ``embedding``, one over its
    ``logits_scaling`` is ``lm_head``)."""

    embedding: float = 1.0
    lm_head: float = 1.0
    attn_in: float = 1.0
    attn_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0
    ssm_z: float = 1.0
    ssm_x: float = 1.0
    ssm_b: float = 1.0
    ssm_c: float = 1.0
    ssm_dt: float = 1.0
    residual: float = 1.0


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
    # Window and full attention layers in a periodic pattern, routed
    # experts of which a SHARE may be held (ArchType.LAGUNA,
    # models/laguna.py). ``layer_period`` P there = one full layer, then
    # P - 1 sliding ones. ``n_heads`` counts a full layer's query heads,
    # ``n_heads_sliding`` a sliding layer's; ``rope_theta`` and the
    # ``rope_scaling_*`` fields are the full layers' YaRN table over
    # ``rope_dim`` lanes, ``rope_theta_sliding`` the sliding layers' plain
    # one over the whole head. ``n_experts`` are the experts HELD here,
    # ``[moe_first_expert, moe_first_expert + n_experts)`` of the
    # ``moe_router_width`` the router scores; ``hidden_dim`` is an expert's
    # width, ``dense_hidden_dim`` the leading dense layers'. The arch
    # implies: pre-norm, no q/k norm, a softmax router, an ungated shared
    # expert, a sigmoid gate per head on the attention output before ``wo``.
    sliding_window: int = 0
    n_heads_sliding: int = 0
    rope_theta_sliding: float = 0.0
    rope_dim: int = 0
    n_dense_layers: int = 0
    dense_hidden_dim: int = 0
    shared_expert_dim: int = 0
    moe_routed_scale: float = 1.0
    moe_router_width: int = 0
    moe_first_expert: int = 0
    # A Mamba-2 (SSD) mixer and grouped-query attention SIDE BY SIDE in
    # every layer of one homogeneous stack (ArchType.FALCON_H1,
    # models/falcon_h1.py): ``ssm_heads`` heads of ``ssm_head_dim`` in
    # ``ssm_groups`` groups that share B and C, a state of ``ssm_state_dim``
    # a lane, ``ssm_conv_kernel`` taps, sub-chunks of ``ssm_chunk`` in the
    # chunk form; ``mult`` are the equation's fixed scalars. The arch
    # implies: pre-norm, no q/k norm, the half-split rotary over the whole
    # head, the mixer's gate before its grouped RMS norm.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state_dim: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk: int = 0
    mult: Multipliers | None = None
    # Latent attention (MLA) over a cache of ONE compressed row a token, a
    # leading dense layer, then a group-limited router over experts of which
    # a SHARE may be held (ArchType.AXK1, models/axk1.py). ``n_heads`` query
    # heads of ``head_dim = qk_nope_dim + qk_rope_dim``; the cache row is
    # ``kv_lora_rank`` normed lanes and ``qk_rope_dim`` rotated ones (the one
    # rotary key all heads share), padded to whole lane tiles
    # (``latent_row``). ``rope_theta`` and the ``rope_scaling_*`` fields are
    # YaRN's over the ``qk_rope_dim`` lanes; its ``yarn_mscale`` numbers
    # scale the tables by their ratio and the WHOLE score by the square of
    # the second (``attn_scale``). The share's fields (``n_experts`` held
    # from ``moe_first_expert`` of ``moe_router_width``, ``n_dense_layers``,
    # ``dense_hidden_dim``, ``shared_expert_dim``, ``moe_routed_scale``) are
    # LAGUNA's; ``moe_score`` is the router's score function and
    # ``moe_n_group`` / ``moe_topk_group`` its group limit (0: none). The
    # arch implies: pre-norm, an RMS norm on both latents, no bias, an
    # ungated shared expert, half-split pairing of the rope lanes.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    moe_score: str = "softmax"
    moe_n_group: int = 0
    moe_topk_group: int = 0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    # Gated short-convolution layers beside grouped-query attention layers,
    # routed experts behind leading dense layers (ArchType.LFM2,
    # models/lfm2.py). ``conv_kernel`` taps a channel (``conv_L_cache``): a
    # conv layer's whole state is the convolution's tail, ``conv_kernel - 1``
    # rows of ``dim``. The first ``n_dense_layers`` layers are conv layers
    # with a dense feed-forward; behind them ``layer_period`` P = one
    # attention layer, then P - 1 conv layers, the last period cut short
    # where the depth says so. The share's fields are LAGUNA's (every expert
    # held: ``n_experts`` = ``moe_router_width``); ``moe_select_bias``: a
    # learned bias a routed layer enters the router's SELECTION only;
    # ``moe_norm_eps`` is added to the chosen scores' sum before it divides
    # them. The arch implies: pre-norm, a per-head RMS norm on q and k, the
    # half-split rotary over the whole head, no bias, no shared expert.
    conv_kernel: int = 0
    moe_select_bias: bool = False
    moe_norm_eps: float = 0.0
    # Every layer ONE pre-norm block (ArchType.NEMOTRON_H,
    # models/nemotron_h.py): ``layer_pattern`` names each layer's kind, ``M``
    # an SSD mixer (the ``ssm_*`` sizes, every multiplier 1), ``*``
    # grouped-query attention WITHOUT positions (no rotary table is built),
    # ``E`` a routed feed-forward. Three stacks, each over its own layers of
    # the pattern: ``n_state_layers`` / ``n_kv_layers`` / ``n_moe_layers``
    # count them, and the pools' layer axes are those counts. The experts
    # are UNGATED (two planes: the stack has no ``we3``; ``hidden_act``
    # relu2) and live in a latent ``moe_latent_dim`` wide (0: the model's
    # width): one projection down in front of the dispatch, one up behind
    # the weighted sum; the router and the shared expert
    # (``shared_expert_dim``, ungated too) read the model's width. The
    # share's fields are LAGUNA's, ``moe_select_bias`` and ``moe_norm_eps``
    # LFM2's.
    layer_pattern: tuple[str, ...] = ()
    moe_latent_dim: int = 0
    # NEMOTRON_H's blocks two a published layer, ``ME`` or ``*E``
    # (ArchType.GRANITE_HYBRID, models/granite_hybrid.py): gated experts and
    # a gated shared one (the stacks have ``we3`` / ``ws3``), a softmax
    # router, no latent; ``mult.residual`` on every block's output,
    # ``mult.embedding`` and ``mult.lm_head``; ``attn_score_scale``
    # multiplies an attention score (0: ``head_dim ** -0.5``);
    # ``tied_embeddings``: ``Params.logits`` IS ``Params.embedding``, one
    # array at the compute dtype.
    attn_score_scale: float = 0.0
    tied_embeddings: bool = False
    # A delta rule whose decay is a VECTOR a head beside gated full
    # attention, routed experts behind both (ArchType.SOLAR_OPEN2,
    # models/solar_open2.py): OLMO_HYBRID's ``layer_period`` and ``lin_*``
    # sizes, with ``lin_decay_dim`` decays a head (0 in every other arch: one
    # number, the gated delta rule; here always ``lin_key_dim``: one a key
    # channel, Kimi Delta Attention),
    # ``lin_gate_rank`` the inner width of the decay's and the output gate's
    # low-rank projections, and ``full_layer_at`` the full layer's place in
    # its period (OLMO_HYBRID's is the last, ``layer_period - 1``). The
    # share's fields are LAGUNA's (no leading dense layer), the router's
    # ``moe_score`` and ``moe_select_bias`` LFM2's. The arch implies:
    # pre-norm, three separate projections with a short convolution each, no
    # q/k norm and no rotary embedding in the full layers, a sigmoid gate a
    # lane on their output, an ungated shared expert.
    lin_decay_dim: int = 0
    lin_gate_rank: int = 0
    full_layer_at: int = 0
    # LAGUNA's walk with the full layer LAST in its period
    # (ArchType.MELLUM, models/mellum.py: ``full_layer_at`` = ``layer_period
    # - 1``), ``n_heads_sliding`` = ``n_heads``, the whole head rotating in
    # both kinds, no dense layer and no shared expert. The arch implies:
    # pre-norm, a per-head RMS norm on q and k before the rotary embedding
    # (``uses_qk_norm``), a softmax router, NO gate on the attention output
    # (``has_attention_gate``).

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
    # Rows of the SLIDING part of an admission's column (models/laguna.py's
    # ``LagunaColumn``): a sliding layer never reads more than ``window - 1``
    # rows behind a chunk, so its K/V rides the chunks in a buffer of the
    # window and the widest chunk, not at the slot's length. Set by the
    # engine from the block size and its prefill buckets
    # (runtime/kvblocks.window_column_rows); 0: the slot's padded length.
    window_column_rows: int = 0

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
        return self.arch in (ArchType.QWEN3, ArchType.MELLUM)

    @property
    def has_attention_gate(self) -> bool:
        """A sigmoid gate a query head on the attention output before
        ``wo`` (models/laguna.py; models/mellum.py's walk has none)."""
        return self.arch == ArchType.LAGUNA

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def has_window_layers(self) -> bool:
        """Sliding-window attention layers beside the full ones: a slot's
        context is blocks of TWO pools (runtime/kvblocks), the window
        layers' returned as the window passes them."""
        return self.sliding_window > 0

    @property
    def paged_only(self) -> bool:
        """Served through the paged generator alone: a slot's context is
        more than one list of K/V blocks (a recurrent state, a second pool),
        which the dense slot pool and the single-sequence path do not
        carry."""
        return (self.has_state or self.has_window_layers
                or self.has_latent_cache)

    @property
    def has_latent_cache(self) -> bool:
        """The cache holds one compressed latent row a token a layer
        (models/axk1.py), not per-head keys and values: ONE pool, no V."""
        return self.kv_lora_rank > 0

    @property
    def has_expert_share(self) -> bool:
        """Routed experts of which this chip may hold a share
        (models/share.py: the router scores ``moe_router_width`` experts,
        ``n_experts`` of them are held): the step and the chunks count their
        pairs on the device."""
        return self.moe_router_width > 0

    @property
    def latent_dim(self) -> int:
        """Useful lanes of a cached latent row: ``c`` and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """Lanes of a latent row as the pool holds it: ``latent_dim``
        rounded up to whole lane tiles of 128 (a manual DMA cannot slice a
        minor dimension that is not, and XLA's tiled layout pads it so in
        HBM anyway); the tail is zero."""
        return -(-self.latent_dim // 128) * 128

    @property
    def cache_heads(self) -> int:
        """Heads of a cache block ``[heads, block_size, width]``."""
        return 1 if self.has_latent_cache else self.n_kv_heads

    @property
    def cache_width(self) -> int:
        """Lanes of a cached row a head: a K (and V) head, or the latent
        row. A head of the short-conv arch's attention layers (64 lanes in
        the published model) is padded to whole lane tiles of 128, as the
        latent row is: the compiled paged kernel's DMA cannot slice a minor
        dimension that is not whole tiles, and XLA's tiled layout pads a
        64-lane row to 128 in HBM anyway, so the pool holds the same bytes
        either way and the zero lanes add nothing to a score."""
        if self.has_latent_cache:
            return self.latent_row
        if self.has_short_conv:
            return -(-self.head_dim // 128) * 128
        return self.head_dim

    @property
    def cache_row_elems(self) -> int:
        """Elements a cached token takes in one layer over all the pool's
        planes: K and V of every K/V head, or the one latent row."""
        return (self.latent_row if self.has_latent_cache
                else 2 * self.n_kv_heads * self.cache_width)

    @property
    def attn_scale(self) -> float:
        """What multiplies an attention score: ``head_dim ** -0.5``, and
        with latent attention under YaRN the square of ``0.1 mscale_all_dim
        ln(factor) + 1`` on top (the whole score's, nope part too)."""
        if self.attn_score_scale:
            return self.attn_score_scale
        scale = self.head_dim ** -0.5
        if self.has_latent_cache and self.rope_scaling_factor > 1.0:
            from .rope import yarn_mscale

            scale *= yarn_mscale(self.rope_scaling_factor,
                                 self.yarn_mscale_all_dim) ** 2
        return scale

    @property
    def score_dim(self) -> float:
        """What the attention kernels take for the score's scale, ``score_dim
        ** -0.5`` (ops/attention.py: "``head_dim`` is the score's scale"):
        the head's width, or where the header states the scale the number
        whose root divides a score by it (1 / 128 a score: 16384)."""
        return (self.attn_score_scale ** -2 if self.attn_score_scale
                else self.head_dim)

    @property
    def prefix_reuse_skipped(self) -> str | None:
        """Why a matched prefix block is passed over whatever it holds (a
        label of ``dllama_prefix_reuse_skipped_total``), or None where blocks
        are shared: with window layers too, where a match is used as far
        back as the window pool still holds its window
        (runtime/kvblocks.match_windowed; a request whose window is gone is
        counted ``window_miss``)."""
        return "recurrent_state" if self.has_state else None

    @property
    def expert_width_held(self) -> int:
        """An expert's hidden width as its planes HOLD it. The routed
        kernels fetch a plane's scales ``[width / 32, out]`` by one DMA, and
        Mosaic pads an HBM operand's second-minor dimension to whole tiles
        of 8 rows and refuses a slice that is not: 2688 lanes are 84 scale
        rows of 88. So where ``layer_pattern`` is set (models/nemotron_h.py)
        a width past 256 is held rounded up to whole tiles of 8 blocks (256
        lanes), the lanes behind it zero in both planes: ``act(0) = 0`` for
        an ungated squared ReLU and zero rows of the down-projection add
        nothing, so the function is the published width's. ArchType.MELLUM
        alike (896 lanes are 28 scale rows, held as 1024): ``silu(0) * 0 =
        0`` for its gated experts."""
        wide = self.hidden_dim
        if wide <= 256 or not (self.layer_pattern
                               or self.arch == ArchType.MELLUM):
            return wide
        return -(-wide // 256) * 256

    @property
    def n_window_layers(self) -> int:
        return (self.n_layers - self.n_layers // self.layer_period
                if self.has_window_layers else 0)

    @property
    def n_moe_layers(self) -> int:
        if self.layer_pattern:
            return self.layer_pattern.count("E")
        return self.n_layers - self.n_dense_layers if self.is_moe else 0

    @property
    def is_hybrid(self) -> bool:
        """Gated delta-rule (linear-attention) layers beside the full ones
        in a periodic pattern: models/hybrid.py's two stacks."""
        return self.lin_heads > 0

    @property
    def has_ssm(self) -> bool:
        """Layers with an SSD mixer: beside attention in every layer
        (models/falcon_h1.py), or the pattern's ``M`` layers
        (models/nemotron_h.py)."""
        return self.ssm_heads > 0

    @property
    def has_short_conv(self) -> bool:
        """Gated short-convolution layers beside attention layers
        (models/lfm2.py): their state is the convolution's tail alone."""
        return self.conv_kernel > 0

    @property
    def n_attn_layers(self) -> int:
        """The short-conv arch's attention layers: the first of each
        period behind the leading conv layers."""
        return (-(-(self.n_layers - self.n_dense_layers) // self.layer_period)
                if self.has_short_conv else 0)

    @property
    def n_conv_layers(self) -> int:
        return self.n_layers - self.n_attn_layers if self.has_short_conv else 0

    @property
    def has_state(self) -> bool:
        """Layers with a recurrent state: a slot's context is K/V blocks
        AND a row of the state pool (runtime/kvblocks.StatePool), whichever
        architecture owns the state's shape."""
        return self.is_hybrid or self.has_ssm or self.has_short_conv

    @property
    def n_state_layers(self) -> int:
        """Layers that own a row of the state pool: a hybrid's linear ones,
        every layer where the mixer sits beside attention, the pattern's
        mixer layers, or the conv layers."""
        if self.has_short_conv:
            return self.n_conv_layers
        if self.layer_pattern:
            return self.layer_pattern.count("M")
        return self.n_layers if self.has_ssm else self.n_linear_layers

    def state_shape(self, rows: int) -> tuple[int, ...] | None:
        """The float32 recurrent state of ``rows`` sequences, the
        architecture's: ``[layers, rows, heads, ...]``; None where the
        convolution's tail is the whole state (a short-conv layer)."""
        if self.has_short_conv:
            return None
        if self.has_ssm:
            return (self.n_state_layers, rows, self.ssm_heads,
                    self.ssm_head_dim, self.ssm_state_dim)
        return (self.n_state_layers, rows, self.lin_heads, self.lin_key_dim,
                self.lin_value_dim)

    def conv_shape(self, rows: int) -> tuple[int, ...]:
        """The causal convolution's last ``K - 1`` inputs of ``rows``
        sequences: ``[layers, rows, K - 1, channels]``."""
        if self.has_short_conv:
            return (self.n_conv_layers, rows, self.conv_kernel - 1, self.dim)
        if self.has_ssm:
            return (self.n_state_layers, rows, self.ssm_conv_kernel - 1,
                    self.ssm_conv_dim)
        return (self.n_state_layers, rows, self.lin_conv_kernel - 1,
                self.lin_conv_dim)

    @property
    def ssm_inner_dim(self) -> int:
        """The SSD mixer's width: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels of the SSD mixer's causal convolution: x, B, C."""
        return self.ssm_inner_dim + 2 * self.ssm_groups * self.ssm_state_dim

    @property
    def ssm_in_dim(self) -> int:
        """Width of the mixer's packed Q40 input projection: z x B C."""
        return self.ssm_inner_dim + self.ssm_conv_dim

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.layer_period if self.layer_period else 0

    @property
    def n_linear_layers(self) -> int:
        return (self.n_periods * (self.layer_period - 1)
                if self.is_hybrid else 0)

    @property
    def n_kv_layers(self) -> int:
        """Layers whose K/V lives in THE block pool: every one, a hybrid's
        full ones, or the full ones beside window layers (those have a pool
        of their own, ``n_window_layers`` deep), the attention layers
        beside short-conv ones, or the pattern's attention layers."""
        if self.has_short_conv:
            return self.n_attn_layers
        if self.layer_pattern:
            return self.layer_pattern.count("*")
        return self.n_periods if self.layer_period else self.n_layers

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
        if h.arch_type == ArchType.SOLAR_OPEN2:
            hybrid = dict(
                layer_period=h.layer_period, full_layer_at=h.full_layer_at,
                lin_heads=h.linear_n_value_heads,
                lin_key_dim=h.linear_key_head_dim,
                lin_value_dim=h.linear_value_head_dim,
                lin_conv_kernel=h.linear_conv_kernel,
                lin_neg_eigval=bool(h.linear_neg_eigval),
                lin_decay_dim=h.linear_decay_dim,
                lin_gate_rank=h.linear_gate_rank,
                moe_select_bias=bool(h.moe_select_bias),
                moe_score=("softmax", "sigmoid")[h.moe_score_func],
                shared_expert_dim=h.shared_expert_dim,
                moe_routed_scale=h.moe_routed_scale_milli / 1000.0,
                moe_router_width=h.moe_router_width,
                moe_first_expert=h.moe_first_expert)
        if h.arch_type == ArchType.FALCON_H1:
            hybrid = dict(
                ssm_heads=h.ssm_n_heads, ssm_head_dim=h.ssm_head_dim,
                ssm_groups=h.ssm_n_groups, ssm_state_dim=h.ssm_state_dim,
                ssm_conv_kernel=h.ssm_conv_kernel, ssm_chunk=h.ssm_chunk_size,
                mult=Multipliers(
                    embedding=h.embedding_mult, lm_head=h.lm_head_mult,
                    attn_in=h.attn_in_mult, attn_out=h.attn_out_mult,
                    key=h.key_mult, ssm_in=h.ssm_in_mult,
                    ssm_out=h.ssm_out_mult, mlp_gate=h.mlp_gate_mult,
                    mlp_down=h.mlp_down_mult, ssm_z=h.ssm_mult_z,
                    ssm_x=h.ssm_mult_x, ssm_b=h.ssm_mult_b,
                    ssm_c=h.ssm_mult_c, ssm_dt=h.ssm_mult_dt))
        if h.arch_type == ArchType.AXK1:
            hybrid = dict(
                q_lora_rank=h.q_lora_rank, kv_lora_rank=h.kv_lora_rank,
                qk_nope_dim=h.qk_nope_head_dim, qk_rope_dim=h.qk_rope_head_dim,
                v_head_dim=h.v_head_dim,
                moe_score=("softmax", "sigmoid")[h.moe_score_func],
                moe_n_group=h.moe_n_group, moe_topk_group=h.moe_topk_group,
                yarn_mscale=h.yarn_mscale,
                yarn_mscale_all_dim=h.yarn_mscale_all_dim,
                n_dense_layers=h.n_dense_layers,
                dense_hidden_dim=h.dense_hidden_dim,
                shared_expert_dim=h.shared_expert_dim,
                moe_routed_scale=h.moe_routed_scale_milli / 1000.0,
                moe_router_width=h.moe_router_width,
                moe_first_expert=h.moe_first_expert)
        if h.arch_type == ArchType.LFM2:
            hybrid = dict(
                layer_period=h.layer_period,
                conv_kernel=h.short_conv_kernel,
                moe_select_bias=bool(h.moe_select_bias),
                moe_norm_eps=1e-6,
                moe_score=("softmax", "sigmoid")[h.moe_score_func],
                n_dense_layers=h.n_dense_layers,
                dense_hidden_dim=h.dense_hidden_dim,
                moe_routed_scale=h.moe_routed_scale_milli / 1000.0,
                moe_router_width=h.moe_router_width,
                moe_first_expert=h.moe_first_expert)
        if h.arch_type in PATTERN_ARCHS:
            granite = h.arch_type == ArchType.GRANITE_HYBRID
            hybrid = dict(
                layer_pattern=tuple(h.layer_pattern),
                ssm_heads=h.ssm_n_heads, ssm_head_dim=h.ssm_head_dim,
                ssm_groups=h.ssm_n_groups, ssm_state_dim=h.ssm_state_dim,
                ssm_conv_kernel=h.ssm_conv_kernel, ssm_chunk=h.ssm_chunk_size,
                # every header field at its default: Multipliers()
                mult=Multipliers(embedding=h.embedding_mult,
                                 lm_head=h.lm_head_mult,
                                 residual=h.residual_mult),
                attn_score_scale=h.attn_scale,
                tied_embeddings=bool(h.tied_embeddings),
                moe_latent_dim=h.moe_latent_dim,
                moe_select_bias=bool(h.moe_select_bias),
                moe_norm_eps=0.0 if granite else 1e-20,
                moe_score=("softmax", "sigmoid")[h.moe_score_func],
                shared_expert_dim=h.shared_expert_dim,
                moe_routed_scale=h.moe_routed_scale_milli / 1000.0,
                moe_router_width=h.moe_router_width,
                moe_first_expert=h.moe_first_expert)
        if h.arch_type in (ArchType.LAGUNA, ArchType.MELLUM):
            hybrid = dict(
                layer_period=h.layer_period,
                full_layer_at=h.full_layer_at,
                sliding_window=h.sliding_window,
                n_heads_sliding=h.n_heads_sliding,
                rope_theta_sliding=float(h.rope_theta_sliding),
                rope_dim=h.rope_dim or h.head_dim,
                n_dense_layers=h.n_dense_layers,
                dense_hidden_dim=h.dense_hidden_dim,
                shared_expert_dim=h.shared_expert_dim,
                moe_routed_scale=h.moe_routed_scale_milli / 1000.0,
                moe_router_width=h.moe_router_width,
                moe_first_expert=h.moe_first_expert)
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

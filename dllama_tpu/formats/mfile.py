""".m model file format — header parse and tensor walker.

Wire-compatible with the reference format (reference: src/llm.cpp:34-145 for the
header parse, src/llm.cpp:499-539 for the tensor order, converter/writer.py:109-147
for the writer):

    int32 magic = 0xA00ABCD
    int32 headerSize            # total header bytes INCLUDING magic + this field
    (int32 key, int32 value) *  # (headerSize - 8) / 8 pairs
    tensor data ...             # starts at offset headerSize

Tensor order (llm.cpp:499-539): embedding (F32), then per layer
q, k, v, wo, w1(gate), w2(down), w3(up) in the weight float type, Qwen3's
per-head q/k norms (F32), block norms 0/1 (F32); finally final_norm (F32) and
the logits matmul (weight float type).

This module is pure numpy/host-side — device placement and the TPU repack live
in :mod:`dllama_tpu.runtime.weights`.
"""

from __future__ import annotations

import enum
import json
import mmap
import os
import struct
import zlib
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from .quants import (F16, F32, Q40, Q40_BLOCK_BYTES, Q40_BLOCK_SIZE, Q80,
                     QUANT_BLOCK_SIZE, dequantize_q40, dequantize_q80,
                     tensor_bytes, unpack_q40)

MODEL_MAGIC = 0xA00ABCD

# checksum manifest sidecar (``<model>.m.sums``): per-tensor crc32 of the
# on-disk bytes, written by the converter and verified by the streaming
# loader. A sidecar (not a trailer) keeps the .m byte stream wire-compatible
# with the reference reader, whose walk requires walk-end == file size.
MANIFEST_SUFFIX = ".sums"
MANIFEST_VERSION = 1
MANIFEST_ALGO = "crc32"


def _dequant_any(buf, n: int, float_type: int) -> np.ndarray:
    """Decode ``n`` elements of any on-disk float type to an owning f32 array
    (all four reference weight formats, converter/writer.py:6-17)."""
    if float_type == F32:
        return np.frombuffer(buf, dtype=np.float32, count=n).copy()
    if float_type == F16:
        return np.frombuffer(buf, dtype=np.float16, count=n).astype(np.float32)
    if float_type == Q40:
        return dequantize_q40(buf, n)
    if float_type == Q80:
        return dequantize_q80(buf, n)
    raise ValueError(f"unsupported tensor float type {float_type}")


class HeaderKey(enum.IntEnum):
    """Header key ids (reference: src/llm.hpp:8-30)."""

    VERSION = 0
    ARCH_TYPE = 1
    DIM = 2
    HIDDEN_DIM = 3
    N_LAYERS = 4
    N_HEADS = 5
    N_KV_HEADS = 6
    N_EXPERTS = 7
    N_ACTIVE_EXPERTS = 8
    VOCAB_SIZE = 9
    SEQ_LEN = 10
    HIDDEN_ACT = 11
    ROPE_THETA = 12
    WEIGHT_FLOAT_TYPE = 13
    ROPE_SCALING_FACTOR = 14
    ROPE_SCALING_LOW_FREQ_FACTOR = 15
    ROPE_SCALING_HIGH_FREQ_FACTORY = 16
    ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
    ROPE_TYPE = 18
    HEAD_DIM = 19
    NORM_EPSILON = 20
    # OUR format extension (reference keys stop at 20): whether MoE router
    # weights are renormalized over the selected top-k (HF norm_topk_prob;
    # Mixtral always normalizes, Qwen3-MoE defaults to raw softmax probs).
    MOE_NORM_TOPK = 21
    # OUR format extension, read by ArchType.OLMO_HYBRID only (the C++
    # reference stops at key 20 and refuses these): the layer pattern as its
    # period (P: each period is P-1 linear-attention layers, then one full
    # one) and the six ``linear_*`` sizes of the gated delta-rule mixer.
    LAYER_PERIOD = 22
    LINEAR_N_KEY_HEADS = 23
    LINEAR_N_VALUE_HEADS = 24
    LINEAR_KEY_HEAD_DIM = 25
    LINEAR_VALUE_HEAD_DIM = 26
    LINEAR_CONV_KERNEL = 27
    LINEAR_NEG_EIGVAL = 28
    # OUR format extension, read by ArchType.LAGUNA only (models/laguna.py).
    # LAYER_PERIOD is shared: there P = one full-attention layer, then P-1
    # sliding-window ones. N_HEADS is the full layers' query heads; the
    # ROPE_* keys 12-17 describe the FULL layers' YaRN table (RopeType.YARN:
    # factor, beta_slow and beta_fast in the low / high frequency factor
    # keys, the original context).
    SLIDING_WINDOW = 29          # keys a sliding layer's query sees, itself included
    N_HEADS_SLIDING = 30         # query heads of a sliding layer
    ROPE_THETA_SLIDING = 31      # the sliding layers' plain rotary base
    ROPE_DIM = 32                # lanes of a FULL layer's head that rotate (partial rotary)
    N_DENSE_LAYERS = 33          # leading layers whose feed-forward is dense
    DENSE_HIDDEN_DIM = 34        # their width (HIDDEN_DIM is an expert's)
    SHARED_EXPERT_DIM = 35       # the shared expert's width (0: none)
    MOE_ROUTED_SCALE_MILLI = 36  # routed sum's scale, in thousandths
    MOE_ROUTER_WIDTH = 37        # experts the router scores (N_EXPERTS are HELD here)
    MOE_FIRST_EXPERT = 38        # the first held expert's index among them
    # OUR format extension, read by ArchType.FALCON_H1 only
    # (models/falcon_h1.py): the Mamba-2 (SSD) mixer's sizes, then what a
    # 32-bit integer cannot say, each as the BITS of its float32: the
    # rotary base (1e11 there) and the fourteen multipliers of the layer
    # equation (``ssm_mult_*``: ``ssm_multipliers`` over the z, x, B, C and
    # dt lanes of the in-projection).
    SSM_N_HEADS = 39
    SSM_HEAD_DIM = 40
    SSM_N_GROUPS = 41
    SSM_STATE_DIM = 42
    SSM_CONV_KERNEL = 43
    SSM_CHUNK_SIZE = 44
    ROPE_THETA_F32 = 45
    EMBEDDING_MULT = 46
    LM_HEAD_MULT = 47
    ATTN_IN_MULT = 48
    ATTN_OUT_MULT = 49
    KEY_MULT = 50
    SSM_IN_MULT = 51
    SSM_OUT_MULT = 52
    MLP_GATE_MULT = 53
    MLP_DOWN_MULT = 54
    SSM_MULT_Z = 55
    SSM_MULT_X = 56
    SSM_MULT_B = 57
    SSM_MULT_C = 58
    SSM_MULT_DT = 59
    # OUR format extension, read by ArchType.AXK1 only (models/axk1.py):
    # latent attention's five sizes (N_HEADS heads whose queries are
    # QK_NOPE_HEAD_DIM + QK_ROPE_HEAD_DIM wide: HEAD_DIM is their sum), the
    # router's groups and score function, and YaRN's two mscale numbers as
    # float32 bits. The arch shares N_DENSE_LAYERS .. MOE_FIRST_EXPERT (33-38)
    # and MOE_NORM_TOPK with LAGUNA, and YaRN's factor, beta_slow, beta_fast
    # and original context in keys 14-17.
    Q_LORA_RANK = 60             # the query's latent width
    KV_LORA_RANK = 61            # the cached latent's width (c)
    QK_NOPE_HEAD_DIM = 62        # a head's lanes that do not rotate
    QK_ROPE_HEAD_DIM = 63        # the lanes that do: ONE k_r a token, shared by the heads
    V_HEAD_DIM = 64
    MOE_N_GROUP = 65             # the router's groups (0 / 1: none)
    MOE_TOPK_GROUP = 66          # groups a token's experts may come from
    MOE_SCORE_FUNC = 67          # 0 softmax, 1 sigmoid
    YARN_MSCALE = 68
    YARN_MSCALE_ALL_DIM = 69
    # OUR format extension, read by ArchType.LFM2 only (models/lfm2.py): the
    # gated short convolution's taps (``conv_L_cache``) and whether the
    # router's selection carries a learned bias a routed layer
    # (``use_expert_bias``: a float32 row of MOE_ROUTER_WIDTH behind the
    # router's rows). The arch shares LAYER_PERIOD (22: an attention layer,
    # then LAYER_PERIOD - 1 conv layers, after the N_DENSE_LAYERS leading conv
    # layers), N_DENSE_LAYERS .. MOE_FIRST_EXPERT (33-38, SHARED_EXPERT_DIM 0),
    # MOE_NORM_TOPK and MOE_SCORE_FUNC with the other routed archs.
    SHORT_CONV_KERNEL = 70
    MOE_SELECT_BIAS = 71
    # OUR format extension, read by ArchType.NEMOTRON_H only
    # (models/nemotron_h.py): every layer is ONE block, and which one is the
    # header's: LAYER_PATTERN may stand more than once, each value the next
    # PATTERN_KINDS_A_WORD layers' kinds at two bits a layer, the first in
    # the lowest bits (0 ``M`` an SSD mixer, 1 ``*`` attention, 2 ``E`` a
    # routed feed-forward); N_LAYERS says how many there are. The experts
    # live in a latent MOE_LATENT_DIM wide (0: the model's width), one
    # projection down in front of the dispatch and one up behind the sum.
    # The arch shares the mixer's sizes (39-44), SHARED_EXPERT_DIM ..
    # MOE_FIRST_EXPERT (35-38), MOE_NORM_TOPK, MOE_SCORE_FUNC and
    # MOE_SELECT_BIAS; HIDDEN_ACT is an expert's (HiddenAct.RELU2: ungated).
    LAYER_PATTERN = 72
    MOE_LATENT_DIM = 73
    # OUR format extension, read by ArchType.GRANITE_HYBRID only
    # (models/granite_hybrid.py): NEMOTRON_H's pattern and keys with GATED
    # experts and a gated shared one (three planes each, as the other routed
    # files order them), and what its equation adds, the floats as float32
    # bits: RESIDUAL_MULT scales every block's output where it joins the
    # stream, ATTN_SCALE multiplies an attention score in place of ``head_dim
    # ** -0.5``; EMBEDDING_MULT and LM_HEAD_MULT (46-47) mean what they mean
    # for FALCON_H1. TIED_EMBEDDINGS 1: the head IS the embedding; the file
    # still carries ``final_matmul_logits`` (the reference format's walk ends
    # in it) and the loader does not read it.
    RESIDUAL_MULT = 74
    ATTN_SCALE = 75
    TIED_EMBEDDINGS = 76
    # OUR format extension, read by ArchType.SOLAR_OPEN2 only
    # (models/solar_open2.py): a delta rule whose decay is a VECTOR a head
    # (Kimi Delta Attention) beside gated full attention, routed experts
    # behind both. The arch shares LAYER_PERIOD and the ``linear_*`` sizes
    # (22-28) with OLMO_HYBRID, SHARED_EXPERT_DIM .. MOE_FIRST_EXPERT
    # (35-38), MOE_NORM_TOPK, MOE_SCORE_FUNC and MOE_SELECT_BIAS with the
    # routed archs. Two of the three STATE what the arch implies and are
    # refused at any other value: LINEAR_DECAY_DIM, decays a head
    # (LINEAR_KEY_HEAD_DIM: one a key channel; one number a head is
    # OLMO_HYBRID's rule and lives there), and FULL_LAYER_AT, where the
    # full layer stands in its period (0, first; OLMO_HYBRID's is
    # LAYER_PERIOD - 1 and not written). LINEAR_GATE_RANK: the inner width
    # of the decay's and the output gate's low-rank projections.
    LINEAR_DECAY_DIM = 77
    LINEAR_GATE_RANK = 78
    FULL_LAYER_AT = 79


class ArchType(enum.IntEnum):
    """Architecture ids (reference: src/llm.hpp:37-40)."""

    LLAMA = 0xABCD00
    QWEN3 = 0xABCD01
    # ours: a hybrid decoder, gated delta-rule (linear-attention) layers and
    # full softmax-attention layers in a periodic pattern (models/hybrid.py)
    OLMO_HYBRID = 0xABCD02
    # ours: window and full attention layers in a periodic pattern with a
    # per-head output gate, a leading dense layer, then routed experts and a
    # shared one; the experts, heads and vocabulary HELD may be one chip's
    # share of a deployment (models/laguna.py)
    LAGUNA = 0xABCD03
    # ours: ONE homogeneous stack whose every layer runs a Mamba-2 (SSD)
    # mixer and grouped-query attention side by side over one normed input
    # and adds both to the residual (models/falcon_h1.py)
    FALCON_H1 = 0xABCD04
    # ours: latent attention (MLA: the cache holds one compressed row a
    # token, no per-head keys or values), a leading dense layer, then a
    # sigmoid group-limited router over experts of which a share may be
    # held, and a shared one (models/axk1.py)
    AXK1 = 0xABCD05
    # ours: gated short-convolution layers (a double gate around a causal
    # depthwise convolution of a few taps: the whole state a sequence carries
    # there is the convolution's tail) beside grouped-query attention layers
    # with a per-head q/k norm, in a periodic pattern behind leading conv
    # layers with a dense feed-forward; every other layer routes over experts
    # through a sigmoid router with a selection-only bias (models/lfm2.py)
    LFM2 = 0xABCD06
    # ours: every layer is ONE pre-norm block, an SSD (Mamba-2) mixer, a
    # grouped-query attention layer without positions, or a routed
    # feed-forward of ungated squared-ReLU experts in a latent space beside a
    # shared one, in the order the header's pattern gives
    # (models/nemotron_h.py)
    NEMOTRON_H = 0xABCD07
    # ours: every published layer is an SSD mixer or attention without
    # positions, THEN gated routed experts beside a gated shared one, each
    # behind its own norm: NEMOTRON_H's blocks two a layer (``ME`` / ``*E``),
    # every block's output under one residual multiplier, the score's scale,
    # the embedding's and the logits' stated, the head tied to the embedding
    # (models/granite_hybrid.py)
    GRANITE_HYBRID = 0xABCD08
    # ours: three delta-rule layers whose decay is a vector a head (Kimi
    # Delta Attention: three projections, a short convolution on each, a
    # low-rank decay and a low-rank sigmoid output gate) behind one full
    # grouped-query layer without positions whose output is gated a lane, in
    # every period; pre-norm; behind EVERY mixer a sigmoid router with a
    # selection-only bias over experts of which a share may be held, and a
    # shared one (models/solar_open2.py)
    SOLAR_OPEN2 = 0xABCD09
    # ours: LAGUNA's window and full layers with the full layer LAST in its
    # period, one head count for both kinds, a per-head RMS norm on q and k
    # before the rotary embedding, no gate, no dense layer, no shared
    # expert, every routed expert held or a share (models/mellum.py over
    # models/laguna.py's walk). LAGUNA's header keys, and FULL_LAYER_AT
    # stating ``LAYER_PERIOD - 1``
    MELLUM = 0xABCD0A


# the archs whose layers are blocks of ``layer_pattern``
PATTERN_ARCHS = (ArchType.NEMOTRON_H, ArchType.GRANITE_HYBRID)


PATTERN_KINDS = "M*E"          # LAYER_PATTERN's two-bit codes, in this order
PATTERN_KINDS_A_WORD = 15      # 30 bits: a header value is a signed int32


def pattern_words(pattern: str) -> list[int]:
    """``pattern`` (a string over ``M * E``) as LAYER_PATTERN's values."""
    n = PATTERN_KINDS_A_WORD
    return [sum(PATTERN_KINDS.index(c) << (2 * i)
                for i, c in enumerate(pattern[at:at + n]))
            for at in range(0, len(pattern), n)]


def pattern_from_words(words: list[int], n_layers: int) -> str:
    kinds = [PATTERN_KINDS[(w >> (2 * i)) & 3]
             for w in words for i in range(PATTERN_KINDS_A_WORD)]
    return "".join(kinds[:n_layers])


class RopeType(enum.IntEnum):
    """RoPE style ids (reference: src/nn/nn-core.hpp rope types)."""

    LLAMA = 0
    FALCON = 1
    LLAMA3_1 = 2
    # ours (ArchType.LAGUNA's full layers): half-split pairing over the first
    # ROPE_DIM lanes, YaRN's banded frequency interpolation, cos and sin
    # scaled by 0.1 ln(factor) + 1 (models/rope.py)
    YARN = 3


class HiddenAct(enum.IntEnum):
    GELU = 0
    SILU = 1
    # ours: relu(x) ** 2, the activation of an UNGATED feed-forward
    RELU2 = 2


@dataclass
class ModelHeader:
    """Parsed .m header — the LlmHeader equivalent (reference: src/llm.hpp:42-71)."""

    version: int = 0
    arch_type: ArchType = ArchType.LLAMA
    dim: int = 0
    hidden_dim: int = 0
    n_layers: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    n_experts: int = 0
    n_active_experts: int = 0
    moe_norm_topk: int = 1  # renormalize selected router weights (sum to 1)
    vocab_size: int = 0
    orig_seq_len: int = 0
    seq_len: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: RopeType = RopeType.LLAMA
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    norm_epsilon: float = 1e-5
    head_dim: int = 0
    weight_type: int = -1
    sync_type: int = F32
    header_size: int = 0
    file_size: int = 0
    # OLMO_HYBRID (HeaderKey 22-28); 0 / defaults for every other arch
    layer_period: int = 0
    linear_n_key_heads: int = 0
    linear_n_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    linear_neg_eigval: int = 0
    # LAGUNA (HeaderKey 29-38); 0 for every other arch
    sliding_window: int = 0
    n_heads_sliding: int = 0
    rope_theta_sliding: int = 0
    rope_dim: int = 0
    n_dense_layers: int = 0
    dense_hidden_dim: int = 0
    shared_expert_dim: int = 0
    moe_routed_scale_milli: int = 1000
    moe_router_width: int = 0
    moe_first_expert: int = 0
    # FALCON_H1 (HeaderKey 39-59); 0 / 1.0 for every other arch
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_n_groups: int = 0
    ssm_state_dim: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk_size: int = 0
    embedding_mult: float = 1.0
    lm_head_mult: float = 1.0
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    mlp_gate_mult: float = 1.0
    mlp_down_mult: float = 1.0
    ssm_mult_z: float = 1.0
    ssm_mult_x: float = 1.0
    ssm_mult_b: float = 1.0
    ssm_mult_c: float = 1.0
    ssm_mult_dt: float = 1.0
    # AXK1 (HeaderKey 60-69); 0 / 1.0 for every other arch
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_n_group: int = 0
    moe_topk_group: int = 0
    moe_score_func: int = 0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    # LFM2 (HeaderKey 70-71); 0 for every other arch
    short_conv_kernel: int = 0
    moe_select_bias: int = 0
    # NEMOTRON_H (HeaderKey 72-73); empty / 0 for every other arch
    layer_pattern: str = ""
    moe_latent_dim: int = 0
    # GRANITE_HYBRID (HeaderKey 74-76); 1.0 / 0 for every other arch
    # (``attn_scale`` 0: ``head_dim ** -0.5``)
    residual_mult: float = 1.0
    attn_scale: float = 0.0
    tied_embeddings: int = 0
    # SOLAR_OPEN2 (HeaderKey 77-79); 0 for every other arch
    linear_decay_dim: int = 0
    linear_gate_rank: int = 0
    full_layer_at: int = 0

    def pattern_layers(self, kind: str) -> list[int]:
        """The model's layers of ``kind`` (one of ``M * E``), in order."""
        return [l for l, c in enumerate(self.layer_pattern) if c == kind]

    @property
    def n_attn_layers(self) -> int:
        """LFM2's attention layers: the first of each period behind the
        leading conv layers (the last period may be cut short)."""
        return -(-(self.n_layers - self.n_dense_layers) // self.layer_period)

    def lfm2_is_attn(self, l: int) -> bool:
        l -= self.n_dense_layers
        return l >= 0 and l % self.layer_period == 0

    @property
    def ssm_inner_dim(self) -> int:
        """The SSD mixer's width (``mamba_d_ssm``): heads x head width."""
        return self.ssm_n_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B, C."""
        return self.ssm_inner_dim + 2 * self.ssm_n_groups * self.ssm_state_dim

    @property
    def ssm_in_dim(self) -> int:
        """Width of the mixer's packed Q40 input projection: z x B C (the
        ``dt`` rows are a float32 plane of their own)."""
        return self.ssm_inner_dim + self.ssm_conv_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels the causal convolution runs over: q~, k~, v~."""
        return (2 * self.linear_n_key_heads * self.linear_key_head_dim
                + self.linear_n_value_heads * self.linear_value_head_dim)

    @property
    def linear_in_dim(self) -> int:
        """Width of the mixer's packed input projection: q~ k~ v~ z."""
        return (self.linear_conv_dim
                + self.linear_n_value_heads * self.linear_value_head_dim)

    @property
    def q_dim(self) -> int:
        return self.head_dim * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_dim * self.n_kv_heads


def _norm_epsilon_from_int(value: int) -> float:
    # The header stores the epsilon exponent (reference: llm.cpp:61-65).
    if value == 5:
        return 1e-5
    if value == 6:
        return 1e-6
    raise ValueError(f"unsupported norm epsilon code {value}")


def norm_epsilon_to_int(eps: float) -> int:
    if abs(eps - 1e-5) < 1e-9:
        return 5
    if abs(eps - 1e-6) < 1e-10:
        return 6
    raise ValueError(f"unsupported norm epsilon {eps}")


# the hybrid arch's keys land in the ModelHeader field of the same name
_HYBRID_KEYS = {k: k.name.lower() for k in (
    HeaderKey.LAYER_PERIOD, HeaderKey.LINEAR_N_KEY_HEADS,
    HeaderKey.LINEAR_N_VALUE_HEADS, HeaderKey.LINEAR_KEY_HEAD_DIM,
    HeaderKey.LINEAR_VALUE_HEAD_DIM, HeaderKey.LINEAR_CONV_KERNEL,
    HeaderKey.LINEAR_NEG_EIGVAL,
    HeaderKey.SLIDING_WINDOW, HeaderKey.N_HEADS_SLIDING,
    HeaderKey.ROPE_THETA_SLIDING, HeaderKey.ROPE_DIM,
    HeaderKey.N_DENSE_LAYERS, HeaderKey.DENSE_HIDDEN_DIM,
    HeaderKey.SHARED_EXPERT_DIM, HeaderKey.MOE_ROUTED_SCALE_MILLI,
    HeaderKey.MOE_ROUTER_WIDTH, HeaderKey.MOE_FIRST_EXPERT,
    HeaderKey.SSM_N_HEADS, HeaderKey.SSM_HEAD_DIM, HeaderKey.SSM_N_GROUPS,
    HeaderKey.SSM_STATE_DIM, HeaderKey.SSM_CONV_KERNEL,
    HeaderKey.SSM_CHUNK_SIZE,
    HeaderKey.Q_LORA_RANK, HeaderKey.KV_LORA_RANK,
    HeaderKey.QK_NOPE_HEAD_DIM, HeaderKey.QK_ROPE_HEAD_DIM,
    HeaderKey.V_HEAD_DIM, HeaderKey.MOE_N_GROUP, HeaderKey.MOE_TOPK_GROUP,
    HeaderKey.MOE_SCORE_FUNC, HeaderKey.SHORT_CONV_KERNEL,
    HeaderKey.MOE_SELECT_BIAS, HeaderKey.MOE_LATENT_DIM,
    HeaderKey.TIED_EMBEDDINGS, HeaderKey.LINEAR_DECAY_DIM,
    HeaderKey.LINEAR_GATE_RANK, HeaderKey.FULL_LAYER_AT)}
# FALCON_H1's float keys: the value is a float32's bit pattern
_F32_BITS_KEYS = {k: k.name.lower() for k in HeaderKey
                  if HeaderKey.EMBEDDING_MULT <= k <= HeaderKey.SSM_MULT_DT}
_F32_BITS_KEYS[HeaderKey.ROPE_THETA_F32] = "rope_theta"
_F32_BITS_KEYS[HeaderKey.YARN_MSCALE] = "yarn_mscale"
_F32_BITS_KEYS[HeaderKey.YARN_MSCALE_ALL_DIM] = "yarn_mscale_all_dim"
_F32_BITS_KEYS[HeaderKey.RESIDUAL_MULT] = "residual_mult"
_F32_BITS_KEYS[HeaderKey.ATTN_SCALE] = "attn_scale"


def f32_bits(x: float) -> int:
    """A float32's bit pattern as the int32 a header value is."""
    return struct.unpack("<i", struct.pack("<f", x))[0]


def f32_from_bits(v: int) -> float:
    return struct.unpack("<f", struct.pack("<i", v))[0]


def parse_header(raw: bytes, path_size: int, max_seq_len: int = 0,
                 sync_type: int = F32) -> ModelHeader:
    """Parse the .m header bytes (reference: llm.cpp:67-145)."""
    magic, header_size = struct.unpack_from("<ii", raw, 0)
    if magic in (0xABCD00, 0xABCD01):
        raise ValueError("old model format is not supported")
    if magic != MODEL_MAGIC:
        raise ValueError(f"unsupported magic number {magic:#x}")
    n_kv = (header_size - 8) // 8
    h = ModelHeader()
    words: list[int] = []
    for i in range(n_kv):
        key, value = struct.unpack_from("<ii", raw, 8 + i * 8)
        if key == HeaderKey.VERSION:
            h.version = value
        elif key == HeaderKey.ARCH_TYPE:
            h.arch_type = ArchType(value)
        elif key == HeaderKey.DIM:
            h.dim = value
        elif key == HeaderKey.HIDDEN_DIM:
            h.hidden_dim = value
        elif key == HeaderKey.N_LAYERS:
            h.n_layers = value
        elif key == HeaderKey.N_HEADS:
            h.n_heads = value
        elif key == HeaderKey.N_KV_HEADS:
            h.n_kv_heads = value
        elif key == HeaderKey.N_EXPERTS:
            h.n_experts = value
        elif key == HeaderKey.N_ACTIVE_EXPERTS:
            h.n_active_experts = value
        elif key == HeaderKey.MOE_NORM_TOPK:
            h.moe_norm_topk = value
        elif key == HeaderKey.VOCAB_SIZE:
            h.vocab_size = value
        elif key == HeaderKey.SEQ_LEN:
            h.seq_len = value
        elif key == HeaderKey.HIDDEN_ACT:
            h.hidden_act = HiddenAct(value)
        elif key == HeaderKey.ROPE_THETA:
            h.rope_theta = float(value)
        elif key == HeaderKey.WEIGHT_FLOAT_TYPE:
            h.weight_type = value
        elif key == HeaderKey.ROPE_SCALING_FACTOR:
            h.rope_scaling_factor = float(value)
        elif key == HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR:
            h.rope_scaling_low_freq_factor = float(value)
        elif key == HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY:
            h.rope_scaling_high_freq_factor = float(value)
        elif key == HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN:
            h.rope_scaling_orig_max_seq_len = value
        elif key == HeaderKey.ROPE_TYPE:
            h.rope_type = RopeType(value)
        elif key == HeaderKey.HEAD_DIM:
            h.head_dim = value
        elif key == HeaderKey.NORM_EPSILON:
            h.norm_epsilon = _norm_epsilon_from_int(value)
        elif key == HeaderKey.LAYER_PATTERN:
            words.append(value)
        elif key in _HYBRID_KEYS:
            setattr(h, _HYBRID_KEYS[key], value)
        elif key in _F32_BITS_KEYS:
            setattr(h, _F32_BITS_KEYS[key], f32_from_bits(value))
        else:
            raise ValueError(f"unsupported header key {key}")

    if h.weight_type == -1:
        raise ValueError("model does not specify weight type")

    h.orig_seq_len = h.seq_len
    if max_seq_len > 0 and h.seq_len > max_seq_len:
        h.seq_len = max_seq_len
    if h.head_dim == 0:
        h.head_dim = h.dim // h.n_heads
    h.sync_type = sync_type
    h.header_size = header_size
    h.file_size = path_size
    h.layer_pattern = pattern_from_words(words, h.n_layers)
    if h.arch_type == ArchType.QWEN3:
        h.rope_type = RopeType.FALCON
    if h.arch_type == ArchType.OLMO_HYBRID:
        if h.layer_period < 2 or h.n_layers % h.layer_period:
            raise ValueError(
                f"hybrid model: layer period {h.layer_period} does not "
                f"divide {h.n_layers} layers into whole periods")
        if h.n_experts:
            raise ValueError("hybrid model: routed experts are unsupported")
    if h.arch_type == ArchType.FALCON_H1:
        h.rope_type = RopeType.FALCON
        per_group = h.ssm_n_heads // max(1, h.ssm_n_groups)
        if not (h.ssm_n_heads and h.ssm_head_dim and h.ssm_state_dim
                and h.ssm_conv_kernel > 1 and h.ssm_chunk_size
                and per_group * h.ssm_n_groups == h.ssm_n_heads):
            raise ValueError(
                f"falcon_h1 model: {h.ssm_n_heads} mixer heads of "
                f"{h.ssm_head_dim} in {h.ssm_n_groups} groups, state "
                f"{h.ssm_state_dim}, {h.ssm_conv_kernel} taps, chunks of "
                f"{h.ssm_chunk_size}: every size must be set and the groups "
                f"must divide the heads")
        if h.n_experts:
            raise ValueError("falcon_h1 model: routed experts are unsupported")
    if h.arch_type in PATTERN_ARCHS:
        arch = h.arch_type.name.lower()
        h.moe_router_width = h.moe_router_width or h.n_experts
        per_group = h.ssm_n_heads // max(1, h.ssm_n_groups)
        if len(h.layer_pattern) != h.n_layers or not h.n_layers:
            raise ValueError(
                f"{arch} model: a pattern of {len(h.layer_pattern)} "
                f"layers for {h.n_layers}")
        if "M" in h.layer_pattern and not (
                h.ssm_n_heads and h.ssm_head_dim and h.ssm_state_dim
                and h.ssm_conv_kernel > 1 and h.ssm_chunk_size
                and per_group * h.ssm_n_groups == h.ssm_n_heads):
            raise ValueError(
                f"{arch} model: {h.ssm_n_heads} mixer heads of "
                f"{h.ssm_head_dim} in {h.ssm_n_groups} groups, state "
                f"{h.ssm_state_dim}, {h.ssm_conv_kernel} taps, chunks of "
                f"{h.ssm_chunk_size}: every size must be set and the groups "
                f"must divide the heads")
        if "E" in h.layer_pattern and not (
                0 < h.n_active_experts <= h.moe_router_width
                and 0 < h.n_experts
                and h.moe_first_expert + h.n_experts <= h.moe_router_width
                and h.moe_score_func in (0, 1)
                and h.moe_select_bias in (0, 1)):
            raise ValueError(
                f"{arch} model: experts [{h.moe_first_expert}, "
                f"{h.moe_first_expert + h.n_experts}) held of a router over "
                f"{h.moe_router_width}, {h.n_active_experts} a token, score "
                f"function code {h.moe_score_func}, selection bias "
                f"{h.moe_select_bias}")
    if h.arch_type == ArchType.GRANITE_HYBRID and (
            h.moe_latent_dim or h.n_dense_layers or h.attn_scale < 0
            or h.residual_mult <= 0):
        raise ValueError(
            f"granite_hybrid model: a latent of {h.moe_latent_dim}, "
            f"{h.n_dense_layers} leading dense layers, a score scale of "
            f"{h.attn_scale}, a residual multiplier of {h.residual_mult}: "
            f"the experts live in the model's width behind every mixer and "
            f"both scalars are positive")
    if h.arch_type == ArchType.SOLAR_OPEN2:
        h.moe_router_width = h.moe_router_width or h.n_experts
        if h.layer_period < 2 or h.n_layers % h.layer_period \
                or h.full_layer_at:
            raise ValueError(
                f"solar_open2 model: layer period {h.layer_period} with the "
                f"full layer at {h.full_layer_at} does not divide "
                f"{h.n_layers} layers into whole periods, each led by its "
                f"full layer")
        if not (h.linear_n_value_heads and h.linear_key_head_dim
                and h.linear_value_head_dim and h.linear_conv_kernel > 1
                and h.linear_gate_rank
                and h.linear_n_key_heads == h.linear_n_value_heads
                and h.linear_decay_dim == h.linear_key_head_dim):
            raise ValueError(
                f"solar_open2 model: {h.linear_n_key_heads} key and "
                f"{h.linear_n_value_heads} value heads of "
                f"{h.linear_key_head_dim} / {h.linear_value_head_dim}, "
                f"{h.linear_conv_kernel} taps, gates through "
                f"{h.linear_gate_rank}, {h.linear_decay_dim} decays a head: "
                f"every size must be set, the heads pair one to one, and a "
                f"head decays by one number a key channel")
        if not (0 < h.n_active_experts <= h.moe_router_width
                and 0 < h.n_experts
                and h.moe_first_expert + h.n_experts <= h.moe_router_width
                and h.moe_score_func in (0, 1)
                and h.moe_select_bias in (0, 1)
                and not h.n_dense_layers):
            raise ValueError(
                f"solar_open2 model: experts [{h.moe_first_expert}, "
                f"{h.moe_first_expert + h.n_experts}) held of a router over "
                f"{h.moe_router_width}, {h.n_active_experts} a token, score "
                f"function code {h.moe_score_func}, selection bias "
                f"{h.moe_select_bias}, {h.n_dense_layers} leading dense "
                f"layers (every layer routes)")
    if h.arch_type == ArchType.LFM2:
        h.rope_type = RopeType.FALCON
        h.moe_router_width = h.moe_router_width or h.n_experts
        if (h.short_conv_kernel < 2 or h.layer_period < 2
                or not 0 < h.n_dense_layers < h.n_layers
                or not h.dense_hidden_dim):
            raise ValueError(
                f"lfm2 model: {h.short_conv_kernel} taps, a period of "
                f"{h.layer_period} (one attention layer, then conv layers) "
                f"behind {h.n_dense_layers} leading conv layers of "
                f"{h.n_layers} with a dense feed-forward {h.dense_hidden_dim} "
                f"wide: every size must be set, at least one layer leading "
                f"and one behind")
        if not (0 < h.n_active_experts <= h.moe_router_width
                and 0 < h.n_experts
                and h.moe_first_expert + h.n_experts <= h.moe_router_width):
            raise ValueError(
                f"lfm2 model: experts [{h.moe_first_expert}, "
                f"{h.moe_first_expert + h.n_experts}) held of a router over "
                f"{h.moe_router_width}, {h.n_active_experts} a token")
        if h.moe_score_func not in (0, 1) or h.moe_select_bias not in (0, 1) \
                or h.shared_expert_dim:
            raise ValueError(
                f"lfm2 model: score function code {h.moe_score_func} (0 "
                f"softmax, 1 sigmoid), selection bias {h.moe_select_bias} "
                f"(0 / 1), shared expert {h.shared_expert_dim} (none)")
    if h.arch_type == ArchType.AXK1:
        h.rope_type = RopeType.YARN
        h.moe_router_width = h.moe_router_width or h.n_experts
        if not (h.q_lora_rank and h.kv_lora_rank and h.qk_nope_head_dim
                and h.qk_rope_head_dim and h.v_head_dim
                and h.head_dim == h.qk_nope_head_dim + h.qk_rope_head_dim
                and h.qk_rope_head_dim % 2 == 0):
            raise ValueError(
                f"axk1 model: latent attention's sizes (q_lora "
                f"{h.q_lora_rank}, kv_lora {h.kv_lora_rank}, nope "
                f"{h.qk_nope_head_dim}, rope {h.qk_rope_head_dim}, v "
                f"{h.v_head_dim}) must all be set, a query head "
                f"({h.head_dim}) being nope + rope")
        if not (0 < h.n_active_experts <= h.moe_router_width
                and 0 < h.n_experts
                and h.moe_first_expert + h.n_experts <= h.moe_router_width):
            raise ValueError(
                f"axk1 model: experts [{h.moe_first_expert}, "
                f"{h.moe_first_expert + h.n_experts}) held of a router over "
                f"{h.moe_router_width}, {h.n_active_experts} a token")
        G, kg = h.moe_n_group, h.moe_topk_group
        if G > 1 and (h.moe_router_width % G or not 0 < kg <= G
                      or h.n_active_experts % kg
                      or h.n_active_experts > kg * (h.moe_router_width // G)):
            raise ValueError(
                f"axk1 model: {h.moe_router_width} experts in {G} groups, "
                f"{kg} groups and {h.n_active_experts} experts a token")
        if h.moe_score_func not in (0, 1):
            raise ValueError(
                f"axk1 model: score function code {h.moe_score_func} "
                f"(0 softmax, 1 sigmoid)")
        if h.n_dense_layers > 1 or (h.n_dense_layers
                                    and not h.dense_hidden_dim):
            raise ValueError(
                f"axk1 model: {h.n_dense_layers} leading dense layers "
                f"(this walk carries at most one, with its width)")
    if h.arch_type == ArchType.MELLUM:
        h.n_heads_sliding = h.n_heads_sliding or h.n_heads
        if (h.full_layer_at != h.layer_period - 1
                or h.n_heads_sliding != h.n_heads or h.n_dense_layers
                or h.shared_expert_dim
                or (h.rope_dim and h.rope_dim != h.head_dim)):
            raise ValueError(
                f"mellum model: the full layer at {h.full_layer_at} of a "
                f"period of {h.layer_period}, {h.n_heads_sliding} sliding "
                f"heads beside {h.n_heads}, {h.n_dense_layers} dense layers, "
                f"a shared expert of {h.shared_expert_dim}, {h.rope_dim} "
                f"rotating lanes: the arch closes its period with the full "
                f"layer, has one head count, no dense layer, no shared "
                f"expert, and rotates the whole head")
    if h.arch_type in (ArchType.LAGUNA, ArchType.MELLUM):
        h.rope_type = RopeType.YARN
        h.moe_router_width = h.moe_router_width or h.n_experts
        if h.layer_period < 2 or h.n_layers % h.layer_period:
            raise ValueError(
                f"laguna model: layer period {h.layer_period} does not "
                f"divide {h.n_layers} layers into whole periods")
        if not (0 < h.sliding_window and h.n_heads_sliding
                and h.n_heads_sliding % h.n_kv_heads == 0):
            raise ValueError(
                f"laguna model: window {h.sliding_window}, "
                f"{h.n_heads_sliding} sliding heads over {h.n_kv_heads} "
                f"K/V heads")
        if not (0 < h.n_active_experts <= h.moe_router_width
                and 0 < h.n_experts
                and h.moe_first_expert + h.n_experts <= h.moe_router_width):
            raise ValueError(
                f"laguna model: experts [{h.moe_first_expert}, "
                f"{h.moe_first_expert + h.n_experts}) held of a router over "
                f"{h.moe_router_width}, {h.n_active_experts} a token")
        if h.n_dense_layers > 1 or (h.n_dense_layers
                                    and not h.dense_hidden_dim):
            raise ValueError(
                f"laguna model: {h.n_dense_layers} leading dense layers "
                f"(this walk carries at most one, with its width)")
    return h


@dataclass
class TensorRecord:
    """One tensor's location inside the .m file."""

    name: str
    layer: int
    shape: tuple[int, ...]  # logical (rows, cols); rows = output dim
    float_type: int
    offset: int
    n_bytes: int


@dataclass
class ModelFile:
    """Memory-mapped .m file with a resolved tensor directory.

    The tensor walk reproduces loadLlmNetWeight (reference: llm.cpp:499-539) but
    produces a flat name→record directory instead of streaming slices to
    workers: on TPU, sharding happens at `jax.device_put` time from this single
    host-side map (SURVEY.md §7.1 "NnRootWeightLoader / splitters").
    """

    path: str
    header: ModelHeader
    tensors: dict[str, TensorRecord] = field(default_factory=dict)
    # False when an MoE file was written without our block_moe_gate extension
    # (i.e. by the reference converter) — parseable but not runnable.
    has_moe_router: bool = True
    # per-tensor crc32 from the .m.sums sidecar; None when the model has no
    # manifest (pre-manifest files stay loadable, just unverified)
    checksums: dict[str, int] | None = None

    _mm: mmap.mmap | None = None
    _file: object | None = None

    @classmethod
    def open(cls, path: str | Path, max_seq_len: int = 0, sync_type: int = F32,
             load_checksums: bool = True) -> "ModelFile":
        """``load_checksums=False`` skips the .m.sums sidecar entirely —
        the manifest WRITER's recompute path needs this (validating the
        stale sidecar it is about to replace would make regeneration
        circular)."""
        path = str(path)
        f = open(path, "rb")
        try:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except Exception:
            f.close()
            raise
        try:
            header = parse_header(mm[:4096] if len(mm) >= 4096 else mm[:], len(mm),
                                  max_seq_len=max_seq_len, sync_type=sync_type)
            mf = cls(path=path, header=header)
            mf._mm = mm
            mf._file = f
            try:
                mf._walk()
            except ValueError as with_router_err:
                if header.n_experts <= 0:
                    raise
                try:
                    # reference-converter MoE layout: no router tensors
                    mf._walk(moe_router=False)
                except ValueError:
                    # neither layout fits — corrupt/truncated file; surface
                    # the router-ful expectation, not the fallback's
                    raise with_router_err from None
                mf.has_moe_router = False
        except Exception:
            mm.close()
            f.close()
            raise
        if load_checksums:
            try:
                mf.checksums = load_manifest(path, file_size=header.file_size)
            except Exception:
                mf.close()
                raise
        return mf

    def close(self) -> None:
        if self._mm is not None:
            self._mm.close()
            self._mm = None
        if self._file is not None:
            self._file.close()  # type: ignore[attr-defined]
            self._file = None

    def __enter__(self) -> "ModelFile":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _add(self, name: str, layer: int, shape: tuple[int, ...], float_type: int,
             offset: int, expert: int | None = None) -> int:
        n = int(np.prod(shape))
        nb = tensor_bytes(float_type, n)
        key = f"{name}.{layer}" if layer >= 0 else name
        if expert is not None:
            key = f"{key}.{expert}"
        self.tensors[key] = TensorRecord(name=name, layer=layer, shape=shape,
                                         float_type=float_type, offset=offset, n_bytes=nb)
        return nb

    def _walk(self, moe_router: bool = True) -> None:
        h = self.header
        wt = h.weight_type
        off = h.header_size
        self.tensors.clear()
        # Tensor names mirror the reference's op names so parity is auditable
        # (llm.cpp:503-538).
        off += self._add("embedding", -1, (h.vocab_size, h.dim), F32, off)
        for l in range(h.n_layers):
            if h.arch_type == ArchType.OLMO_HYBRID:
                off = self._walk_hybrid_layer(l, off)
                continue
            if h.arch_type in (ArchType.LAGUNA, ArchType.MELLUM):
                off = self._walk_laguna_layer(l, off)
                continue
            if h.arch_type == ArchType.FALCON_H1:
                off = self._walk_falcon_h1_layer(l, off)
                continue
            if h.arch_type == ArchType.AXK1:
                off = self._walk_axk1_layer(l, off)
                continue
            if h.arch_type == ArchType.LFM2:
                off = self._walk_lfm2_layer(l, off)
                continue
            if h.arch_type in PATTERN_ARCHS:
                off = self._walk_nemotron_h_layer(l, off)
                continue
            if h.arch_type == ArchType.SOLAR_OPEN2:
                off = self._walk_solar_open2_layer(l, off)
                continue
            off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
            off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
            if h.n_experts > 0:
                # Expert disk order (w3, w1, w2 per expert) matches the
                # reference converter (convert-hf.py:73-80). The router
                # (block_moe_gate) is OUR format extension: the reference
                # converter never emits it and its runtime can't run MoE at
                # all (SURVEY.md §2.2); files without it still parse
                # (has_moe_router=False) but can't be run.
                if moe_router:
                    off += self._add("block_moe_gate", l, (h.n_experts, h.dim),
                                     F32, off)
                for e in range(h.n_experts):
                    off += self._add("block_expert_w3", l, (h.hidden_dim, h.dim),
                                     wt, off, expert=e)
                    off += self._add("block_expert_w1", l, (h.hidden_dim, h.dim),
                                     wt, off, expert=e)
                    off += self._add("block_expert_w2", l, (h.dim, h.hidden_dim),
                                     wt, off, expert=e)
            else:
                off += self._add("block_matmul_w1", l, (h.hidden_dim, h.dim), wt, off)
                off += self._add("block_matmul_w2", l, (h.dim, h.hidden_dim), wt, off)
                off += self._add("block_matmul_w3", l, (h.hidden_dim, h.dim), wt, off)
            if h.arch_type == ArchType.QWEN3:
                off += self._add("block_norm_q", l, (h.head_dim,), F32, off)
                off += self._add("block_norm_k", l, (h.head_dim,), F32, off)
            off += self._add("block_norm_0", l, (h.dim,), F32, off)
            off += self._add("block_norm_1", l, (h.dim,), F32, off)
        off += self._add("final_norm", -1, (h.dim,), F32, off)
        off += self._add("final_matmul_logits", -1, (h.vocab_size, h.dim), wt, off)
        if off != h.file_size:
            raise ValueError(
                f"weight file size mismatch: file has {h.file_size} bytes, "
                f"tensor walk needs {off}")

    def _walk_hybrid_layer(self, l: int, off: int) -> int:
        """One layer of an OLMO_HYBRID file (OUR layout; the reference has
        none). A linear-attention layer: the mixer's packed input
        projection (q~ k~ v~ z rows, in that order), the a/b gate
        projections (F32), the convolution taps ``[kernel, channels]``,
        ``A_log``, ``dt_bias``, the output norm over a value head, the
        output projection. A full layer (the last of each period): q k v
        wo and the q/k norms over the whole projection. Both end with w1
        w2 w3 and the two block norms."""
        h, wt = self.header, self.header.weight_type
        if (l + 1) % h.layer_period:
            nh = h.linear_n_value_heads
            vdim = nh * h.linear_value_head_dim
            off += self._add("block_gdn_in", l, (h.linear_in_dim, h.dim), wt, off)
            off += self._add("block_gdn_ab", l, (2 * nh, h.dim), F32, off)
            off += self._add("block_gdn_conv", l,
                             (h.linear_conv_kernel, h.linear_conv_dim), F32, off)
            off += self._add("block_gdn_a_log", l, (nh,), F32, off)
            off += self._add("block_gdn_dt_bias", l, (nh,), F32, off)
            off += self._add("block_gdn_norm", l, (h.linear_value_head_dim,), F32, off)
            off += self._add("block_gdn_out", l, (h.dim, vdim), wt, off)
        else:
            off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
            off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
            off += self._add("block_norm_q", l, (h.q_dim,), F32, off)
            off += self._add("block_norm_k", l, (h.kv_dim,), F32, off)
        off += self._add("block_matmul_w1", l, (h.hidden_dim, h.dim), wt, off)
        off += self._add("block_matmul_w2", l, (h.dim, h.hidden_dim), wt, off)
        off += self._add("block_matmul_w3", l, (h.hidden_dim, h.dim), wt, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    def _walk_solar_open2_layer(self, l: int, off: int) -> int:
        """One layer of a SOLAR_OPEN2 file (OUR layout; the reference has
        none). The full layer of a period (at ``full_layer_at``): q k v wo
        and the output gate's plane (one gate a lane of the heads'
        output). A delta-rule layer: the three projections q k v, the
        convolution taps ``[kernel, channels]`` of each (q~, k~, v~: three
        records; the loader joins them side by side), ``A_log`` a head, the decay's low-rank pair (down ``[rank, dim]``, up
        ``[H decays, rank]``) and ``dt_bias`` (a decay each), the ``beta``
        rows, the output gate's low-rank pair, the output norm over a value
        head (all F32), the output projection. Then, in both, the router's
        rows, its selection bias, the HELD experts and the shared one
        (:meth:`_walk_share_ffn`) and the two block norms."""
        h, wt = self.header, self.header.weight_type
        if l % h.layer_period == h.full_layer_at:
            off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
            off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
            off += self._add("block_matmul_wg", l, (h.q_dim, h.dim), wt, off)
        else:
            nh, rank = h.linear_n_value_heads, h.linear_gate_rank
            kdim, vdim = nh * h.linear_key_head_dim, nh * h.linear_value_head_dim
            decays = nh * h.linear_decay_dim
            off += self._add("block_kda_q", l, (kdim, h.dim), wt, off)
            off += self._add("block_kda_k", l, (kdim, h.dim), wt, off)
            off += self._add("block_kda_v", l, (vdim, h.dim), wt, off)
            for name, wide in (("q", kdim), ("k", kdim), ("v", vdim)):
                off += self._add("block_kda_conv_" + name, l,
                                 (h.linear_conv_kernel, wide), F32, off)
            off += self._add("block_kda_a_log", l, (nh,), F32, off)
            off += self._add("block_kda_f_down", l, (rank, h.dim), F32, off)
            off += self._add("block_kda_f_up", l, (decays, rank), F32, off)
            off += self._add("block_kda_dt_bias", l, (decays,), F32, off)
            off += self._add("block_kda_b", l, (nh, h.dim), F32, off)
            off += self._add("block_kda_g_down", l, (rank, h.dim), F32, off)
            off += self._add("block_kda_g_up", l, (vdim, rank), F32, off)
            off += self._add("block_kda_norm", l, (h.linear_value_head_dim,), F32, off)
            off += self._add("block_kda_out", l, (h.dim, vdim), wt, off)
        off = self._walk_share_ffn(l, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    def _walk_falcon_h1_layer(self, l: int, off: int) -> int:
        """One layer of a FALCON_H1 file (OUR layout; the reference has
        none): q k v wo; the SSD mixer's packed input projection (z x B C
        rows, in that order: the published ``in_proj`` without its last
        ``ssm_n_heads`` rows), those ``dt`` rows (F32), the convolution
        taps ``[kernel, channels]`` and bias, ``A_log``, ``D``,
        ``dt_bias``, the gated norm's weight over the mixer's width, the
        output projection; w1 w2 w3 and the two block norms."""
        h, wt = self.header, self.header.weight_type
        nh = h.ssm_n_heads
        off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
        off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
        off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
        off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
        off += self._add("block_ssm_in", l, (h.ssm_in_dim, h.dim), wt, off)
        off += self._add("block_ssm_dt", l, (nh, h.dim), F32, off)
        off += self._add("block_ssm_conv", l,
                         (h.ssm_conv_kernel, h.ssm_conv_dim), F32, off)
        off += self._add("block_ssm_conv_bias", l, (h.ssm_conv_dim,), F32, off)
        off += self._add("block_ssm_a_log", l, (nh,), F32, off)
        off += self._add("block_ssm_d", l, (nh,), F32, off)
        off += self._add("block_ssm_dt_bias", l, (nh,), F32, off)
        off += self._add("block_ssm_norm", l, (h.ssm_inner_dim,), F32, off)
        off += self._add("block_ssm_out", l, (h.dim, h.ssm_inner_dim), wt, off)
        off += self._add("block_matmul_w1", l, (h.hidden_dim, h.dim), wt, off)
        off += self._add("block_matmul_w2", l, (h.dim, h.hidden_dim), wt, off)
        off += self._add("block_matmul_w3", l, (h.hidden_dim, h.dim), wt, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    def _walk_axk1_layer(self, l: int, off: int) -> int:
        """One layer of an AXK1 file (OUR layout; the reference has none):
        latent attention's ``W_dq``, the query latent's norm (F32), ``W_uq``
        (a head's nope lanes, then its rope lanes), ``W_dkv`` (the latent's
        ``kv_lora_rank`` rows, then the ``qk_rope_head_dim`` rows of the one
        shared rotary key), the cached latent's norm (F32), ``W_ukv`` (a
        head's nope key rows, then its value rows), ``W_o``; then a leading
        dense layer's w1 w2 w3 or the router, the HELD experts and the
        shared one, as :meth:`_walk_laguna_layer` orders them; the two block
        norms. The rope lanes pair half-split (lane ``j`` with ``j + r/2``)
        in the file's order."""
        h, wt = self.header, self.header.weight_type
        H, r = h.n_heads, h.kv_lora_rank
        off += self._add("block_mla_dq", l, (h.q_lora_rank, h.dim), wt, off)
        off += self._add("block_mla_norm_q", l, (h.q_lora_rank,), F32, off)
        off += self._add("block_mla_uq", l, (H * h.head_dim, h.q_lora_rank),
                         wt, off)
        off += self._add("block_mla_dkv", l,
                         (r + h.qk_rope_head_dim, h.dim), wt, off)
        off += self._add("block_mla_norm_kv", l, (r,), F32, off)
        off += self._add("block_mla_ukv", l,
                         (H * (h.qk_nope_head_dim + h.v_head_dim), r), wt, off)
        off += self._add("block_matmul_wo", l, (h.dim, H * h.v_head_dim),
                         wt, off)
        off = self._walk_share_ffn(l, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    def _walk_lfm2_layer(self, l: int, off: int) -> int:
        """One layer of an LFM2 file (OUR layout; the reference has none).
        An attention layer (:meth:`ModelHeader.lfm2_is_attn`): q k v wo and
        the per-head q and k norms (F32, ``head_dim`` each). A conv layer:
        ``W_in`` (``3 dim`` rows in the order B, C, X), the convolution's
        taps (F32, ``[K, dim]``, tap ``K - 1`` on the current position),
        ``W_out``. Then a leading dense layer's w1 w2 w3 or the router, its
        selection bias (F32, the router's width, where the header says it has
        one) and the HELD experts (:meth:`_walk_share_ffn`); the two block
        norms (the operator's, the feed-forward's)."""
        h, wt = self.header, self.header.weight_type
        if h.lfm2_is_attn(l):
            off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
            off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
            off += self._add("block_norm_q", l, (h.head_dim,), F32, off)
            off += self._add("block_norm_k", l, (h.head_dim,), F32, off)
        else:
            off += self._add("block_conv_in", l, (3 * h.dim, h.dim), wt, off)
            off += self._add("block_conv_taps", l,
                             (h.short_conv_kernel, h.dim), F32, off)
            off += self._add("block_conv_out", l, (h.dim, h.dim), wt, off)
        off = self._walk_share_ffn(l, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    def _walk_nemotron_h_layer(self, l: int, off: int) -> int:
        """One layer of a NEMOTRON_H file (OUR layout; the reference has
        none), ONE block of the kind ``layer_pattern[l]`` and its norm
        (``block_norm_0``). ``M``: the SSD mixer's tensors as
        :meth:`_walk_falcon_h1_layer` orders them (the packed ``z x B C``
        rows, the ``dt`` rows in F32, taps and bias, ``A_log``, ``D``,
        ``dt_bias``, the gated norm's weight, the output projection). ``*``:
        q k v wo. ``E``: the router's rows over ``moe_router_width`` (F32),
        its selection bias (F32, where the header says it has one), the
        projection into the latent, the HELD experts (w1 up, w2 down: two
        planes each, in the latent's width), the projection out of it, the
        shared expert's w1 w2 over the model's width. A GRANITE_HYBRID
        file's ``E`` block is :meth:`_walk_share_ffn`'s: gated experts (w3 w1
        w2 each) and a gated shared one (w1 w2 w3), no latent."""
        h, wt = self.header, self.header.weight_type
        kind = h.layer_pattern[l]
        if kind == "M":
            nh = h.ssm_n_heads
            off += self._add("block_ssm_in", l, (h.ssm_in_dim, h.dim), wt, off)
            off += self._add("block_ssm_dt", l, (nh, h.dim), F32, off)
            off += self._add("block_ssm_conv", l,
                             (h.ssm_conv_kernel, h.ssm_conv_dim), F32, off)
            off += self._add("block_ssm_conv_bias", l, (h.ssm_conv_dim,), F32,
                             off)
            off += self._add("block_ssm_a_log", l, (nh,), F32, off)
            off += self._add("block_ssm_d", l, (nh,), F32, off)
            off += self._add("block_ssm_dt_bias", l, (nh,), F32, off)
            off += self._add("block_ssm_norm", l, (h.ssm_inner_dim,), F32, off)
            off += self._add("block_ssm_out", l, (h.dim, h.ssm_inner_dim), wt,
                             off)
        elif kind == "*":
            off += self._add("block_matmul_q", l, (h.q_dim, h.dim), wt, off)
            off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
            off += self._add("block_matmul_wo", l, (h.dim, h.q_dim), wt, off)
        elif h.arch_type == ArchType.GRANITE_HYBRID:
            off = self._walk_share_ffn(l, off)
        else:
            lat = h.moe_latent_dim or h.dim
            off += self._add("block_moe_gate", l,
                             (h.moe_router_width, h.dim), F32, off)
            if h.moe_select_bias:
                off += self._add("block_moe_bias", l, (h.moe_router_width,),
                                 F32, off)
            if h.moe_latent_dim:
                off += self._add("block_latent_in", l, (lat, h.dim), wt, off)
            for e in range(h.n_experts):
                off += self._add("block_expert_w1", l, (h.hidden_dim, lat),
                                 wt, off, expert=e)
                off += self._add("block_expert_w2", l, (lat, h.hidden_dim),
                                 wt, off, expert=e)
            if h.moe_latent_dim:
                off += self._add("block_latent_out", l, (h.dim, lat), wt, off)
            if h.shared_expert_dim:
                wide = h.shared_expert_dim
                off += self._add("block_shared_w1", l, (wide, h.dim), wt, off)
                off += self._add("block_shared_w2", l, (h.dim, wide), wt, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        return off

    def _walk_share_ffn(self, l: int, off: int) -> int:
        """The feed-forward of a layer whose routed experts may be a SHARE:
        a leading dense layer's w1 w2 w3 at ``dense_hidden_dim``, or the
        router's rows over ``moe_router_width`` (F32), the HELD experts (w3
        w1 w2 each, as the other MoE files order them) and the shared
        expert's w1 w2 w3."""
        h, wt = self.header, self.header.weight_type
        if l < h.n_dense_layers:
            wide = h.dense_hidden_dim
            off += self._add("block_matmul_w1", l, (wide, h.dim), wt, off)
            off += self._add("block_matmul_w2", l, (h.dim, wide), wt, off)
            off += self._add("block_matmul_w3", l, (wide, h.dim), wt, off)
            return off
        off += self._add("block_moe_gate", l,
                         (h.moe_router_width, h.dim), F32, off)
        if h.moe_select_bias:
            off += self._add("block_moe_bias", l, (h.moe_router_width,),
                             F32, off)
        for e in range(h.n_experts):
            off += self._add("block_expert_w3", l, (h.hidden_dim, h.dim),
                             wt, off, expert=e)
            off += self._add("block_expert_w1", l, (h.hidden_dim, h.dim),
                             wt, off, expert=e)
            off += self._add("block_expert_w2", l, (h.dim, h.hidden_dim),
                             wt, off, expert=e)
        if h.shared_expert_dim:
            wide = h.shared_expert_dim
            off += self._add("block_shared_w1", l, (wide, h.dim), wt, off)
            off += self._add("block_shared_w2", l, (h.dim, wide), wt, off)
            off += self._add("block_shared_w3", l, (wide, h.dim), wt, off)
        return off

    def _walk_laguna_layer(self, l: int, off: int) -> int:
        """One layer of a LAGUNA file (OUR layout; the reference has none):
        q k v wo at the layer kind's head count (the first of each period is
        a full layer), the per-head gate's rows (F32), then a leading dense
        layer's w1 w2 w3 at ``dense_hidden_dim``, or the router's rows over
        ``moe_router_width`` (F32), the HELD experts (w3 w1 w2 each, as the
        other MoE files order them) and the shared expert's w1 w2 w3; the
        two block norms. A MELLUM file's layer is the same walk with the
        full layer where ``full_layer_at`` says and, in the gate's place,
        the q and k norms' ``head_dim`` weights (F32)."""
        h, wt = self.header, self.header.weight_type
        heads = (h.n_heads if l % h.layer_period == h.full_layer_at
                 else h.n_heads_sliding)
        q_dim = heads * h.head_dim
        off += self._add("block_matmul_q", l, (q_dim, h.dim), wt, off)
        off += self._add("block_matmul_k", l, (h.kv_dim, h.dim), wt, off)
        off += self._add("block_matmul_v", l, (h.kv_dim, h.dim), wt, off)
        off += self._add("block_matmul_wo", l, (h.dim, q_dim), wt, off)
        if h.arch_type == ArchType.MELLUM:
            off += self._add("block_norm_q", l, (h.head_dim,), F32, off)
            off += self._add("block_norm_k", l, (h.head_dim,), F32, off)
        else:
            off += self._add("block_attn_gate", l, (heads, h.dim), F32, off)
        off = self._walk_share_ffn(l, off)
        off += self._add("block_norm_0", l, (h.dim,), F32, off)
        off += self._add("block_norm_1", l, (h.dim,), F32, off)
        return off

    # -- tensor access ------------------------------------------------------

    def raw(self, key: str) -> memoryview:
        rec = self.tensors[key]
        assert self._mm is not None, "file closed"
        return memoryview(self._mm)[rec.offset:rec.offset + rec.n_bytes]

    def tensor_f32(self, key: str) -> np.ndarray:
        """Read a tensor fully dequantized to float32 with its logical shape.

        Always returns an owning copy so the array stays valid after
        :meth:`close` (a zero-copy view would make ``mmap.close`` raise
        ``BufferError``); bulk load paths that want zero-copy use :meth:`raw`.
        """
        rec = self.tensors[key]
        buf = self.raw(key)
        n = int(np.prod(rec.shape))
        arr = _dequant_any(buf, n, rec.float_type)
        return arr.reshape(rec.shape)

    def tensor_q40_planes(self, key: str) -> tuple[np.ndarray, np.ndarray]:
        """Read a Q40 matmul weight as separated (scales, int4-codes) planes.

        Returns ``scales: float16 [rows, cols/32]`` and ``codes: int8 [rows, cols]``
        — the TPU-friendly repack of the reference's 18-byte interleaved blocks
        (SURVEY.md §7.4).
        """
        rec = self.tensors[key]
        assert rec.float_type == Q40, rec
        rows, cols = rec.shape
        scales, codes = unpack_q40(self.raw(key), rows * cols)
        return (scales.reshape(rows, cols // 32), codes.reshape(rows, cols))

    def tensor_f32_rows(self, key: str, lo: int, hi: int) -> np.ndarray:
        """Rows ``[lo:hi)`` of a tensor, dequantized to f32.

        Disk rows are the output dim and contiguous, so a row range is one
        byte range — only those mmap pages are touched. This is the unit the
        streaming loader reads (the reference's per-node row slice,
        splitRowMatmulWeight, nn-core.cpp:276-292).
        """
        rec = self.tensors[key]
        rows, cols = rec.shape if len(rec.shape) == 2 else (1, rec.shape[0])
        assert 0 <= lo <= hi <= rows, (key, lo, hi, rows)
        row_bytes = rec.n_bytes // rows
        buf = memoryview(self._mm)[rec.offset + lo * row_bytes:
                                   rec.offset + hi * row_bytes]
        n = (hi - lo) * cols
        return _dequant_any(buf, n, rec.float_type).reshape(hi - lo, cols)

    def _quant_kmajor_sub(self, key: str, out_lo: int, out_hi: int,
                          in_lo: int, in_hi: int, *, float_type: int,
                          block_bytes: int,
                          unpack) -> tuple[np.ndarray, np.ndarray]:
        """Shared K-major sub-block reader for the block-quantized formats:
        ``scales f32 [(in_hi-in_lo)/32, out_hi-out_lo]``, ``codes int8 [in, out]``.

        K-major column ranges are disk ROW ranges (contiguous); K-major row
        ranges are disk column-block ranges (strided, 32-element granularity).
        Only the selected blocks are copied out of the mmap, so peak host
        memory is the slice, not the tensor — the loader's building block for
        sharded weights.
        """
        rec = self.tensors[key]
        assert rec.float_type == float_type, rec
        rows, cols = rec.shape
        assert 0 <= out_lo <= out_hi <= rows, (key, out_lo, out_hi)
        assert 0 <= in_lo <= in_hi <= cols and in_lo % QUANT_BLOCK_SIZE == 0 \
            and in_hi % QUANT_BLOCK_SIZE == 0, (key, in_lo, in_hi)
        n_blk = cols // QUANT_BLOCK_SIZE
        blk_lo, blk_hi = in_lo // QUANT_BLOCK_SIZE, in_hi // QUANT_BLOCK_SIZE
        row_bytes = rec.n_bytes // rows
        sub_rows = memoryview(self._mm)[rec.offset + out_lo * row_bytes:
                                        rec.offset + out_hi * row_bytes]
        if blk_lo == 0 and blk_hi == n_blk:
            sel = bytes(sub_rows)  # full-width fast path: one copy
        else:
            as_blocks = np.frombuffer(sub_rows, dtype=np.uint8).reshape(
                out_hi - out_lo, n_blk, block_bytes)
            sel = np.ascontiguousarray(as_blocks[:, blk_lo:blk_hi]).tobytes()
        n = (out_hi - out_lo) * (in_hi - in_lo)
        if float_type == Q40 and blk_lo == 0 and blk_hi == n_blk:
            # single-pass nibble repack (the Q80 codes are already int8 —
            # a native fast path would buy nothing there)
            from .. import native

            if native.available():
                out = native.q40_repack_kmajor(sel, out_hi - out_lo, cols)
                if out is not None:
                    return out
        scales, codes = unpack(sel, n)
        scales = scales.reshape(out_hi - out_lo, (in_hi - in_lo) // QUANT_BLOCK_SIZE)
        codes = codes.reshape(out_hi - out_lo, in_hi - in_lo)
        return (np.ascontiguousarray(scales.T.astype(np.float32)),
                np.ascontiguousarray(codes.T))

    def tensor_crc32(self, key: str) -> int:
        """crc32 of a tensor's raw on-disk bytes (the manifest unit)."""
        return zlib.crc32(self.raw(key)) & 0xFFFFFFFF

    def tensor_scales_kmajor_sub(self, key: str, out_lo: int, out_hi: int,
                                 in_lo: int, in_hi: int) -> np.ndarray:
        """ONLY the K-major scales plane of a block-quantized weight:
        ``f32 [(in_hi-in_lo)/32, out_hi-out_lo]``.

        Both block formats lead each block with a float16 scale (Q40: 2+16
        bytes, Q80: 2+32 — quants.py module docstring), so the scales come
        out of a strided view without ever decoding the codes. This is what
        keeps the streaming loader's scales CALLBACK allocation proportional
        to the scales slice itself — the shared pair reader materializes the
        ~16x larger codes plane just to throw it away
        (tests/test_streaming_loader.py bounds this)."""
        rec = self.tensors[key]
        assert rec.float_type in (Q40, Q80), rec
        from .quants import Q80_BLOCK_BYTES

        block_bytes = Q40_BLOCK_BYTES if rec.float_type == Q40 \
            else Q80_BLOCK_BYTES
        rows, cols = rec.shape
        assert 0 <= out_lo <= out_hi <= rows, (key, out_lo, out_hi)
        assert 0 <= in_lo <= in_hi <= cols and in_lo % QUANT_BLOCK_SIZE == 0 \
            and in_hi % QUANT_BLOCK_SIZE == 0, (key, in_lo, in_hi)
        n_blk = cols // QUANT_BLOCK_SIZE
        blk_lo, blk_hi = in_lo // QUANT_BLOCK_SIZE, in_hi // QUANT_BLOCK_SIZE
        row_bytes = rec.n_bytes // rows
        sub_rows = memoryview(self._mm)[rec.offset + out_lo * row_bytes:
                                        rec.offset + out_hi * row_bytes]
        as_blocks = np.frombuffer(sub_rows, dtype=np.uint8).reshape(
            out_hi - out_lo, n_blk, block_bytes)
        d16 = np.ascontiguousarray(
            as_blocks[:, blk_lo:blk_hi, :2]).view(np.float16)
        # -> [n_blocks, out] f32, matching _quant_kmajor_sub's scales plane
        return np.ascontiguousarray(
            d16.reshape(out_hi - out_lo, blk_hi - blk_lo).T.astype(np.float32))

    def tensor_q40_kmajor_sub(self, key: str, out_lo: int, out_hi: int,
                              in_lo: int, in_hi: int) -> tuple[np.ndarray, np.ndarray]:
        """A K-major sub-block of a Q40 weight (see _quant_kmajor_sub)."""
        return self._quant_kmajor_sub(
            key, out_lo, out_hi, in_lo, in_hi, float_type=Q40,
            block_bytes=Q40_BLOCK_BYTES, unpack=unpack_q40)

    def tensor_q80_kmajor_sub(self, key: str, out_lo: int, out_hi: int,
                              in_lo: int, in_hi: int) -> tuple[np.ndarray, np.ndarray]:
        """A K-major sub-block of a Q80 weight: 34-byte blocks (f16 scale +
        32 int8), landing in the same QuantizedWeight plane layout Q40 uses
        so every downstream path (XLA dequant-dot, Pallas kernel, TP
        sharding) is shared. Reference analogue: the Q80 matmul kernels,
        nn-cpu-ops.cpp."""
        from .quants import Q80_BLOCK_BYTES, unpack_q80

        return self._quant_kmajor_sub(
            key, out_lo, out_hi, in_lo, in_hi, float_type=Q80,
            block_bytes=Q80_BLOCK_BYTES, unpack=unpack_q80)

    def tensor_q40_kmajor(self, key: str) -> tuple[np.ndarray, np.ndarray]:
        """Read a Q40 matmul weight as K-major device planes:
        ``scales: float32 [cols/32, rows]``, ``codes: int8 [cols, rows]``.

        The single-pass native repack (dllama_tpu/native) when built — the
        data-loader hot loop, replacing the reference's per-shard weight
        splitter+streamer (NnRootWeightLoader, nn-network.cpp:809-854) — with
        a numpy transpose fallback.
        """
        rec = self.tensors[key]
        assert rec.float_type == Q40, rec
        rows, cols = rec.shape
        from .. import native

        if native.available():
            out = native.q40_repack_kmajor(self.raw(key), rows, cols)
            if out is not None:
                return out
        scales, codes = self.tensor_q40_planes(key)
        return (np.ascontiguousarray(scales.T.astype(np.float32)),
                np.ascontiguousarray(codes.T))


# ---------------------------------------------------------------------------
# Writer (converter backend + test fixture generator)
# ---------------------------------------------------------------------------


def write_header(f, params: dict) -> None:
    """Write the .m header (reference: converter/writer.py:109-147)."""
    data = b""
    for key, value in params.items():
        k = HeaderKey[key.upper()]
        # LAYER_PATTERN takes the string and stands once a word of it
        if k == HeaderKey.LAYER_PATTERN:
            data += b"".join(struct.pack("<ii", int(k), w)
                             for w in pattern_words(value))
            continue
        # FALCON_H1's float keys take the float and store its float32 bits
        data += struct.pack("<ii", int(k), f32_bits(value)
                            if k in _F32_BITS_KEYS else int(value))
    f.write(struct.pack("<i", MODEL_MAGIC))
    f.write(struct.pack("<i", 8 + len(data)))
    f.write(data)


# ---------------------------------------------------------------------------
# Checksum manifest (sidecar <model>.m.sums)
# ---------------------------------------------------------------------------


def manifest_path(path: str | Path) -> str:
    return str(path) + MANIFEST_SUFFIX


def compute_checksums(mf: "ModelFile") -> dict[str, int]:
    """crc32 of every tensor's on-disk bytes, keyed by walker key
    (``name[.layer[.expert]]``) — one sequential pass over the mmap."""
    return {key: mf.tensor_crc32(key) for key in mf.tensors}


def write_manifest(path: str | Path,
                   checksums: dict[str, int] | None = None) -> str:
    """Write the checksum sidecar for an existing .m file. ``checksums``
    skips the recompute when the caller already has them (the converter
    checksums as it writes). Atomic: written to a temp file then renamed,
    so a crashed writer can never leave a half-manifest that would make a
    GOOD model look corrupt."""
    path = str(path)
    if checksums is None:
        # load_checksums=False: regeneration must not validate (and choke
        # on) the stale sidecar it exists to replace
        with ModelFile.open(path, load_checksums=False) as mf:
            checksums = compute_checksums(mf)
    out = manifest_path(path)
    doc = {"version": MANIFEST_VERSION, "algo": MANIFEST_ALGO,
           "file_size": os.path.getsize(path), "tensors": checksums}
    tmp = out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=0, sort_keys=True)
    os.replace(tmp, out)
    return out


def load_manifest(path: str | Path,
                  file_size: int | None = None) -> dict[str, int] | None:
    """Load the checksum sidecar for a .m file; None when absent (legacy
    files load unverified). A malformed or STALE manifest (recorded
    file_size differs from the actual file) raises — silently skipping
    verification because the sidecar rotted would defeat its purpose."""
    mpath = manifest_path(path)
    if not os.path.exists(mpath):
        return None
    try:
        with open(mpath, encoding="utf-8") as f:
            doc = json.load(f)
        algo, tensors = doc["algo"], doc["tensors"]
        recorded = int(doc["file_size"])
        sums = {str(k): int(v) for k, v in tensors.items()}
    except (ValueError, KeyError, TypeError, AttributeError) as e:
        raise ValueError(f"malformed checksum manifest {mpath}: {e} — "
                         f"regenerate it (python -m dllama_tpu verify "
                         f"--model {path} --write) or delete it to load "
                         f"unverified") from e
    if algo != MANIFEST_ALGO:
        raise ValueError(f"checksum manifest {mpath} uses unsupported "
                         f"algo {algo!r} (want {MANIFEST_ALGO!r})")
    actual = os.path.getsize(path) if file_size is None else file_size
    if recorded != actual:
        raise ValueError(
            f"checksum manifest {mpath} is stale or the model is "
            f"truncated: manifest records {recorded} bytes, file has "
            f"{actual} — reconvert, regenerate the manifest, or delete "
            f"it to load unverified")
    return sums

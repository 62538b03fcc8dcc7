"""HF safetensors / Meta .pth checkpoint → .m converter.

Behavior parity with the reference converter (reference: converter/convert-hf.py
for the plan + config mapping, converter/convert-llama.py for Meta checkpoints,
converter/writer.py for tensor encoding), re-done as a declarative tensor plan
over vectorized numpy codecs (:mod:`dllama_tpu.formats.quants`). No torch
needed for the safetensors path; the Meta path uses torch only to unpickle.
"""

from __future__ import annotations

import json
import zlib
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterable

import numpy as np

from ..formats.mfile import (ArchType, HiddenAct, ModelFile, RopeType,
                             write_header, write_manifest)
from ..formats.quants import F16, F32, Q40, Q80, quantize_q40, quantize_q80

FLOAT_TYPE_BY_NAME = {"f32": F32, "f16": F16, "q40": Q40, "q80": Q80}
FLOAT_NAME_BY_TYPE = {v: k for k, v in FLOAT_TYPE_BY_NAME.items()}

ARCH_BY_MODEL_TYPE = {
    # reference: convert-hf.py:144-152; the MoE entries are ours (the
    # reference can convert Mixtral experts but not run them)
    "llama": ArchType.LLAMA,
    "mistral": ArchType.LLAMA,
    "mixtral": ArchType.LLAMA,
    "qwen3": ArchType.QWEN3,
    "qwen3_moe": ArchType.QWEN3,
    "olmo_hybrid": ArchType.OLMO_HYBRID,
    "laguna": ArchType.LAGUNA,
    "falcon_h1": ArchType.FALCON_H1,
    "axk1": ArchType.AXK1,
    "lfm2_moe": ArchType.LFM2,
    "nemotron_h": ArchType.NEMOTRON_H,
    "granitemoehybrid": ArchType.GRANITE_HYBRID,
    "solar_open2": ArchType.SOLAR_OPEN2,
    "mellum": ArchType.MELLUM,
}

# behind a refusal of ``tie_word_embeddings``: which families do carry a tie
_TIE_CARRIED_BY = ("(a tie is carried by granitemoehybrid, whose head and "
                   "embedding are ONE array, and by the Llama and Qwen3 "
                   "files, which hold it twice)")

HIDDEN_ACT_BY_NAME = {"gelu": HiddenAct.GELU, "silu": HiddenAct.SILU,
                      "relu2": HiddenAct.RELU2}


def _keyed_checksums(path: str | Path, crcs: list[int]) -> dict[str, int]:
    """Attach walker keys to crc32s accumulated in emission order — the
    .m tensor walk IS the converter's emission order, and the directory
    walk reads only the header, so the manifest costs zero re-reads of a
    multi-GB model (write_manifest's recompute path would read it all
    again)."""
    with ModelFile.open(path, load_checksums=False) as mf:
        keys = list(mf.tensors)
    if len(keys) != len(crcs):  # a plan/walk disagreement is a format bug
        raise ValueError(f"converter emitted {len(crcs)} tensors but the "
                         f"walker found {len(keys)} — refusing to write a "
                         f"misaligned checksum manifest")
    return dict(zip(keys, crcs))


def parse_float_type(name: str) -> int:
    try:
        return FLOAT_TYPE_BY_NAME[name]
    except KeyError:
        raise ValueError(f"unsupported float type {name!r}; "
                         f"expected one of {sorted(FLOAT_TYPE_BY_NAME)}") from None


def permute_rope_rows(w: np.ndarray, n_heads: int) -> np.ndarray:
    """Reorder Q/K projection rows from HF's half-split rotary layout to the
    interleaved layout the llama rope kernel expects (reference:
    convert-hf.py:12-15). Operates on ``[out, in]`` weight matrices where
    ``out = n_heads * head_dim``."""
    out_dim = w.shape[0]
    head_dim = out_dim // n_heads
    return (w.reshape(n_heads, 2, head_dim // 2, *w.shape[1:])
            .swapaxes(1, 2)
            .reshape(w.shape))


def encode_tensor(x: np.ndarray, float_type: int) -> bytes:
    """Encode a tensor body the way the reference writer does
    (reference: converter/writer.py:29-107)."""
    flat = np.ascontiguousarray(x, dtype=np.float32).reshape(-1)
    if float_type == F32:
        return flat.tobytes()
    if float_type == F16:
        return flat.astype(np.float16).tobytes()
    if float_type == Q40:
        return quantize_q40(flat)
    if float_type == Q80:
        return quantize_q80(flat)
    raise ValueError(f"unsupported target float type {float_type}")


# ---------------------------------------------------------------------------
# config.json → header params
# ---------------------------------------------------------------------------


def load_hf_config(folder: str | Path, weight_float_type: int) -> dict:
    """Map an HF ``config.json`` to .m header params keyed by
    :class:`~dllama_tpu.formats.mfile.HeaderKey` names
    (reference: convert-hf.py:178-229)."""
    folder = Path(folder)
    with open(folder / "config.json", encoding="utf-8") as f:
        cfg = json.load(f)

    model_type = cfg["model_type"]
    if model_type not in ARCH_BY_MODEL_TYPE:
        raise ValueError(f"unsupported arch type: {model_type}")

    params: dict = {
        "version": 0,
        "arch_type": int(ARCH_BY_MODEL_TYPE[model_type]),
        # the laguna, lfm2_moe and solar_open2 configs name no activation:
        # their feed-forwards are SwiGLU
        # nemotron_h names its feed-forwards' activation ``mlp_hidden_act``
        "hidden_act": int(HIDDEN_ACT_BY_NAME[
            cfg.get("hidden_act", "silu")
            if model_type in ("laguna", "lfm2_moe", "solar_open2")
            else cfg["mlp_hidden_act"] if model_type == "nemotron_h"
            else cfg["hidden_act"]]),
        "dim": cfg["hidden_size"],
        "hidden_dim": cfg["intermediate_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "weight_float_type": weight_float_type,
        "seq_len": cfg["max_position_embeddings"],
        "vocab_size": cfg["vocab_size"],
    }

    # Mixtral: num_local_experts; Qwen3-MoE: num_experts (+ the experts' own
    # hidden size in moe_intermediate_size, which becomes the header's
    # hidden_dim since MoE layers have no dense FFN)
    n_experts = cfg.get("num_local_experts") or cfg.get("num_experts")
    n_active = cfg.get("num_active_local_experts") or cfg.get("num_experts_per_tok")
    params["n_experts"] = int(n_experts) if n_experts else 0
    params["n_active_experts"] = int(n_active) if n_active else 0
    if params["n_experts"] > 0:
        if cfg.get("moe_intermediate_size"):
            params["hidden_dim"] = int(cfg["moe_intermediate_size"])
        # Mixtral always renormalizes the selected router weights; Qwen3-MoE
        # follows norm_topk_prob (HF Qwen3MoeConfig default: False)
        if model_type == "qwen3_moe":
            params["moe_norm_topk"] = int(bool(cfg.get("norm_topk_prob", False)))
            # Mixed dense/MoE stacks (some layers plain MLP) can't be
            # expressed in the .m layer plan, which assumes every layer is
            # MoE — converting one would write expert tensors for layers the
            # checkpoint doesn't have (advisor round-1 finding). Reject.
            sparse_step = int(cfg.get("decoder_sparse_step") or 1)
            mlp_only = list(cfg.get("mlp_only_layers") or [])
            if sparse_step != 1 or mlp_only:
                raise ValueError(
                    f"qwen3_moe with mixed dense/MoE layers is unsupported: "
                    f"decoder_sparse_step={sparse_step}, "
                    f"mlp_only_layers={mlp_only} — every layer must be MoE")
        else:
            params["moe_norm_topk"] = 1

    if model_type == "olmo_hybrid":
        params.update(_olmo_hybrid_header(cfg))
    if model_type == "laguna":
        params.update(_laguna_header(cfg))
    if model_type == "mellum":
        params.update(_mellum_header(cfg))

    if model_type == "axk1":
        return {**params, **_axk1_header(cfg)}
    if model_type == "lfm2_moe":
        return {**params, **_lfm2_header(cfg)}
    if model_type == "nemotron_h":
        return {**params, **_nemotron_h_header(cfg)}
    if model_type == "granitemoehybrid":
        return {**params, **_granite_hybrid_header(cfg)}
    if model_type == "solar_open2":
        return {**params, **_solar_open2_header(cfg)}

    if model_type == "falcon_h1":
        params.update(_falcon_h1_header(cfg))
    elif cfg.get("rope_theta") is not None:
        params["rope_theta"] = int(cfg["rope_theta"])

    rs = cfg.get("rope_scaling")
    if rs is not None:
        if rs.get("rope_type") != "llama3":
            raise ValueError(f"unsupported rope scaling type {rs.get('rope_type')!r}")
        params["rope_scaling_factor"] = int(rs["factor"])
        params["rope_scaling_low_freq_factor"] = int(rs["low_freq_factor"])
        params["rope_scaling_high_freq_factory"] = int(rs["high_freq_factor"])
        params["rope_scaling_orig_max_seq_len"] = int(
            rs["original_max_position_embeddings"])
        params["rope_type"] = int(RopeType.LLAMA3_1)

    if cfg.get("head_dim") is not None:
        params["head_dim"] = cfg["head_dim"]

    eps = cfg.get("rms_norm_eps")
    if eps is not None:
        if eps == 1e-5:
            params["norm_epsilon"] = 5
        elif eps == 1e-6:
            params["norm_epsilon"] = 6
        else:
            raise ValueError(f"unsupported rms_norm_eps {eps}")
    return params


def _olmo_hybrid_header(cfg: dict) -> dict:
    """``model_type: olmo_hybrid``'s config keys as the header's extension
    keys (formats/mfile.py, HeaderKey 22-28). ``layer_types`` must be whole
    periods of linear-attention layers closed by one full layer. Three things
    the published config does not say are taken from the Olmo 2/3 family:
    block norms on a sublayer's output, the q/k norm over the whole
    projection, and, ``rope_parameters.rope_theta`` being null, no rotary
    embedding. The head width is ``hidden_size / num_attention_heads``."""
    kinds = list(cfg["layer_types"])
    period = kinds.index("full_attention") + 1
    want = (["linear_attention"] * (period - 1) + ["full_attention"]) \
        * (len(kinds) // period)
    if kinds != want or len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(
            "olmo_hybrid: layer_types is not whole periods of linear "
            "layers closed by one full layer")
    rope = (cfg.get("rope_parameters") or {}).get("rope_theta")
    out = {
        "layer_period": period,
        "linear_n_key_heads": cfg["linear_num_key_heads"],
        "linear_n_value_heads": cfg["linear_num_value_heads"],
        "linear_key_head_dim": cfg["linear_key_head_dim"],
        "linear_value_head_dim": cfg["linear_value_head_dim"],
        "linear_conv_kernel": cfg["linear_conv_kernel_dim"],
        "linear_neg_eigval": int(bool(cfg.get("linear_allow_neg_eigval"))),
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
    }
    if rope is not None:
        raise ValueError(
            f"olmo_hybrid: rope_parameters.rope_theta is {rope!r}; this "
            f"architecture's full layers carry no rotary embedding here")
    return out


def _falcon_h1_header(cfg: dict) -> dict:
    """``model_type: falcon_h1``'s config keys as the header's extension
    keys (formats/mfile.py, HeaderKey 39-59): the ``mamba_*`` sizes, the
    rotary base as a float (1e11 does not fit the integer key) and the
    fourteen multipliers. What the layer equation here does not carry is
    refused (a projection bias, a mixer whose norm is absent or comes
    before the gate, attention in some layers only)."""
    if cfg.get("attention_bias") or cfg.get("mamba_proj_bias") \
            or cfg.get("mlp_bias") or cfg.get("projectors_bias") \
            or not cfg.get("mamba_conv_bias", True) \
            or not cfg.get("mamba_rms_norm", True) \
            or cfg.get("mamba_norm_before_gate") \
            or cfg.get("attn_layer_indices") is not None \
            or cfg.get("rope_scaling") is not None \
            or cfg.get("tie_word_embeddings"):
        raise ValueError(
            "falcon_h1: projection biases, a convolution without its bias, "
            "a mixer without its gated norm or with the norm before the "
            "gate, attention in some layers only, rope scaling and tied "
            "embeddings are not carried " + _TIE_CARRIED_BY)
    heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if heads * hd != int(cfg["mamba_d_ssm"]):
        raise ValueError(
            f"falcon_h1: mamba_d_ssm {cfg['mamba_d_ssm']} is not "
            f"{heads} heads of {hd}")
    gate, down = cfg["mlp_multipliers"]
    z, x, b, c, dt = cfg["ssm_multipliers"]
    return {
        "ssm_n_heads": heads, "ssm_head_dim": hd,
        "ssm_n_groups": int(cfg["mamba_n_groups"]),
        "ssm_state_dim": int(cfg["mamba_d_state"]),
        "ssm_conv_kernel": int(cfg["mamba_d_conv"]),
        "ssm_chunk_size": int(cfg["mamba_chunk_size"]),
        "rope_theta_f32": float(cfg["rope_theta"]),
        "embedding_mult": float(cfg["embedding_multiplier"]),
        "lm_head_mult": float(cfg["lm_head_multiplier"]),
        "attn_in_mult": float(cfg["attention_in_multiplier"]),
        "attn_out_mult": float(cfg["attention_out_multiplier"]),
        "key_mult": float(cfg["key_multiplier"]),
        "ssm_in_mult": float(cfg["ssm_in_multiplier"]),
        "ssm_out_mult": float(cfg["ssm_out_multiplier"]),
        "mlp_gate_mult": float(gate), "mlp_down_mult": float(down),
        "ssm_mult_z": float(z), "ssm_mult_x": float(x),
        "ssm_mult_b": float(b), "ssm_mult_c": float(c),
        "ssm_mult_dt": float(dt),
    }


def _axk1_header(cfg: dict) -> dict:
    """``model_type: axk1``'s config keys as the header's extension keys
    (formats/mfile.py, HeaderKey 60-69, the share's 33-38 and the
    rope-scaling keys 14-17 for YaRN over the rope lanes). A whole checkpoint
    holds every expert: the router's width is ``n_routed_experts`` and the
    first held expert 0 (a share is written by whoever cuts one).
    ``topk_method: "none"`` is read as "no score-correction bias", the
    selection group-limited as ``n_group`` / ``topk_group`` state; what the
    config does not say (pre-norm, an ungated shared expert, an RMS norm on
    both latents) the arch implies (models/axk1.py)."""
    rs = cfg.get("rope_scaling") or {}
    if (cfg.get("attention_bias") or cfg.get("moe_layer_freq", 1) != 1
            or cfg.get("hidden_act", "silu") != "silu"
            or cfg.get("tie_word_embeddings")
            or cfg.get("scoring_func") not in ("sigmoid", "softmax")
            or cfg.get("topk_method", "none") not in ("none", "group_limited_greedy")
            or rs.get("type", rs.get("rope_type")) != "yarn"
            or cfg.get("rms_norm_eps") != 1e-6):
        raise ValueError(
            "axk1: attention bias, an expert layer frequency other than 1, "
            "an activation other than silu, tied embeddings, a scoring "
            "function other than sigmoid or softmax, a top-k method with a "
            "score-correction bias, a rope scaling other than yarn or a norm "
            "epsilon other than 1e-6 are not carried " + _TIE_CARRIED_BY)
    n_shared = int(cfg.get("n_shared_experts") or 0)
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "n_experts": int(cfg["n_routed_experts"]),
        "n_active_experts": int(cfg["num_experts_per_tok"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", False))),
        "head_dim": int(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
        "norm_epsilon": 6,
        "rope_theta": int(cfg["rope_theta"]),
        "rope_type": int(RopeType.YARN),
        "rope_scaling_factor": int(rs["factor"]),
        "rope_scaling_low_freq_factor": int(rs["beta_slow"]),
        "rope_scaling_high_freq_factory": int(rs["beta_fast"]),
        "rope_scaling_orig_max_seq_len": int(
            rs["original_max_position_embeddings"]),
        "yarn_mscale": float(rs.get("mscale", 1.0)),
        "yarn_mscale_all_dim": float(rs.get("mscale_all_dim", 0.0)),
        "q_lora_rank": int(cfg["q_lora_rank"]),
        "kv_lora_rank": int(cfg["kv_lora_rank"]),
        "qk_nope_head_dim": int(cfg["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(cfg["qk_rope_head_dim"]),
        "v_head_dim": int(cfg["v_head_dim"]),
        "moe_n_group": int(cfg.get("n_group") or 0),
        "moe_topk_group": int(cfg.get("topk_group") or 0),
        "moe_score_func": int(cfg["scoring_func"] == "sigmoid"),
        "n_dense_layers": int(cfg.get("first_k_dense_replace") or 0),
        "dense_hidden_dim": int(cfg["intermediate_size"]),
        "shared_expert_dim": n_shared * int(cfg["moe_intermediate_size"]),
        "moe_routed_scale_milli": int(round(
            float(cfg.get("routed_scaling_factor", 1.0)) * 1000)),
        "moe_router_width": int(cfg["n_routed_experts"]),
        "moe_first_expert": 0,
    }


def _nemotron_h_header(cfg: dict) -> dict:
    """``model_type: nemotron_h``'s config keys as the header's extension
    keys (formats/mfile.py, HeaderKey 72-73, the mixer's 39-44, the share's
    35-38, 21, 67 and 71). ``hybrid_override_pattern`` is a string over ``M *
    E`` of ``num_hidden_layers`` characters. A whole checkpoint holds every
    expert: the router's width is ``n_routed_experts`` and the first held
    expert 0. What the config does not say (no positions in attention, the
    ``z x B C dt`` order of the in-projection's rows, the gate before the
    grouped norm, the router and the shared expert on the layer's input, the
    ``1e-20`` in the weights' renormalisation) the arch implies
    (models/nemotron_h.py). The multi-token-prediction head
    (``num_nextn_predict_layers``) is not written: it never enters the
    next-token logits, and nothing here serves it."""
    pattern = cfg["hybrid_override_pattern"]
    heads = int(cfg["mamba_num_heads"])
    eps = cfg.get("layer_norm_epsilon", cfg.get("norm_eps"))
    if (len(pattern) != cfg["num_hidden_layers"] or set(pattern) - set("M*E")
            or heads * cfg["mamba_head_dim"]
            != cfg["expand"] * cfg["hidden_size"]
            or eps not in (1e-5, 1e-6)):
        raise ValueError(
            "nemotron_h: hybrid_override_pattern is not num_hidden_layers "
            "characters over M * E, the mixer's heads are not expand x "
            "hidden_size wide, or the norm epsilon is neither 1e-5 nor 1e-6")
    if (cfg.get("mlp_hidden_act") != "relu2" or not cfg.get("use_conv_bias")
            or cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1
            or cfg.get("n_shared_experts", 1) != 1
            or any(cfg.get(k) for k in ("attention_bias", "mlp_bias",
                                        "mamba_proj_bias", "use_bias"))):
        raise ValueError(
            "nemotron_h: another activation than relu2, a projection bias, a "
            "convolution without its bias, a group limit or more than one "
            "shared expert are not carried")
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "n_experts": int(cfg["n_routed_experts"]),
        "n_active_experts": int(cfg["num_experts_per_tok"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", True))),
        "head_dim": int(cfg["head_dim"]),
        "norm_epsilon": 5 if eps == 1e-5 else 6,
        "rope_theta": int(cfg.get("rope_theta", 10000)),
        "shared_expert_dim": int(cfg["moe_shared_expert_intermediate_size"]),
        "moe_routed_scale_milli": int(round(
            float(cfg.get("routed_scaling_factor", 1.0)) * 1000)),
        "moe_router_width": int(cfg["n_routed_experts"]),
        "moe_first_expert": 0,
        "ssm_n_heads": heads,
        "ssm_head_dim": int(cfg["mamba_head_dim"]),
        "ssm_n_groups": int(cfg["n_groups"]),
        "ssm_state_dim": int(cfg["ssm_state_size"]),
        "ssm_conv_kernel": int(cfg["conv_kernel"]),
        "ssm_chunk_size": int(cfg["chunk_size"]),
        "moe_score_func": 1,
        "moe_select_bias": 1,
        "moe_latent_dim": int(cfg.get("moe_latent_size") or 0),
        "layer_pattern": pattern,
    }


def _ssd_mixer_items(mx, wt: int, n_dt: int) -> list["PlanItem"]:
    """One SSD mixer's tensors in the walk's order, ``mx(name)`` the
    candidate keys of the checkpoint's tensor ``name``: ``in_proj`` split (its
    last ``n_dt`` rows are the float32 ``dt`` plane), ``conv1d.weight`` ``[C, 1,
    K]`` as taps ``[K, C]`` (tap ``K - 1`` on the current position)."""
    return [
        PlanItem(mx("in_proj.weight"), wt, lambda w: w[:-n_dt]),
        PlanItem(mx("in_proj.weight"), F32, lambda w: w[-n_dt:]),
        PlanItem(mx("conv1d.weight"), F32,
                 lambda w: np.ascontiguousarray(w[:, 0, :].T)),
        PlanItem(mx("conv1d.bias"), F32),
        PlanItem(mx("A_log"), F32),
        PlanItem(mx("D"), F32),
        PlanItem(mx("dt_bias"), F32),
        PlanItem(mx("norm.weight"), F32),
        PlanItem(mx("out_proj.weight"), wt)]


def _nemotron_h_plan(params: dict) -> list["PlanItem"]:
    """``model_type: nemotron_h``'s tensors in the order
    ``mfile._walk_nemotron_h_layer`` reads them. A layer's block is
    ``<prefix>.layers.N.mixer`` whatever its kind, its norm
    ``<prefix>.layers.N.norm``; ``<prefix>`` is ``backbone`` (``model`` is
    taken too). The mixer's ``in_proj`` is split: its last ``mamba_num_heads``
    rows are the float32 ``dt`` plane; ``conv1d.weight`` ``[C, 1, K]`` becomes
    taps ``[K, C]`` (tap ``K - 1`` on the current position, as the causal
    convolution has it)."""
    wt = params["weight_float_type"]
    n_dt = params["ssm_n_heads"]
    both = lambda tail: (f"backbone.{tail}", f"model.{tail}")
    plan = [PlanItem(both("embeddings.weight") + ("model.embed_tokens.weight",),
                     F32)]
    for l, kind in enumerate(params["layer_pattern"]):
        mx = lambda name, l=l: both(f"layers.{l}.mixer.{name}")
        if kind == "M":
            plan += _ssd_mixer_items(mx, wt, n_dt)
        elif kind == "*":
            plan += [PlanItem(mx(f"{p}_proj.weight"), wt) for p in "qkvo"]
        else:
            plan += [PlanItem(mx("gate.weight"), F32),
                     PlanItem(mx("gate.e_score_correction_bias"), F32)]
            if params["moe_latent_dim"]:
                plan.append(PlanItem(mx("fc1_latent_proj.weight"), wt))
            for e in range(params["n_experts"]):
                plan += [PlanItem(mx(f"experts.{e}.up_proj.weight"), wt),
                         PlanItem(mx(f"experts.{e}.down_proj.weight"), wt)]
            if params["moe_latent_dim"]:
                plan.append(PlanItem(mx("fc2_latent_proj.weight"), wt))
            plan += [PlanItem(mx("shared_experts.up_proj.weight"), wt),
                     PlanItem(mx("shared_experts.down_proj.weight"), wt)]
        plan.append(PlanItem(both(f"layers.{l}.norm.weight"), F32))
    plan.append(PlanItem(both("norm_f.weight") + ("model.norm.weight",), F32))
    plan.append(PlanItem(("lm_head.weight",), wt))
    return plan


def _granite_hybrid_header(cfg: dict) -> dict:
    """``model_type: granitemoehybrid``'s config keys as the header's
    extension keys (formats/mfile.py, HeaderKey 74-76, 46-47, the pattern 72,
    the mixer's 39-44, the share's 35-38, 21 and 67). ``layer_types`` is
    ``num_hidden_layers`` entries of ``mamba`` / ``attention``; the header's
    pattern is of BLOCKS, two a layer (``ME`` / ``*E``), and ``n_layers`` counts
    them. ``intermediate_size`` is read as an expert's width (the config has
    no key of its own for it), the head's width is ``hidden_size /
    num_attention_heads``, ``rope_theta`` is not read (``position_embedding_
    type: nope``). What the config does not say (the ``z x B C dt`` order of
    the in-projection's rows, the gate before the grouped norm, ``dt``
    unclamped, the fused ``input_linear``'s first half under ``silu``) the
    arch implies (models/granite_hybrid.py)."""
    from ..models.granite_hybrid import BLOCKS, layer_pattern

    kinds = list(cfg["layer_types"])
    heads = int(cfg["mamba_n_heads"])
    eps = cfg.get("rms_norm_eps")
    if (len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(BLOCKS)
            or heads * cfg["mamba_d_head"]
            != cfg["mamba_expand"] * cfg["hidden_size"]
            or eps not in (1e-5, 1e-6)):
        raise ValueError(
            "granitemoehybrid: layer_types is not num_hidden_layers entries "
            "of mamba / attention, the mixer's heads are not mamba_expand x "
            "hidden_size wide, or the norm epsilon is neither 1e-5 nor 1e-6")
    if (cfg.get("hidden_act", "silu") != "silu"
            or not cfg.get("mamba_conv_bias", True)
            or cfg.get("position_embedding_type", "nope") != "nope"
            or cfg.get("normalization_function", "rmsnorm") != "rmsnorm"
            or not cfg.get("num_local_experts")
            or not cfg.get("shared_intermediate_size")
            or any(cfg.get(k) for k in ("attention_bias", "mamba_proj_bias"))):
        raise ValueError(
            "granitemoehybrid: another activation than silu, a projection "
            "bias, a convolution without its bias, positions in attention "
            "(rope), another norm than rmsnorm, or a layer without routed "
            "experts and a shared one are not carried")
    return {
        "n_layers": 2 * len(kinds),
        "hidden_dim": int(cfg["intermediate_size"]),
        "n_experts": int(cfg["num_local_experts"]),
        "n_active_experts": int(cfg["num_experts_per_tok"]),
        "moe_norm_topk": 1,
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "norm_epsilon": 5 if eps == 1e-5 else 6,
        "rope_theta": int(cfg.get("rope_theta") or 10000),
        "shared_expert_dim": int(cfg["shared_intermediate_size"]),
        "moe_routed_scale_milli": 1000,
        "moe_router_width": int(cfg["num_local_experts"]),
        "moe_first_expert": 0,
        "ssm_n_heads": heads,
        "ssm_head_dim": int(cfg["mamba_d_head"]),
        "ssm_n_groups": int(cfg["mamba_n_groups"]),
        "ssm_state_dim": int(cfg["mamba_d_state"]),
        "ssm_conv_kernel": int(cfg["mamba_d_conv"]),
        "ssm_chunk_size": int(cfg["mamba_chunk_size"]),
        "moe_score_func": 0,
        "embedding_mult": float(cfg.get("embedding_multiplier", 1.0)),
        "lm_head_mult": 1.0 / float(cfg.get("logits_scaling", 1.0)),
        "residual_mult": float(cfg.get("residual_multiplier", 1.0)),
        "attn_scale": float(cfg["attention_multiplier"]),
        "tied_embeddings": int(bool(cfg.get("tie_word_embeddings"))),
        "layer_pattern": layer_pattern(kinds),
    }


def _granite_hybrid_plan(params: dict) -> list["PlanItem"]:
    """``model_type: granitemoehybrid``'s tensors in the order
    ``mfile._walk_nemotron_h_layer`` reads a GRANITE_HYBRID file's blocks.
    Published layer ``N`` is blocks ``2N`` (``model.layers.N.mamba`` or
    ``.self_attn`` behind ``input_layernorm``) and ``2N + 1`` (``block_sparse_moe``
    and ``shared_mlp`` behind ``post_attention_layernorm``). The mixer's
    ``in_proj`` and ``conv1d`` as :func:`_nemotron_h_plan` splits them. The
    fused ``input_linear`` ``[E, 2 W, dim]`` (``[2 W, dim]`` for the shared
    expert) is split into the halves the kernels read as planes: the FIRST
    ``W`` rows are the one under ``silu`` (``w1``), the second the other
    (``w3``). A tied head is written from the embedding (the reference
    format's walk ends in the head; the loader keeps one array)."""
    wt = params["weight_float_type"]
    n_dt, hid, wide = (params["ssm_n_heads"], params["hidden_dim"],
                       params["shared_expert_dim"])
    plan = [PlanItem(("model.embed_tokens.weight",), F32)]
    blocks = params["layer_pattern"]
    for n in range(len(blocks) // 2):
        at = lambda name, n=n: (f"model.layers.{n}.{name}",)
        if blocks[2 * n] == "M":
            plan += _ssd_mixer_items(lambda name: at("mamba." + name), wt,
                                     n_dt)
        else:
            plan += [PlanItem(at(f"self_attn.{p}_proj.weight"), wt)
                     for p in "qkvo"]
        plan.append(PlanItem(at("input_layernorm.weight"), F32))
        fused, out = (at("block_sparse_moe.input_linear.weight"),
                      at("block_sparse_moe.output_linear.weight"))
        plan.append(PlanItem(at("block_sparse_moe.router.layer.weight"), F32))
        for e in range(params["n_experts"]):
            plan += [PlanItem(fused, wt, lambda w, e=e: w[e, hid:]),
                     PlanItem(fused, wt, lambda w, e=e: w[e, :hid]),
                     PlanItem(out, wt, lambda w, e=e: w[e])]
        shared = at("shared_mlp.input_linear.weight")
        plan += [PlanItem(shared, wt, lambda w: w[:wide]),
                 PlanItem(at("shared_mlp.output_linear.weight"), wt),
                 PlanItem(shared, wt, lambda w: w[wide:]),
                 PlanItem(at("post_attention_layernorm.weight"), F32)]
    plan.append(PlanItem(("model.norm.weight",), F32))
    plan.append(PlanItem(("lm_head.weight", "model.embed_tokens.weight"), wt))
    return plan


def _solar_open2_header(cfg: dict) -> dict:
    """``model_type: solar_open2``'s config keys as the header's extension keys
    (formats/mfile.py, HeaderKey 77-79, the hybrid's 22-28, the share's 35-38,
    21, 67 and 71). ``gqa_layers`` must be the FIRST layer of every period of
    ``gqa_interval + 1``. A whole checkpoint holds every expert: the router's
    width is ``n_routed_experts`` and the first held expert 0. What the config
    does not say (pre-norm; the full layers' gate a plane of the normed input,
    one sigmoid a lane in front of ``o_proj``; the low-rank gates' inner width
    = ``linear_attn_config.head_dim``, which is the Kimi Delta Attention
    paper's; a decay a key channel from ``A_log`` a head and ``dt_bias`` a
    channel; a sigmoid router whose bias enters the selection only; an
    ungated shared expert ``moe_intermediate_size`` wide; ``intermediate_size``
    unread, there being no dense layer) the arch implies
    (models/solar_open2.py)."""
    lin = cfg["linear_attn_config"]
    P, L = int(cfg["gqa_interval"]) + 1, int(cfg["num_hidden_layers"])
    eps = cfg.get("rms_norm_eps")
    if L % P or list(cfg["gqa_layers"]) != list(range(0, L, P)) \
            or eps not in (1e-5, 1e-6) \
            or lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError(
            "solar_open2: gqa_layers is not the first of every gqa_interval "
            "+ 1 layers in whole periods, the norm epsilon is neither 1e-5 "
            "nor 1e-6, or the mixer's K/V heads are not its heads")
    if (cfg.get("use_rope") or not cfg.get("use_gqa_gate")
            or cfg.get("kda_use_full_proj") or cfg.get("first_k_dense_replace")
            or cfg.get("n_shared_experts") != 1
            or cfg.get("tie_word_embeddings")):
        raise ValueError(
            "solar_open2: a rotary embedding, an ungated full layer, "
            "full-rank gate projections, leading dense layers, another "
            "count of shared experts than one and tied embeddings are not "
            "carried " + _TIE_CARRIED_BY)
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "n_experts": int(cfg["n_routed_experts"]),
        "n_active_experts": int(cfg["num_experts_per_tok"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", True))),
        "head_dim": int(cfg["head_dim"]),
        "norm_epsilon": 5 if eps == 1e-5 else 6,
        "layer_period": P,
        "full_layer_at": 0,
        "linear_n_key_heads": int(lin["num_heads"]),
        "linear_n_value_heads": int(lin["num_heads"]),
        "linear_key_head_dim": int(lin["head_dim"]),
        "linear_value_head_dim": int(lin["head_dim"]),
        "linear_conv_kernel": int(lin["short_conv_kernel_size"]),
        "linear_neg_eigval": int(bool(cfg.get("kda_allow_neg_eigval"))),
        "linear_decay_dim": int(lin["head_dim"]),
        "linear_gate_rank": int(lin["head_dim"]),
        "shared_expert_dim": int(cfg["moe_intermediate_size"]),
        "moe_routed_scale_milli": round(
            1000 * float(cfg.get("routed_scaling_factor", 1.0))),
        "moe_router_width": int(cfg["n_routed_experts"]),
        "moe_first_expert": 0,
        "moe_score_func": 1,
        "moe_select_bias": 1,
    }


def _solar_open2_plan(params: dict) -> list["PlanItem"]:
    """``model_type: solar_open2``'s tensors in the order
    ``mfile._walk_solar_open2_layer`` reads them. NO CHECKPOINT WAS AT HAND:
    the delta-rule layers' names are the Kimi Delta Attention reference
    implementation's (``self_attn.q_proj k_proj v_proj``, ``q_conv1d k_conv1d
    v_conv1d`` with weights ``[C, 1, K]``, ``A_log``, ``f_a_proj f_b_proj``,
    ``dt_bias``, ``b_proj``, ``g_a_proj g_b_proj``, ``o_norm``, ``o_proj``), whose
    ``linear_attn_config`` keys this config repeats letter for letter; the
    routed half's are the DeepSeek-V3 family's (``mlp.gate`` with its
    ``e_score_correction_bias``, ``mlp.experts.N.*``, ``mlp.shared_experts.*``);
    the full layers' gate is ASSUMED to be ``self_attn.g_proj``. A name that
    differs fails with the tensor it did not find."""
    wt = params["weight_float_type"]
    P, at0 = params["layer_period"], params["full_layer_at"]
    taps = lambda w: np.ascontiguousarray(w[:, 0, :].T)   # [C, 1, K] -> [K, C]
    flat = lambda w: np.ascontiguousarray(w.reshape(-1))
    plan = [PlanItem(("model.embed_tokens.weight",), F32)]
    for l in range(params["n_layers"]):
        at = lambda name, l=l: (f"model.layers.{l}.{name}",)
        sa = lambda name: at("self_attn." + name)
        if l % P == at0:
            plan += [PlanItem(sa(f"{p}_proj.weight"), wt) for p in "qkvog"]
        else:
            plan += [PlanItem(sa(f"{p}_proj.weight"), wt) for p in "qkv"]
            plan += [PlanItem(sa(f"{p}_conv1d.weight"), F32, taps)
                     for p in "qkv"]
            plan += [PlanItem(sa("A_log"), F32, flat),
                     PlanItem(sa("f_a_proj.weight"), F32),
                     PlanItem(sa("f_b_proj.weight"), F32),
                     PlanItem(sa("dt_bias"), F32, flat),
                     PlanItem(sa("b_proj.weight"), F32),
                     PlanItem(sa("g_a_proj.weight"), F32),
                     PlanItem(sa("g_b_proj.weight"), F32),
                     PlanItem(sa("o_norm.weight"), F32),
                     PlanItem(sa("o_proj.weight"), wt)]
        plan += [PlanItem(at("mlp.gate.weight"), F32),
                 PlanItem(at("mlp.gate.e_score_correction_bias"), F32)]
        for e in range(params["n_experts"]):
            plan += [PlanItem(at(f"mlp.experts.{e}.{p}_proj.weight"), wt)
                     for p in ("up", "gate", "down")]
        plan += [PlanItem(at(f"mlp.shared_experts.{p}_proj.weight"), wt)
                 for p in ("gate", "down", "up")]
        plan += [PlanItem(at("input_layernorm.weight"), F32),
                 PlanItem(at("post_attention_layernorm.weight"), F32)]
    plan.append(PlanItem(("model.norm.weight",), F32))
    plan.append(PlanItem(("lm_head.weight",), wt))
    return plan


def _lfm2_header(cfg: dict) -> dict:
    """``model_type: lfm2_moe``'s config keys as the header's extension keys
    (formats/mfile.py, HeaderKey 70-71, 22, the share's 33-38, 21 and 67).
    ``layer_types`` must be ``num_dense_layers`` leading ``conv`` layers and
    then periods of one ``full_attention`` layer and ``conv`` ones (the last
    may be cut short). A whole checkpoint holds every expert: the router's
    width is ``num_experts`` and the first held expert 0. What the config
    does not say (pre-norm, SwiGLU, the half-split rotary pairing, the order
    ``B C X`` of the in-projection's rows, the ``1e-6`` in the weights'
    renormalisation, the expert bias entering the selection only, a sigmoid
    router, tied embeddings written twice) the arch implies
    (models/lfm2.py)."""
    kinds = list(cfg["layer_types"])
    lead = int(cfg.get("num_dense_layers") or 0)
    behind = kinds[lead:]
    period = (behind.index("full_attention", 1)
              if "full_attention" in behind[1:] else len(behind))
    want = ["conv"] * lead + [
        "full_attention" if i % period == 0 else "conv"
        for i in range(len(behind))]
    if kinds != want or len(kinds) != cfg["num_hidden_layers"] or not lead \
            or not behind:
        raise ValueError(
            "lfm2_moe: layer_types is not num_dense_layers leading conv "
            "layers and then periods of a full_attention layer and conv ones")
    rope = cfg.get("rope_parameters") or {}
    if cfg.get("conv_bias") or rope.get("rope_type", "default") != "default" \
            or cfg.get("norm_eps") not in (1e-5, 1e-6):
        raise ValueError(
            "lfm2_moe: a convolution bias, a scaled rotary table or a norm "
            "epsilon other than 1e-5 / 1e-6 are not carried")
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "n_experts": int(cfg["num_experts"]),
        "n_active_experts": int(cfg["num_experts_per_tok"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", False))),
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "norm_epsilon": 5 if cfg["norm_eps"] == 1e-5 else 6,
        "rope_theta": int(rope.get("rope_theta", cfg.get("rope_theta", 1e6))),
        "rope_type": int(RopeType.FALCON),
        "layer_period": period,
        "short_conv_kernel": int(cfg["conv_L_cache"]),
        "moe_select_bias": int(bool(cfg.get("use_expert_bias"))),
        "moe_score_func": 1,
        "n_dense_layers": lead,
        "dense_hidden_dim": int(cfg["intermediate_size"]),
        "shared_expert_dim": 0,
        "moe_routed_scale_milli": int(round(
            float(cfg.get("routed_scaling_factor", 1.0)) * 1000)),
        "moe_router_width": int(cfg["num_experts"]),
        "moe_first_expert": 0,
    }


def _laguna_header(cfg: dict) -> dict:
    """``model_type: laguna``'s config keys as the header's extension keys
    (formats/mfile.py, HeaderKey 22, 29-38, and the rope-scaling keys 14-17
    for the full layers' YaRN table). ``layer_types`` must be whole periods
    of one full layer and then sliding ones, ``num_attention_heads_per_layer``
    one number a kind, ``mlp_only_layers`` the leading layers. A whole
    checkpoint holds every expert: the router's width is ``num_experts`` and
    the first held expert 0 (a share is written by whoever cuts one). What
    the published config does not say (pre-norm, no q/k norm, a softmax
    router, an ungated shared expert, the per-head gate's form, a window
    that counts the current token) the arch implies (models/laguna.py)."""
    kinds = list(cfg["layer_types"])
    heads = list(cfg["num_attention_heads_per_layer"])
    period = (kinds.index("full_attention", 1)
              if "full_attention" in kinds[1:] else len(kinds))
    want = (["full_attention"] + ["sliding_attention"] * (period - 1)) \
        * (len(kinds) // period)
    slide = {h for h, k in zip(heads, kinds) if k == "sliding_attention"}
    full = {h for h, k in zip(heads, kinds) if k == "full_attention"}
    if (kinds != want or len(kinds) != cfg["num_hidden_layers"]
            or len(heads) != len(kinds) or len(slide) != 1
            or full != {cfg["num_attention_heads"]}):
        raise ValueError(
            "laguna: layer_types is not whole periods of a full layer and "
            "then sliding ones, or num_attention_heads_per_layer is not one "
            "number a layer kind")
    dense = list(cfg.get("mlp_only_layers") or [])
    if dense != list(range(len(dense))) or len(dense) > 1 \
            or int(cfg.get("decoder_sparse_step") or 1) != 1:
        raise ValueError(
            f"laguna: mlp_only_layers {dense} must be the leading layer (or "
            f"none) and decoder_sparse_step 1")
    if cfg.get("gating") != "per-head" or cfg.get("attention_bias") \
            or cfg.get("moe_apply_router_weight_on_input") \
            or cfg.get("moe_router_logit_softcapping"):
        raise ValueError(
            "laguna: a gate that is not per head, attention bias, the "
            "router's weight on the input or a router soft cap are not "
            "carried")
    rope = cfg["rope_parameters"]
    rf, rs = rope["full_attention"], rope["sliding_attention"]
    if rf.get("rope_type") != "yarn" or rs.get("rope_type") != "default" \
            or rs.get("partial_rotary_factor", 1) != 1:
        raise ValueError("laguna: rope_parameters must be yarn on the full "
                         "layers and default over the whole head on the "
                         "sliding ones")
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", False))),
        "rope_theta": int(rf["rope_theta"]),
        "rope_type": int(RopeType.YARN),
        "rope_scaling_factor": int(rf["factor"]),
        "rope_scaling_low_freq_factor": int(rf["beta_slow"]),
        "rope_scaling_high_freq_factory": int(rf["beta_fast"]),
        "rope_scaling_orig_max_seq_len": int(
            rf["original_max_position_embeddings"]),
        "layer_period": period,
        "sliding_window": int(cfg["sliding_window"]),
        "n_heads_sliding": slide.pop(),
        "rope_theta_sliding": int(rs["rope_theta"]),
        "rope_dim": int(round(cfg["head_dim"]
                              * rf.get("partial_rotary_factor", 1))),
        "n_dense_layers": len(dense),
        "dense_hidden_dim": int(cfg["intermediate_size"]),
        "shared_expert_dim": int(
            cfg.get("shared_expert_intermediate_size") or 0),
        "moe_routed_scale_milli": int(round(
            float(cfg.get("moe_routed_scaling_factor", 1.0)) * 1000)),
        "moe_router_width": int(cfg["num_experts"]),
        "moe_first_expert": 0,
    }


def _mellum_header(cfg: dict) -> dict:
    """``model_type: mellum``'s config keys as LAGUNA's extension keys and
    FULL_LAYER_AT (formats/mfile.py). ``layer_types`` must be whole periods
    of sliding layers CLOSED by a full one, ``mlp_layer_types`` all sparse;
    one head count, the whole head rotating in both kinds, no shared expert,
    a whole checkpoint's every expert held. What the published config does
    not say (pre-norm, the per-head q/k norm of the Qwen3-MoE layout whose
    keys it uses, a window that counts the current token) the arch implies
    (models/mellum.py)."""
    kinds = list(cfg["layer_types"])
    period = (kinds.index("full_attention") + 1
              if "full_attention" in kinds else 0)
    want = (["sliding_attention"] * (period - 1) + ["full_attention"]) \
        * (len(kinds) // max(period, 1))
    if period < 2 or kinds != want or len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(
            "mellum: layer_types is not whole periods of sliding layers "
            "closed by a full one")
    if set(cfg.get("mlp_layer_types") or ["sparse"]) != {"sparse"} \
            or cfg.get("attention_bias") or not cfg.get("use_sliding_window", True) \
            or cfg.get("max_window_layers") \
            or cfg.get("shared_expert_intermediate_size"):
        raise ValueError(
            "mellum: a dense layer, attention bias, a shared expert, "
            "max_window_layers or use_sliding_window false are not carried")
    rope = cfg["rope_parameters"]
    rf, rs = rope["full_attention"], rope["sliding_attention"]
    if rf.get("rope_type") != "yarn" or rs.get("rope_type") != "default" \
            or rf.get("partial_rotary_factor", 1) != 1 \
            or rs.get("partial_rotary_factor", 1) != 1:
        raise ValueError("mellum: rope_parameters must be yarn on the full "
                         "layers and default on the sliding ones, both over "
                         "the whole head")
    return {
        "hidden_dim": int(cfg["moe_intermediate_size"]),
        "head_dim": int(cfg["head_dim"]),
        "moe_norm_topk": int(bool(cfg.get("norm_topk_prob", False))),
        "rope_theta": int(rf["rope_theta"]),
        "rope_type": int(RopeType.YARN),
        "rope_scaling_factor": int(rf["factor"]),
        "rope_scaling_low_freq_factor": int(rf["beta_slow"]),
        "rope_scaling_high_freq_factory": int(rf["beta_fast"]),
        "rope_scaling_orig_max_seq_len": int(
            rf["original_max_position_embeddings"]),
        "layer_period": period,
        "full_layer_at": period - 1,
        "sliding_window": int(cfg["sliding_window"]),
        "n_heads_sliding": int(cfg["num_attention_heads"]),
        "rope_theta_sliding": int(rs["rope_theta"]),
        "rope_dim": int(cfg["head_dim"]),
        "moe_routed_scale_milli": 1000,
        "moe_router_width": int(cfg["num_experts"]),
        "moe_first_expert": 0,
    }


# ---------------------------------------------------------------------------
# tensor plan
# ---------------------------------------------------------------------------


@dataclass
class PlanItem:
    """One tensor to emit: candidate source keys (first found wins — the
    second entry expresses lm_head→embedding weight tying,
    reference: convert-hf.py:101-102), target encoding, optional transform."""

    keys: tuple[str, ...]
    float_type: int
    transform: Callable[[np.ndarray], np.ndarray] | None = None


def hf_tensor_plan(params: dict) -> list[PlanItem]:
    """The .m tensor emission order for an HF checkpoint
    (reference: convert-hf.py:58-102; consumed by llm.cpp:499-539 and our
    :meth:`dllama_tpu.formats.mfile.ModelFile._walk`)."""
    wt = params["weight_float_type"]
    arch = ArchType(params["arch_type"])
    if arch == ArchType.OLMO_HYBRID:
        raise NotImplementedError(
            "olmo_hybrid: the header is mapped (load_hf_config), the "
            "checkpoint's tensor names are not: they could not be read where "
            "this was written, and a guessed map is worse than none. The "
            "target layout is formats/mfile.py's _walk_hybrid_layer")
    if arch == ArchType.LAGUNA:
        raise NotImplementedError(
            "laguna: the header is mapped (load_hf_config), the checkpoint's "
            "tensor names are not: they could not be read where this was "
            "written, and a guessed map is worse than none. The target "
            "layout is formats/mfile.py's _walk_laguna_layer")
    if arch == ArchType.MELLUM:
        raise NotImplementedError(
            "mellum: the header is mapped (load_hf_config), the checkpoint's "
            "tensor names are not: they could not be read where this was "
            "written, and a guessed map is worse than none. The target "
            "layout is formats/mfile.py's _walk_laguna_layer (q k v wo, the "
            "q and k norms' weights in the gate's place, the router's rows, "
            "w3 w1 w2 an expert, the two block norms; q and k rows paired "
            "half-split)")
    if arch == ArchType.AXK1:
        raise NotImplementedError(
            "axk1: the header is mapped (load_hf_config), the checkpoint's "
            "tensor names are not: they could not be read where this was "
            "written, and a guessed map is worse than none. The target "
            "layout is formats/mfile.py's _walk_axk1_layer (its rope lanes "
            "pair half-split: a published checkpoint's interleaved pairs are "
            "permuted in W_uq's and W_dkv's rope rows, as permute_rope_rows "
            "does for the Llama files)")
    if arch == ArchType.LFM2:
        raise NotImplementedError(
            "lfm2_moe: the header is mapped (load_hf_config), the "
            "checkpoint's tensor names are not: they could not be read where "
            "this was written, and a guessed map is worse than none. The "
            "target layout is formats/mfile.py's _walk_lfm2_layer (the "
            "in-projection's rows in the order B, C, X; the taps [K, dim] "
            "with tap K - 1 on the current position; q and k rows paired "
            "half-split)")
    if arch == ArchType.NEMOTRON_H:
        return _nemotron_h_plan(params)
    if arch == ArchType.GRANITE_HYBRID:
        return _granite_hybrid_plan(params)
    if arch == ArchType.SOLAR_OPEN2:
        return _solar_open2_plan(params)
    if arch == ArchType.FALCON_H1:
        raise NotImplementedError(
            "falcon_h1: the header is mapped (load_hf_config), the "
            "checkpoint's tensor names are not: they could not be read where "
            "this was written, and a guessed map is worse than none. The "
            "target layout is formats/mfile.py's _walk_falcon_h1_layer (the "
            "published in_proj is split there: its last mamba_n_heads rows "
            "are the float32 dt plane)")
    n_heads = params["n_heads"]
    n_kv_heads = params["n_kv_heads"]

    def permute_q(w: np.ndarray) -> np.ndarray:
        return permute_rope_rows(w, n_heads)

    def permute_k(w: np.ndarray) -> np.ndarray:
        return permute_rope_rows(w, n_kv_heads)

    # Qwen3 ships rotary halves directly (neox rope) — no permutation there.
    q_tr = permute_q if arch == ArchType.LLAMA else None
    k_tr = permute_k if arch == ArchType.LLAMA else None

    plan = [PlanItem(("model.embed_tokens.weight",), F32)]
    for l in range(params["n_layers"]):
        pre = f"model.layers.{l}"
        plan.append(PlanItem((f"{pre}.self_attn.q_proj.weight",), wt, q_tr))
        plan.append(PlanItem((f"{pre}.self_attn.k_proj.weight",), wt, k_tr))
        plan.append(PlanItem((f"{pre}.self_attn.v_proj.weight",), wt))
        plan.append(PlanItem((f"{pre}.self_attn.o_proj.weight",), wt))
        if params["n_experts"] > 0:
            # Router first — OUR extension (block_moe_gate; the reference
            # converter omits it, making its MoE files unrunnable) — then the
            # experts in the reference's w3/w1/w2 order (convert-hf.py:73-80).
            # Key pairs cover Mixtral (block_sparse_moe.*) and Qwen3-MoE
            # (mlp.gate / mlp.experts.*.{gate,down,up}_proj) checkpoints.
            plan.append(PlanItem((f"{pre}.block_sparse_moe.gate.weight",
                                  f"{pre}.mlp.gate.weight"), F32))
            for e in range(params["n_experts"]):
                mx = f"{pre}.block_sparse_moe.experts.{e}"
                qw = f"{pre}.mlp.experts.{e}"
                plan.append(PlanItem((f"{mx}.w3.weight",
                                      f"{qw}.up_proj.weight"), wt))
                plan.append(PlanItem((f"{mx}.w1.weight",
                                      f"{qw}.gate_proj.weight"), wt))
                plan.append(PlanItem((f"{mx}.w2.weight",
                                      f"{qw}.down_proj.weight"), wt))
        else:
            plan.append(PlanItem((f"{pre}.mlp.gate_proj.weight",), wt))  # w1
            plan.append(PlanItem((f"{pre}.mlp.down_proj.weight",), wt))  # w2
            plan.append(PlanItem((f"{pre}.mlp.up_proj.weight",), wt))    # w3
        if arch == ArchType.QWEN3:
            plan.append(PlanItem((f"{pre}.self_attn.q_norm.weight",), F32))
            plan.append(PlanItem((f"{pre}.self_attn.k_norm.weight",), F32))
        plan.append(PlanItem((f"{pre}.input_layernorm.weight",), F32))
        plan.append(PlanItem((f"{pre}.post_attention_layernorm.weight",), F32))
    plan.append(PlanItem(("model.norm.weight",), F32))
    plan.append(PlanItem(("lm_head.weight", "model.embed_tokens.weight"), wt))
    return plan


class SafetensorsDirectory:
    """Lazy multi-file safetensors reader: keeps at most one shard open,
    resolves key→file via the (tiny) headers up front — unlike the reference's
    sequential guessing walk (convert-hf.py:104-136), the index is exact."""

    def __init__(self, files: Iterable[str | Path]):
        from safetensors import safe_open
        self._safe_open = safe_open
        self.files = [str(f) for f in files]
        if not self.files:
            raise ValueError("no safetensors files given")
        self.key_to_file: dict[str, str] = {}
        for path in self.files:
            with safe_open(path, framework="numpy", device="cpu") as f:
                for key in f.keys():
                    self.key_to_file[key] = path
        self._open_path: str | None = None
        self._open_file = None

    def __contains__(self, key: str) -> bool:
        return key in self.key_to_file

    def get(self, key: str) -> np.ndarray:
        path = self.key_to_file[key]
        if path != self._open_path:
            if self._open_file is not None:
                self._open_file.__exit__(None, None, None)
            self._open_file = self._safe_open(path, framework="numpy", device="cpu")
            self._open_file.__enter__()
            self._open_path = path
        t = self._open_file.get_tensor(key)
        # bf16 arrives as an ml_dtypes.bfloat16 ndarray; astype handles it
        return np.asarray(t).astype(np.float32)

    def close(self) -> None:
        if self._open_file is not None:
            self._open_file.__exit__(None, None, None)
            self._open_file = None
            self._open_path = None


def convert_hf(source_dir: str | Path, weight_float_type: int | str,
               output_path: str | Path, *, progress: bool = True) -> str:
    """Convert an HF safetensors model directory to a .m file
    (reference: convert-hf.py main flow)."""
    if isinstance(weight_float_type, str):
        weight_float_type = parse_float_type(weight_float_type)
    source_dir = Path(source_dir)
    params = load_hf_config(source_dir, weight_float_type)

    files = sorted(p for p in source_dir.iterdir()
                   if p.name.endswith(".safetensors") and not p.name.startswith("."))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {source_dir}")
    src = SafetensorsDirectory(files)

    plan = hf_tensor_plan(params)
    skipped = [k for k in src.key_to_file if k.startswith("mtp.")]
    if skipped and progress:
        print(f"⏭️ skipping {len(skipped)} mtp.* tensors: the multi-token-"
              f"prediction head is not served")
    crcs: list[int] = []
    try:
        with open(output_path, "wb") as out:
            write_header(out, params)
            for item in plan:
                key = next((k for k in item.keys if k in src), None)
                if key is None:
                    raise KeyError(f"tensor {item.keys[0]} not found in checkpoint")
                tensor = src.get(key)
                if item.transform is not None:
                    tensor = item.transform(tensor)
                if progress:
                    print(f"🔶 Writing {key} {tensor.shape} as "
                          f"{FLOAT_NAME_BY_TYPE[item.float_type]}")
                data = encode_tensor(tensor, item.float_type)
                crcs.append(zlib.crc32(data) & 0xFFFFFFFF)
                out.write(data)
    finally:
        src.close()
    # per-tensor crc32 sidecar: the streaming loader verifies each tensor
    # against it at load and names the exact corrupt tensor on mismatch
    sums = write_manifest(output_path, _keyed_checksums(output_path, crcs))
    if progress:
        print(f"🔏 checksum manifest → {sums}")
    return str(output_path)


# ---------------------------------------------------------------------------
# Meta (consolidated.*.pth) checkpoints
# ---------------------------------------------------------------------------


def convert_meta_llama(source_dir: str | Path, weight_float_type: int | str,
                       output_path: str | Path, *, progress: bool = True) -> str:
    """Convert a Meta-format Llama checkpoint (params.json +
    consolidated.NN.pth shards) to .m (reference: convert-llama.py:11-121).

    Shards are column-chunks for row-parallel tensors (embedding, wo, w2 —
    concat on axis 1) and row-chunks for the rest (concat on axis 0); 1-D
    tensors are replicated. Shards are opened with ``mmap=True`` so tensor
    storages stay lazy; peak memory is one tensor × n_shards, not the model.
    """
    import torch  # CPU-only unpickle of the .pth shards

    if isinstance(weight_float_type, str):
        weight_float_type = parse_float_type(weight_float_type)
    source_dir = Path(source_dir)
    with open(source_dir / "params.json", encoding="utf-8") as f:
        meta = json.load(f)
    if meta.get("vocab_size", -1) < 1:
        raise ValueError("vocab_size missing/invalid in params.json")
    if meta.get("max_seq_len") is None:
        raise ValueError("max_seq_len is required in params.json")

    shard_paths = sorted(source_dir.glob("consolidated.*.pth"))
    if not shard_paths:
        raise FileNotFoundError(f"no consolidated.*.pth in {source_dir}")
    shards = [torch.load(p, map_location="cpu", weights_only=True, mmap=True)
              for p in shard_paths]

    n_layers = meta["n_layers"]
    params: dict = {
        "version": 0,
        "arch_type": int(ArchType.LLAMA),
        "hidden_act": int(HiddenAct.SILU),
        "dim": meta["dim"],
        "hidden_dim": shards[0]["layers.0.feed_forward.w1.weight"].shape[0]
                      * len(shards),
        "n_layers": n_layers,
        "n_heads": meta["n_heads"],
        "n_kv_heads": meta.get("n_kv_heads") or meta["n_heads"],
        "n_experts": 0,
        "n_active_experts": 0,
        "weight_float_type": weight_float_type,
        "seq_len": meta["max_seq_len"],
        "vocab_size": meta["vocab_size"],
    }
    if "rope_theta" in meta:
        params["rope_theta"] = int(meta["rope_theta"])
    if "norm_eps" in meta:
        if meta["norm_eps"] == 1e-5:
            params["norm_epsilon"] = 5
        elif meta["norm_eps"] == 1e-6:
            params["norm_epsilon"] = 6

    names: list[str] = ["tok_embeddings.weight"]
    for l in range(n_layers):
        names += [f"layers.{l}.attention.wq.weight",
                  f"layers.{l}.attention.wk.weight",
                  f"layers.{l}.attention.wv.weight",
                  f"layers.{l}.attention.wo.weight",
                  f"layers.{l}.feed_forward.w1.weight",
                  f"layers.{l}.feed_forward.w2.weight",
                  f"layers.{l}.feed_forward.w3.weight",
                  f"layers.{l}.attention_norm.weight",
                  f"layers.{l}.ffn_norm.weight"]
    names += ["norm.weight", "output.weight"]

    col_chunked = {"tok_embeddings.weight"}
    f32_always = {"tok_embeddings.weight", "norm.weight"}

    def merged(name: str) -> np.ndarray:
        parts = [np.asarray(s[name].to(torch.float32).numpy()) for s in shards]
        if len(parts) == 1 or parts[0].ndim == 1:
            return parts[0]
        axis = 1 if (name in col_chunked or name.endswith(".attention.wo.weight")
                     or name.endswith(".feed_forward.w2.weight")) else 0
        return np.concatenate(parts, axis=axis)

    crcs: list[int] = []
    with open(output_path, "wb") as out:
        write_header(out, params)
        for name in names:
            is_f32 = (name in f32_always or name.endswith(".attention_norm.weight")
                      or name.endswith(".ffn_norm.weight"))
            ft = F32 if is_f32 else weight_float_type
            tensor = merged(name)
            if progress:
                print(f"🔶 Writing {name} {tensor.shape} as {FLOAT_NAME_BY_TYPE[ft]}")
            data = encode_tensor(tensor, ft)
            crcs.append(zlib.crc32(data) & 0xFFFFFFFF)
            out.write(data)
    sums = write_manifest(output_path, _keyed_checksums(output_path, crcs))
    if progress:
        print(f"🔏 checksum manifest → {sums}")
    return str(output_path)


def default_output_name(name: str, weight_float_type: int) -> str:
    return f"dllama_model_{name}_{FLOAT_NAME_BY_TYPE[weight_float_type]}.m"

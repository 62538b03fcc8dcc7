"""Seeded weights of a decoder of latent attention (MLA) layers with a sigmoid
group-limited router over experts of which a SHARE is held, and its sparse
``.m``: the ``weights`` module of ``a.x-k1`` (README, "A layer equation").

This module owns the header (arch id 0xABCD05, the dense fields, key 21 for
``norm_topk_prob``, YaRN's numbers in the reference's own rope-scaling keys
14-17, the share's keys 33-38 and latent attention's 60-69: the five MLA
sizes, the router's groups and score function, YaRN's two mscales as float32
bits), the walk size (``dllama_tpu/formats/mfile.py::_walk_axk1_layer``) and
the ``Params`` tree (``models/axk1.py::AxK1Layers``). The rest is
``weights.py``'s.

What the published config does not state is ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the tree is drawn, and why.** Every Q40 plane has gain 1 over its fan-in
(``weights.py``), so a unit-RMS input gives unit-RMS outputs and the residual
stream grows by O(1) a layer through 10 layers. Three departures:

* ``W_dkv``'s 512 latent rows are drawn at gain ``C_GAIN`` = 2, its 64 rotary
  rows at gain 1 (two planes side by side, then zero columns up to
  ``latent_row``): the RMS norm on ``c`` then DOES something (without it the
  nope score and the value are twice as large), and with ``c`` normed and
  ``W_ukv`` at gain 1 the nope part of a score has spread ``sqrt(128)`` = 11.3
  and the rope part ``sqrt(64)`` = 8 before the scale: comparable weight.
* ``W_uk`` and ``W_uv`` are held per head in the compute dtype (the absorbed
  form contracts them on their plane's output side): the Q40 plane ``W_ukv``
  is drawn as every other, dequantized (code x scale, rounded once to the
  compute dtype) and split. The reference reads those same held numbers.
* the router's rows are normals of spread ``ROUTER_GAIN / sqrt(hidden)`` = 4 /
  sqrt(hidden): a token's 192 logits have spread 4, so the chosen experts'
  sigmoids lie in 0.9-1.0, where a bfloat16 is 0.004 wide: a router (or its
  sigmoid) computed in bfloat16 ties several of a token's best scores and
  takes another eight (``reference.py``'s ``bf16router``), where the float32
  one the configuration states orders them. The eight weights are near
  uniform (2.5 / 8 each), so a changed choice among the HELD experts moves
  the layer's output by a third of an expert. Laguna's common direction (PR
  34) is no use here: a sigmoid does not cancel a shift common to the logits.
  With 8 groups of 24 a token's plain top 8 of 192 leave its 4 best groups
  for most tokens (``tests/test_axk1.py`` states the share), so the group
  limit is seen.

The builder draws its keys in this order: ``wdq wuq``, ``wdkv``'s latent rows,
its rotary rows, ``wukv wo``; the dense layer's ``w1 w2 w3``; the router's
rows; ``we1 we2 we3``; the shared expert's ``ws1 ws2 ws3``; embedding; head.
"""

import struct

import weights as dense

ARCH_AXK1 = 0xABCD05
# dllama_tpu/formats/mfile.py: HeaderKey 14-17, 21, 33-38, 60-69
ROPE_FACTOR, ROPE_BETA_SLOW, ROPE_BETA_FAST, ROPE_ORIG_MAX = 14, 15, 16, 17
MOE_NORM_TOPK = 21
(N_DENSE_LAYERS, DENSE_HIDDEN_DIM, SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH,
 FIRST_EXPERT) = range(33, 39)
(Q_LORA_RANK, KV_LORA_RANK, QK_NOPE_HEAD_DIM, QK_ROPE_HEAD_DIM, V_HEAD_DIM, MOE_N_GROUP, MOE_TOPK_GROUP,
 MOE_SCORE_FUNC, YARN_MSCALE, YARN_MSCALE_ALL_DIM) = range(60, 70)
ROPE_TYPE_YARN = 3
ROUTER_GAIN = 4.0
C_GAIN = 2.0
# what the program implements where the published config is silent (models/axk1.py)
ASSUMED = {"norm_placement": "pre", "latent_norms": "rms_on_c_q_and_on_c", "shared_expert_gate": False,
           "rope_pairing": "half_split", "router_bias": "none", "router_group_score": "sum_of_top_2",
           "attention_scale": "head_dim**-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)**2"}


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def latent_row(model: dict) -> int:
    """Lanes of a cached row as the pool holds it: 576 useful in 640."""
    return -(-(model["kv_lora_rank"] + model["qk_rope_head_dim"]) // 128) * 128


def header_fields(model: dict) -> dict:
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/axk1.py implements {value!r}")
    rs = model["rope_scaling"]
    if (model["attention_bias"] or model["moe_layer_freq"] != 1 or model["hidden_act"] != "silu"
            or model["tie_word_embeddings"] or model["topk_method"] != "none" or rs["type"] != "yarn"
            or model["scoring_func"] not in ("sigmoid", "softmax") or model["n_shared_experts"] != 1
            or model["num_key_value_heads"] != model["num_attention_heads"]):
        raise ValueError("attention bias, an expert layer frequency other than 1, an activation other than silu, "
                         "tied embeddings, a top-k method other than none, a rope scaling other than yarn, a "
                         "scoring function other than sigmoid or softmax, more or fewer than one shared expert: "
                         "models/axk1.py carries none of them")
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_AXK1,
        "dim": model["hidden_size"], "hidden_dim": model["moe_intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["n_routed_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": int(model["rope_theta"]), "rope_type": ROPE_TYPE_YARN,
        "weight_float_type": dense.Q40, "head_dim": model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        "norm_epsilon": eps,
        ROPE_FACTOR: int(rs["factor"]), ROPE_BETA_SLOW: int(rs["beta_slow"]),
        ROPE_BETA_FAST: int(rs["beta_fast"]), ROPE_ORIG_MAX: int(rs["original_max_position_embeddings"]),
        MOE_NORM_TOPK: int(bool(model["norm_topk_prob"])),
        N_DENSE_LAYERS: model["first_k_dense_replace"], DENSE_HIDDEN_DIM: model["intermediate_size"],
        SHARED_EXPERT_DIM: model["n_shared_experts"] * model["moe_intermediate_size"],
        ROUTED_SCALE_MILLI: int(round(model["routed_scaling_factor"] * 1000)),
        ROUTER_WIDTH: model["router_width"], FIRST_EXPERT: model["first_expert"],
        Q_LORA_RANK: model["q_lora_rank"], KV_LORA_RANK: model["kv_lora_rank"],
        QK_NOPE_HEAD_DIM: model["qk_nope_head_dim"], QK_ROPE_HEAD_DIM: model["qk_rope_head_dim"],
        V_HEAD_DIM: model["v_head_dim"], MOE_N_GROUP: model["n_group"], MOE_TOPK_GROUP: model["topk_group"],
        MOE_SCORE_FUNC: int(model["scoring_func"] == "sigmoid"),
        YARN_MSCALE: _f32_bits(rs["mscale"]), YARN_MSCALE_ALL_DIM: _f32_bits(rs["mscale_all_dim"]),
    }


def _sizes(model: dict) -> dict:
    H = model["num_attention_heads"]
    return {"d": model["hidden_size"], "H": H, "ql": model["q_lora_rank"], "r": model["kv_lora_rank"],
            "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"], "v": model["v_head_dim"],
            "hd": model["qk_nope_head_dim"] + model["qk_rope_head_dim"], "hid": model["moe_intermediate_size"],
            "wide": model["intermediate_size"], "L": model["num_hidden_layers"],
            "nd": model["first_k_dense_replace"], "E": model["n_routed_experts"], "W": model["router_width"],
            "sh": model["n_shared_experts"] * model["moe_intermediate_size"], "V": model["vocab_size"]}


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a layer's five
    latent-attention planes (``W_dkv`` at its 576 rows: the padding is the
    loader's) and two latent norms (f32), W_o; the dense layer's w1 w2 w3, or
    the router's rows (f32), three planes a held expert and the shared
    expert's three; two block norms; final norm, head."""
    s = _sizes(model)
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    d = s["d"]
    attn = (qb(s["ql"] * d) + s["ql"] * 4 + qb(s["H"] * s["hd"] * s["ql"]) + qb((s["r"] + s["rope"]) * d)
            + s["r"] * 4 + qb(s["H"] * (s["nope"] + s["v"]) * s["r"]) + qb(d * s["H"] * s["v"]))
    ffn_dense = 3 * qb(s["wide"] * d)
    ffn_routed = s["W"] * d * 4 + s["E"] * 3 * qb(s["hid"] * d) + 3 * qb(s["sh"] * d)
    layers = sum(attn + (ffn_dense if l < s["nd"] else ffn_routed) + 2 * d * 4 for l in range(s["L"]))
    return header_size + s["V"] * d * 4 + layers + d * 4 + qb(s["V"] * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.axk1 import AxK1Layers
    from dllama_tpu.ops.linear import QuantizedWeight, dequantize_weight

    t = dense.Trunk(cfg, plan)
    d, L, H = cfg.dim, cfg.n_layers, cfg.n_heads
    ND, NM, E = cfg.n_dense_layers, cfg.n_moe_layers, cfg.n_experts
    r, rope, nope, v = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    row, cdt = cfg.latent_row, jnp.dtype(cfg.compute_dtype)
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    attn_mats = [("wdq", cfg.q_lora_rank, d), ("wuq", H * cfg.head_dim, cfg.q_lora_rank)]
    wide, hid, sh = cfg.dense_hidden_dim, cfg.hidden_dim, cfg.shared_expert_dim
    dense_mats = [("w1", wide, d), ("w2", d, wide), ("w3", wide, d)]
    expert_mats = [("we1", hid, d), ("we2", d, hid), ("we3", hid, d)]
    shared_mats = [("ws1", sh, d), ("ws2", d, sh), ("ws3", sh, d)]
    q = lambda o, i, pre: t.qshard(o, i, None, None, pre=pre)
    out_sh = t.params_shardings(AxK1Layers(
        **{n: q(o, i, (L,)) for n, o, i in attn_mats}, wdkv=q(row, d, (L,)), wo=q(d, H * v, (L,)),
        norm_qa=stacked(L, cfg.q_lora_rank), norm_kva=stacked(L, r),
        wuk=stacked(L, H, nope, r), wuv=stacked(L, H, v, r), norm_att=stacked(L, d), norm_ffn=stacked(L, d),
        **{n: q(o, i, (ND,)) for n, o, i in dense_mats},
        moe_gate=stacked(NM, cfg.moe_router_width, d),
        **{n: t.qshard(o, i, None, None, pre=(NM, E), lead=("layers", "experts")) for n, o, i in expert_mats},
        **{n: q(o, i, (NM,)) for n, o, i in shared_mats}))

    def build(key):
        keys = iter(jax.random.split(key, 32))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        attn = {n: t.plane(next(keys), o, i, pre=(L,)) for n, o, i in attn_mats}
        latent = t.plane(next(keys), r, d, pre=(L,), gain=C_GAIN)
        rotary = t.plane(next(keys), rope, d, pre=(L,))
        pad = lambda a, b: jnp.concatenate(
            [a, b, jnp.zeros(a.shape[:-1] + (row - r - rope,), a.dtype)], axis=-1)
        wdkv = QuantizedWeight(scales=pad(latent.scales, rotary.scales), codes=pad(latent.codes, rotary.codes))
        wukv = dequantize_weight(t.plane(next(keys), H * (nope + v), r, pre=(L,)), dtype=cdt)   # [L, r, H (nope + v)]
        wukv = jnp.swapaxes(wukv, 1, 2).reshape(L, H, nope + v, r)
        wo = t.plane(next(keys), d, H * v, pre=(L,))
        dense_ffn = {n: t.plane(next(keys), o, i, pre=(ND,)) for n, o, i in dense_mats}
        gate = jax.random.normal(next(keys), (NM, cfg.moe_router_width, d), jnp.float32) * (ROUTER_GAIN * d ** -0.5)
        experts = {n: t.plane(next(keys), o, i, pre=(NM, E)) for n, o, i in expert_mats}
        shared = {n: t.plane(next(keys), o, i, pre=(NM,)) for n, o, i in shared_mats}
        layers = AxK1Layers(**attn, wdkv=wdkv, wo=wo, norm_qa=ones(L, cfg.q_lora_rank), norm_kva=ones(L, r),
                            wuk=wukv[:, :, :nope], wuv=wukv[:, :, nope:], norm_att=ones(L, d), norm_ffn=ones(L, d),
                            **dense_ffn, moe_gate=gate, **experts, **shared)
        return t.params(next(keys), next(keys), layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

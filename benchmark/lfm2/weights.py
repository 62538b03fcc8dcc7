"""Seeded weights of a decoder of gated short-convolution and attention layers
with routed experts behind leading dense layers, and its sparse ``.m``: the
``weights`` module of ``lfm2-24b-a2b`` (``lfm2/README.md``).

This module owns the header (arch id 0xABCD06, the dense fields, key 21 for
``norm_topk_prob``, 22 for the layer period, the share's keys 33-38, 67 for the
router's score function and 70-71: the convolution's taps and whether the
router's selection carries a bias), the walk size
(``dllama_tpu/formats/mfile.py::_walk_lfm2_layer``) and the ``Params`` tree
(``models/lfm2.py::Lfm2Layers``). The rest is ``weights.py``'s.

What the published config does not state is ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the tree is drawn, and why.** Every Q40 plane has gain 1 over its fan-in
(``weights.py``), so a unit-RMS input gives unit-RMS outputs. Departures:

* ``W_q`` and ``W_k`` are drawn at gain ``QK_GAIN`` = 2: the per-head RMS norm
  on q and k (weights of ones) then DOES something: without it every score
  is 16 times as large (``reference.py``'s ``noqknorm``).
* the convolution's taps are normals of spread ``1 / sqrt(K)``: ``B``, ``C`` and
  ``X`` are unit-RMS, so ``v = B * X`` is, and ``c`` is unit-RMS again with a
  third of its energy from each tap: two thirds of it come through the TAIL.
* the router's rows are normals of spread ``ROUTER_GAIN / sqrt(hidden)`` = 4 /
  sqrt(hidden), as ``a_x_k1/weights.py`` draws them and for its reason: a
  token's 64 logits have spread 4, its four best sigmoids lie above 0.998,
  1e-3 apart, where a bfloat16 is 0.004 wide: a router (or its sigmoid)
  computed in bfloat16 ties a token's best six scores and lets the bias
  alone pick among them (``bf16router``), where the float32 one orders them; the
  honest program's noise there (0.04 in a logit, times a sigmoid's slope of
  1e-3) is far below the gaps.
* an expert's down-projection ``we2`` is drawn at gain ``EXPERT_OUT_GAIN`` = 0.25, so
  a routed layer adds a sixteenth of the variance a mixer adds. Why: a top-4
  of 64 gaussian logits has its fourth and fifth 0.14 of their spread apart on
  average, and the bfloat16 stream the program carries differs from the
  reference's float32 one by some 1% at depth, so in 7-14% of (row, layer)
  pairs the program's float32 router and the reference's take ANOTHER fourth
  expert: another function and not an error, intrinsic to a router over noisy
  inputs. Here every expert is held and the four weights are near uniform, so
  such a flip swaps a quarter of the layer's output (laguna's tenth expert
  weighs 2% of its first; A.X-K1 holds 12 of 192). At gain 1 that moved the
  stream by 6-16% a flip, most rows carry one over 16 routed layers, and the
  honest program read 32% of its positions over 0.1 (my chip runs, PR 44, three
  seeds: 0.323-0.328 of 1,800-2,300 positions), a floor no control could be
  seen above. At 0.25 a flip moves the stream by 2%; the routing controls
  (every row misrouted in most layers) still read far above it
  (``gap_tolerance.json``).
* the selection bias is NON-ZERO: normals of spread ``BIAS_SPREAD`` = 0.003 a
  routed layer an expert, three times the gap between a token's fourth and
  fifth score, so it changes the chosen set in a good share of rows
  (``tests/test_lfm2.py`` states the share) and ``nobias`` reads like
  misrouting. The chosen experts' weights are near uniform either way (their
  scores are all near 1), so WEIGHTS taken from ``s + b`` (``biasweight``) move a
  logit by far less than bfloat16 compute does: ``gap_tolerance.json`` names
  that control as one the tokens cannot show.

The builder draws its keys in this order: the conv stack's ``w_in w_out``, its
taps; the attention stack's ``wq wk wv wo``; the dense layers' ``w1 w2 w3``; the
router's rows; its bias; ``we1 we2 we3``; embedding; head.
"""

import weights as dense

ARCH_LFM2 = 0xABCD06
# dllama_tpu/formats/mfile.py: HeaderKey 21-22, 33-38, 67, 70-71
MOE_NORM_TOPK, LAYER_PERIOD = 21, 22
(N_DENSE_LAYERS, DENSE_HIDDEN_DIM, SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH,
 FIRST_EXPERT) = range(33, 39)
MOE_SCORE_FUNC, SHORT_CONV_KERNEL, MOE_SELECT_BIAS = 67, 70, 71
ROPE_TYPE_HALF_SPLIT = 1
ROUTER_GAIN = 4.0
BIAS_SPREAD = 0.003
QK_GAIN = 2.0
EXPERT_OUT_GAIN = 0.25
# what the program implements where the published config is silent (models/lfm2.py)
ASSUMED = {"norm_placement": "pre", "rope_pairing": "half_split", "in_proj_order": "B_C_X",
           "router_score": "sigmoid", "expert_bias": "selection_only", "norm_topk_eps": 1e-6,
           "conv_activation": "none", "qk_norm": "rms_per_head"}


def pattern(model: dict) -> tuple[int, int]:
    """``(leading conv layers, period)``: ``layer_types`` must be
    ``num_dense_layers`` conv layers, then periods of one full_attention layer
    and conv ones (the last may be cut short)."""
    kinds, lead = list(model["layer_types"]), int(model["num_dense_layers"])
    behind = kinds[lead:]
    P = behind.index("full_attention", 1) if "full_attention" in behind[1:] else len(behind)
    want = ["conv"] * lead + ["full_attention" if i % P == 0 else "conv" for i in range(len(behind))]
    if kinds != want or len(kinds) != model["num_hidden_layers"] or not lead or not behind:
        raise ValueError("layer_types is not num_dense_layers leading conv layers and then periods of a "
                         "full_attention layer and conv ones")
    return lead, P


def header_fields(model: dict) -> dict:
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/lfm2.py implements {value!r}")
    if model["conv_bias"] or model["rope_parameters"]["rope_type"] != "default" \
            or model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("a convolution bias, a scaled rotary table or heads that do not divide the width: "
                         "models/lfm2.py carries none of them")
    lead, P = pattern(model)
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_LFM2,
        "dim": model["hidden_size"], "hidden_dim": model["moe_intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["num_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": int(model["rope_parameters"]["rope_theta"]),
        "rope_type": ROPE_TYPE_HALF_SPLIT, "weight_float_type": dense.Q40,
        "head_dim": model["hidden_size"] // model["num_attention_heads"], "norm_epsilon": eps,
        MOE_NORM_TOPK: int(bool(model["norm_topk_prob"])), LAYER_PERIOD: P,
        N_DENSE_LAYERS: lead, DENSE_HIDDEN_DIM: model["intermediate_size"], SHARED_EXPERT_DIM: 0,
        ROUTED_SCALE_MILLI: int(round(model["routed_scaling_factor"] * 1000)),
        ROUTER_WIDTH: model["router_width"], FIRST_EXPERT: model["first_expert"],
        MOE_SCORE_FUNC: 1, SHORT_CONV_KERNEL: model["conv_L_cache"],
        MOE_SELECT_BIAS: int(bool(model["use_expert_bias"])),
    }


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; an attention
    layer's q k v wo and two head norms (f32), or a conv layer's in-projection,
    taps (f32) and out-projection; the dense layer's w1 w2 w3, or the router's
    rows (f32), its bias (f32) and three planes a held expert; two block norms;
    final norm, head."""
    d, v = model["hidden_size"], model["vocab_size"]
    hd = d // model["num_attention_heads"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    lead, _P = pattern(model)
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    attn = 2 * qb(q * d) + 2 * qb(kv * d) + 2 * hd * 4
    conv = qb(3 * d * d) + model["conv_L_cache"] * d * 4 + qb(d * d)
    ffn_dense = 3 * qb(model["intermediate_size"] * d)
    ffn_routed = (model["router_width"] * d * 4 + (model["router_width"] * 4 if model["use_expert_bias"] else 0)
                  + model["num_experts"] * 3 * qb(model["moe_intermediate_size"] * d))
    layers = sum((attn if kind == "full_attention" else conv) + (ffn_dense if l < lead else ffn_routed) + 2 * d * 4
                 for l, kind in enumerate(model["layer_types"]))
    return header_size + v * d * 4 + layers + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.lfm2 import AttnParams, ConvParams, Lfm2Layers

    t = dense.Trunk(cfg, plan)
    d, hd, L, K = cfg.dim, cfg.head_dim, cfg.n_layers, cfg.conv_kernel
    NC, NA, ND, NM, E, W = (cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_dense_layers, cfg.n_moe_layers,
                            cfg.n_experts, cfg.moe_router_width)
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    q = lambda o, i, pre: t.qshard(o, i, None, None, pre=pre)
    conv_mats = [("w_in", 3 * d, d, 1.0), ("w_out", d, d, 1.0)]
    attn_mats = [("wq", cfg.q_dim, d, QK_GAIN), ("wk", cfg.kv_dim, d, QK_GAIN), ("wv", cfg.kv_dim, d, 1.0),
                 ("wo", d, cfg.q_dim, 1.0)]
    wide, hid = cfg.dense_hidden_dim, cfg.hidden_dim
    dense_mats = [("w1", wide, d), ("w2", d, wide), ("w3", wide, d)]
    expert_mats = [("we1", hid, d), ("we2", d, hid), ("we3", hid, d)]
    out_sh = t.params_shardings(Lfm2Layers(
        conv=ConvParams(**{n: q(o, i, (NC,)) for n, o, i, _g in conv_mats}, conv_w=stacked(NC, K, d),
                        norm_att=stacked(NC, d)),
        attn=AttnParams(**{n: q(o, i, (NA,)) for n, o, i, _g in attn_mats}, norm_q=stacked(NA, hd),
                        norm_k=stacked(NA, hd), norm_att=stacked(NA, d)),
        norm_ffn=stacked(L, d), **{n: q(o, i, (ND,)) for n, o, i in dense_mats},
        moe_gate=stacked(NM, W, d), moe_bias=stacked(NM, W) if cfg.moe_select_bias else None,
        **{n: t.qshard(o, i, None, None, pre=(NM, E), lead=("layers", "experts")) for n, o, i in expert_mats}))

    def build(key):
        keys = iter(jax.random.split(key, 24))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        conv = {n: t.plane(next(keys), o, i, pre=(NC,), gain=g) for n, o, i, g in conv_mats}
        taps = jax.random.normal(next(keys), (NC, K, d), jnp.float32) * K ** -0.5
        attn = {n: t.plane(next(keys), o, i, pre=(NA,), gain=g) for n, o, i, g in attn_mats}
        dense_ffn = {n: t.plane(next(keys), o, i, pre=(ND,)) for n, o, i in dense_mats}
        gate = jax.random.normal(next(keys), (NM, W, d), jnp.float32) * (ROUTER_GAIN * d ** -0.5)
        k_bias = next(keys)
        bias = (jax.random.normal(k_bias, (NM, W), jnp.float32) * BIAS_SPREAD if cfg.moe_select_bias else None)
        experts = {n: t.plane(next(keys), o, i, pre=(NM, E), gain=EXPERT_OUT_GAIN if n == "we2" else 1.0)
                   for n, o, i in expert_mats}
        layers = Lfm2Layers(
            conv=ConvParams(**conv, conv_w=taps, norm_att=ones(NC, d)),
            attn=AttnParams(**attn, norm_q=ones(NA, hd), norm_k=ones(NA, hd), norm_att=ones(NA, d)),
            norm_ffn=ones(L, d), **dense_ffn, moe_gate=gate, moe_bias=bias, **experts)
        return t.params(next(keys), next(keys), layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

"""Seeded weights of a decoder of delta-rule layers whose decay is a VECTOR a
head (Kimi Delta Attention) beside gated full attention, routed experts of
which a SHARE is held behind every mixer, and its sparse ``.m``: the ``weights``
module of ``solar-open2-250b`` (``solar_open2/README.md``).

This module owns the header (arch id 0xABCD09, the dense fields, key 21 for
``norm_topk_prob``, 22-28 the period and the mixer's sizes as the gated-delta
hybrid has them, 35-38 the shared expert's width, the routed scale and THE
SHARE, 67 / 71 the router's score function and its selection bias, 77-79: the
decays a head, the gates' inner width, the full layer's place in its period),
the walk size (``dllama_tpu/formats/mfile.py::_walk_solar_open2_layer``) and the
``Params`` tree (``models/solar_open2.py::SolarLayers``: three stacks). The rest
is ``weights.py``'s.

What the published config does not state is ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the tree is drawn, and why.** Every Q40 plane has gain 1 over its fan-in
(``weights.py``), so a unit-RMS input gives unit-RMS outputs. Departures:

* **the decay really is a vector.** ``g = -exp(A_log[h]) softplus(f + dt_bias[h,
  c])``: ``dt_bias = softplus^-1(dt0)`` with ``dt0`` log-uniform in [0.001, 0.7] A
  CHANNEL, so at a zero gate input the 128 channels of ONE head decay by 0.999
  to 0.5 a token, side by side: a head whose channels were given their mean
  (the control ``scalardecay``: the gated delta rule under this model's name)
  is another function, heard at every depth of context. ``A_log`` uniform in
  [-0.5, 0.5] a head (a head's rates 0.6 to 1.65 times the draw). The decay's
  low-rank pair: ``W_f^down`` normals of spread ``1 / sqrt(hidden)``, ``W_f^up`` of
  ``F_GAIN / sqrt(rank)`` = 0.5 / sqrt(rank): ``f`` has spread 0.5, so a
  channel's step moves by ``e^+-0.5`` with the token.
* ``beta``'s rows normals of spread ``1 / sqrt(hidden)``: ``beta = 2 sigmoid(N(0,
  1))``, 0.5 to 1.5 for two rows in three, over 1 (a negative eigenvalue) for
  half. The output gate's pair at ``1 / sqrt(hidden)`` and ``1 / sqrt(rank)``:
  gates half open on average. Taps normals of spread 1/2; norms ones.
* the state cannot blow up whatever the draw: ``k`` has unit length, ``beta <=
  2`` and every ``alpha_c <= 1``, so a step's transition ``(I - beta k k^T)
  Diag(alpha)`` has no singular value over 1.
* ``W_q`` of the full layers at gain ``Q_GAIN`` = 2: scores of spread 2 (there are
  no positions to draw for). Their gate's plane at gain 1: ``sigmoid(N(0, 1))``
  a lane; with the gate left out (``nogate``) a full layer's output doubles.
* the router as ``nemotron_h/weights.py`` draws a sigmoid router with a
  selection-only bias, and for its reasons: rows normals of spread ``ROUTER_GAIN
  / sqrt(hidden)`` = 4 / sqrt(hidden) (a token's 320 logits have spread 4, its
  eight best sigmoids lie above 0.999, where a bfloat16 is 0.004 wide); the
  bias normals of spread ``BIAS_SPREAD`` = 2.5e-4, three times the 8.7e-5
  between a token's eighth and ninth score (logit 7.8, slope 4e-4, 4.7 logits a
  unit), so it moves the last of the eight and weights taken from it would
  differ in the fourth digit.
* an expert's down-projection ``we2`` at gain ``EXPERT_OUT_GAIN`` = 0.25: eight of
  320 near-uniform sigmoids is a router full of near-ties (a fifth of (row,
  layer) pairs take ANOTHER eighth expert under the bfloat16 stream's 1%:
  another function, not an error); a flip whose expert is held moves the
  layer's routed output by an eighth of one expert's. The shared expert at
  gain 1: a routed layer adds about a unit through it and a sixth of that
  through the held experts (an eighth of the eight chosen are held on average).

The builder draws its keys in this order: the delta-rule stack's ``wq wk wv
w_out``, its taps, ``dt0``, ``A_log``, ``W_f^down W_f^up``, ``W_b``, ``W_g^down
W_g^up``; the full stack's ``wq wk wv wo wg``; the router's rows; its bias; ``we1
we2 we3``; ``ws1 ws2 ws3``; embedding; head.
"""

import weights as dense

ARCH_SOLAR_OPEN2 = 0xABCD09
# dllama_tpu/formats/mfile.py: HeaderKey 21-28, 35-38, 67, 71, 77-79
MOE_NORM_TOPK, LAYER_PERIOD, LIN_K_HEADS, LIN_V_HEADS, LIN_K_DIM, LIN_V_DIM, LIN_CONV, LIN_NEG_EIGVAL = range(21, 29)
SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH, FIRST_EXPERT = range(35, 39)
MOE_SCORE_FUNC, MOE_SELECT_BIAS = 67, 71
LIN_DECAY_DIM, LIN_GATE_RANK, FULL_LAYER_AT = 77, 78, 79
DT0_MIN, DT0_MAX = 1e-3, 0.7
A_LOG_SPREAD = 0.5
F_GAIN = 0.5
Q_GAIN = 2.0
ROUTER_GAIN = 4.0
BIAS_SPREAD = 2.5e-4
EXPERT_OUT_GAIN = 0.25
# what the program implements where the published config is silent (models/solar_open2.py)
ASSUMED = {"norm_placement": "pre", "gqa_gate": "sigmoid_of_a_plane_of_the_normed_input_a_lane_before_wo",
           "kda_gate_rank": "linear_attn_head_dim", "kda_decay": "neg_exp_A_log_softplus_lowrank_plus_dt_bias_a_channel",
           "kda_output_gate": "sigmoid_lowrank_on_the_normed_heads", "router_score": "sigmoid",
           "expert_bias": "selection_only", "shared_expert_gate": False}


def period(model: dict) -> int:
    """The layer pattern's period: ``gqa_layers`` must be the FIRST layer of
    every period of ``gqa_interval + 1`` layers, the periods whole."""
    P, L = model["gqa_interval"] + 1, model["num_hidden_layers"]
    if L % P or list(model["gqa_layers"]) != list(range(0, L, P)):
        raise ValueError(f"gqa_layers {model['gqa_layers']!r} is not the first of every {P} of {L} layers")
    return P


def mixer_dims(model: dict) -> tuple[int, int, int, int]:
    """``(heads, a head's width, conv channels, the gates' inner width)``."""
    lin = model["linear_attn_config"]
    H, hd = lin["num_heads"], lin["head_dim"]
    if lin["num_kv_heads"] not in (None, H):
        raise ValueError(f"{lin['num_kv_heads']} K/V heads against {H}: the mixer pairs them one to one")
    return H, hd, 3 * H * hd, hd


def header_fields(model: dict) -> dict:
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/solar_open2.py implements {value!r}")
    if model["use_rope"] or not model["use_gqa_gate"] or model["kda_use_full_proj"] or model["first_k_dense_replace"] \
            or model["n_shared_experts"] != 1 or model["tie_word_embeddings"] or model["partial_rotary_factor"] != 1:
        raise ValueError("a rotary embedding, an ungated full layer, full-rank gate projections, leading dense "
                         "layers, another count of shared experts or a tied head: models/solar_open2.py carries "
                         "none of them")
    H, hd, _conv, rank = mixer_dims(model)
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_SOLAR_OPEN2,
        "dim": model["hidden_size"], "hidden_dim": model["moe_intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["n_routed_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": 0, "rope_type": 0,      # neither is read: no rotary embedding
        "weight_float_type": dense.Q40, "head_dim": model["head_dim"], "norm_epsilon": eps,
        MOE_NORM_TOPK: int(bool(model["norm_topk_prob"])), LAYER_PERIOD: period(model),
        LIN_K_HEADS: H, LIN_V_HEADS: H, LIN_K_DIM: hd, LIN_V_DIM: hd,
        LIN_CONV: model["linear_attn_config"]["short_conv_kernel_size"],
        LIN_NEG_EIGVAL: int(bool(model["kda_allow_neg_eigval"])),
        SHARED_EXPERT_DIM: model["moe_intermediate_size"],
        ROUTED_SCALE_MILLI: round(1000 * model["routed_scaling_factor"]),
        ROUTER_WIDTH: model["router_width"], FIRST_EXPERT: model["first_expert"],
        MOE_SCORE_FUNC: 1, MOE_SELECT_BIAS: 1,
        LIN_DECAY_DIM: hd, LIN_GATE_RANK: rank, FULL_LAYER_AT: 0,
    }


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a full layer's q
    k v wo and its gate's plane; a delta-rule layer's three projections, taps,
    ``A_log``, the decay's pair and ``dt_bias``, the ``beta`` rows, the output
    gate's pair, the output norm (all f32) and the output projection; in both
    the router's rows and bias (f32), three planes a HELD expert, the shared
    expert's three, two block norms; final norm, head."""
    d, v = model["hidden_size"], model["vocab_size"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    H, lhd, conv, rank = mixer_dims(model)
    wide, W, E = model["moe_intermediate_size"], model["router_width"], model["n_routed_experts"]
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    routed = (W * d + W) * 4 + (E + 1) * 3 * qb(wide * d) + 2 * d * 4
    full = 3 * qb(q * d) + 2 * qb(kv * d) + routed
    K = model["linear_attn_config"]["short_conv_kernel_size"]
    small = K * conv + H + 2 * rank * d + 2 * H * lhd * rank + H * lhd + H * d + lhd
    kda = 4 * qb(H * lhd * d) + small * 4 + routed
    n_full = model["num_hidden_layers"] // period(model)
    n_kda = model["num_hidden_layers"] - n_full
    return header_size + v * d * 4 + n_kda * kda + n_full * full + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.solar_open2 import FullParams, KdaParams, MoeParams, SolarLayers

    t = dense.Trunk(cfg, plan)
    d, L, NL, NF = cfg.dim, cfg.n_layers, cfg.n_linear_layers, cfg.n_kv_layers
    H, dk, dv, rank, K = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim, cfg.lin_gate_rank, cfg.lin_conv_kernel
    hid, wide, E, W = cfg.hidden_dim, cfg.shared_expert_dim, cfg.n_experts, cfg.moe_router_width
    decays = H * cfg.lin_decay_dim
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    q = lambda o, i, pre: t.qshard(o, i, None, None, pre=pre)
    kda_mats = [("wq", H * dk, d), ("wk", H * dk, d), ("wv", H * dv, d), ("w_out", d, H * dv)]
    kda_small = {"conv_w": (K, cfg.lin_conv_dim), "a_log": (H,), "w_f_down": (rank, d), "w_f_up": (decays, rank),
                 "dt_bias": (decays,), "w_b": (H, d), "w_g_down": (rank, d), "w_g_up": (H * dv, rank),
                 "norm_o": (dv,), "norm_att": (d,)}
    full_mats = [("wq", cfg.q_dim, d, Q_GAIN), ("wk", cfg.kv_dim, d, 1.0), ("wv", cfg.kv_dim, d, 1.0),
                 ("wo", d, cfg.q_dim, 1.0), ("wg", cfg.q_dim, d, 1.0)]
    expert_mats = [("we1", hid, d, 1.0), ("we2", d, hid, EXPERT_OUT_GAIN), ("we3", hid, d, 1.0)]
    shared_mats = [("ws1", wide, d), ("ws2", d, wide), ("ws3", wide, d)]
    out_sh = t.params_shardings(SolarLayers(
        kda=KdaParams(**{n: q(o, i, (NL,)) for n, o, i in kda_mats},
                      **{n: stacked(NL, *shape) for n, shape in kda_small.items()}),
        full=FullParams(**{n: q(o, i, (NF,)) for n, o, i, _g in full_mats}, norm_att=stacked(NF, d)),
        moe=MoeParams(norm_ffn=stacked(L, d), moe_gate=stacked(L, W, d), moe_bias=stacked(L, W),
                      **{n: q(o, i, (L,)) for n, o, i in shared_mats},
                      **{n: t.qshard(o, i, None, None, pre=(L, E), lead=("layers", "experts"))
                         for n, o, i, _g in expert_mats})))

    def build(key):
        keys = iter(jax.random.split(key, 32))
        normal = lambda shape, spread: jax.random.normal(next(keys), shape, jnp.float32) * spread
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        kda = {n: t.plane(next(keys), o, i, pre=(NL,)) for n, o, i in kda_mats}
        conv_w = normal((NL, K, cfg.lin_conv_dim), 0.5)
        dt0 = jnp.exp(jax.random.uniform(next(keys), (NL, decays), jnp.float32, jnp.log(DT0_MIN), jnp.log(DT0_MAX)))
        a_log = jax.random.uniform(next(keys), (NL, H), jnp.float32, -A_LOG_SPREAD, A_LOG_SPREAD)
        kda_layers = KdaParams(
            **kda, conv_w=conv_w, a_log=a_log,
            w_f_down=normal((NL, rank, d), d ** -0.5), w_f_up=normal((NL, decays, rank), F_GAIN * rank ** -0.5),
            dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),      # softplus^-1(dt0)
            w_b=normal((NL, H, d), d ** -0.5),
            w_g_down=normal((NL, rank, d), d ** -0.5), w_g_up=normal((NL, H * dv, rank), rank ** -0.5),
            norm_o=ones(NL, dv), norm_att=ones(NL, d))
        full = FullParams(**{n: t.plane(next(keys), o, i, pre=(NF,), gain=g) for n, o, i, g in full_mats},
                          norm_att=ones(NF, d))
        gate = normal((L, W, d), ROUTER_GAIN * d ** -0.5)
        bias = normal((L, W), BIAS_SPREAD)
        experts = {n: t.plane(next(keys), o, i, pre=(L, E), gain=g) for n, o, i, g in expert_mats}
        shared = {n: t.plane(next(keys), o, i, pre=(L,)) for n, o, i in shared_mats}
        moe = MoeParams(norm_ffn=ones(L, d), moe_gate=gate, moe_bias=bias, **experts, **shared)
        return t.params(next(keys), next(keys), SolarLayers(kda=kda_layers, full=full, moe=moe))

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

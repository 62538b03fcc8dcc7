"""Seeded weights of a decoder whose every layer is ONE block of a pattern (an
SSD mixer, attention without positions, a routed feed-forward in a latent
space) and its sparse ``.m``: the ``weights`` module of
``nemotron-3-super-120b-a12b`` (``nemotron_h/README.md``).

This module owns the header (arch id 0xABCD07, the dense fields, the mixer's
sizes in keys 39-44, the share's in 35-38, 21 / 67 / 71 for the router, key 72
ONCE A WORD of the pattern: 15 layers at two bits each, ``M`` 0, ``*`` 1, ``E``
2, and 73 for the latent's width), the walk size
(``dllama_tpu/formats/mfile.py::_walk_nemotron_h_layer``) and the ``Params`` tree
(``models/nemotron_h.py::NemotronHLayers``). The rest is ``weights.py``'s.

What the published config does not state is ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the tree is drawn, and why.** Every Q40 plane has gain 1 over its fan-in
(``weights.py``), so a unit-RMS input gives unit-RMS outputs. Departures:

* the mixer as ``falcon_h1/weights.py`` draws it and for its reasons, every
  multiplier 1: ``dt`` rows normals of spread ``DT_GAIN / sqrt(hidden)``;
  ``dt_bias = softplus^-1(dt0)``, ``dt0`` log-uniform in [0.05, 0.5] a head; a
  rate ``r`` log-uniform in [0.001, 0.1] a head and ``A_log = log(r / dt0)``
  (heads that forget within ten tokens beside heads that remember a thousand:
  a lost or rounded state is heard); ``D`` 1, taps normals of spread 1/2, their
  bias of spread 0.1, norms ones.
* ``W_q`` at gain ``Q_GAIN`` = 2: scores of spread 2, a softmax peaked enough for
  16 hidden positions to be missed. There are no positions to draw for.
* the router's rows are normals of spread ``ROUTER_GAIN / sqrt(hidden)`` = 4 /
  sqrt(hidden), as ``lfm2/weights.py`` draws them and for its reason: a token's
  512 logits have spread 4, its 22 best sigmoids lie above 0.999, where a
  bfloat16 is 0.004 wide: a router computed in bfloat16 ties a token's best
  forty scores and lets the bias alone pick among them (``bf16router``), where
  the float32 one orders them.
* the selection bias is NON-ZERO: normals of spread ``BIAS_SPREAD`` = 2.5e-4 a
  routed layer an expert. Around a token's 22nd score (logit 6.9, slope of the
  sigmoid 1e-3) the 512 scores lie 8.6e-5 apart on average (46 logits a unit of
  spread at 1.72 spreads), so the bias is three of those gaps: it moves the
  last few of the 22 (``nobias`` reads like misrouting a seventh of the pairs),
  and weights taken from it would differ in the fourth digit.
* an expert's down-projection ``we2`` is drawn at gain ``EXPERT_OUT_GAIN`` = 0.25.
  **Twenty-two of 512 is a router full of near-ties**: the 22nd and 23rd scores
  of a row lie 8.6e-5 apart, and the bfloat16 stream the program carries
  differs from the reference's float32 one by some 1%, 4e-5 in a score: in a
  third of (row, layer) pairs the program's float32 router and the reference's
  take ANOTHER 22nd expert: another function and not an error. The 22 weights
  are near uniform (5 / 22 each), so a flip whose expert is held moves the
  layer's output by 0.23 of one expert's, 0.28 x the gain at unit planes
  (``relu(n)^2`` has RMS 1.22). At 0.25 that is 0.07 beside a stream of RMS 2-4:
  2-3% a flip, below the tolerance with the two flips a position carries over
  ten routed layers (a quarter of the experts held). The routing controls
  (every pair of most layers) still read far above it
  (``gap_tolerance.json``).
* the shared expert and both latent projections at gain 1: a routed layer
  adds 1.2 units through its shared expert, a sixth of that through the held
  experts.

The builder draws its keys in this order: the mixer stack's ``w_in w_out``, its
``dt`` rows, taps, their bias, ``dt0``, the rate; the attention stack's ``wq wk
wv wo``; the router's rows; its bias; ``w_lat_in w_lat_out``; ``we1 we2``; ``ws1
ws2``; embedding; head.
"""

import os
import struct

import weights as dense

ARCH_NEMOTRON_H = 0xABCD07
# dllama_tpu/formats/mfile.py: HeaderKey 21, 35-38, 39-44, 67, 71-73
MOE_NORM_TOPK = 21
SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH, FIRST_EXPERT = range(35, 39)
SSM_N_HEADS, SSM_HEAD_DIM, SSM_N_GROUPS, SSM_STATE_DIM, SSM_CONV_KERNEL, SSM_CHUNK_SIZE = range(39, 45)
MOE_SCORE_FUNC, MOE_SELECT_BIAS, LAYER_PATTERN, MOE_LATENT_DIM = 67, 71, 72, 73
HIDDEN_ACT_RELU2 = 2
PATTERN_KINDS, KINDS_A_WORD = "M*E", 15
Q_GAIN = 2.0
DT_GAIN = 0.5
DT0_MIN, DT0_MAX = 0.05, 0.5
RATE_MIN, RATE_MAX = 1e-3, 1e-1
ROUTER_GAIN = 4.0
BIAS_SPREAD = 2.5e-4
EXPERT_OUT_GAIN = 0.25
# what the program implements where the published config is silent (models/nemotron_h.py)
ASSUMED = {"dt_clamp": "none", "in_proj_order": "z_x_B_C_dt", "mixer_norm": "gate_then_group_rms",
           "attention_positions": "none", "router_input": "hidden", "shared_expert_input": "hidden",
           "latent_projections": "fc1_latent_proj_fc2_latent_proj", "norm_topk_eps": 1e-20,
           "expert_bias": "selection_only"}


def pattern(model: dict) -> str:
    p = model["hybrid_override_pattern"]
    if len(p) != model["num_hidden_layers"] or set(p) - set(PATTERN_KINDS):
        raise ValueError(f"hybrid_override_pattern {p!r} is not num_hidden_layers characters over M * E")
    return p


def mixer_dims(model: dict) -> tuple[int, int, int, int]:
    """``(heads, mixer width, conv channels, packed Q40 input width)``."""
    H = model["mamba_num_heads"]
    d_ssm = H * model["mamba_head_dim"]
    if d_ssm != model["expand"] * model["hidden_size"]:
        raise ValueError(f"{H} mixer heads of {model['mamba_head_dim']} are not expand x hidden_size")
    conv = d_ssm + 2 * model["n_groups"] * model["ssm_state_size"]
    return H, d_ssm, conv, d_ssm + conv


def header_fields(model: dict) -> list[tuple]:
    """``(key, value)`` in the header's order; key 72 stands once a word."""
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/nemotron_h.py implements {value!r}")
    if model["mlp_hidden_act"] != "relu2" or model["mamba_hidden_act"] != "silu" or not model["use_conv_bias"] \
            or model["n_group"] != 1 or model["topk_group"] != 1 or model["n_shared_experts"] != 1 \
            or model["moe_intermediate_size"] != model["intermediate_size"] \
            or any(model[k] for k in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")):
        raise ValueError("another activation, a projection bias, a convolution without its bias, a group limit "
                         "or more than one shared expert: models/nemotron_h.py carries none of them")
    p = pattern(model)
    mixer_dims(model)
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    words = [sum(PATTERN_KINDS.index(c) << (2 * i) for i, c in enumerate(p[at:at + KINDS_A_WORD]))
             for at in range(0, len(p), KINDS_A_WORD)]
    named = {
        "version": 1, "arch_type": ARCH_NEMOTRON_H,
        "dim": model["hidden_size"], "hidden_dim": model["moe_intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["n_routed_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": HIDDEN_ACT_RELU2, "rope_theta": int(model["rope_theta"]), "rope_type": 0,
        "weight_float_type": dense.Q40, "head_dim": model["head_dim"], "norm_epsilon": eps,
    }
    fields = [(dense.HEADER_KEYS[k], v) for k, v in named.items()]
    fields += [
        (MOE_NORM_TOPK, int(bool(model["norm_topk_prob"]))),
        (SHARED_EXPERT_DIM, model["moe_shared_expert_intermediate_size"]),
        (ROUTED_SCALE_MILLI, int(round(model["routed_scaling_factor"] * 1000))),
        (ROUTER_WIDTH, model["router_width"]), (FIRST_EXPERT, model["first_expert"]),
        (SSM_N_HEADS, model["mamba_num_heads"]), (SSM_HEAD_DIM, model["mamba_head_dim"]),
        (SSM_N_GROUPS, model["n_groups"]), (SSM_STATE_DIM, model["ssm_state_size"]),
        (SSM_CONV_KERNEL, model["conv_kernel"]), (SSM_CHUNK_SIZE, model["chunk_size"]),
        (MOE_SCORE_FUNC, 1), (MOE_SELECT_BIAS, 1), (MOE_LATENT_DIM, model["moe_latent_size"]),
    ]
    return fields + [(LAYER_PATTERN, w) for w in words]


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a layer's ONE
    block and its norm (an ``M`` layer's packed z x B C projection, dt rows
    (f32), taps and bias, ``A_log``, ``D``, ``dt_bias``, the gated norm's weight,
    the output projection; a ``*`` layer's q k v wo; an ``E`` layer's router rows
    and bias (f32), the two latent projections, two planes a held expert, the
    shared expert's two); final norm, head."""
    d, v = model["hidden_size"], model["vocab_size"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    H, d_ssm, conv, w_in = mixer_dims(model)
    lat, hid, wide = model["moe_latent_size"], model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    W, E = model["router_width"], model["n_routed_experts"]
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    block = {
        "M": qb(w_in * d) + H * d * 4 + (model["conv_kernel"] + 1) * conv * 4 + 3 * H * 4 + d_ssm * 4 + qb(d * d_ssm),
        "*": 2 * qb(q * d) + 2 * qb(kv * d),
        "E": W * d * 4 + W * 4 + 2 * qb(lat * d) + E * 2 * qb(hid * lat) + 2 * qb(wide * d),
    }
    layers = sum(block[kind] + d * 4 for kind in pattern(model))
    return header_size + v * d * 4 + layers + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    """``weights.write_sparse`` with a key that may stand more than once."""
    data = b"".join(struct.pack("<ii", k, int(val)) for k, val in header_fields(model))
    header = struct.pack("<ii", dense._MAGIC, 8 + len(data)) + data
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(header)
        f.truncate(walk_size(model, len(header)))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.nemotron_h import AttnParams, MixerParams, NemotronHLayers

    t = dense.Trunk(cfg, plan)
    d, H, K = cfg.dim, cfg.ssm_heads, cfg.ssm_conv_kernel
    NM, NA, NE, E, W = cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers, cfg.n_experts, cfg.moe_router_width
    d_ssm, lat, hid, wide = cfg.ssm_inner_dim, cfg.moe_latent_dim, cfg.hidden_dim, cfg.shared_expert_dim
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    q = lambda o, i, pre: t.qshard(o, i, None, None, pre=pre)
    mixer_mats = [("w_in", cfg.ssm_in_dim, d), ("w_out", d, d_ssm)]
    mixer_small = {"w_dt": (H, d), "conv_w": (K, cfg.ssm_conv_dim), "conv_b": (cfg.ssm_conv_dim,), "a_log": (H,),
                   "d_skip": (H,), "dt_bias": (H,), "norm_ssm": (d_ssm,), "norm": (d,)}
    attn_mats = [("wq", cfg.q_dim, d, Q_GAIN), ("wk", cfg.kv_dim, d, 1.0), ("wv", cfg.kv_dim, d, 1.0),
                 ("wo", d, cfg.q_dim, 1.0)]
    latent_mats = [("w_lat_in", lat, d), ("w_lat_out", d, lat)]
    # an expert's planes are HELD ``cfg.expert_width_held`` wide (whole tiles of 8 scale blocks: the routed
    # kernels' DMA), the lanes behind ``hidden_dim`` zero in both; ``keep`` is the plane's axis they lie on
    held = cfg.expert_width_held
    expert_mats = [("we1", held, lat, 1.0, -1), ("we2", lat, held, EXPERT_OUT_GAIN * (held / hid) ** 0.5, -2)]
    shared_mats = [("ws1", wide, d), ("ws2", d, wide)]
    out_sh = t.params_shardings(NemotronHLayers(
        mixer=MixerParams(**{n: q(o, i, (NM,)) for n, o, i in mixer_mats},
                          **{n: stacked(NM, *shape) for n, shape in mixer_small.items()}),
        attn=AttnParams(**{n: q(o, i, (NA,)) for n, o, i, _g in attn_mats}, norm=stacked(NA, d)),
        norm_moe=stacked(NE, d), moe_gate=stacked(NE, W, d), moe_bias=stacked(NE, W),
        **{n: q(o, i, (NE,)) for n, o, i in latent_mats + shared_mats},
        **{n: t.qshard(o, i, None, None, pre=(NE, E), lead=("layers", "experts"))
           for n, o, i, _g, _a in expert_mats}))

    def zero_behind(plane, axis):
        """``plane`` with the lanes behind ``hid`` on ``axis`` zeroed (codes alone: a zero code is a zero weight)."""
        lane = jnp.arange(held).reshape((held, 1) if axis == -2 else (held,))
        return plane._replace(codes=jnp.where(lane < hid, plane.codes, jnp.int8(0)))

    def build(key):
        keys = iter(jax.random.split(key, 24))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        mixer = {n: t.plane(next(keys), o, i, pre=(NM,)) for n, o, i in mixer_mats}
        w_dt = jax.random.normal(next(keys), (NM, H, d), jnp.float32) * (DT_GAIN / d ** 0.5)
        conv_w = jax.random.normal(next(keys), (NM, K, cfg.ssm_conv_dim), jnp.float32) * 0.5
        conv_b = jax.random.normal(next(keys), (NM, cfg.ssm_conv_dim), jnp.float32) * 0.1
        log_uniform = lambda k, lo, hi: jnp.exp(jax.random.uniform(k, (NM, H), jnp.float32, jnp.log(lo), jnp.log(hi)))
        dt0 = log_uniform(next(keys), DT0_MIN, DT0_MAX)
        rate = log_uniform(next(keys), RATE_MIN, RATE_MAX)
        attn = {n: t.plane(next(keys), o, i, pre=(NA,), gain=g) for n, o, i, g in attn_mats}
        gate = jax.random.normal(next(keys), (NE, W, d), jnp.float32) * (ROUTER_GAIN * d ** -0.5)
        bias = jax.random.normal(next(keys), (NE, W), jnp.float32) * BIAS_SPREAD
        latent = {n: t.plane(next(keys), o, i, pre=(NE,)) for n, o, i in latent_mats}
        experts = {n: zero_behind(t.plane(next(keys), o, i, pre=(NE, E), gain=g), axis)
                   for n, o, i, g, axis in expert_mats}
        shared = {n: t.plane(next(keys), o, i, pre=(NE,)) for n, o, i in shared_mats}
        layers = NemotronHLayers(
            mixer=MixerParams(**mixer, w_dt=w_dt, conv_w=conv_w, conv_b=conv_b, a_log=jnp.log(rate / dt0),
                              d_skip=ones(NM, H), dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),      # softplus^-1(dt0)
                              norm_ssm=ones(NM, d_ssm), norm=ones(NM, d)),
            attn=AttnParams(**attn, norm=ones(NA, d)),
            norm_moe=ones(NE, d), moe_gate=gate, moe_bias=bias, **latent, **experts, **shared)
        return t.params(next(keys), next(keys), layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

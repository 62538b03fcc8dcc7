"""Seeded weights of a decoder whose every layer is an SSD mixer or attention
without positions, THEN gated routed experts beside a gated shared one, under
four scalar multipliers and a tied head; and its sparse ``.m``: the ``weights``
module of ``granite-4.0-h-small`` (``granite_hybrid/README.md``).

This module owns the header (arch id 0xABCD08, the dense fields, the mixer's
sizes in keys 39-44, the share's in 35-38, 21 / 67 for the router, key 72 ONCE A
WORD of the pattern of BLOCKS, two a published layer: ``mamba -> ME``,
``attention -> *E``; 46 / 47 the embedding's multiplier and one over
``logits_scaling``, 74 the residual multiplier, 75 the score's scale, each as
float32 bits, 76 the tie), the walk size (``dllama_tpu/formats/mfile.py::
_walk_nemotron_h_layer`` with ``_walk_share_ffn`` for an ``E`` block; the file
carries the head a second time as the reference format does, the loader does
not read it) and the ``Params`` tree (``models/nemotron_h.py::NemotronHLayers``
with ``we3`` / ``ws3``; ``Params.logits`` IS ``Params.embedding``).

What the published config does not state is ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the tree is drawn, and why.** Every Q40 plane has gain 1 over its fan-in
(``weights.py``), so a unit-RMS input gives unit-RMS outputs. Departures:

* **the embedding, which is also the head**, is uniform at RMS ``EMBED_RMS`` =
  1 / 240, so that ``12 E[token]`` enters the stream at RMS 0.05 beside blocks
  that each add ``0.22 x`` a unit. A head that IS the embedding reads the input
  token's own row against ``12 E[token]`` in the stream: a coherent sum of
  4096 squares where every other row reads a random one, 64 times the spread
  at equal share. At RMS 1 / 12 (a stream that starts at 1) a random tied model
  echoes its last input token at every position, 14 spreads above the rest,
  and nothing a layer does can be seen in its tokens. At 1 / 240 the echo
  stands 3 spreads up, under the best of 100,352 others (4.4): the layers
  decide the token, as in a trained model, which learns the same cancellation.
  The logits are small (spread 0.017 after ``/ 16``); the gap divides by it.
* the mixer as ``falcon_h1/weights.py`` and ``nemotron_h/weights.py`` draw it and
  for their reasons: ``dt`` rows normals of spread ``DT_GAIN / sqrt(hidden)``;
  ``dt_bias = softplus^-1(dt0)``, ``dt0`` log-uniform in [0.05, 0.5] a head; a rate
  ``r`` log-uniform in [0.001, 0.1] a head and ``A_log = log(r / dt0)``: heads
  that forget within ten tokens beside heads that remember a thousand, so over
  8k tokens a state neither dies (the slowest head keeps e^-8 of position 0 at
  8,000, the fastest a fresh state every ten tokens) nor blows up (``exp(dt A)
  < 1`` always; a head's stationary state has the scale of ``dt0 / sqrt(2 r)``
  inputs, 0.1 to 11); a lost or rounded state is heard. ``D`` 1, taps normals of
  spread 1/2, their bias of spread 0.1, norms ones.
* ``W_q`` at gain ``Q_SCORE_SPREAD / (attention_multiplier sqrt(head_dim))`` = 2
  sqrt(128): the published score is ``q . k / 128``, not ``/ sqrt(128)``, so
  unit-gain projections give scores of spread 0.09 and a uniform softmax that
  no control could see. At this gain the scores have spread 2 under the
  published scale (22.6 under ``128 ** -0.5``: the control ``sqrtscale`` reads a
  one-hot attention). There are no positions to draw for.
* the router's rows are normals of spread ``ROUTER_GAIN / sqrt(hidden)`` = 4 /
  sqrt(hidden): a token's 72 logits have spread 4, its tenth and eleventh
  largest lie 0.25 apart on average (72 x 0.223 logits a unit of spread at 1.08
  spreads), and the gates are a softmax over the ten chosen LOGITS: the tenth
  carries ``e^-5`` of the first's weight. So ten of 72 is not a near-tie that
  matters anywhere: where the program's bfloat16 stream (1% of a logit's
  spread, 0.04) takes the other tenth expert, the expert it swaps carries half
  a percent of the layer's routed output. No second pass, no pooled share
  (``nemotron_h/reference.py`` needs both at 22 of 512 near-uniform sigmoids).
* ONE direction a routed block, normals of spread ``ROUTER_COMMON / sqrt(hidden)``
  = 800 / sqrt(hidden), added to all 72 rows of the block alike
  (``laguna/weights.py``, part 2, and for its reason): it moves every one of a
  token's logits by the same 800 x N(0, 1) and cancels in the top-k and in the
  softmax over the chosen, in float32 and whatever error the router's INPUT
  carries. Rows rounded to bfloat16 do not cancel (a logit near 800 is 4 apart
  from the next bfloat16, the 72 logits' whole spread): a router computed below
  float32 reads like misrouting (``bf16router``), where without the direction
  nothing told it from the float32 one (the tiny size read 0.0006 and 0.0).
* an expert's down-projection ``we2`` at gain ``EXPERT_OUT_GAIN`` = 0.5 and the
  shared expert's at 1: a routed layer adds about one unit through its shared
  expert and half of one through its two or three leading experts.

The builder draws its keys in this order: the mixer stack's ``w_in w_out``, its
``dt`` rows, taps, their bias, ``dt0``, the rate; the attention stack's ``wq wk
wv wo``; the router's rows; their common direction; ``we1 we2 we3``; ``ws1 ws2 ws3``; the embedding.
"""

import os
import struct

import weights as dense

ARCH_GRANITE_HYBRID = 0xABCD08
# dllama_tpu/formats/mfile.py: HeaderKey 21, 35-38, 39-44, 46-47, 67, 72, 74-76
MOE_NORM_TOPK = 21
SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH, FIRST_EXPERT = range(35, 39)
SSM_N_HEADS, SSM_HEAD_DIM, SSM_N_GROUPS, SSM_STATE_DIM, SSM_CONV_KERNEL, SSM_CHUNK_SIZE = range(39, 45)
EMBEDDING_MULT, LM_HEAD_MULT, MOE_SCORE_FUNC, LAYER_PATTERN = 46, 47, 67, 72
RESIDUAL_MULT, ATTN_SCALE, TIED_EMBEDDINGS = 74, 75, 76
HIDDEN_ACT_SILU = 1
PATTERN_KINDS, KINDS_A_WORD = "M*E", 15
BLOCKS = {"mamba": "ME", "attention": "*E"}
EMBED_RMS = 1.0 / 240.0
Q_SCORE_SPREAD = 2.0
DT_GAIN = 0.5
DT0_MIN, DT0_MAX = 0.05, 0.5
RATE_MIN, RATE_MAX = 1e-3, 1e-1
ROUTER_GAIN = 4.0
ROUTER_COMMON = 800.0      # the direction every row of a block's router shares
EXPERT_OUT_GAIN = 0.5
# what the program implements where the published config is silent (models/granite_hybrid.py)
ASSUMED = {"dt_clamp": "none", "in_proj_order": "z_x_B_C_dt", "mixer_norm": "gate_then_group_rms",
           "fused_input_linear": "first_half_under_silu", "router": "topk_then_softmax_over_the_chosen"}


def f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", x))[0]


def pattern(model: dict) -> str:
    """The published ``layer_types`` as the header's pattern of blocks."""
    kinds = model["layer_types"]
    if len(kinds) != model["num_hidden_layers"] or set(kinds) - set(BLOCKS):
        raise ValueError(f"layer_types {kinds!r} is not num_hidden_layers entries of 'mamba' / 'attention'")
    return "".join(BLOCKS[kind] for kind in kinds)


def mixer_dims(model: dict) -> tuple[int, int, int, int]:
    """``(heads, mixer width, conv channels, packed Q40 input width)``."""
    H = model["mamba_n_heads"]
    d_ssm = H * model["mamba_d_head"]
    if d_ssm != model["mamba_expand"] * model["hidden_size"]:
        raise ValueError(f"{H} mixer heads of {model['mamba_d_head']} are not mamba_expand x hidden_size")
    conv = d_ssm + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    return H, d_ssm, conv, d_ssm + conv


def header_fields(model: dict) -> list[tuple]:
    """``(key, value)`` in the header's order; key 72 stands once a word."""
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/granite_hybrid.py implements {value!r}")
    if model["hidden_act"] != "silu" or not model["mamba_conv_bias"] or model["position_embedding_type"] != "nope" \
            or model["normalization_function"] != "rmsnorm" or not model["tie_word_embeddings"] \
            or model["hidden_size"] != model["num_attention_heads"] * model["head_dim"] \
            or any(model[k] for k in ("attention_bias", "mamba_proj_bias")):
        raise ValueError("another activation or norm, a projection bias, a convolution without its bias, positions "
                         "in attention or an untied head: models/granite_hybrid.py carries none of them")
    p = pattern(model)
    mixer_dims(model)
    eps = {1e-5: 5, 1e-6: 6}[float(model["rms_norm_eps"])]
    words = [sum(PATTERN_KINDS.index(c) << (2 * i) for i, c in enumerate(p[at:at + KINDS_A_WORD]))
             for at in range(0, len(p), KINDS_A_WORD)]
    named = {
        "version": 1, "arch_type": ARCH_GRANITE_HYBRID,
        "dim": model["hidden_size"], "hidden_dim": model["intermediate_size"],
        "n_layers": len(p), "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["num_local_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": HIDDEN_ACT_SILU, "rope_theta": int(model["rope_theta"]), "rope_type": 0,
        "weight_float_type": dense.Q40, "head_dim": model["head_dim"], "norm_epsilon": eps,
    }
    fields = [(dense.HEADER_KEYS[k], v) for k, v in named.items()]
    fields += [
        (MOE_NORM_TOPK, 1), (SHARED_EXPERT_DIM, model["shared_intermediate_size"]),
        (ROUTED_SCALE_MILLI, 1000), (ROUTER_WIDTH, model["num_local_experts"]), (FIRST_EXPERT, 0),
        (SSM_N_HEADS, model["mamba_n_heads"]), (SSM_HEAD_DIM, model["mamba_d_head"]),
        (SSM_N_GROUPS, model["mamba_n_groups"]), (SSM_STATE_DIM, model["mamba_d_state"]),
        (SSM_CONV_KERNEL, model["mamba_d_conv"]), (SSM_CHUNK_SIZE, model["mamba_chunk_size"]),
        (MOE_SCORE_FUNC, 0),
        (EMBEDDING_MULT, f32_bits(model["embedding_multiplier"])),
        (LM_HEAD_MULT, f32_bits(1.0 / model["logits_scaling"])),
        (RESIDUAL_MULT, f32_bits(model["residual_multiplier"])),
        (ATTN_SCALE, f32_bits(model["attention_multiplier"])), (TIED_EMBEDDINGS, 1),
    ]
    return fields + [(LAYER_PATTERN, w) for w in words]


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; each BLOCK and
    its norm (an ``M`` block's packed z x B C projection, dt rows (f32), taps and
    bias, ``A_log``, ``D``, ``dt_bias``, the gated norm's weight, the output
    projection; a ``*`` block's q k v wo; an ``E`` block's router rows (f32), three
    planes an expert, the shared expert's three); final norm, head."""
    d, v = model["hidden_size"], model["vocab_size"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    H, d_ssm, conv, w_in = mixer_dims(model)
    hid, wide, E = model["intermediate_size"], model["shared_intermediate_size"], model["num_local_experts"]
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    block = {
        "M": qb(w_in * d) + H * d * 4 + (model["mamba_d_conv"] + 1) * conv * 4 + 3 * H * 4 + d_ssm * 4 + qb(d * d_ssm),
        "*": 2 * qb(q * d) + 2 * qb(kv * d),
        "E": E * d * 4 + E * 3 * qb(hid * d) + 3 * qb(wide * d),
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
    """``(build(key) -> Params WITHOUT its head, out_shardings)``: a jit gives
    every output a buffer of its own, so the one array that is embedding AND
    head is made once and named twice behind the jit (:func:`device_params`)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import Params
    from dllama_tpu.models.nemotron_h import AttnParams, MixerParams, NemotronHLayers

    t = dense.Trunk(cfg, plan)
    d, H, K = cfg.dim, cfg.ssm_heads, cfg.ssm_conv_kernel
    NM, NA, NE, E, W = cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers, cfg.n_experts, cfg.moe_router_width
    d_ssm, hid, wide = cfg.ssm_inner_dim, cfg.hidden_dim, cfg.shared_expert_dim
    if cfg.expert_width_held != hid:
        raise ValueError(f"an expert {hid} wide is held in {cfg.expert_width_held}: this builder pads nothing")
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    q = lambda o, i, pre: t.qshard(o, i, None, None, pre=pre)
    mixer_mats = [("w_in", cfg.ssm_in_dim, d), ("w_out", d, d_ssm)]
    mixer_small = {"w_dt": (H, d), "conv_w": (K, cfg.ssm_conv_dim), "conv_b": (cfg.ssm_conv_dim,), "a_log": (H,),
                   "d_skip": (H,), "dt_bias": (H,), "norm_ssm": (d_ssm,), "norm": (d,)}
    q_gain = Q_SCORE_SPREAD / (cfg.attn_scale * cfg.head_dim ** 0.5)
    attn_mats = [("wq", cfg.q_dim, d, q_gain), ("wk", cfg.kv_dim, d, 1.0), ("wv", cfg.kv_dim, d, 1.0),
                 ("wo", d, cfg.q_dim, 1.0)]
    expert_mats = [("we1", hid, d, 1.0), ("we2", d, hid, EXPERT_OUT_GAIN), ("we3", hid, d, 1.0)]
    shared_mats = [("ws1", wide, d), ("ws2", d, wide), ("ws3", wide, d)]
    layer_sh = NemotronHLayers(
        mixer=MixerParams(**{n: q(o, i, (NM,)) for n, o, i in mixer_mats},
                          **{n: stacked(NM, *shape) for n, shape in mixer_small.items()}),
        attn=AttnParams(**{n: q(o, i, (NA,)) for n, o, i, _g in attn_mats}, norm=stacked(NA, d)),
        norm_moe=stacked(NE, d), moe_gate=stacked(NE, W, d), moe_bias=None, w_lat_in=None, w_lat_out=None,
        **{n: q(o, i, (NE,)) for n, o, i in shared_mats},
        **{n: t.qshard(o, i, None, None, pre=(NE, E), lead=("layers", "experts")) for n, o, i, _g in expert_mats})
    out_sh = Params(embedding=t.rep(cfg.vocab_size, d), layers=layer_sh, final_norm=t.rep(d), logits=None)

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
        common = jax.random.normal(next(keys), (NE, 1, d), jnp.float32) * (ROUTER_COMMON * d ** -0.5)
        experts = {n: t.plane(next(keys), o, i, pre=(NE, E), gain=g) for n, o, i, g in expert_mats}
        shared = {n: t.plane(next(keys), o, i, pre=(NE,)) for n, o, i in shared_mats}
        a = EMBED_RMS * 3 ** 0.5
        emb = jax.random.uniform(next(keys), (cfg.vocab_size, d), jnp.float32, -a, a).astype(
            jnp.dtype(cfg.compute_dtype))
        layers = NemotronHLayers(
            mixer=MixerParams(**mixer, w_dt=w_dt, conv_w=conv_w, conv_b=conv_b, a_log=jnp.log(rate / dt0),
                              d_skip=ones(NM, H), dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),      # softplus^-1(dt0)
                              norm_ssm=ones(NM, d_ssm), norm=ones(NM, d)),
            attn=AttnParams(**attn, norm=ones(NA, d)),
            norm_moe=ones(NE, d), moe_gate=gate + common, moe_bias=None, w_lat_in=None, w_lat_out=None,
            **experts, **shared)
        return Params(embedding=emb, layers=layers, final_norm=ones(d), logits=None)

    return build, out_sh


def device_params(cfg, plan, seed: int):
    """The tree on the device, the head named as the embedding's own buffer."""
    params = dense.device_params(cfg, plan, seed, params_builder)
    return params._replace(logits=params.embedding)


def install_seam(seed: int) -> None:
    """``weights.install_seam`` with the tie made behind the jit."""
    import dllama_tpu.runtime.engine as engine_mod

    def load_params_from_mfile(mf, cfg, weight_mode="auto", plan=None):
        if weight_mode != "auto":
            raise ValueError("the benchmark serves Q40 planes (weight_mode auto) only")
        return device_params(cfg, plan, seed)

    engine_mod.load_params_from_mfile = load_params_from_mfile

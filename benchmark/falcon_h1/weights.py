"""Seeded weights of a decoder whose every layer runs an SSD (Mamba-2) mixer
and grouped-query attention side by side, and its sparse ``.m``: the
``weights`` module of ``falcon-h1-34b`` (README, "A layer equation").

This module owns the header (arch id 0xABCD04, the dense fields, and the
program's keys 39-59: the ``mamba_*`` sizes as integers, the rotary base and
the fourteen multipliers each as the BITS of its float32), the walk size (one
kind of layer, as ``dllama_tpu/formats/mfile.py`` walks it) and the ``Params``
tree (one stack, ``FalconH1Layers``). The rest is ``weights.py``'s.

**The published multipliers presuppose trained weights.** They are muP's: each
undoes the scale a trained plane has grown to. Over planes drawn at the
benchmark's usual unit gain they would leave nothing to check: a key scaled by
0.011 gives a flat softmax that no lost K/V block can disturb, a feed-forward
scaled by 0.011 and mixers scaled by 0.04-0.09 vanish beside an embedding
scaled by 5.7, and the logits would be the embedding's alone. So **every plane
is drawn at the gain under which its multiplier leaves an O(1) signal**: the
product of gain and multiplier is what the dense decoders' unit gain is.

* embedding: the usual unit draw divided by ``embedding_multiplier``; head:
  gain ``1 / lm_head_multiplier`` (logits of spread about 1).
* ``wk``: gain ``1 / key_multiplier``; ``wq``: gain ``Q_GAIN`` = 2, so that
  attention logits have a spread of about 2 and a softmax peaked enough for a
  lost block of 16 positions to be missed; ``wo``: ``1 / attention_out_multiplier``.
* the in-projection's four lane ranges, each ``1 / (ssm_in_multiplier *
  ssm_multipliers[i])``: z, x, B and C reach the convolution and the gate at
  unit scale. It is four planes drawn apart and joined along their output
  lanes; the ``dt`` rows are float32 normals of spread ``DT_GAIN / (sqrt(d)
  ssm_in ssm_multipliers[4])``: ``dt``'s pre-activation moves by +-0.5 with the
  token.
* ``dt_bias = softplus^-1(dt0)``, ``dt0`` log-uniform in [0.05, 0.5] a head:
  the step is how hard a token writes into the state, and at Mamba's own
  [0.001, 0.1] the state's readout would be a tenth of the skip ``D x`` and a
  lost state invisible. The DECAY is drawn apart from it: a rate ``r``
  log-uniform in [0.001, 0.1] a head and ``A_log = log(r / dt0)``, so that at
  a zero gate input ``exp(dt A) = exp(-r)`` spreads over 0.905-0.999: heads
  that forget within ten tokens beside heads that remember a thousand.
* ``D`` = 1, the four taps normals of spread 1/2, their bias normals of
  spread 0.1, every norm ones.
* ``w_out``: ``1 / ssm_out_multiplier``; ``w1`` (gate): ``1 /
  mlp_multipliers[0]``; ``w2`` (down): ``1 / mlp_multipliers[1]``; ``w3``: 1.

Both mixers then add about one unit a layer to a residual stream that starts
at one: the logits hear the attention and the state alike.

The builder draws its keys in this order: ``wq wk wv wo``, the four parts of
``w_in`` (z x B C), ``w_out``, ``w1 w2 w3``, the ``dt`` rows, the taps, their
bias, ``dt0``, the rate; embedding; head.
"""

import struct

import weights as dense

ARCH_FALCON_H1 = 0xABCD04
# dllama_tpu/formats/mfile.py: HeaderKey 39-59, the program's format extension
SSM_N_HEADS, SSM_HEAD_DIM, SSM_N_GROUPS, SSM_STATE_DIM, SSM_CONV_KERNEL, SSM_CHUNK_SIZE = 39, 40, 41, 42, 43, 44
ROPE_THETA_F32 = 45
# the multipliers' keys, in this order, from 46
MULTIPLIER_KEYS = ("embedding", "lm_head", "attn_in", "attn_out", "key", "ssm_in", "ssm_out",
                   "mlp_gate", "mlp_down", "ssm_z", "ssm_x", "ssm_b", "ssm_c", "ssm_dt")
FIRST_MULTIPLIER_KEY = 46
Q_GAIN = 2.0
DT_GAIN = 0.5
DT0_MIN, DT0_MAX = 0.05, 0.5
RATE_MIN, RATE_MAX = 1e-3, 1e-1


def multipliers(model: dict) -> dict:
    """The fourteen scalars of the layer equation by the program's names."""
    gate, down = model["mlp_multipliers"]
    z, x, b, c, dt = model["ssm_multipliers"]
    return {"embedding": model["embedding_multiplier"], "lm_head": model["lm_head_multiplier"],
            "attn_in": model["attention_in_multiplier"], "attn_out": model["attention_out_multiplier"],
            "key": model["key_multiplier"], "ssm_in": model["ssm_in_multiplier"],
            "ssm_out": model["ssm_out_multiplier"], "mlp_gate": gate, "mlp_down": down,
            "ssm_z": z, "ssm_x": x, "ssm_b": b, "ssm_c": c, "ssm_dt": dt}


def _f32_bits(x: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(x)))[0]


def header_fields(model: dict) -> dict:
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    mult = multipliers(model)
    fields = {
        "version": 1, "arch_type": ARCH_FALCON_H1,
        "dim": model["hidden_size"], "hidden_dim": model["intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"], "n_experts": 0, "n_active_experts": 0,
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_type": 1,     # half-split pairing; the base rides key 45 as a float
        "weight_float_type": dense.Q40,
        "head_dim": model["head_dim"], "norm_epsilon": eps,
        SSM_N_HEADS: model["mamba_n_heads"], SSM_HEAD_DIM: model["mamba_d_head"],
        SSM_N_GROUPS: model["mamba_n_groups"], SSM_STATE_DIM: model["mamba_d_state"],
        SSM_CONV_KERNEL: model["mamba_d_conv"], SSM_CHUNK_SIZE: model["mamba_chunk_size"],
        ROPE_THETA_F32: _f32_bits(model["rope_theta"]),
    }
    fields.update({FIRST_MULTIPLIER_KEY + i: _f32_bits(mult[name]) for i, name in enumerate(MULTIPLIER_KEYS)})
    return fields


def mixer_dims(model: dict) -> tuple[int, int, int, int]:
    """``(heads, mixer width, conv channels, packed Q40 input width)``."""
    H, d_ssm = model["mamba_n_heads"], model["mamba_d_ssm"]
    if H * model["mamba_d_head"] != d_ssm:
        raise ValueError(f"mamba_d_ssm {d_ssm} is not {H} heads of {model['mamba_d_head']}")
    conv = d_ssm + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    return H, d_ssm, conv, d_ssm + conv


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a layer's q k
    v wo, the packed z x B C projection, the dt rows (f32), taps and their
    bias, ``A_log``, ``D``, ``dt_bias``, the gated norm's weight, the output
    projection, w1 w2 w3 and two block norms; final norm, head."""
    d, h, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    H, d_ssm, conv, w_in = mixer_dims(model)
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    layer = (qb(q * d) + 2 * qb(kv * d) + qb(d * q) + qb(w_in * d) + H * d * 4
             + (model["mamba_d_conv"] + 1) * conv * 4 + 3 * H * 4 + d_ssm * 4 + qb(d * d_ssm)
             + 3 * qb(h * d) + 2 * d * 4)
    return header_size + v * d * 4 + model["num_hidden_layers"] * layer + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.falcon_h1 import FalconH1Layers
    from dllama_tpu.ops.linear import QuantizedWeight

    t = dense.Trunk(cfg, plan)
    m = cfg.mult
    d, hdim, L, H = cfg.dim, cfg.hidden_dim, cfg.n_layers, cfg.ssm_heads
    d_ssm, gn = cfg.ssm_inner_dim, cfg.ssm_groups * cfg.ssm_state_dim
    gains = {"wq": Q_GAIN, "wk": 1.0 / m.key, "wv": 1.0, "wo": 1.0 / m.attn_out,
             "w_out": 1.0 / m.ssm_out, "w1": 1.0 / m.mlp_gate, "w2": 1.0 / m.mlp_down, "w3": 1.0}
    # (name, out, in, out_axis, in_axis): the loader's own table, w_in apart
    mats = t.attention + [("w_out", d, d_ssm, None, None), ("w1", hdim, d, "hidden", None),
                          ("w2", d, hdim, None, "hidden"), ("w3", hdim, d, "hidden", None)]
    # the in-projection's lane ranges: z, x, B, C with their widths and multipliers
    in_parts = [(d_ssm, m.ssm_z), (d_ssm, m.ssm_x), (gn, m.ssm_b), (gn, m.ssm_c)]
    small = {"w_dt": (H, d), "conv_w": (cfg.ssm_conv_kernel, cfg.ssm_conv_dim), "conv_b": (cfg.ssm_conv_dim,),
             "a_log": (H,), "d_skip": (H,), "dt_bias": (H,), "norm_ssm": (d_ssm,), "norm_att": (d,), "norm_ffn": (d,)}
    out_sh = t.params_shardings(FalconH1Layers(
        **{n: t.qshard(o, i, oa, ia) for n, o, i, oa, ia in mats},
        w_in=t.qshard(cfg.ssm_in_dim, d, None, None),
        **{n: t.stacked_rep(*shape) for n, shape in small.items()}))

    def build(key):
        keys = iter(jax.random.split(key, 24))
        attn = {n: t.plane(next(keys), o, i, gain=gains[n]) for n, o, i, _oa, _ia in t.attention}
        parts = [t.plane(next(keys), width, d, gain=1.0 / (m.ssm_in * mult)) for width, mult in in_parts]
        w_in = QuantizedWeight(scales=jnp.concatenate([p.scales for p in parts], axis=-1),
                               codes=jnp.concatenate([p.codes for p in parts], axis=-1))
        rest = {n: t.plane(next(keys), o, i, gain=gains[n]) for n, o, i, _oa, _ia in mats[len(t.attention):]}
        w_dt = (jax.random.normal(next(keys), (L, H, d), jnp.float32)
                * (DT_GAIN / (d ** 0.5 * m.ssm_in * m.ssm_dt)))
        conv_w = jax.random.normal(next(keys), (L, cfg.ssm_conv_kernel, cfg.ssm_conv_dim), jnp.float32) * 0.5
        conv_b = jax.random.normal(next(keys), (L, cfg.ssm_conv_dim), jnp.float32) * 0.1
        log_uniform = lambda k, lo, hi: jnp.exp(jax.random.uniform(k, (L, H), jnp.float32, jnp.log(lo), jnp.log(hi)))
        dt0 = log_uniform(next(keys), DT0_MIN, DT0_MAX)
        rate = log_uniform(next(keys), RATE_MIN, RATE_MAX)
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        layers = FalconH1Layers(
            **attn, w_in=w_in, **rest, w_dt=w_dt, conv_w=conv_w, conv_b=conv_b,
            a_log=jnp.log(rate / dt0), d_skip=ones(L, H),
            dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),      # softplus^-1(dt0)
            norm_ssm=ones(L, d_ssm), norm_att=ones(L, d), norm_ffn=ones(L, d))
        p = t.params(next(keys), next(keys), layers)
        emb = (p.embedding.astype(jnp.float32) / m.embedding).astype(p.embedding.dtype)
        head = p.logits
        if isinstance(head, QuantizedWeight):
            head = QuantizedWeight(scales=(head.scales.astype(jnp.float32) / m.lm_head).astype(head.scales.dtype),
                                   codes=head.codes)
        else:
            head = (head.astype(jnp.float32) / m.lm_head).astype(head.dtype)
        return p._replace(embedding=emb, logits=head)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

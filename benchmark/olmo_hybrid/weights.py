"""Seeded weights of a hybrid decoder (gated delta-rule layers and full
attention layers in a periodic pattern) and its sparse ``.m``: the
``weights`` module of ``olmo-hybrid-7b`` (README, "A layer equation").

This module owns the header (arch id 0xABCD02, the dense fields, and the
program's keys 22-28: the layer period and the six ``linear_*`` sizes), the
walk size (two kinds of layer, as
``dllama_tpu/formats/mfile.py`` walks them) and the ``Params`` tree (two
stacks: ``HybridLayers.lin`` over the linear layers, ``.full`` over the full
ones). The rest is ``weights.py``'s.

**How the mixer's parameters are drawn**, so that over 4096 tokens the state
neither dies nor blows up and every head has work to do:

* ``A_log`` = 0 and ``dt_bias = softplus^-1(dt)`` with ``dt`` log-uniform in
  [0.001, 0.1] per head (Mamba's time-step draw): at a zero gate input ``alpha
  = exp(-dt)`` spreads over 0.905-0.999, so a layer holds heads that forget
  within ten tokens beside heads that remember a thousand.
* the gate rows ``W_ab`` are float32 normals whose scale follows the layer's
  input: the norms sit on a sublayer's OUTPUT, so layer ``l`` sees a residual
  stream of RMS about ``sqrt(1 + 2 l)``, and the rows are divided by it. ``a``
  then has a spread of 0.5 at every depth (``dt`` moves by ``e^+-0.5`` with
  the token) and ``b`` a spread of 1 (``beta`` = 2 sigmoid(b) over about
  0.5-1.5).
* the convolution's four taps are normals of spread 1/2; the output norm and
  every block norm are ones; the q/k norms over the whole projection are ones.
  Tried on the chip and NOT kept (PERF.md, PR 30): q/k norms of 2 (attention
  logits of spread 4, a peaked softmax) raise the honest gap's mean sevenfold
  and still do not bring a lost K/V block out at 1024-3072-token prompts;
  ``dt`` down to 1e-4 with gate rows of spread 2-3 make the whole model
  amplify bfloat16 rounding (honest worst position 1.7-3.0).
* the state cannot blow up whatever the draw: ``k`` has unit length and ``beta
  <= 2``, so a step's transition ``alpha (I - beta k k^T)`` has no eigenvalue
  outside [-1, 1]; it cannot die because the delta rule writes ``beta (v -
  S^T k)`` at every token.

The builder draws its keys in this order: ``w_in``, ``w_out``, the linear
stack's ``w1 w2 w3``, ``w_ab``, the taps, ``dt``; the full stack's ``wq wk wv
wo w1 w2 w3``; embedding; head.
"""

import weights as dense

ARCH_OLMO_HYBRID = 0xABCD02
# dllama_tpu/formats/mfile.py: HeaderKey 22-28, the program's format extension
LAYER_PERIOD, LIN_K_HEADS, LIN_V_HEADS, LIN_K_DIM, LIN_V_DIM = 22, 23, 24, 25, 26
LIN_CONV, LIN_NEG_EIGVAL = 27, 28
DT_MIN, DT_MAX = 1e-3, 1e-1
A_GAIN, B_GAIN = 0.5, 1.0


def period(model: dict) -> int:
    """The layer pattern's period: ``layer_types`` must be whole periods of
    linear layers closed by one full layer."""
    kinds = model["layer_types"]
    p = kinds.index("full_attention") + 1
    want = (["linear_attention"] * (p - 1) + ["full_attention"]) * (len(kinds) // p)
    if kinds != want or len(kinds) != model["num_hidden_layers"]:
        raise ValueError(f"layer_types is not {len(kinds) // p} periods of {p - 1} linear layers and a full one")
    return p


def header_fields(model: dict) -> dict:
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_OLMO_HYBRID,
        "dim": model["hidden_size"], "hidden_dim": model["intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"], "n_experts": 0, "n_active_experts": 0,
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": 0, "rope_type": 0,      # neither is read: no rotary embedding
        "weight_float_type": dense.Q40,
        "head_dim": model["head_dim"], "norm_epsilon": eps,
        LAYER_PERIOD: period(model),
        LIN_K_HEADS: model["linear_num_key_heads"], LIN_V_HEADS: model["linear_num_value_heads"],
        LIN_K_DIM: model["linear_key_head_dim"], LIN_V_DIM: model["linear_value_head_dim"],
        LIN_CONV: model["linear_conv_kernel_dim"], LIN_NEG_EIGVAL: int(bool(model["linear_allow_neg_eigval"])),
    }


def mixer_dims(model: dict) -> tuple[int, int, int, int]:
    """``(heads, conv channels, packed input width, value width)``."""
    H, dk, dv = model["linear_num_value_heads"], model["linear_key_head_dim"], model["linear_value_head_dim"]
    conv = H * (2 * dk + dv)
    return H, conv, conv + H * dv, H * dv


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a linear
    layer's packed input projection, gate rows (f32), taps, ``A_log``,
    ``dt_bias``, output norm, output projection; a full layer's q k v wo and
    its q/k norms; w1 w2 w3 and two block norms in both; final norm, head."""
    d, h, v = model["hidden_size"], model["intermediate_size"], model["vocab_size"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    H, conv, w_in, vdim = mixer_dims(model)
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    ffn = 3 * qb(h * d) + 2 * d * 4
    linear = (qb(w_in * d) + 2 * H * d * 4 + model["linear_conv_kernel_dim"] * conv * 4 + 2 * H * 4
              + model["linear_value_head_dim"] * 4 + qb(d * vdim) + ffn)
    full = qb(q * d) + 2 * qb(kv * d) + qb(d * q) + (q + kv) * 4 + ffn
    n_full = model["num_hidden_layers"] // period(model)
    n_linear = model["num_hidden_layers"] - n_full
    return header_size + v * d * 4 + n_linear * linear + n_full * full + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.hybrid import HybridLayers, LinearLayerParams
    from dllama_tpu.models.llama import LayerParams

    t = dense.Trunk(cfg, plan)
    d, hdim, P = cfg.dim, cfg.hidden_dim, cfg.layer_period
    NL, NF, H = cfg.n_linear_layers, cfg.n_kv_layers, cfg.lin_heads
    ffn = [("w1", hdim, d, "hidden", None), ("w2", d, hdim, None, "hidden"), ("w3", hdim, d, "hidden", None)]
    lin_mats = [("w_in", cfg.lin_in_dim, d, None, None), ("w_out", d, H * cfg.lin_value_dim, None, None)] + ffn
    full_mats = t.attention + ffn
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    lin_small = {"w_ab": (2 * H, d), "conv_w": (cfg.lin_conv_kernel, cfg.lin_conv_dim), "a_log": (H,),
                 "dt_bias": (H,), "norm_o": (cfg.lin_value_dim,), "norm_att": (d,), "norm_ffn": (d,)}
    out_sh = t.params_shardings(HybridLayers(
        lin=LinearLayerParams(**{n: t.qshard(o, i, oa, ia, pre=(NL,)) for n, o, i, oa, ia in lin_mats},
                              **{n: stacked(NL, *shape) for n, shape in lin_small.items()}),
        full=LayerParams(**{n: t.qshard(o, i, oa, ia, pre=(NF,)) for n, o, i, oa, ia in full_mats},
                         norm_att=stacked(NF, d), norm_ffn=stacked(NF, d),
                         norm_q=stacked(NF, cfg.q_dim), norm_k=stacked(NF, cfg.kv_dim))))
    # the model's layer index of each linear layer, for the gate rows' scale
    depth = jnp.asarray([l for l in range(cfg.n_layers) if (l + 1) % P], jnp.float32)

    def build(key):
        keys = iter(jax.random.split(key, 24))
        lin = {n: t.plane(next(keys), o, i, pre=(NL,)) for n, o, i, _oa, _ia in lin_mats}
        rows = jax.random.normal(next(keys), (NL, 2 * H, d), jnp.float32)
        gain = jnp.concatenate([jnp.full((H,), A_GAIN), jnp.full((H,), B_GAIN)])
        w_ab = rows * gain[None, :, None] / jnp.sqrt(d * (1.0 + 2.0 * depth))[:, None, None]
        conv_w = jax.random.normal(next(keys), (NL, cfg.lin_conv_kernel, cfg.lin_conv_dim), jnp.float32) * 0.5
        dt = jnp.exp(jax.random.uniform(next(keys), (NL, H), jnp.float32, jnp.log(DT_MIN), jnp.log(DT_MAX)))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        linear = LinearLayerParams(
            **lin, w_ab=w_ab, conv_w=conv_w, a_log=jnp.zeros((NL, H), jnp.float32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
            norm_o=ones(NL, cfg.lin_value_dim), norm_att=ones(NL, d), norm_ffn=ones(NL, d))
        full = LayerParams(**{n: t.plane(next(keys), o, i, pre=(NF,)) for n, o, i, _oa, _ia in full_mats},
                           norm_att=ones(NF, d), norm_ffn=ones(NF, d),
                           norm_q=ones(NF, cfg.q_dim), norm_k=ones(NF, cfg.kv_dim))
        return t.params(next(keys), next(keys), HybridLayers(lin=linear, full=full))

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

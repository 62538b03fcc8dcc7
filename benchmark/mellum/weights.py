"""Seeded weights of a decoder whose period is sliding layers closed by a full
one, q and k normed a head, every layer routed, and its sparse ``.m``: the
``weights`` module of ``mellum2-12b-a2.5b`` (README, "A layer equation").

This module owns the header (arch id 0xABCD0A, the dense fields, laguna's
extension keys 21-22 and 29-38 with the sliding layers' heads the full layers'
own, the whole head rotating, no dense layer, no shared expert, a routed scale
of 1, the router's width the experts held, and key 79, the full layer's place:
the LAST of its period), the walk size
(``dllama_tpu/formats/mfile.py::_walk_laguna_layer``, the q and k norms' weights
in the gate's place) and the ``Params`` tree (``models/laguna.py::LagunaLayers``
with no gate rows, no dense planes and no shared expert). The rest is
``weights.py``'s.

The conventions the published config does not state are ONE value each in the
configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the router's rows are drawn**: as ``laguna/weights.py`` draws them, and
for its reasons. (1) Normals of spread ``ROUTER_GAIN / sqrt(hidden)``: a row's
64 logits have a spread of 4, so the 8th and 9th largest lie some 0.3 apart on
average and bfloat16's noise in the stream rarely takes a choice the other
way. (2) ONE direction a layer, normals of spread ``ROUTER_COMMON /
sqrt(hidden)``, added to all 64 rows of the layer alike: it cancels in a
float32 softmax (and so does an error in the router's input), and rows rounded
to bfloat16 carry their own noise of spread 1 into each logit, so the
reference's ``bf16router`` control, and a program whose router fell to
bfloat16, read like misrouting.

The q and k norms' weights are ones, as the block norms' are.

The builder draws its keys in this order: the full stack's ``wq wk wv wo``; the
sliding stack's the same; the router's rows; ``we1 we2 we3``; embedding; head;
the routers' common directions.
"""

import weights as dense

ARCH_MELLUM = 0xABCD0A
# dllama_tpu/formats/mfile.py: HeaderKey 14-17, 21-22, 29-38, 79
ROPE_FACTOR, ROPE_BETA_SLOW, ROPE_BETA_FAST, ROPE_ORIG_MAX = 14, 15, 16, 17
MOE_NORM_TOPK, LAYER_PERIOD = 21, 22
(SLIDING_WINDOW, N_HEADS_SLIDING, ROPE_THETA_SLIDING, ROPE_DIM, N_DENSE_LAYERS, DENSE_HIDDEN_DIM,
 SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH, FIRST_EXPERT) = range(29, 39)
FULL_LAYER_AT = 79
ROPE_TYPE_YARN = 3
ROUTER_GAIN = 4.0
ROUTER_COMMON = 800.0      # the direction every row of a layer's router shares (module text, part 2)
# what the program implements where the published config is silent (models/mellum.py)
ASSUMED = {"norm_placement": "pre", "qk_norm": True, "rope_pairing": "half_split",
           "window_counts_current_token": True, "mtp_head": False}


def period_of(model: dict) -> int:
    """``layer_types`` must be whole periods of sliding layers closed by a full
    one, ``mlp_layer_types`` all sparse."""
    kinds, L = model["layer_types"], model["num_hidden_layers"]
    P = kinds.index("full_attention") + 1 if "full_attention" in kinds else 0
    if P < 2 or len(kinds) != L or kinds != (["sliding_attention"] * (P - 1) + ["full_attention"]) * (L // P) \
            or model["mlp_layer_types"] != ["sparse"] * L:
        raise ValueError(f"layer_types is not {L} layers in whole periods of sliding layers closed by a full one, "
                         f"every one sparse")
    return P


def header_fields(model: dict) -> dict:
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/mellum.py implements {value!r}")
    if model["attention_bias"] or model["max_window_layers"] or not model["use_sliding_window"] \
            or model["tie_word_embeddings"] or model["hidden_act"] != "silu":
        raise ValueError("attention bias, max_window_layers, use_sliding_window false, a tied head or another "
                         "activation than silu: models/mellum.py carries none of them")
    P = period_of(model)
    rope_f, rope_s = model["rope_parameters"]["full_attention"], model["rope_parameters"]["sliding_attention"]
    if rope_f["rope_type"] != "yarn" or rope_s["rope_type"] != "default":
        raise ValueError("rope_parameters: full layers yarn, sliding layers default, both over the whole head")
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_MELLUM,
        "dim": model["hidden_size"], "hidden_dim": model["moe_intermediate_size"],
        "n_layers": model["num_hidden_layers"], "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": model["num_experts"], "n_active_experts": model["num_experts_per_tok"],
        "vocab_size": model["vocab_size"], "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": int(rope_f["rope_theta"]), "rope_type": ROPE_TYPE_YARN,
        "weight_float_type": dense.Q40, "head_dim": model["head_dim"], "norm_epsilon": eps,
        ROPE_FACTOR: int(rope_f["factor"]), ROPE_BETA_SLOW: int(rope_f["beta_slow"]),
        ROPE_BETA_FAST: int(rope_f["beta_fast"]), ROPE_ORIG_MAX: int(rope_f["original_max_position_embeddings"]),
        MOE_NORM_TOPK: int(bool(model["norm_topk_prob"])), LAYER_PERIOD: P,
        SLIDING_WINDOW: model["sliding_window"], N_HEADS_SLIDING: model["num_attention_heads"],
        ROPE_THETA_SLIDING: int(rope_s["rope_theta"]), ROPE_DIM: model["head_dim"],
        N_DENSE_LAYERS: 0, DENSE_HIDDEN_DIM: 0, SHARED_EXPERT_DIM: 0, ROUTED_SCALE_MILLI: 1000,
        ROUTER_WIDTH: model["num_experts"], FIRST_EXPERT: 0, FULL_LAYER_AT: P - 1,
    }


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a layer's q k v
    wo, the q and k norms' weights (f32), the router's rows (f32), three planes
    an expert, two block norms; final norm, head."""
    d, v, hd = model["hidden_size"], model["vocab_size"], model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    layer = (2 * qb(q * d) + 2 * qb(kv * d) + 2 * hd * 4 + model["num_experts"] * d * 4
             + model["num_experts"] * 3 * qb(model["moe_intermediate_size"] * d) + 2 * d * 4)
    return header_size + v * d * 4 + model["num_hidden_layers"] * layer + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.laguna import AttnParams, LagunaLayers

    t = dense.Trunk(cfg, plan)
    d, hd, L = cfg.dim, cfg.head_dim, cfg.n_layers
    NF, NS, E = cfg.n_periods, cfg.n_window_layers, cfg.n_experts
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))
    attn_mats = [("wq", cfg.q_dim, d), ("wk", cfg.kv_dim, d), ("wv", cfg.kv_dim, d), ("wo", d, cfg.q_dim)]
    # an expert's planes are HELD ``cfg.expert_width_held`` wide (whole tiles of 8 scale blocks: the routed kernels'
    # DMA; 896 lanes in 1024), the lanes behind ``hidden_dim`` zero; the last entry is the plane's axis they lie on
    hid, held = cfg.hidden_dim, cfg.expert_width_held
    expert_mats = [("we1", held, d, -1), ("we2", d, held, -2), ("we3", held, d, -1)]
    nothing = dict(w1=None, w2=None, w3=None, ws1=None, ws2=None, ws3=None)    # no dense layer, no shared expert

    def attn_sh(n):
        return AttnParams(**{name: t.qshard(o, i, None, None, pre=(n,)) for name, o, i in attn_mats}, wg=None,
                          norm_att=stacked(n, d), norm_q=stacked(n, hd), norm_k=stacked(n, hd))

    out_sh = t.params_shardings(LagunaLayers(
        full=attn_sh(NF), slide=attn_sh(NS), norm_ffn=stacked(L, d), moe_gate=stacked(L, cfg.moe_router_width, d),
        **{n: t.qshard(o, i, None, None, pre=(L, E), lead=("layers", "experts")) for n, o, i, _a in expert_mats},
        **nothing))

    def zero_behind(plane, axis):
        """``plane`` with the lanes behind ``hid`` on ``axis`` zeroed (codes alone: a zero code is a zero weight)."""
        lane = jnp.arange(held).reshape((held, 1) if axis == -2 else (held,))
        return plane._replace(codes=jnp.where(lane < hid, plane.codes, jnp.int8(0)))

    def build(key):
        keys = iter(jax.random.split(key, 16))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)

        def attn(n):
            planes = {name: t.plane(next(keys), o, i, pre=(n,)) for name, o, i in attn_mats}
            return AttnParams(**planes, wg=None, norm_att=ones(n, d), norm_q=ones(n, hd), norm_k=ones(n, hd))

        full, slide = attn(NF), attn(NS)
        gate = jax.random.normal(next(keys), (L, cfg.moe_router_width, d), jnp.float32) * (ROUTER_GAIN * d ** -0.5)
        experts = {n: zero_behind(t.plane(next(keys), o, i, pre=(L, E), gain=dense.LAYER_GAIN * (
            (held / hid) ** 0.5 if axis == -2 else 1.0)), axis) for n, o, i, axis in expert_mats}
        k_embedding, k_head = next(keys), next(keys)
        # drawn after every other key, so that the rest of the model is what it was without the direction
        common = jax.random.normal(next(keys), (L, 1, d), jnp.float32) * (ROUTER_COMMON * d ** -0.5)
        layers = LagunaLayers(full=full, slide=slide, norm_ffn=ones(L, d), moe_gate=gate + common, **experts,
                              **nothing)
        return t.params(k_embedding, k_head, layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

"""Seeded weights of a decoder of window and full attention layers with routed
experts of which a SHARE is held, and its sparse ``.m``: the ``weights`` module
of ``laguna-s-2.1`` (README, "A layer equation").

This module owns the header (arch id 0xABCD03, the dense fields, the program's
key 21 for ``norm_topk_prob``, 22 for the layer period and 29-38: the window,
the sliding layers' heads and rotary base, the full layers' rotating lanes and
YaRN numbers in the reference's own rope-scaling keys, the leading dense
layers and their width, the shared expert's width, the routed scale, and THE
SHARE: ``n_experts`` = the experts held, the router's width, the first expert
held), the walk size (``dllama_tpu/formats/mfile.py::_walk_laguna_layer``) and
the ``Params`` tree (``models/laguna.py::LagunaLayers``). The rest is
``weights.py``'s.

The six conventions the published config does not state are ONE value each in
the configuration's ``program`` (:data:`ASSUMED`); the program implements these
values and no others, so a configuration that states another is refused here,
before a header is written.

**How the router's rows are drawn.** Two parts. (1) Normals of spread
``ROUTER_GAIN / sqrt(hidden)`` = 4 / sqrt(hidden): the input is unit-RMS (a norm
of ones in front), so a row's 256 logits have a spread of 4. With a spread of 1
the 10th and 11th largest of 256 lie 0.046 apart on average and bf16's noise in
the logits (about 0.01) takes one row in five the other way in every layer; at
4 the margin is 0.18 on average, the 10th expert's weight is some 2% of the
first's (a flip there moves little), and one row in ten has a margin under
0.02. A flip is still another function and not an error, so over 23 routed
layers most rows carry one somewhere: what that does to the gap is measured
(``gap_tolerance.json``), not assumed away. Routing stays near uniform over the
experts: rows are independent of each other and of the token.
(2) ONE direction a layer, normals of spread ``ROUTER_COMMON / sqrt(hidden)`` =
800 / sqrt(hidden), added to all 256 rows of the layer alike. It moves every
logit of a token by the same amount (some hundreds), so the softmax, the
choice and the weights are what part (1) alone gives: the float32 router the
configuration states computes ``Wr h2`` to about 3e-4 there, and an error in
the router's INPUT (the bf16 stream the program carries: 1% at depth) is
common to the 256 logits and cancels the same way. What does not cancel is
the rounding of the ROWS: an element is near 14, a bfloat16 there is 0.0625
wide, part (1) is 0.07 wide, so rows held or multiplied in bfloat16 carry
their own noise of spread 1.3 into each logit (against 0.009 without the
direction), a fifth to a third of the ten choices of EVERY token change and
the weights move by a factor of e. That is what makes the router's stated
precision visible in the tokens: the reference's ``bf16router`` control and
a program whose router falls to bfloat16 rows or to the MXU's default
one-pass product both read like misrouting (``gap_tolerance.json``). Without
it no statistic of 200-400 emitted tokens told a bfloat16 router from the
float32 one (the first session's readings, PERF.md section 6, PR 34).

The per-head gate's rows are normals of spread ``1 / sqrt(hidden)``: ``g =
sigmoid(N(0, 1))``, heads half open on average, between 0.27 and 0.73 for two
rows in three.

The builder draws its keys in this order: the full stack's ``wq wk wv wo``, its
gate rows; the sliding stack's the same; the dense layer's ``w1 w2 w3``; the
router's rows; ``we1 we2 we3``; the shared expert's ``ws1 ws2 ws3``; embedding;
head; the routers' common directions.
"""

import weights as dense

ARCH_LAGUNA = 0xABCD03
# dllama_tpu/formats/mfile.py: HeaderKey 14-17, 21-22, 29-38
ROPE_FACTOR, ROPE_BETA_SLOW, ROPE_BETA_FAST, ROPE_ORIG_MAX = 14, 15, 16, 17
MOE_NORM_TOPK, LAYER_PERIOD = 21, 22
(SLIDING_WINDOW, N_HEADS_SLIDING, ROPE_THETA_SLIDING, ROPE_DIM, N_DENSE_LAYERS, DENSE_HIDDEN_DIM,
 SHARED_EXPERT_DIM, ROUTED_SCALE_MILLI, ROUTER_WIDTH, FIRST_EXPERT) = range(29, 39)
ROPE_TYPE_YARN = 3
ROUTER_GAIN = 4.0
ROUTER_COMMON = 800.0      # the direction every row of a layer's router shares (module text, part 2)
# what the program implements where the published config is silent (models/laguna.py)
ASSUMED = {"norm_placement": "pre", "qk_norm": False, "router_score": "softmax", "shared_expert_gate": False,
           "attention_gate": "sigmoid_of_normed_input_on_head_output_before_wo", "window_counts_current_token": True}


def pattern(model: dict) -> tuple[int, int, int]:
    """``(period, full heads, sliding heads)``: ``layer_types`` must be whole
    periods of one full layer and then sliding ones, the heads per layer one
    number a kind."""
    kinds, heads = model["layer_types"], model["num_attention_heads_per_layer"]
    L = model["num_hidden_layers"]
    P = kinds.index("full_attention", 1) if "full_attention" in kinds[1:] else len(kinds)
    want = (["full_attention"] + ["sliding_attention"] * (P - 1)) * (L // P)
    if kinds != want or len(heads) != L:
        raise ValueError(f"layer_types is not {L // P} periods of a full layer and {P - 1} sliding ones")
    full, slide = {h for h, k in zip(heads, kinds) if k == "full_attention"}, \
        {h for h, k in zip(heads, kinds) if k != "full_attention"}
    if len(full) != 1 or len(slide) != 1 or full != {model["num_attention_heads"]}:
        raise ValueError("num_attention_heads_per_layer is not one number a layer kind, the full layers' "
                         "being num_attention_heads")
    return P, full.pop(), slide.pop()


def n_dense(model: dict) -> int:
    dense_layers = list(model.get("mlp_only_layers") or [])
    if dense_layers != list(range(len(dense_layers))):
        raise ValueError(f"mlp_only_layers {dense_layers} are not the leading layers")
    return len(dense_layers)


def header_fields(model: dict) -> dict:
    for key, value in ASSUMED.items():
        if model[key] != value:
            raise ValueError(f"program.{key} is {model[key]!r}; models/laguna.py implements {value!r}")
    if model["moe_apply_router_weight_on_input"] or model["moe_router_logit_softcapping"] \
            or model["attention_bias"] or model["decoder_sparse_step"] != 1 or model["gating"] != "per-head":
        raise ValueError("router weight on the input, a router soft cap, attention bias, a sparse step other "
                         "than 1 or a gate that is not per head: models/laguna.py carries none of them")
    P, _full, slide = pattern(model)
    rope_f, rope_s = model["rope_parameters"]["full_attention"], model["rope_parameters"]["sliding_attention"]
    if rope_f["rope_type"] != "yarn" or rope_s["rope_type"] != "default" or rope_s["partial_rotary_factor"] != 1:
        raise ValueError("rope_parameters: full layers yarn, sliding layers default over the whole head")
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH_LAGUNA,
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
        SLIDING_WINDOW: model["sliding_window"], N_HEADS_SLIDING: slide,
        ROPE_THETA_SLIDING: int(rope_s["rope_theta"]),
        ROPE_DIM: int(round(model["head_dim"] * rope_f["partial_rotary_factor"])),
        N_DENSE_LAYERS: n_dense(model), DENSE_HIDDEN_DIM: model["intermediate_size"],
        SHARED_EXPERT_DIM: model["shared_expert_intermediate_size"],
        ROUTED_SCALE_MILLI: int(round(model["moe_routed_scaling_factor"] * 1000)),
        ROUTER_WIDTH: model["router_width"], FIRST_EXPERT: model["first_expert"],
    }


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects: embedding f32; a layer's q k v
    wo at its kind's heads and its gate rows (f32); the dense layer's w1 w2
    w3, or the router's rows (f32), three planes a held expert and the shared
    expert's three; two block norms; final norm, head."""
    d, v, hd = model["hidden_size"], model["vocab_size"], model["head_dim"]
    P, full, slide = pattern(model)
    kv = hd * model["num_key_value_heads"]
    qb = lambda n: dense.tensor_bytes(n, dense.Q40)
    attn = lambda heads: 2 * qb(heads * hd * d) + 2 * qb(kv * d) + heads * d * 4
    ffn_dense = 3 * qb(model["intermediate_size"] * d)
    ffn_routed = (model["router_width"] * d * 4 + model["num_experts"] * 3 * qb(model["moe_intermediate_size"] * d)
                  + 3 * qb(model["shared_expert_intermediate_size"] * d))
    L, nd = model["num_hidden_layers"], n_dense(model)
    layers = sum(attn(full if l % P == 0 else slide) + (ffn_dense if l < nd else ffn_routed) + 2 * d * 4
                 for l in range(L))
    return header_size + v * d * 4 + layers + d * 4 + qb(v * d)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def params_builder(cfg, plan):
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.laguna import AttnParams, LagunaLayers

    t = dense.Trunk(cfg, plan)
    d, hd, L = cfg.dim, cfg.head_dim, cfg.n_layers
    NF, NS, ND, NM, E = cfg.n_periods, cfg.n_window_layers, cfg.n_dense_layers, cfg.n_moe_layers, cfg.n_experts
    stacked = lambda n, *tail: t.plan.sharding_for((n, *tail), "layers", *([None] * len(tail)))

    def attn_mats(heads):
        return [("wq", heads * hd, d), ("wk", cfg.kv_dim, d), ("wv", cfg.kv_dim, d), ("wo", d, heads * hd)]

    def attn_sh(n, heads):
        return AttnParams(**{name: t.qshard(o, i, None, None, pre=(n,)) for name, o, i in attn_mats(heads)},
                          wg=stacked(n, heads, d), norm_att=stacked(n, d))

    wide, hid, sh = cfg.dense_hidden_dim, cfg.hidden_dim, cfg.shared_expert_dim
    dense_mats = [("w1", wide, d), ("w2", d, wide), ("w3", wide, d)]
    expert_mats = [("we1", hid, d), ("we2", d, hid), ("we3", hid, d)]
    shared_mats = [("ws1", sh, d), ("ws2", d, sh), ("ws3", sh, d)]
    out_sh = t.params_shardings(LagunaLayers(
        full=attn_sh(NF, cfg.n_heads), slide=attn_sh(NS, cfg.n_heads_sliding), norm_ffn=stacked(L, d),
        **{n: t.qshard(o, i, None, None, pre=(ND,)) for n, o, i in dense_mats},
        moe_gate=stacked(NM, cfg.moe_router_width, d),
        **{n: t.qshard(o, i, None, None, pre=(NM, E), lead=("layers", "experts")) for n, o, i in expert_mats},
        **{n: t.qshard(o, i, None, None, pre=(NM,)) for n, o, i in shared_mats}))

    def build(key):
        keys = iter(jax.random.split(key, 32))
        ones = lambda *shape: jnp.ones(shape, jnp.float32)

        def attn(n, heads):
            planes = {name: t.plane(next(keys), o, i, pre=(n,)) for name, o, i in attn_mats(heads)}
            wg = jax.random.normal(next(keys), (n, heads, d), jnp.float32) * d ** -0.5
            return AttnParams(**planes, wg=wg, norm_att=ones(n, d))

        full, slide = attn(NF, cfg.n_heads), attn(NS, cfg.n_heads_sliding)
        dense_ffn = {n: t.plane(next(keys), o, i, pre=(ND,)) for n, o, i in dense_mats}
        gate = jax.random.normal(next(keys), (NM, cfg.moe_router_width, d), jnp.float32) * (ROUTER_GAIN * d ** -0.5)
        experts = {n: t.plane(next(keys), o, i, pre=(NM, E)) for n, o, i in expert_mats}
        shared = {n: t.plane(next(keys), o, i, pre=(NM,)) for n, o, i in shared_mats}
        k_embedding, k_head = next(keys), next(keys)
        # drawn after every other key, so that the rest of the model is what it was without the direction
        common = jax.random.normal(next(keys), (NM, 1, d), jnp.float32) * (ROUTER_COMMON * d ** -0.5)
        layers = LagunaLayers(full=full, slide=slide, norm_ffn=ones(L, d), **dense_ffn, moe_gate=gate + common,
                              **experts, **shared)
        return t.params(k_embedding, k_head, layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

"""Seeded weights of a decoder whose feed-forward is routed, and its sparse
``.m``: the worked case of README's "A layer equation". This module owns the
header (the dense fields, the expert counts, and the program's own key 21 for
``norm_topk_prob``), the walk size (per layer a float32 gate and three planes
an expert in place of w1 w2 w3) and the ``Params`` tree (a gate ``[L, E, dim]``
and expert stacks ``[L, E, in, out]``); the rest is ``weights.py``'s.
"""

import weights as dense

MOE_NORM_TOPK = 21      # dllama_tpu/formats/mfile.py: HeaderKey.MOE_NORM_TOPK, the program's format extension


def header_fields(model: dict) -> dict:
    return {**dense.header_fields(model), "hidden_dim": model["moe_intermediate_size"],
            "n_experts": model["num_experts"], "n_active_experts": model["num_experts_per_tok"],
            MOE_NORM_TOPK: int(bool(model["norm_topk_prob"]))}


def ffn_bytes(model: dict) -> int:
    d, h, E = model["hidden_size"], model["moe_intermediate_size"], model["num_experts"]
    return E * d * 4 + E * 3 * dense.tensor_bytes(h * d, dense.Q40)


def write_sparse_model(path: str, model: dict) -> None:
    dense.write_sparse(path, header_fields(model),
                       lambda header_size: dense.walk_size(model, header_size, ffn_bytes(model)))


def params_builder(cfg, plan):
    """Draws, in order: wq wk wv wo, the gate, we1 we2 we3, embedding, head."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import LayerParams

    t = dense.Trunk(cfg, plan)
    L, E, d, hdim = cfg.n_layers, cfg.n_experts, cfg.dim, cfg.hidden_dim
    experts = [("we1", hdim, d, "hidden", None), ("we2", d, hdim, None, "hidden"), ("we3", hdim, d, "hidden", None)]
    no_dense_ffn = {"w1": None, "w2": None, "w3": None}
    out_sh = t.params_shardings(LayerParams(
        **{n: t.qshard(o, i, oa, ia) for n, o, i, oa, ia in t.attention}, **no_dense_ffn, **t.norm_shardings(),
        moe_gate=t.stacked_rep(E, d),
        **{n: t.qshard(o, i, oa, ia, pre=(L, E), lead=("layers", "experts")) for n, o, i, oa, ia in experts}))

    def build(key):
        keys = iter(jax.random.split(key, 16))
        attention = {n: t.plane(next(keys), o, i) for n, o, i, _oa, _ia in t.attention}
        gate = jax.random.normal(next(keys), (L, E, d), jnp.float32) * d ** -0.5    # unit-RMS rows in: logits of spread 1
        layers = LayerParams(**attention, **no_dense_ffn, **t.norms(), moe_gate=gate,
                             **{n: t.plane(next(keys), o, i, pre=(L, E)) for n, o, i, _oa, _ia in experts})
        return t.params(next(keys), next(keys), layers)

    return build, out_sh


def install_seam(seed: int) -> None:
    dense.install_seam(seed, params_builder)

"""A decoder whose every layer is a state-space mixer or attention without
positions, THEN routed experts beside a shared one (``ArchType.GRANITE_HYBRID``;
Granite 4.0-H Small is 40 such layers, 36 mixers and 4 attention layers in a
period of ten, ``m m m m m a m m m m``).

**The equations.** ``x_0 = embedding_multiplier * E[token]``; with ``r`` the
residual multiplier and RMS norms::

    u = rmsnorm(x; w_l^in);    x <- x + r * Mixer_l(u)
    v = rmsnorm(x; w_l^post);  x <- x + r * (Routed_l(v) + Shared_l(v))

``Mixer_l`` is the SSD mixer of ``models/ssd_mixer.py`` (every ``ssm_*``
multiplier 1) or grouped-query attention with NO positional embedding whose
score is multiplied by ``attention_multiplier`` (a stated number, not ``head_dim
** -0.5``). ``Routed_l`` takes the ``k`` largest of its router's logits, gates
by a softmax over THOSE ``k`` logits and sums gated experts (``W_o (silu(a) *
b)``, ``[a | b] = W_i v``); ``Shared_l`` is one more such expert every token
takes. A final norm, and ``logits = (E x) / logits_scaling``: the head IS the
embedding.

**No walk of its own.** A published layer is two of ``models/nemotron_h.py``'s
blocks, each behind its own norm: ``mamba -> ME``, ``attention -> *E``
(:func:`layer_pattern`), so a period of ten is ``MEMEMEMEME*EMEMEMEME``, which
``nemotron_h.pattern_runs`` cuts into ``(ME) x 5, *, (EM) x 4, E``. That
module's ``_run_layers`` applies what this equation adds where ``cfg`` says so
(the residual multiplier on every block's output, the embedding's and the
logits' multipliers; the score's scale rides ``cfg.score_dim`` into the
attention kernels), ``models/share.py`` reads the experts' form off the stack
(``we3`` / ``ws3`` present: gated) and the router's off ``cfg`` (softmax,
``moe_norm_topk``, no bias, no group limit, no latent). **Softmax over the
whole width and then the chosen ``k`` renormalised IS softmax over the chosen
``k`` logits**: ``exp(s_i) / Z`` over ``sum_chosen exp(s_j) / Z`` has no ``Z``
left, and the ``k`` largest scores are the ``k`` largest logits. ``share.route``
computes it the first way; the benchmark's plain reference
(``benchmark/granite_hybrid/reference.py``) the published way.

**A tied head is ONE array**: ``Params.logits is Params.embedding``
(``runtime/weights.StreamingLoader.params``), a dense ``[vocab, dim]`` weight at
the compute dtype, and the budget counts it once.

``family.tick`` is None: a chunk and a step are two programs, as nemotron_h's.
"""

from __future__ import annotations

import functools

from . import nemotron_h as nh
from .config import ModelConfig
from .family import Family, state_refusal

# a published layer's kind as the blocks it is made of
BLOCKS = {"mamba": "ME", "attention": "*E"}


def layer_pattern(layer_types) -> str:  # dlint: static-fn
    """The published ``layer_types`` as nemotron_h's pattern, two blocks a
    layer."""
    return "".join(BLOCKS[kind] for kind in layer_types)


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # a mixer's packed in-projection and out-projection; q k v wo; the held
    # experts' THREE planes with the router over its whole width and the
    # shared expert's three. The head is the embedding: not a plane
    mixer = cfg.dim * (cfg.ssm_in_dim + cfg.ssm_inner_dim)
    attn = 2 * cfg.dim * (cfg.q_dim + cfg.kv_dim)
    routed = (cfg.dim * cfg.moe_router_width
              + 3 * cfg.dim * cfg.expert_width_held * cfg.n_experts
              + 3 * cfg.dim * cfg.shared_expert_dim)
    head = 0 if cfg.tied_embeddings else cfg.dim * cfg.vocab_size
    return (cfg.n_state_layers * mixer + cfg.n_kv_layers * attn
            + cfg.n_moe_layers * routed + head)


def _describe(cfg: ModelConfig, engine) -> str:
    m = cfg.mult
    return (f"; blocks: {nh.pattern_words(cfg)}"
            f": {cfg.n_moe_layers} layers of a mixer then experts, "
            f"{cfg.n_state_layers} SSD mixers ({cfg.ssm_heads} heads of "
            f"{cfg.ssm_head_dim} in {cfg.ssm_groups} groups, state "
            f"{cfg.ssm_state_dim}), {cfg.n_kv_layers} attention without "
            f"positions ({cfg.n_heads}:{cfg.n_kv_heads} heads of "
            f"{cfg.head_dim}, scores x {cfg.attn_scale:g}); experts: "
            f"{cfg.n_experts} of {cfg.moe_router_width} held from "
            f"{cfg.moe_first_expert}, {cfg.n_active_experts} a token, gated, "
            f"{cfg.hidden_dim} wide, shared {cfg.shared_expert_dim}; "
            f"multipliers: embedding {m.embedding:g}, residual "
            f"{m.residual:g}, logits {m.lm_head:g}; head "
            f"{'tied to the embedding (one array)' if cfg.tied_embeddings else 'untied'}")


FAMILY = Family(
    forward=nh.forward,
    paged_forward=nh.paged_forward,
    tick=None,
    column=nh.FAMILY.column,
    load_params=functools.partial(nh._load_params, gated=True),
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=nh.FAMILY.layer_kinds,
    describe=_describe,
    refusal=state_refusal(
        "a decoder of a mixer then routed experts a layer (an SSD mixer's "
        "recurrent state in the state pool, routing counters beside it; the "
        "three-stack walk has no mesh plan yet)"))

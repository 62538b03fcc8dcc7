"""A decoder of window and full attention layers whose period CLOSES with the
full layer, with a per-head RMS norm on q and k and every layer routed
(``ArchType.MELLUM``; Mellum2-12B-A2.5B is three sliding layers and a full one
a period, 32 query heads on 4 K/V heads in both kinds, 8 of 64 softmax-routed
experts in every layer, no shared expert, no dense layer).

**The equations.** Layer ``l``, input ``x``:

* ``h = rmsnorm(x; w_a)``; ``q = Wq h`` (``n_heads`` heads), ``k = Wk h``, ``v = Wv
  h``; ``q_j <- rmsnorm(q_j; w_q)``, ``k_j <- rmsnorm(k_j; w_k)`` over the lanes
  of each head, in front of the rotary embedding.
* rotary over the WHOLE head, half-split pairing: a sliding layer plain
  tables at ``rope_theta_sliding``, a full layer YaRN's (models/rope.py).
* causal softmax attention, query head ``j`` on K/V head ``j // G``; in a
  sliding layer the query at ``i`` sees keys ``i - window + 1 .. i``.
  ``x <- x + Wo concat_j(o_j)``. No gate.
* ``h2 = rmsnorm(x; w_f)``; ``p = softmax(Wr h2)`` over the router's whole
  width in float32; ``T`` = the ``n_active_experts`` largest; ``w_e = p_e /
  sum_T p``; ``x <- x + sum_{e in T, e held} w_e E_e(h2)``.

**Nothing here walks a layer.** The walk is models/laguna.py's ONE
``_scan_periods``, and the three programs are laguna's: where the full layer
stands in its period (``cfg.full_layer_at`` = ``layer_period - 1``), that
there is no gate (``AttnParams.wg`` None) and that q and k are normed
(``AttnParams.norm_q`` / ``norm_k``), that no layer is dense and no expert
shared (``LagunaLayers.w1`` / ``ws1`` None) are DATA of the header and of the
stacks, as models/granite_hybrid.py is data over nemotron_h's programs. A
slot's context is blocks of laguna's two pools, its admission column
laguna's (the full layers dense, the sliding layers' a buffer of the window
and the widest chunk), and a matched prefix brings its window with it
(runtime/kvblocks.py, "Window layers"). The experts held may be a share
(models/share.py); the published deployment holds every one.
"""

from __future__ import annotations

from . import laguna
from .config import ModelConfig
from .family import Family, Refusal, layer_kinds


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # what is HELD: every layer's attention at one head count, the router
    # over its whole width and the held experts at the width their planes
    # are held in (896 lanes in 1024), the vocabulary's rows
    attn = 2 * cfg.dim * (cfg.q_dim + cfg.kv_dim)
    routed = cfg.dim * (cfg.moe_router_width
                        + 3 * cfg.expert_width_held * cfg.n_experts)
    return cfg.n_layers * (attn + routed) + cfg.dim * cfg.vocab_size


FAMILY = Family(
    forward=laguna.forward,
    paged_forward=laguna.paged_forward,
    tick=laguna.forward_and_step,
    column=laguna.LagunaColumn.behind,
    load_params=laguna._load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(full=cfg.n_kv_layers,
                                        sliding=cfg.n_window_layers),
    describe=lambda cfg, engine: (
        f"; layers: {cfg.layer_period - 1} sliding (window "
        f"{cfg.sliding_window}) and a full one a period, "
        f"{cfg.n_periods} periods, q/k normed; experts: {cfg.n_experts} of "
        f"{cfg.moe_router_width} held from {cfg.moe_first_expert}, "
        f"{cfg.n_active_experts} a token, {cfg.hidden_dim} wide (held in "
        f"{cfg.expert_width_held})"),
    refusal=Refusal(
        what=("a decoder with window layers and routed experts (two block "
              "pools a sequence; the period scan has no mesh plan yet)"),
        carries="the two block pools",
        spec_lookup=laguna.FAMILY.refusal.spec_lookup,
        kv_host_blocks=laguna.FAMILY.refusal.kv_host_blocks))

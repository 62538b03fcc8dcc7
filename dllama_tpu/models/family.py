"""What a decoder family IS, said once: its programs, its admission column,
its loader, its sizes, its refusals and its start-up words.

A family's module (``models/llama.py`` for the dense Llama / Qwen3 equations,
``hybrid.py``, ``falcon_h1.py``, ``laguna.py``, ``axk1.py``, ``lfm2.py``,
``nemotron_h.py``, ``granite_hybrid.py``, ``solar_open2.py``, ``mellum.py``)
ends
in ``FAMILY = Family(...)``; :func:`family_of` picks it by ``cfg.arch``, and
``llama.forward`` / ``llama.paged_forward`` (the one entry of every family),
the engine, the HBM guard, the loader, the paged generator and the start-up
line ask IT. What a slot's context is MADE OF (``ModelConfig.has_state``,
``has_window_layers``, ``has_latent_cache``, ``has_expert_share``, the cache
and state shapes) is not a family's name and stays with ``ModelConfig``:
``runtime/kvblocks.py`` and ``PagedGenerator`` act on those shapes.

A new family is its own module, one row of ``_MODULES`` here, its header
fields in ``models/config.py``, and its tensor walk and converter mapping
(``formats/mfile.py``, ``convert/hf.py``)."""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Callable, NamedTuple

from ..formats.mfile import ArchType

# the labels of ``dllama_layer_kinds`` (runtime/telemetry.LAYER_KINDS)
LAYER_KINDS = ("linear", "ssm_beside_full", "full", "latent", "sliding",
               "conv", "mamba", "attention", "moe")


def layer_kinds(**counts: int) -> dict[str, int]:
    """Every kind's count, the ones not named zero: the gauge is the
    process's, so an engine states all of them."""
    return {**dict.fromkeys(LAYER_KINDS, 0), **counts}


class Refusal(NamedTuple):
    """Why the engine refuses a paged-only family what it does not carry to
    it: the subject of the sentence, and the reason beside each of the three
    flags whose reason is the family's (the engine owns the flags)."""

    what: str
    carries: str          # what only the paged generator carries
    spec_lookup: str
    kv_host_blocks: str


def state_refusal(what: str) -> Refusal:
    """The refusal of a family whose layers carry a recurrent state
    (``cfg.has_state``): the three reasons are the state pool's."""
    return Refusal(
        what=what, carries="the state pool",
        spec_lookup=("a rejected draft cannot be rolled back out of a "
                     "recurrent state"),
        kv_host_blocks=("the host tier spills and pages in K/V blocks; a "
                        "state has no host copy"))


@dataclass(frozen=True)
class Family:
    """What the seven ladders used to decide, and nothing else."""

    # (params, cfg, tokens, start_pos, column, n_valid) -> logits, column
    forward: Callable
    # (params, cfg, tokens, pos_vec, cache, tables, write_lens)
    #   -> logits, cache
    paged_forward: Callable
    # a prefill chunk and the tick's decode rows as ONE program
    # (llama.forward_and_step's arguments, plus the chunk's valid length in
    # front of ``poison`` where ``cfg.paged_only``), or None: a chunk and a
    # step are two. Brought by the dense decoders (PR 47), falcon_h1 (PR 52),
    # lfm2 (PR 53), the hybrid (PR 55) and laguna (PR 57): a third set of
    # closures over the family's one layer walk. ``cache`` is ``(column,
    # <the step's cache>)``; where that holds routing counters the program
    # adds ITS dispatches' to the totals' chunk row (models/lfm2.py says why)
    tick: Callable | None
    # (cfg, k, v) -> an admission's column from the slot's gathered view
    # (``v`` None where the pool has no V plane)
    column: Callable
    # (loader, cfg) -> Params, through runtime/weights' streaming loader
    load_params: Callable
    # (cfg) -> the matmul planes' weights (runtime/hbm.py's payload)
    matmul_weight_count: Callable
    # (cfg) -> {kind: layers} over LAYER_KINDS
    layer_kinds: Callable
    # (cfg, engine) -> the start-up line's words on the layers, or ""
    describe: Callable
    # None where every serving path carries the family
    refusal: Refusal | None


_MODULES = {
    ArchType.LLAMA: "llama",
    ArchType.QWEN3: "llama",
    ArchType.OLMO_HYBRID: "hybrid",
    ArchType.LAGUNA: "laguna",
    ArchType.FALCON_H1: "falcon_h1",
    ArchType.AXK1: "axk1",
    ArchType.LFM2: "lfm2",
    ArchType.NEMOTRON_H: "nemotron_h",
    ArchType.GRANITE_HYBRID: "granite_hybrid",
    ArchType.SOLAR_OPEN2: "solar_open2",
    ArchType.MELLUM: "mellum",
}


def family_of(cfg) -> Family:
    """The family of ``cfg.arch``; its module is imported when first asked
    for, so a dense start imports no other family's."""
    return importlib.import_module(
        "." + _MODULES[cfg.arch], __package__).FAMILY

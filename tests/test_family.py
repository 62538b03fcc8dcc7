"""One description a decoder family (``dllama_tpu/models/family.py``): what
``family_of`` hands back for each of the nine ``ArchType`` values, that
``runtime/`` and ``serve/`` ask IT and name no family themselves, and that the
three small answers the ladders used to give (the HBM guard's weight count, the
layer-kind gauges, the start-up line's words) are, for each family's tiny
configuration, exactly what the parent commit gave. The expected values below
were written from a run of that parent (PR 49's tree: its engines built on the
same tiny files); the whole-engine tests of each family cover the rest.

The tiny configurations are the family tests' own: the benchmark's selftest
files through the benchmark's weight-makers for the six families that bring a
module, ``helpers.tiny_header_params`` for the dense two.
"""

import ast
import dataclasses
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

from dllama_tpu.formats import mfile
from dllama_tpu.formats.mfile import ArchType
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.models.family import LAYER_KINDS, Family, Refusal, family_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

# arch -> (the benchmark's directory, its selftest configuration), or None for
# the dense equations
TINY = {
    ArchType.LLAMA: None,
    ArchType.QWEN3: None,
    ArchType.OLMO_HYBRID: ("olmo_hybrid", "tiny-olmo-hybrid.json"),
    ArchType.LAGUNA: ("laguna", "tiny-laguna.json"),
    ArchType.FALCON_H1: ("falcon_h1", "tiny-falcon-h1.json"),
    ArchType.AXK1: ("a_x_k1", "tiny-a.x-k1.json"),
    ArchType.LFM2: ("lfm2", "tiny-lfm2.json"),
    ArchType.NEMOTRON_H: ("nemotron_h", "tiny-nemotron-h.json"),
    ArchType.GRANITE_HYBRID: ("granite_hybrid", "tiny-granite-hybrid.json"),
    ArchType.SOLAR_OPEN2: ("solar_open2", "tiny-solar-open2.json"),
    ArchType.MELLUM: ("mellum", "tiny-mellum.json"),
}
ARCHS = list(ArchType)

# what the parent commit gave (see the module's docstring)
PARENT = {
    ArchType.LLAMA: (69632, {"full": 2}, ""),
    ArchType.QWEN3: (69632, {"full": 2}, ""),
    ArchType.OLMO_HYBRID: (286720, {"linear": 6, "full": 2}, "; layers: 6 linear, 2 full"),
    ArchType.LAGUNA: (1418240, {"full": 2, "sliding": 6},
                      "; layers: 2 full, 6 sliding (window 32); experts: 8 of 16 held from 4, 4 a token"),
    ArchType.FALCON_H1: (270336, {"ssm_beside_full": 4}, "; layers: 4 with an SSD mixer beside attention"),
    ArchType.AXK1: (312320, {"latent": 4},
                    "; layers: 4 of latent attention (a row of 40 in 128 lanes a token); experts: 8 of 16 held "
                    "from 4, 4 a token of 2 of 4 groups"),
    ArchType.LFM2: (552448, {"full": 2, "conv": 7},
                    "; layers: 7 conv (3 taps, a tail of 2 x 64 a sequence), 2 full (heads of 16 lanes cached in "
                    "128: the paged kernel compiles for them); experts: 8 of 8 held from 0, 2 a token, selection "
                    "bias"),
    # no parent: PR 51 brought the family; what its own tiny configuration gives
    ArchType.NEMOTRON_H: (1257472, {"mamba": 4, "attention": 2, "moe": 4},
                          "; layers: EMEM*EMEM* = 2 x [(EM)x2 *]: 4 SSD mixers (4 heads of 32 in 2 groups, state 16), 2 "
                          "attention without positions (4:2 heads of 16), 4 routed; experts: 8 of 16 held from 4, 4 a "
                          "token, 288 wide (held in 512) in a latent of 32, shared 64, selection bias"),
    # no parent: PR 54 brought the family; its head is the embedding, so no head among the planes
    ArchType.GRANITE_HYBRID: (502784, {"mamba": 4, "attention": 2, "moe": 6},
                              "; blocks: MEME*EMEME*E = 1 x [(ME)x2 * (EM)x2 E * E]: 6 layers of a mixer then experts, 4 "
                              "SSD mixers (4 heads of 32 in 1 groups, state 16), 2 attention without positions (4:2 "
                              "heads of 16, scores x 0.0625); experts: 8 of 8 held from 0, 3 a token, gated, 32 wide, "
                              "shared 64; multipliers: embedding 12, residual 0.22, logits 0.0625; head tied to the "
                              "embedding (one array)"),
    # no parent: PR 58 brought the family; a short module over the hybrid's period scan
    ArchType.SOLAR_OPEN2: (393216, {"linear": 6, "full": 2, "moe": 8},
                           "; layers: 2 full (gated, no positions, 4:2 heads of 16; the first of every 4), 6 delta-rule "
                           "(4 heads of 16 x 16, a decay a key channel (16 a head), gates through 16); experts behind "
                           "every mixer: 4 of 8 held from 2, 3 a token, 32 wide, shared 32, selection bias"),
    # no parent: PR 60 brought the family; data over laguna's period scan
    ArchType.MELLUM: (1138688, {"full": 2, "sliding": 6},
                      "; layers: 3 sliding (window 32) and a full one a period, 2 periods, q/k normed; experts: 16 of 16 "
                      "held from 0, 4 a token, 32 wide (held in 32)"),
}
DENSE_MOE_WEIGHTS = 180736   # tiny_header_params(QWEN3, n_experts=4, n_active_experts=2), the parent's count


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _dense_cfg(path, arch, **kw):
    from helpers import tiny_header_params, write_tiny_model

    rope = mfile.RopeType.FALCON if arch == ArchType.QWEN3 else mfile.RopeType.LLAMA
    write_tiny_model(path, tiny_header_params(arch=arch, rope_type=rope, **kw), np.random.default_rng(0))
    return ModelConfig.from_header(mfile.ModelFile.open(path).header)


@pytest.fixture(scope="module")
def cfgs(tmp_path_factory):
    """Each family's tiny configuration as the engine would read it: the
    header of a file the family's own writer made."""
    sys.path.insert(0, BENCH)       # as run.py puts it, and as the family tests do
    import run as bench_run

    tmp = tmp_path_factory.mktemp("families")
    out = {}
    for arch, tiny in TINY.items():
        path = str(tmp / f"{arch.name}.m")
        if tiny is None:
            out[arch] = _dense_cfg(path, arch)
            continue
        folder, name = tiny
        weights = _import(f"{folder}_weights_for_family", os.path.join(BENCH, folder, "weights.py"))
        with open(os.path.join(BENCH, folder, "selftest", "configs", name), encoding="utf-8") as f:
            weights.write_sparse_model(path, bench_run.model_view(json.load(f)))
        out[arch] = ModelConfig.from_header(mfile.ModelFile.open(path, max_seq_len=512).header)
    return out


@pytest.mark.parametrize("arch", ARCHS, ids=lambda a: a.name)
def test_every_arch_has_a_family_with_every_field_set(cfgs, arch):
    cfg = cfgs[arch]
    assert cfg.arch == arch
    fam = family_of(cfg)
    assert isinstance(fam, Family)
    may_be_none = {"tick", "refusal"}
    for field in dataclasses.fields(Family):
        value = getattr(fam, field.name)
        if field.name in may_be_none:
            continue
        assert callable(value), field.name
    dense = TINY[arch] is None
    # the dense equations alone are carried by every path
    assert (fam.refusal is None) == dense
    assert dense == (not cfg.paged_only)
    if not dense:
        assert isinstance(fam.refusal, Refusal) and all(fam.refusal)
        assert fam.refusal.what.startswith("a ") and not fam.refusal.carries.endswith(")")


# which families bring a program for a tick that carries a chunk (``Family.tick``: the chunk and the tick's decode rows
# in ONE pass over the weights), as module and function, and which keep a chunk and a step apart (ROADMAP Speed 2 has
# their order)
TICK = {
    ArchType.LLAMA: ("llama", "forward_and_step"),
    ArchType.QWEN3: ("llama", "forward_and_step"),
    ArchType.OLMO_HYBRID: ("hybrid", "forward_and_step"),
    ArchType.LAGUNA: ("laguna", "forward_and_step"),
    ArchType.FALCON_H1: ("falcon_h1", "forward_and_step"),
    ArchType.AXK1: None,
    ArchType.LFM2: ("lfm2", "forward_and_step"),
    ArchType.NEMOTRON_H: None,
    ArchType.GRANITE_HYBRID: None,
    ArchType.SOLAR_OPEN2: None,
    ArchType.MELLUM: ("laguna", "forward_and_step"),       # laguna's three programs, the header's data
}


@pytest.mark.parametrize("arch", ARCHS, ids=lambda a: a.name)
def test_which_families_bring_a_tick_program(cfgs, arch):
    """The table above, a case a family; one that brings a tick brings its
    OWN module's, under the name the benchmark times as a chunk
    (``jit_forward...``), taking the dense program's arguments and, where a
    recurrent state would keep what padding wrote, the chunk's valid length."""
    import importlib
    import inspect

    fam = family_of(cfgs[arch])
    if TICK[arch] is None:
        assert fam.tick is None
        return
    module, name = TICK[arch]
    assert fam.tick is getattr(importlib.import_module("dllama_tpu.models." + module), name)
    assert fam.tick.__name__ == "forward_and_step"
    args = list(inspect.signature(fam.tick).parameters)
    valid = ["n_valid"] if cfgs[arch].paged_only else []
    assert args == ["params", "cfg", "tokens", "pos_vec", "cache", "tables", "chunk", "chunk_pos", *valid, "poison"]


def test_the_dense_equations_share_one_family_and_the_entry_is_llamas(cfgs):
    from dllama_tpu.models import llama

    assert family_of(cfgs[ArchType.LLAMA]) is family_of(cfgs[ArchType.QWEN3]) is llama.FAMILY
    assert llama.FAMILY.tick is llama.forward_and_step
    # the one entry of every family keeps its name (the engine jits it as program ``forward``)
    assert llama.forward.__name__ == "forward" and llama.paged_forward.__name__ == "paged_forward"
    others = {family_of(cfgs[a]) for a in ARCHS if TINY[a] is not None}
    assert len(others) == 9 and llama.FAMILY not in others


FAMILY_MODULES = {"hybrid", "falcon_h1", "laguna", "axk1", "lfm2", "nemotron_h", "granite_hybrid", "solar_open2",
                  "mellum"}
FAMILY_NAMING = {"is_hybrid", "has_ssm", "has_short_conv"}
FAMILY_ARCHS = {a.name for a in ARCHS} - {"LLAMA", "QWEN3"}


def _python_files(*folders):
    for folder in folders:
        for base, _dirs, files in os.walk(os.path.join(ROOT, "dllama_tpu", folder)):
            yield from (os.path.join(base, f) for f in files if f.endswith(".py"))


@pytest.mark.parametrize("path", sorted(_python_files("runtime", "serve")), ids=lambda p: os.path.relpath(p, ROOT))
def test_runtime_and_serve_name_no_family(path):
    """No import of a family's module, no read of a family-naming predicate,
    no comparison against one of the six families' ``ArchType`` values."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            module = (node.module or "").split(".")
            names = {a.name for a in node.names}
            if module[-1:] and module[-1] in FAMILY_MODULES and "models" in module:
                found.append((node.lineno, "import", node.module))
            if module[-1:] == ["models"] and names & FAMILY_MODULES:
                found.append((node.lineno, "import", sorted(names & FAMILY_MODULES)))
        elif isinstance(node, ast.Import):
            for a in node.names:
                parts = a.name.split(".")
                if "models" in parts and parts[-1] in FAMILY_MODULES:
                    found.append((node.lineno, "import", a.name))
        elif isinstance(node, ast.Attribute):
            if node.attr in FAMILY_NAMING:
                found.append((node.lineno, "read", node.attr))
            if node.attr in FAMILY_ARCHS and isinstance(node.value, ast.Name) and node.value.id == "ArchType":
                found.append((node.lineno, "arch", node.attr))
    assert not found, f"{os.path.relpath(path, ROOT)} names a decoder family: {found}"


def test_the_walk_sees_what_it_is_looking_for():
    """The same walk over ``models/`` finds the families (so an empty result
    above is a finding, not a blind walk)."""
    hits = set()
    for path in _python_files("models"):
        with open(path, encoding="utf-8") as f:
            for node in ast.walk(ast.parse(f.read())):
                if isinstance(node, ast.Attribute) and node.attr in FAMILY_NAMING:
                    hits.add(node.attr)
    assert hits == FAMILY_NAMING


@pytest.mark.parametrize("arch", ARCHS, ids=lambda a: a.name)
def test_weight_count_layer_kinds_and_words_are_the_parents(cfgs, arch):
    from dllama_tpu.runtime import hbm, introspection

    cfg = cfgs[arch]
    weights, kinds, words = PARENT[arch]
    fam = family_of(cfg)
    assert hbm.matmul_weight_count(cfg) == fam.matmul_weight_count(cfg) == weights
    assert fam.layer_kinds(cfg) == {**dict.fromkeys(LAYER_KINDS, 0), **kinds}
    engine = types.SimpleNamespace(cfg=cfg, startup_s={"header": 0.5, "weight_load": 1.25}, kv_block_size=16)
    assert fam.describe(cfg, engine) == words
    assert introspection.startup_line(engine) == "🧮 start-up: 1.75 s (header 0.50, weight_load 1.25)" + words


def test_a_dense_routed_files_weight_count_is_the_parents(tmp_path):
    from dllama_tpu.runtime import hbm

    cfg = _dense_cfg(str(tmp_path / "moe.m"), ArchType.QWEN3, n_experts=4, n_active_experts=2)
    assert cfg.is_moe and hbm.matmul_weight_count(cfg) == DENSE_MOE_WEIGHTS


@pytest.mark.parametrize("arch", ARCHS, ids=lambda a: a.name)
def test_an_admissions_column_is_the_familys(cfgs, arch):
    """What ``PagedGenerator._take`` gets from the slot's gathered view:
    the view itself where prefix blocks are shared (the dense decoders' K/V,
    the latent rows, the full layers' K/V beside window layers' empty buffer),
    a sequence's start where they never are (a zero state and tail in the
    compute dtype beside the view)."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.kvblocks import StateColumn
    from dllama_tpu.runtime.kvcache import KVCache, padded_cache_len

    cfg = cfgs[arch]
    S = 64
    k = jnp.ones((cfg.n_kv_layers, 1, cfg.cache_heads, S, cfg.cache_width), jnp.bfloat16)
    v = None if cfg.has_latent_cache else 2 * k
    col = family_of(cfg).column(cfg, k, v)
    if arch in (ArchType.LLAMA, ArchType.QWEN3):
        assert isinstance(col, KVCache) and col.k is k and col.v is v
    elif arch == ArchType.AXK1:
        assert type(col).__name__ == "LatentColumn" and col.c is k and not col.stats.any()
    elif arch in (ArchType.LAGUNA, ArchType.MELLUM):
        # the full layers' view itself (matched prefix blocks are shared), and an empty buffer for the sliding
        # layers at position 0: the generator gathers a match's last window into it
        assert type(col).__name__ == "LagunaColumn" and col.k is k and col.v is v
        assert col.wk.shape == (cfg.n_window_layers, 1, cfg.n_kv_heads, S, cfg.head_dim)
        assert col.wk.dtype == k.dtype and not col.wk.any() and not col.wv.any() and int(col.base) == 0
        assert padded_cache_len(cfg.seq_len) > S
    else:
        assert isinstance(col, StateColumn) and col.k is k and col.v is v
        assert col.conv.shape == cfg.conv_shape(1) and col.conv.dtype == jnp.dtype(cfg.compute_dtype)
        assert not col.conv.any() and (col.s is None) == (cfg.state_shape(1) is None)
        assert (col.stats is None) == (not cfg.has_expert_share)

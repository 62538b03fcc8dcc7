"""Every decoder family's served programs against the text they lowered to.

``tests/goldens/program_hlo_sha256.json`` holds, a family, the sha256 of the
lowered text of ``forward`` (a prefill chunk), ``step``
(``paged_sampled_step_guarded``) and, where the family brings one, ``tick``
(``Family.tick``, its ``forward_and_step``), taken with
``helpers.lowered_program_digest`` at one small geometry on commit a8d74b0
(PR 61), the PARENT of the change that said the tick's row split and epilogue
once (PR 63): the fourteen cells of the benchmark serve through these
programs and no other, so a refactoring that holds every digest changed no
served program. The file replaces the per-family tests that held ``forward``
and the step alone (falcon_h1's and the hybrid's from PR 51 / PR 54, lfm2's
from PR 52, nemotron_h's from PR 51, laguna's step from PR 56: their digests
are in it unchanged) and adds the tick programs, which nothing held.

After a DELIBERATE change to what a family compiles, take its digests anew
with the same function and say in the golden's commit why they moved. The
dense decoders' bfloat16 programs as the server packs them stay with
``tools/dense_hlo_digest.py`` and ``tests/goldens/dense_hlo_sha256.json``.
"""

import json
import os

import pytest

from helpers import LOWERED_PROGRAMS, lowered_program_digest, tiny_family_engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "goldens", "program_hlo_sha256.json"), encoding="utf-8") as f:
    GOLDEN = json.load(f)
# the families whose equations are ``models/llama.py``'s own, built from a config; every other name is its folder
# under ``benchmark/``, whose selftest model the family's own tests run
DENSE = {"llama": ("LLAMA", "LLAMA"), "qwen3": ("QWEN3", "FALCON")}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """``family -> (cfg, params)``, each made when first asked for and kept
    for the file; the engines closed and the loader's seam put back behind it."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig

    made, engines = {}, []

    def get(family):
        if family in made:
            return made[family]
        if family in DENSE:
            arch, rope = DENSE[family]
            cfg = ModelConfig(arch=ArchType[arch], dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                              head_dim=16, vocab_size=128, seq_len=128, norm_epsilon=1e-5, rope_theta=10000.0,
                              rope_type=RopeType[rope], compute_dtype="float32")
            made[family] = cfg, llama.init_random_params(cfg, quantized=True)
        else:
            engines.append(tiny_family_engine(family, tmp_path_factory.mktemp(family)))
            made[family] = engines[-1].cfg, engines[-1].params
        return made[family]

    yield get
    for engine in engines:
        engine.close()
    engine_mod.load_params_from_mfile = llama.load_params_from_mfile


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_the_program_lowers_to_the_parents_text(built, key):
    family, program = key.rsplit(".", 1)
    assert lowered_program_digest(*built(family), program) == GOLDEN[key]


def test_the_golden_names_every_program_a_family_brings(built):
    """No tick program without its digest, and no digest of a program that
    is not there: a family that gains a tick program gains a line."""
    from dllama_tpu.models.family import _MODULES, family_of

    families = sorted({key.rsplit(".", 1)[0] for key in GOLDEN})
    assert {built(family)[0].arch for family in families} == set(_MODULES)        # every architecture, once
    for family in families:
        brings = [p for p in LOWERED_PROGRAMS if p != "tick" or family_of(built(family)[0]).tick is not None]
        assert sorted(p for p in LOWERED_PROGRAMS if f"{family}.{p}" in GOLDEN) == sorted(brings), family

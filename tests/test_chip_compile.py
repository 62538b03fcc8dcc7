"""Compile the main path's Pallas kernels for a TPU v5e that is DESCRIBED, not
attached, at Llama-3.2-1B widths — the chip's own compiler refuses here what
it would refuse there (unaligned slices, too much VMEM), at no chip time.
Plus a CPU rehearsal of ``chip_smoke.py``'s control flow at a toy size.

Nothing runs on a device: a compile that passes is not a chip run.

The rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture that skips when it
cannot be, never at import, never in a ``skipif`` or a ``parametrize``
argument, never ``autouse``; shardings and shapes are built in fixtures or
tests; every compile runs in the test's own process (the process that loaded
the TPU library keeps it); the persistent compilation cache is off around
them (an entry compiled for a described chip cannot be read back without
one). All such tests live in THIS file, so that one xdist worker gets them.
"""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# meta-llama/Llama-3.2-1B
DIM, HIDDEN, VOCAB = 2048, 8192, 128256
N_HEADS, N_KV, HEAD_DIM = 32, 8, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops it, the tests cannot run
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_kernels(fn, *args) -> dict:
    """Compile ``fn`` for the described chip; the Mosaic kernels in the
    result (a kernel lowered in interpret mode would not be among them)."""
    from dllama_tpu.runtime.introspection import mosaic_kernels

    return mosaic_kernels(jax.jit(fn).lower(*args).compile().as_text())


def _ssd_step_operands(text: str) -> list[str]:
    """The shapes the ONE ``ssd_step`` Mosaic call of a compiled program takes
    (``operand_layout_constraints``: layer, rows, ``dt x`` and the decay a
    head a lane, the groups' B and C, the pool) and the readout's, last."""
    import re

    (call,) = [ln for ln in text.splitlines() if "custom-call(" in ln and ln.lstrip().startswith("%ssd_step")]
    taken = re.search(r"operand_layout_constraints=\{(.*?)\}\}", call).group(1)
    return re.findall(r"\w+\[[\d,]*\]", taken) + re.findall(r"\w+\[[\d,]*\]", call.split("=", 1)[1])[:1]


def test_described_device_is_a_v5e_with_a_roofline_row(topo):
    from dllama_tpu.runtime import roofline

    dev = topo.devices[0]
    assert dev.platform == "tpu" and len(topo.devices) == 4
    ceil = roofline.nameplate_ceilings(dev.device_kind)
    assert (ceil.tflops, ceil.hbm_gbps) == (197.0, 819.0), dev.device_kind


# every plane of the model, at decode (1 row), verify (16) and prefill (128)
# widths; exact = f32 scales + f32 activations, fast = bf16 scales + bf16
@pytest.mark.parametrize("k,n,rows,fast,fused", [
    (DIM, DIM, 1, False, False),       # wq / wo
    (DIM, 512, 1, False, False),       # wk / wv
    (DIM, HIDDEN, 1, False, False),    # w1 / w3
    (HIDDEN, DIM, 1, False, False),    # w2
    (DIM, VOCAB, 1, False, False),     # the logits head
    (DIM, HIDDEN, 16, False, False),
    (HIDDEN, DIM, 128, False, False),
    (DIM, VOCAB, 128, False, False),
    (DIM, HIDDEN, 128, True, False),
    (DIM, HIDDEN, 1, False, True),     # the decode-shaped fused kernel
    (HIDDEN, DIM, 16, True, True),
    (DIM, VOCAB, 1, True, True),
])
def test_quant_matmul_compiles_for_v5e(one_chip, k, n, rows, fast, fused):
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import (quant_matmul, supports,
                                             supports_decode)

    act = jnp.bfloat16 if fast else jnp.float32
    w = QuantizedWeight(scales=_shape(one_chip, (k // 32, n), act),
                        codes=_shape(one_chip, (k, n), jnp.int8))
    x = _shape(one_chip, (1, rows, k), act)
    assert supports(x.shape, w)
    if fused:
        assert supports_decode(x.shape, w, fast)
    kernels = _compiled_kernels(
        functools.partial(quant_matmul, interpret=False, fast=fast,
                          fused=fused), x, w)
    assert kernels.get("quant_matmul") == 1, kernels


# the benchmark's two configurations (benchmark/configs): every Q40 plane
# of a layer, [K, N], and the quantized head of each
MISTRAL_7B = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]
QWEN3_4B = [(2560, 4096), (2560, 1024), (4096, 2560), (2560, 9728),
            (9728, 2560)]
HEADS = [(4096, 32768), (2560, 151936)]
# allenai/Olmo-Hybrid-7B: the mixer's packed q k v z plane (17280 = 135 x 128)
# and its output plane, q k v wo of the full layers, the feed-forward
OLMO_HYBRID_7B = [(3840, 17280), (5760, 3840), (3840, 3840), (3840, 11008),
                  (11008, 3840)]


# tiiuae/Falcon-H1-34B-Instruct: q, k and v, wo, the mixer's packed z x B C
# plane (9216 = 72 x 128; the published in-projection is 9248 wide, its 32 dt
# rows are a float32 plane) and its output plane, the feed-forward
FALCON_H1_34B = [(5120, 2560), (5120, 512), (2560, 5120), (5120, 9216),
                 (4096, 5120), (5120, 21504), (21504, 5120)]


# skt/A.X-K1: W_dq, W_uq, W_dkv (576 wide, padded to 640), W_o, an expert's
# gate / up and down (in the chunk form read out of the FLATTENED [layers x
# held] stack, models/share._experts_chunk_vmem), layer 0's dense feed-forward
# (K = 18432 at 256 resident rows is the widest contraction the chunk regime
# holds)
A_X_K1 = [(7168, 1536), (1536, 12288), (7168, 640), (8192, 7168),
          (7168, 2048), (2048, 7168), (7168, 18432), (18432, 7168)]


@pytest.mark.parametrize("rows", [4, 16])
@pytest.mark.parametrize("k,n", MISTRAL_7B + QWEN3_4B + OLMO_HYBRID_7B
                         + FALCON_H1_34B + A_X_K1)
def test_decode_kernel_stack_entry_compiles_for_v5e(one_chip, k, n, rows):
    """The fused dequant-GEMV as the paged step calls it in fast mode: bf16
    rows, bf16 scales as a fast-mode load stores them, the LAYER STACK and
    a traced index (scalar prefetch), at long-prompt's 4 rows and
    batch-decode's 16."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import quant_matmul, supports_decode

    L = 4
    stack = QuantizedWeight(
        scales=_shape(one_chip, (L, k // 32, n), jnp.bfloat16),
        codes=_shape(one_chip, (L, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                            for p in stack))
    x = _shape(one_chip, (rows, 1, k), jnp.bfloat16)
    assert supports_decode(x.shape, one, True)
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True,
                                     fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


@pytest.mark.parametrize("rows", [64, 256])
@pytest.mark.parametrize("k,n", MISTRAL_7B + QWEN3_4B + OLMO_HYBRID_7B
                         + FALCON_H1_34B + A_X_K1)
def test_chunk_kernel_stack_entry_compiles_for_v5e(one_chip, k, n, rows):
    """The same kernel as a prefill chunk's ``forward`` calls it (PR 35):
    a 64- or 256-row bucket of bf16 rows, the layer stack and a traced
    index. K = 14336 with 256 rows resident is what the raised VMEM limit
    is for; K = 3840 / 5760 / 11008 / 9728 / 2560 are whole-K blocks no
    512-row tile divides; N = 17280 takes 128-wide stripes."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import fused_path, quant_matmul

    L = 4
    stack = QuantizedWeight(
        scales=_shape(one_chip, (L, k // 32, n), jnp.bfloat16),
        codes=_shape(one_chip, (L, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                            for p in stack))
    x = _shape(one_chip, (1, rows, k), jnp.bfloat16)
    assert fused_path(x.shape, one, True) == "chunk"
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True,
                                     fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


@pytest.mark.parametrize("k,n,rows", [
    (k, n, rows) for rows in (272, 320) for k, n in MISTRAL_7B + QWEN3_4B]
    + [(k, n, 272) for k, n in FALCON_H1_34B] + [(k, n, 260) for k, n in OLMO_HYBRID_7B])
def test_chunk_kernel_compiles_at_the_joined_widths_for_v5e(one_chip, k, n, rows):
    """The same kernel as a tick program calls it (PR 47,
    ``models.llama.forward_and_step``; PR 52,
    ``models.falcon_h1.forward_and_step``; PR 55,
    ``models.hybrid.forward_and_step``): the widest bucket's 256 rows with
    16 slots' decode rows joined to them (the hybrid's 4), over the layer
    stack and a traced index, and for the two dense decoders the regime's
    upper edge. K = 14336 with 320 rows resident stays inside the chunk
    regime's VMEM limit; the joined rows take the stripe width the 256-row
    ``forward`` had (falcon's K = 21504 takes 256-wide stripes, every other
    plane of its seven 512; the hybrid's 17280-wide packed plane 128-wide ones,
    its four others 256), so a tick program brings no kernel parameter of its
    own."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import (CHUNK_MAX_M, _decode_blocks,
                                             fused_path, quant_matmul)

    assert rows <= CHUNK_MAX_M
    assert _decode_blocks(rows, k, n, True) == _decode_blocks(256, k, n, True)
    if (k, n) in FALCON_H1_34B:
        assert _decode_blocks(rows, k, n, True)[0] == (256 if k == 21504 else 512)
    L = 4
    stack = QuantizedWeight(
        scales=_shape(one_chip, (L, k // 32, n), jnp.bfloat16),
        codes=_shape(one_chip, (L, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                            for p in stack))
    x = _shape(one_chip, (1, rows, k), jnp.bfloat16)
    assert fused_path(x.shape, one, True) == "chunk"
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True,
                                     fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


@pytest.mark.parametrize("rows", [17, 40, 128])
def test_chunk_kernel_compiles_off_the_buckets_for_v5e(one_chip, rows):
    """Row counts no bucket has (a speculative verify over several slots, a
    pinned chunk) and a plane pair without the stack, at Mistral's w2."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import fused_path, quant_matmul

    k, n = 14336, 4096
    w = QuantizedWeight(scales=_shape(one_chip, (k // 32, n), jnp.bfloat16),
                        codes=_shape(one_chip, (k, n), jnp.int8))
    x = _shape(one_chip, (rows, k), jnp.bfloat16)
    assert fused_path(x.shape, w, True) == "chunk"
    kernels = _compiled_kernels(
        functools.partial(quant_matmul, interpret=False, fast=True,
                          fused=True), x, w)
    assert kernels.get("quant_matmul") == 1, kernels


def test_the_decode_regimes_picks_are_the_ones_pr28_measured():
    """The chunk regime shares ``_decode_blocks``: rows 1..16 must still
    get the stripe and the VMEM limit their programs were measured with."""
    from dllama_tpu.ops import quant_matmul as qm

    narrow = {(14336, 4096): 256, (9728, 2560): 256, (3840, 17280): 128,
              (5760, 3840): 256, (3840, 3840): 256, (3840, 11008): 256,
              (11008, 3840): 256, (2560, 151936): 128}   # the rest: 512
    for k, n in MISTRAL_7B + QWEN3_4B + OLMO_HYBRID_7B + HEADS:
        want = (narrow.get((k, n), 512), 4 if k == 5760 else 8)
        for rows in (1, 4, 16):
            assert qm._decode_blocks(rows, k, n, True) == want, (k, n, rows)
    assert qm._FUSED_VMEM_LIMIT == 32 * 1024 * 1024
    assert qm.FUSED_MAX_M == 16


@pytest.mark.parametrize("rows", [4, 16])
@pytest.mark.parametrize("k,n", HEADS)
def test_decode_kernel_compiles_at_the_heads_for_v5e(one_chip, k, n, rows):
    """The two vocabularies as one 2-D plane pair (a head quantized by
    DLLAMA_TPU_DENSE_LOGITS=off; fast mode's default head is dense)."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import quant_matmul, supports_decode

    w = QuantizedWeight(scales=_shape(one_chip, (k // 32, n), jnp.bfloat16),
                        codes=_shape(one_chip, (k, n), jnp.int8))
    x = _shape(one_chip, (rows, 1, k), jnp.bfloat16)
    assert supports_decode(x.shape, w, True)
    kernels = _compiled_kernels(
        functools.partial(quant_matmul, interpret=False, fast=True,
                          fused=True), x, w)
    assert kernels.get("quant_matmul") == 1, kernels


def test_tiled_kernel_declines_the_tp4_logits_shard():
    """128256 / 4 = 32064 = 64 x 501 has no 128-aligned divisor. Taken as one
    whole-N block it needs 134 MB of VMEM and the chip's compiler refuses the
    tp=4 prefill program; the shape gate must say no, so that the shard takes
    the XLA dequant+dot path."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import supports

    n = VOCAB // 4
    w = QuantizedWeight(scales=jax.ShapeDtypeStruct((DIM // 32, n), jnp.float32),
                        codes=jax.ShapeDtypeStruct((DIM, n), jnp.int8))
    assert not supports((1, 64, DIM), w)
    assert not supports((1, 1, DIM), w)


@pytest.mark.parametrize("seq,rows,cache", [
    (1024, 1, jnp.float32),
    (4096, 1, jnp.float32),    # chip_smoke.py's --max-seq-len
    (4096, 64, jnp.float32),   # a prefill chunk
    (8192, 1, jnp.bfloat16),
    (4096, 64, jnp.bfloat16),
])
def test_flash_attention_compiles_for_v5e(one_chip, seq, rows, cache):
    from dllama_tpu.ops.flash_attention import flash_attention, supports

    q = _shape(one_chip, (1, rows, N_HEADS, HEAD_DIM), jnp.float32)
    kv = _shape(one_chip, (1, N_KV, seq, HEAD_DIM), cache)
    assert supports(q.shape, N_KV, seq)
    kernels = _compiled_kernels(
        functools.partial(flash_attention, head_dim=HEAD_DIM, interpret=False),
        q, kv, kv, _shape(one_chip, (), jnp.int32))
    assert kernels.get("_call") == 1, kernels


def _compile_paged_attention(one_chip, slots, t, n_heads, n_kv, head_dim,
                             per_seq, block, pool):
    """``paged_ragged_attention`` for the described chip at one geometry:
    one Mosaic kernel, its plan's resident set under the module's budget."""
    from dllama_tpu.ops import paged_attention as pa

    q = _shape(one_chip, (slots, t, n_heads, head_dim), jnp.float32)
    # the whole pool and a layer index: two layers, so the index is live
    kv = _shape(one_chip, (2, slots * per_seq + 1, n_kv, block, head_dim),
                pool)
    itemsize = jnp.dtype(pool).itemsize
    assert pa.supports(q.shape, n_kv, per_seq, block, compiled=True)
    tq = t * n_heads // n_kv
    heads, group = pa._plan(n_kv, tq, head_dim, per_seq, block, itemsize)
    assert n_kv % heads == 0 and 1 <= group <= per_seq
    assert pa.vmem_bytes(heads, group, tq, head_dim, block,
                         itemsize) <= pa._VMEM_BUDGET
    kernels = _compiled_kernels(
        functools.partial(pa.paged_ragged_attention, head_dim=head_dim,
                          interpret=False),
        q, kv, kv, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (slots, per_seq), jnp.int32),
        _shape(one_chip, (slots, t), jnp.int32))
    assert kernels.get("paged_ragged_attention") == 1, kernels
    return heads, group


@pytest.mark.parametrize("block,per_seq,slots,pool", [
    (16, 8, 4, jnp.float32),
    (16, 64, 4, jnp.bfloat16),
    (16, 256, 4, jnp.bfloat16),   # chip_smoke.py: 4096 / 16, 4 slots, bf16
    (32, 64, 4, jnp.float32),
    (128, 32, 8, jnp.bfloat16),
])
def test_paged_attention_compiles_for_v5e(one_chip, block, per_seq, slots,
                                          pool):
    """The pool geometries the 1B-width cases always held, at heads of 128
    lanes: compiled, the kernel's manual DMA cannot slice an HBM ref whose
    minor dim is not lane-aligned, so at this file's 64-wide heads
    (Llama-3.2-1B) the gate keeps the gather + oracle on the chip."""
    from dllama_tpu.ops.paged_attention import supports

    q64 = (slots, 1, N_HEADS, HEAD_DIM)
    assert supports(q64, N_KV, per_seq, block)            # interpret mode
    assert not supports(q64, N_KV, per_seq, block, compiled=True)
    _compile_paged_attention(one_chip, slots, 1, N_HEADS, N_KV, 128, per_seq,
                             block, pool)


# rows, query width, heads, K/V heads, head dim, table entries (blocks of
# 16, bf16 pools): the benchmark's cells, and the widths other callers bring
@pytest.mark.parametrize("slots,t,n_heads,n_kv,per_seq", [
    (16, 1, 32, 8, 64),      # mistral-7b-v0.3, both 16-slot cells
    (16, 1, 32, 8, 80),      # qwen3-4b.chat
    (4, 1, 32, 8, 256),      # mistral-7b-v0.3.long-prompt
    (16, 5, 32, 8, 64),      # paged_verify_step under --spec-lookup 4
    (4, 16, 32, 8, 256),     # a 16-wide verify / prefill tail
    (2, 128, 32, 8, 64),     # MAX_TQ folded query rows: fewer heads a step
    (16, 1, 20, 4, 80),      # falcon-h1-34b.chat: a group of 5 query heads a K/V head
])
def test_paged_attention_compiles_at_the_cells_geometries_for_v5e(
        one_chip, slots, t, n_heads, n_kv, per_seq):
    heads, group = _compile_paged_attention(
        one_chip, slots, t, n_heads, n_kv, 128, per_seq, 16, jnp.bfloat16)
    assert group * 16 == 128
    assert heads == (n_kv if t <= 16 else 4)


@pytest.mark.parametrize("slots", [4, 16])
def test_gated_delta_step_compiles_for_v5e(one_chip, slots):
    """The step form's kernel at Olmo-Hybrid-7B's sizes (24 linear layers, 30
    heads of 96 x 192), over the state pool in place: one Mosaic kernel, its
    output aliased onto the pool."""
    from dllama_tpu.ops.gated_delta import gated_delta_step

    H, dk, dv = 30, 96, 192
    f32 = jnp.float32
    pool = _shape(one_chip, (24, slots + 1, H, dk, dv), f32)
    vec = lambda *tail: _shape(one_chip, (slots, H) + tail, f32)
    compiled = jax.jit(functools.partial(gated_delta_step, interpret=False),
                       donate_argnums=(0,)).lower(
        pool, _shape(one_chip, (), jnp.int32), _shape(one_chip, (slots,), jnp.int32),
        vec(dk), vec(dk), vec(dv), vec(), vec()).compile()
    from dllama_tpu.runtime.introspection import mosaic_kernels

    assert mosaic_kernels(compiled.as_text()).get("gated_delta_step") == 1
    # in place: the program holds no second pool (24 x 5 x 2.2 MB = 265 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024 * 1024


@pytest.mark.parametrize("T", [32, 256])
@pytest.mark.parametrize("H,dk,dv,per_channel", [(30, 96, 192, False), (64, 128, 128, True)])
def test_gated_delta_chunk_compiles_for_v5e(one_chip, H, dk, dv, per_channel, T):
    """The chunk form's kernel at Olmo-Hybrid-7B's head shape (one decay a
    head; dk 96 is not a lane tile) and Solar-Open2's (a decay a key channel),
    one sequence's narrowest and widest bucket: one Mosaic kernel, and nothing
    of the rule's ``[.., 64, 64]`` pair matrices in the program around it."""
    from dllama_tpu.ops.gated_delta import gated_delta_chunk
    from dllama_tpu.runtime.introspection import mosaic_kernels

    f32 = jnp.float32
    tok = lambda *tail: _shape(one_chip, (1, T, H) + tail, f32)
    compiled = jax.jit(functools.partial(gated_delta_chunk, interpret=False)).lower(
        tok(dk), tok(dk), tok(dv), tok(dk) if per_channel else tok(), tok(),
        _shape(one_chip, (1, H, dk, dv), f32)).compile()
    text = compiled.as_text()
    assert mosaic_kernels(text).get("gated_delta_chunk") == 1
    assert "64,64]" not in text


def test_ssd_step_compiles_for_v5e(one_chip):
    """The SSD step form's kernel at Falcon-H1-34B's sizes (12 layers held,
    32 heads of 128 x 256 in 2 groups, 16 slots and the null row), over the
    state pool in place: one Mosaic kernel, its output aliased onto the pool."""
    from dllama_tpu.ops.ssd import ssd_step
    from dllama_tpu.runtime.introspection import mosaic_kernels

    slots, H, P, G, N = 16, 32, 128, 2, 256
    f32 = jnp.float32
    pool = _shape(one_chip, (12, slots + 1, H, P, N), f32)
    compiled = jax.jit(functools.partial(ssd_step, interpret=False),
                       donate_argnums=(0,)).lower(
        pool, _shape(one_chip, (), jnp.int32), _shape(one_chip, (slots,), jnp.int32),
        _shape(one_chip, (slots, H, P), f32), _shape(one_chip, (slots, H), f32),
        _shape(one_chip, (slots, H), f32), _shape(one_chip, (slots, G, N), f32),
        _shape(one_chip, (slots, G, N), f32)).compile()
    assert mosaic_kernels(compiled.as_text()).get("ssd_step") == 1
    # 128 KB a head: 8 heads are the 1 MB a grid step moves (``ssd.heads_per_step``), half a
    # group, as the fixed list gave: ``dt x`` and the decay ``[16, 4, 2, 128, 8]``, one group's
    # B and C a block, ``y`` ``[16, 4, 128, 8]``
    assert _ssd_step_operands(compiled.as_text()) == [
        "s32[1]", "s32[16]", "f32[16,4,2,128,8]", "f32[16,2,8,256]", "f32[12,17,32,128,256]", "f32[16,4,128,8]"]
    # in place: the program holds no second pool (12 x 17 x 4.19 MB = 856 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024 * 1024


def test_paged_attention_compiles_at_30_kv_heads_for_v5e(one_chip):
    """Olmo-Hybrid-7B's full layers: 30:30 heads of 128 (``kv_mul`` 1), 4
    slots of 4096 in blocks of 16. A whole block, 122 KB over its 30 heads,
    is one copy."""
    heads, _ = _compile_paged_attention(one_chip, 4, 1, 30, 30, 128, 256, 16,
                                        jnp.bfloat16)
    assert heads == 30


# -- chip_smoke.py's control flow, without a chip ------------------------------


@pytest.mark.parametrize("heads,layers,blocks,window", [(6, 6, 4097, 0), (9, 18, 545, 512)])
def test_paged_attention_compiles_at_one_kv_head_with_a_window_for_v5e(one_chip, heads, layers, blocks, window):
    """One chip's share of a layer (laguna-s-2.1): query groups of 6 and 9
    over ONE K/V head, a block of 4 KB; the sliding layers' walk starts at
    the row's first live block and masks by the window."""
    from dllama_tpu.ops import paged_attention as pa

    kernels = _compiled_kernels(
        lambda q, k, v, layer, tables, pos: pa.paged_ragged_attention(
            q, k, v, layer, tables, pos, 128, window=window),
        _shape(one_chip, (16, 1, heads, 128), jnp.bfloat16),
        _shape(one_chip, (layers, blocks, 1, 16, 128), jnp.bfloat16),
        _shape(one_chip, (layers, blocks, 1, 16, 128), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (16, 256), jnp.int32),
        _shape(one_chip, (16, 1), jnp.int32))
    assert kernels


@pytest.mark.parametrize("k,n", [(3072, 1024), (1024, 3072)])
def test_expert_gemv_compiles_for_v5e(one_chip, k, n):
    """The routed decode kernel at laguna-s-2.1's expert (3072 x 1024, gate /
    up and down), 23 layers of 32 held experts, 160 pairs (16 rows x 10): two
    landing halves of a whole 3 MB plane and its dequantized copy in VMEM."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.supports(160, k, n, True)
    stack = QuantizedWeight(scales=_shape(one_chip, (23, 32, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (23, 32, k, n), jnp.int8))
    kernels = _compiled_kernels(
        lambda x, st, layer, experts, n_pairs: eg.expert_gemv(x, st, layer, experts, n_pairs, fast=True),
        _shape(one_chip, (160, k), jnp.bfloat16), stack, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (160,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("expert_gemv" in name for name in kernels), kernels


@pytest.mark.parametrize("k,n,tn", [(7168, 2048, 512), (2048, 7168, 1792)])
def test_expert_gemv_stripes_a_wide_plane_for_v5e(one_chip, k, n, tn):
    """A.X-K1's expert (7168 x 2048, gate / up and down), 9 layers of 12 held
    experts, 128 pairs (16 rows x 8): a whole plane, twice, with its
    dequantized copy is 58.7 MB, so the kernel walks stripes of ``tn`` output
    columns, a stripe a double-buffer half."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.stripe(128, k, n, True) == tn
    stack = QuantizedWeight(scales=_shape(one_chip, (9, 12, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (9, 12, k, n), jnp.int8))
    kernels = _compiled_kernels(
        lambda x, st, layer, experts, n_pairs: eg.expert_gemv(x, st, layer, experts, n_pairs, fast=True),
        _shape(one_chip, (128, k), jnp.bfloat16), stack, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (128,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("expert_gemv" in name for name in kernels), kernels


@pytest.mark.parametrize("layers,held,k,kk,n,rows,scatter", [
    (23, 32, 10, 3072, 1024, 256, False), (23, 32, 10, 1024, 3072, 256, True),     # laguna-s-2.1: a plane lands whole
    (9, 12, 8, 7168, 2048, 256, False), (9, 12, 8, 2048, 7168, 256, True),         # A.X-K1: in stripes
    (23, 32, 10, 3072, 1024, 32, False), (23, 32, 10, 1024, 3072, 32, True),       # the narrowest bucket: 40 pairs over 32 experts
    (4, 64, 8, 3072, 1024, 256, False), (4, 64, 8, 1024, 3072, 256, True)]         # a whole layer held: 32 rows an expert
    # laguna's TICK (PR 57): a bucket's rows and the 16 decode rows as one dispatch, whole (no piece: a plane once a run)
    + [case for rows in (48, 80, 144, 272) for case in ((23, 32, 10, 3072, 1024, rows, False),
                                                        (23, 32, 10, 1024, 3072, rows, True))])
def test_expert_chunk_compiles_for_v5e(one_chip, layers, held, k, kk, n, rows, scatter):
    """The routed chunk kernel at the two clients' expert planes, both ends,
    at the static bound of the fed layout (2,432 rows for A.X-K1's 2,048
    pairs over 12 held, 3,584 for laguna's 2,560 over 32, in tiles of 32): the chunk's rows
    and the result resident in VMEM beside two landing halves of a stripe
    and its dequantized copy, the runs and the sorted pairs' rows (and the
    router's weights, float32) as scalar-prefetch operands."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight

    pairs = rows * min(k, held)
    fed = ec.fed_rows(pairs, held)
    tn = ec.stripe(rows, fed, kk, n, True, scatter)
    assert tn is not None and (tn == n) == (kk * n < 4 * 1024 * 1024)
    stack = QuantizedWeight(scales=_shape(one_chip, (layers, held, kk // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (layers, held, kk, n), jnp.int8))
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    runs = (i32(),) + tuple(i32(held) for _ in range(4))
    if scatter:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r, at, w: ec.expert_chunk(x, st, layer, runs, r, (at, w), rows_out=rows, fast=True),
            _shape(one_chip, (fed, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs), i32(pairs), _shape(one_chip, (rows, k), jnp.float32))
    else:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r: ec.expert_chunk(x, st, layer, runs, r, rows_out=fed, fast=True),
            _shape(one_chip, (rows, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs))
    assert any("expert_chunk" in name for name in kernels), kernels


def test_mla_paged_step_compiles_for_v5e(one_chip):
    """A.X-K1's latent walk: 16 rows x 64 heads over rows of 576 values in 640
    lanes, 10 layers of 17,409 blocks of 16 (3.57 GB in bfloat16), a table of
    1088 entries: one 20 KB copy a block, 32 a fetch group."""
    from dllama_tpu.ops import mla

    assert mla.supports((16, 1, 64, 640), 640, 1088, 16, compiled=True)
    kernels = _compiled_kernels(
        lambda qa, pool, layer, tables, pos: mla.mla_paged_step(
            qa, pool, layer, tables, pos, scale=0.1309, vdim=512),
        _shape(one_chip, (16, 1, 64, 640), jnp.bfloat16),
        _shape(one_chip, (10, 17409, 1, 16, 640), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (16, 1088), jnp.int32),
        _shape(one_chip, (16,), jnp.int32))
    assert any("mla_paged_step" in name for name in kernels), kernels


@pytest.mark.parametrize("T", [64, 256])
def test_mla_chunk_compiles_for_v5e(one_chip, T):
    """A.X-K1's chunk form: ``T`` tokens x 64 heads of absorbed query rows in
    tiles of 2048 against key blocks of 512 of a whole latent column (9 layers
    of 17,408 rows of 640 lanes), the layer scalar-prefetched, the score tile
    in VMEM."""
    from dllama_tpu.ops import mla

    assert mla._chunk_tiles(T * 64, 17408, True) == (2048, 512)
    kernels = _compiled_kernels(
        lambda qa, col, layer, start: mla.mla_chunk_kernel(qa, col, layer, start, scale=0.1309, vdim=512),
        _shape(one_chip, (T, 64, 640), jnp.bfloat16),
        _shape(one_chip, (9, 1, 1, 17408, 640), jnp.bfloat16),
        _shape(one_chip, (), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("mla_chunk" in name for name in kernels), kernels


# LiquidAI/LFM2-24B-A2B (PR 44): 2048 wide, 32:8 heads of 64 lanes cached in 128, experts 2048 x 1536, 64 held
LFM2_STEP = [(2048, 6144), (2048, 2048), (2048, 11776), (11776, 2048), (2048, 512)]


def test_paged_attention_compiles_at_64_lane_heads_padded_for_v5e(one_chip):
    """lfm2-24b-a2b's step: 32 slots of 1024, 32:8 heads whose 64 lanes the
    pool holds in 128 (``cfg.cache_width``): the kernel that the 128-lane
    cells compile, every K/V head a grid step, the score's scale the head's own
    64. At 64 lanes the compiled gate still says no: the padding is what
    takes the path off the gather + oracle."""
    from dllama_tpu.ops.paged_attention import supports

    assert not supports((32, 1, 32, 64), 8, 64, 16, compiled=True)
    heads, group = _compile_paged_attention(one_chip, 32, 1, 32, 8, 128, 64, 16, jnp.bfloat16)
    assert (heads, group * 16) == (8, 128)


@pytest.mark.parametrize("rows", [32, 64, 256, 288])
@pytest.mark.parametrize("k,n", LFM2_STEP)
def test_chunk_kernel_compiles_at_lfm2s_planes_for_v5e(one_chip, k, n, rows):
    """lfm2-24b-a2b's Q40 planes (the conv mixer's 6144-wide in-projection,
    its square out-projection, the dense feed-forward at 11776, a K/V
    projection) in the chunk regime, which is ALSO its 32-row step's: stack +
    index, one Mosaic kernel. 288 rows are its widest TICK (PR 53: a 256-row
    chunk and 32 decode rows joined, ``lfm2.forward_and_step``)."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import fused_path, quant_matmul

    stack = QuantizedWeight(scales=_shape(one_chip, (4, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (4, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype) for p in stack))
    x = _shape(one_chip, (1, rows, k), jnp.bfloat16)
    assert fused_path(x.shape, one, True) == "chunk"
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True, fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


@pytest.mark.parametrize("kk,n,rows,scatter", [(2048, 1536, 32, False), (1536, 2048, 32, True),
                                               (2048, 1536, 256, False), (1536, 2048, 256, True)]
                         + [case for rows in (64, 96, 160, 288)
                            for case in ((2048, 1536, rows, False), (1536, 2048, rows, True))])
def test_expert_chunk_compiles_at_lfm2s_experts_for_v5e(one_chip, kk, n, rows, scatter):
    """The grouped routed kernel at lfm2-24b-a2b's expert (2048 x 1536, the
    size of laguna's and not its shape), 16 layers of 64 held of 64, 4 a
    token: the 32-row STEP's form (128 pairs over up to 64 runs), a chunk's,
    and (PR 53) the TICK's joined dispatch at every bucket, 32 / 64 / 128 /
    256 chunk rows and 32 decode rows: ``stripe(...) is not None`` says the
    kernel takes the joined rows WHOLE (where ``share._chunk_pieces`` halved
    them a plane would be fetched once a piece, twice again)."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight

    held, k = 64, 4
    pairs = rows * k
    fed = ec.fed_rows(pairs, held)
    assert ec.stripe(rows, fed, kk, n, True, scatter) is not None
    stack = QuantizedWeight(scales=_shape(one_chip, (16, held, kk // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (16, held, kk, n), jnp.int8))
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    runs = (i32(),) + tuple(i32(held) for _ in range(4))
    if scatter:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r, at, w: ec.expert_chunk(x, st, layer, runs, r, (at, w), rows_out=rows, fast=True),
            _shape(one_chip, (fed, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs), i32(pairs), _shape(one_chip, (rows, k), jnp.float32))
    else:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r: ec.expert_chunk(x, st, layer, runs, r, rows_out=fed, fast=True),
            _shape(one_chip, (rows, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs))
    assert any("expert_chunk" in name for name in kernels), kernels


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)])
def test_expert_gemv_compiles_at_lfm2s_experts_for_v5e(one_chip, k, n):
    """The decode form at the same planes, 64 pairs (16 rows x 4): what a
    generator of up to 16 slots would run (``share.STEP_FORM_MAX_ROWS``)."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.supports(64, k, n, True)
    stack = QuantizedWeight(scales=_shape(one_chip, (16, 64, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (16, 64, k, n), jnp.int8))
    kernels = _compiled_kernels(
        lambda x, st, layer, experts, n_pairs: eg.expert_gemv(x, st, layer, experts, n_pairs, fast=True),
        _shape(one_chip, (64, k), jnp.bfloat16), stack, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (64,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("expert_gemv" in name for name in kernels), kernels


# -- nemotron-3-super-120b-a12b at its published widths (PR 51) ------------------------
# the fused / chunk GEMV's planes: the mixer's 18,432-wide in-projection and its
# out-projection, the shared expert at 5376, the two latent projections at 1024,
# q (4096) and k / v (256)
NEMOTRON_STEP = [(4096, 18432), (8192, 4096), (4096, 5376), (5376, 4096), (4096, 1024), (1024, 4096),
                 (4096, 4096), (4096, 256)]


def test_ssd_step_compiles_at_nemotrons_state_for_v5e(one_chip):
    """The SSD step form's kernel at ``[10, 33, 128, 64, 128]``: ten mixer
    layers held, 128 heads of 64 in 8 groups, a state of 128, 32 slots and
    the null row, the pool in place."""
    from dllama_tpu.ops import ssd
    from dllama_tpu.runtime.introspection import mosaic_kernels

    slots, H, P, G, N = 32, 128, 64, 8, 128
    f32 = jnp.float32
    pool = _shape(one_chip, (10, slots + 1, H, P, N), f32)
    compiled = jax.jit(functools.partial(ssd.ssd_step, interpret=False),
                       donate_argnums=(0,)).lower(
        pool, _shape(one_chip, (), jnp.int32), _shape(one_chip, (slots,), jnp.int32),
        _shape(one_chip, (slots, H, P), f32), _shape(one_chip, (slots, H), f32),
        _shape(one_chip, (slots, H), f32), _shape(one_chip, (slots, G, N), f32),
        _shape(one_chip, (slots, G, N), f32)).compile()
    assert mosaic_kernels(compiled.as_text()).get("ssd_step") == 1
    # 32 KB a head: 32 heads, TWO groups of 16, are the 1 MB a grid step moves (PR 59; the
    # fixed list's 8 moved 256 KB). ``dt x``, the decay and ``y`` reach the kernel a head a
    # LANE, ``[32, 4, 2, 64, 32]`` and ``[32, 4, 64, 32]``: 8.4 MB and 4.2 MB as tiled (the
    # 32 lanes padded to 128), where 8 lanes were 34 MB and 17 MB and a minor dimension of
    # 2 and 1 134 MB each (PR 51)
    assert _ssd_step_operands(compiled.as_text()) == [
        "s32[1]", "s32[32]", "f32[32,4,2,64,32]", "f32[32,8,8,128]", "f32[10,33,128,64,128]", "f32[32,4,64,32]"]
    # in place: the program holds no second pool (10 x 33 x 4.19 MB = 1.38 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024 * 1024


@pytest.mark.parametrize("k,n", [(1024, 2816), (2816, 1024)])
def test_expert_gemv_compiles_at_nemotrons_experts_for_v5e(one_chip, k, n):
    """The decode form at an expert's two planes in the latent's width, 352
    pairs (16 rows x 22 a token) over 128 held of 10 routed layers. The
    planes are HELD 2816 wide (``cfg.expert_width_held``): at the published
    2688 a plane's scales are 84 rows, Mosaic pads the HBM operand to 88
    (whole tiles of 8) and refuses the DMA's slice of 84."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.supports(352, k, n, True)
    stack = QuantizedWeight(scales=_shape(one_chip, (10, 128, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, 128, k, n), jnp.int8))
    kernels = _compiled_kernels(
        lambda x, st, layer, experts, n_pairs: eg.expert_gemv(x, st, layer, experts, n_pairs, fast=True),
        _shape(one_chip, (352, k), jnp.bfloat16), stack, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (352,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("expert_gemv" in name for name in kernels), kernels


@pytest.mark.parametrize("kk,n,rows,scatter", [(1024, 2816, 32, False), (2816, 1024, 32, True)])
def test_expert_chunk_compiles_at_nemotrons_experts_for_v5e(one_chip, kk, n, rows, scatter):
    """The grouped routed kernel at the same planes, 22 a token over 128 held
    of 512, the 32-row STEP's form: 704 pairs of which a quarter fall here,
    the fed layout at its static bound of 4,800 rows. A prefill chunk of 128
    rows or more is past the kernel's VMEM predicate (the bound of the fed
    layout, 6,912 rows and up, times 2816 float32 lanes: 78 MB of a budget
    of 72) and takes the every-row form through ``linear``: the gate says
    so."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight

    held, k = 128, 22
    pairs = rows * k
    fed = ec.fed_rows(pairs, held)
    assert ec.stripe(rows, fed, kk, n, True, scatter) is not None
    assert scatter or (ec.stripe(64, ec.fed_rows(64 * k, held), kk, n, True, False) == 1408
                       and ec.stripe(128, ec.fed_rows(128 * k, held), kk, n, True, False) is None)
    stack = QuantizedWeight(scales=_shape(one_chip, (10, held, kk // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, held, kk, n), jnp.int8))
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    runs = (i32(),) + tuple(i32(held) for _ in range(4))
    if scatter:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r, at, w: ec.expert_chunk(x, st, layer, runs, r, (at, w), rows_out=rows, fast=True),
            _shape(one_chip, (fed, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs), i32(pairs), _shape(one_chip, (rows, k), jnp.float32))
    else:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r: ec.expert_chunk(x, st, layer, runs, r, rows_out=fed, fast=True),
            _shape(one_chip, (rows, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs))
    assert any("expert_chunk" in name for name in kernels), kernels


def test_paged_attention_compiles_at_a_group_of_16_over_2_kv_heads_for_v5e(one_chip):
    """The step's walk: 32 slots of 2048 in blocks of 16, 32:2 heads of 128
    (a group of 16 query heads a K/V head), no rotary table anywhere."""
    heads, group = _compile_paged_attention(one_chip, 32, 1, 32, 2, 128, 128, 16, jnp.bfloat16)
    assert heads == 2 and group >= 1


@pytest.mark.parametrize("rows", [16, 32, 256])
@pytest.mark.parametrize("k,n", NEMOTRON_STEP)
def test_quant_matmul_compiles_at_nemotrons_planes_for_v5e(one_chip, k, n, rows):
    """The dense Q40 planes of the three blocks, stack + index: the fused
    GEMV up to 16 rows, the chunk regime beyond (which is also the 32-row
    step's): one Mosaic kernel each."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import fused_path, quant_matmul

    stack = QuantizedWeight(scales=_shape(one_chip, (10, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype) for p in stack))
    x = _shape(one_chip, (1, rows, k), jnp.bfloat16)
    assert fused_path(x.shape, one, True) == ("fused" if rows <= 16 else "chunk")
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True, fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


# -- granite-4.0-h-small at its published widths (PR 54) ----------------------------------
# the fused / chunk GEMV's planes: the mixer's 16,640-wide in-projection and its
# out-projection, the gated shared expert at 1536, q / o (4096) and k / v (1024)
GRANITE_STEP = [(4096, 16640), (8192, 4096), (4096, 1536), (1536, 4096), (4096, 4096), (4096, 1024)]


def test_ssd_step_compiles_at_one_group_for_v5e(one_chip):
    """The SSD step form's kernel at ``[9, 17, 128, 64, 128]``: nine mixer
    layers held, 128 heads of 64 on ONE group of B and C (the grid is slots x
    blocks of 32 heads, 1 MB of state: all four read the same group), 16 slots
    and the null row, the pool in place."""
    from dllama_tpu.ops import ssd
    from dllama_tpu.runtime.introspection import mosaic_kernels

    slots, H, P, G, N = 16, 128, 64, 1, 128
    f32 = jnp.float32
    pool = _shape(one_chip, (9, slots + 1, H, P, N), f32)
    compiled = jax.jit(functools.partial(ssd.ssd_step, interpret=False),
                       donate_argnums=(0,)).lower(
        pool, _shape(one_chip, (), jnp.int32), _shape(one_chip, (slots,), jnp.int32),
        _shape(one_chip, (slots, H, P), f32), _shape(one_chip, (slots, H), f32),
        _shape(one_chip, (slots, H), f32), _shape(one_chip, (slots, G, N), f32),
        _shape(one_chip, (slots, G, N), f32)).compile()
    assert mosaic_kernels(compiled.as_text()).get("ssd_step") == 1
    assert _ssd_step_operands(compiled.as_text()) == [
        "s32[1]", "s32[16]", "f32[16,4,2,64,32]", "f32[16,1,8,128]", "f32[9,17,128,64,128]", "f32[16,4,64,32]"]
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024 * 1024


@pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096)])
def test_expert_gemv_compiles_at_granites_experts_for_v5e(one_chip, k, n):
    """The pair form at an expert's planes in the model's width, 160 pairs (16
    rows x 10 a token) over 72 held of 10 routed blocks: what ``share.step_form``
    leaves to 7 rows and fewer, and what the builder's wrapper forced to 16 for
    the comparison PERF.md has."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.supports(160, k, n, True)
    stack = QuantizedWeight(scales=_shape(one_chip, (10, 72, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, 72, k, n), jnp.int8))
    kernels = _compiled_kernels(
        lambda x, st, layer, experts, n_pairs: eg.expert_gemv(x, st, layer, experts, n_pairs, fast=True),
        _shape(one_chip, (160, k), jnp.bfloat16), stack, _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (160,), jnp.int32), _shape(one_chip, (), jnp.int32))
    assert any("expert_gemv" in name for name in kernels), kernels


@pytest.mark.parametrize("kk,n,rows,scatter", [(4096, 768, 16, False), (768, 4096, 16, True),
                                               (4096, 768, 256, False), (768, 4096, 256, True)])
def test_expert_chunk_compiles_at_granites_experts_for_v5e(one_chip, kk, n, rows, scatter):
    """The run form at the same planes, ten a token over 72 held of 72: the
    16-row STEP's (160 pairs over up to 72 runs: ``share.step_form``) and a
    256-row prefill chunk's, which the kernel takes WHOLE (``stripe(...) is not
    None``: no pieces, a plane fetched once a chunk)."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight

    held, k = 72, 10
    pairs = rows * k
    fed = ec.fed_rows(pairs, held)
    assert ec.stripe(rows, fed, kk, n, True, scatter) is not None
    stack = QuantizedWeight(scales=_shape(one_chip, (10, held, kk // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, held, kk, n), jnp.int8))
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    runs = (i32(),) + tuple(i32(held) for _ in range(4))
    if scatter:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r, at, w: ec.expert_chunk(x, st, layer, runs, r, (at, w), rows_out=rows, fast=True),
            _shape(one_chip, (fed, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs), i32(pairs), _shape(one_chip, (rows, k), jnp.float32))
    else:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r: ec.expert_chunk(x, st, layer, runs, r, rows_out=fed, fast=True),
            _shape(one_chip, (rows, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs))
    assert any("expert_chunk" in name for name in kernels), kernels


def test_paged_attention_compiles_at_8704_tokens_a_slot_for_v5e(one_chip):
    """The step's walk: 16 slots of 8,704 in blocks of 16 (544 table entries a
    row), 32:8 heads of 128, ONE attention layer, no rotary table anywhere."""
    heads, group = _compile_paged_attention(one_chip, 16, 1, 32, 8, 128, 8704 // 16, 16, jnp.bfloat16)
    assert heads == 8 and group >= 1


@pytest.mark.parametrize("rows", [16, 256])
@pytest.mark.parametrize("k,n", GRANITE_STEP)
def test_quant_matmul_compiles_at_granites_planes_for_v5e(one_chip, k, n, rows):
    """The dense Q40 planes of the mixer, the attention layer and the shared
    expert, stack + index: the fused GEMV at the step's 16 rows, the chunk
    regime at a prefill chunk's 256: one Mosaic kernel each."""
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import fused_path, quant_matmul

    stack = QuantizedWeight(scales=_shape(one_chip, (10, k // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (10, k, n), jnp.int8))
    one = QuantizedWeight(*(jax.ShapeDtypeStruct(p.shape[1:], p.dtype) for p in stack))
    x = _shape(one_chip, (1, rows, k), jnp.bfloat16)
    assert fused_path(x.shape, one, True) == ("fused" if rows <= 16 else "chunk")
    kernels = _compiled_kernels(
        lambda x, w, l: quant_matmul(x, w, interpret=False, fast=True, fused=True, layer=l),
        x, stack, _shape(one_chip, (), jnp.int32))
    assert kernels.get("quant_matmul") == 1, kernels


@pytest.mark.parametrize("program", ["step", "forward"])
def test_granites_two_programs_compile_at_the_cells_size_for_v5e(one_chip, program, monkeypatch):
    """``paged_sampled_step_guarded`` over 16 rows (pools of 16 x 8,704 tokens,
    donated) and ``forward`` over a 256-token chunk into an 8,704-token column,
    from the cell's own configuration and the benchmark's shapes: every Q40
    plane a kernel, the step's routed blocks the RUN form (``share.step_form``:
    160 pairs over 72 planes), the mixers' step form ``ssd_step``, the walk
    ``paged_ragged_attention``; the chunk's mixers XLA under the ``ssd_chunk``
    scope, which is in the instructions' metadata and NOT in their names (why
    the cell reads ``prefill_xla_share`` and not a kernel's share). The head is
    the embedding: the programs are handed ONE array for both."""
    import importlib.util
    import struct

    from dllama_tpu.formats import mfile
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.share import zero_totals
    from dllama_tpu.ops import quant_matmul
    from dllama_tpu.parallel import api
    from dllama_tpu.runtime.introspection import mosaic_kernels
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StateColumn, StatePool

    bench = os.path.join(REPO, "benchmark")
    sys.path.insert(0, bench)
    import run as bench_run

    spec = importlib.util.spec_from_file_location("granite_hybrid_weights_for_compile",
                                                  os.path.join(bench, "granite_hybrid", "weights.py"))
    weights = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(weights)
    with open(os.path.join(bench, "configs", "granite-4.0-h-small.json"), encoding="utf-8") as f:
        conf = json.load(f)
    data = b"".join(struct.pack("<ii", k, int(v)) for k, v in weights.header_fields(bench_run.model_view(conf)))
    header = mfile.parse_header(struct.pack("<ii", mfile.MODEL_MAGIC, 8 + len(data)) + data, 0,
                                max_seq_len=conf["engine"]["max_seq_len"])
    cfg = ModelConfig.from_header(header, "bfloat16")
    for module in (quant_matmul, llama, api):          # the gates ask jax.default_backend(), which is the CPU here
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    on_chip = lambda tree: jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(weights.params_builder(cfg, None)[0], jax.random.PRNGKey(0)))
    params = params._replace(logits=params.embedding)
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    slots, seq, block = conf["engine"]["slots"], cfg.seq_len, conf["engine"]["kv_block_size"]
    if program == "step":
        cache = (on_chip(jax.eval_shape(lambda: PagedKVCache.create(cfg, slots * seq // block + 1, block, dtype=bf16))),
                 on_chip(jax.eval_shape(lambda: StatePool.create(cfg, slots, bf16))),
                 on_chip(jax.eval_shape(lambda: zero_totals(cfg))))
        rows = lambda dtype, *tail: _shape(one_chip, (slots, *tail), dtype)
        compiled = jax.jit(llama.paged_sampled_step_guarded, static_argnums=1, donate_argnums=(4,)).lower(
            params, cfg, rows(i32, 1), rows(i32), cache, rows(i32, seq // block), rows(f32), rows(f32), rows(f32),
            _shape(one_chip, (), f32)).compile()
        assert mosaic_kernels(compiled.as_text()) == {"quant_matmul": 17, "expert_chunk": 9, "ssd_step": 2,
                                                      "paged_ragged_attention": 1}
        # the pools are written in place: no second state pool (0.65 GB) or K/V pool (0.57 GB) among the temporaries
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024
        return
    kv = lambda: jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq, cfg.cache_width), bf16)
    column = on_chip(jax.eval_shape(lambda: StateColumn.zeros(cfg, kv(), kv(), bf16)))
    compiled = jax.jit(llama.forward, static_argnums=1, donate_argnums=(4,)).lower(
        params, cfg, _shape(one_chip, (1, 256), i32), _shape(one_chip, (), i32), column,
        _shape(one_chip, (), i32)).compile()
    text = compiled.as_text()
    kernels = mosaic_kernels(text)
    assert kernels.get("quant_matmul") == 17 and kernels.get("expert_chunk") == 9 and "ssd_step" not in kernels
    scoped = [line for line in text.splitlines() if "ssd_chunk/" in line]
    assert len(scoped) > 50 and not any(line.split(" = ")[0].count("ssd_chunk") for line in scoped)


# -- solar-open2-250b at its published widths (PR 58) ----------------------------------


def test_gated_delta_step_compiles_with_a_decay_a_channel_for_v5e(one_chip):
    """The step form's ONE kernel at ``[6, 17, 64, 128, 128]`` with ``alpha [16,
    64, 128]``: six delta-rule layers held, 64 heads of 128 x 128, 16 slots and
    the null row, the pool in place; q, k and the decays ride as ROWS of one
    ``[16, 64, 8, 128]`` operand (whole lane tiles: as columns ``[.., 128, 3]``
    the tiled layout padded them to 128 lanes, 67 MB a layer beside 134 MB of
    state) and the kernel turns them into columns."""
    from dllama_tpu.ops.gated_delta import gated_delta_step
    from dllama_tpu.runtime.introspection import mosaic_kernels

    slots, H, d = 16, 64, 128
    f32 = jnp.float32
    vec = lambda *tail: _shape(one_chip, (slots, H, *tail), f32)
    compiled = jax.jit(functools.partial(gated_delta_step, interpret=False), donate_argnums=(0,)).lower(
        _shape(one_chip, (6, slots + 1, H, d, d), f32), _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (slots,), jnp.int32), vec(d), vec(d), vec(d), vec(d), vec()).compile()
    assert mosaic_kernels(compiled.as_text()).get("gated_delta_step") == 1
    # in place: no second pool (0.43 GB), and no lane-padded copy of the vectors (67 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 1024 * 1024


def _cell_at_its_size(one_chip, monkeypatch, config: str, weights_dir: str):
    """``(conf, cfg, params, on_chip)`` of a benchmark configuration whose
    family brings its own ``weights`` module: the cell's file, the
    ``ModelConfig`` its header gives, the parameters' shapes on the described
    chip, and the function that puts a tree of shapes there. The gates are
    told they are on a TPU (they ask ``jax.default_backend()``, which is the
    CPU here)."""
    import importlib.util
    import struct

    from dllama_tpu.formats import mfile
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.ops import quant_matmul
    from dllama_tpu.parallel import api

    bench = os.path.join(REPO, "benchmark")
    sys.path.insert(0, bench)
    import run as bench_run
    import weights as dense_weights

    spec = importlib.util.spec_from_file_location(weights_dir + "_weights_for_compile",
                                                  os.path.join(bench, weights_dir, "weights.py"))
    weights = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(weights)
    with open(os.path.join(bench, "configs", config + ".json"), encoding="utf-8") as f:
        conf = json.load(f)
    data = b"".join(struct.pack("<ii", k if isinstance(k, int) else dense_weights.HEADER_KEYS[k], int(v))
                    for k, v in weights.header_fields(bench_run.model_view(conf)).items())
    header = mfile.parse_header(struct.pack("<ii", mfile.MODEL_MAGIC, 8 + len(data)) + data, 0,
                                max_seq_len=conf["engine"]["max_seq_len"])
    cfg = ModelConfig.from_header(header, "bfloat16")
    for module in (quant_matmul, llama, api):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    on_chip = lambda tree: jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(weights.params_builder(cfg, None)[0], jax.random.PRNGKey(0)))
    return conf, cfg, params, on_chip


@pytest.mark.parametrize("program", ["step", "forward"])
def test_solars_two_programs_compile_at_the_cells_size_for_v5e(one_chip, program, monkeypatch):
    """``paged_sampled_step_guarded`` over 16 rows (pools of 16 x 9,472 tokens,
    donated) and ``forward`` over a 256-token chunk into a 9,472-token column,
    from the cell's own configuration and the benchmark's shapes: every Q40
    plane a kernel, the step's routed halves the PAIR form (``share.step_form``:
    128 pairs over 320), the rule's step form ONE ``gated_delta_step`` a traced
    layer body, the walk ``paged_ragged_attention``; the chunk's rule ONE
    ``gated_delta_chunk`` a traced layer body since PR 61 (and none of the XLA
    form's ``[.., H, 64, 64]`` pair matrices or ``[.., 16, 16, 128]`` exponents
    left in the program), its routed halves ``expert_chunk``."""
    from dllama_tpu.models import llama
    from dllama_tpu.models.share import zero_totals
    from dllama_tpu.runtime.introspection import mosaic_kernels
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StateColumn, StatePool

    conf, cfg, params, on_chip = _cell_at_its_size(one_chip, monkeypatch, "solar-open2-250b", "solar_open2")
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    slots, seq, block = conf["engine"]["slots"], cfg.seq_len, conf["engine"]["kv_block_size"]
    if program == "step":
        cache = (on_chip(jax.eval_shape(lambda: PagedKVCache.create(cfg, slots * seq // block + 1, block, dtype=bf16))),
                 on_chip(jax.eval_shape(lambda: StatePool.create(cfg, slots, bf16))),
                 on_chip(jax.eval_shape(lambda: zero_totals(cfg))))
        rows = lambda dtype, *tail: _shape(one_chip, (slots, *tail), dtype)
        compiled = jax.jit(llama.paged_sampled_step_guarded, static_argnums=1, donate_argnums=(4,)).lower(
            params, cfg, rows(i32, 1), rows(i32), cache, rows(i32, seq // block), rows(f32), rows(f32), rows(f32),
            _shape(one_chip, (), f32)).compile()
        kernels = mosaic_kernels(compiled.as_text())
        assert kernels.get("gated_delta_step") == 1 and kernels.get("paged_ragged_attention") == 1, kernels
        assert kernels.get("expert_gemv") == 6 and "expert_chunk" not in kernels, kernels     # two traced routed bodies
        assert kernels.get("quant_matmul") == 15, kernels      # 4 + 3 a delta-rule body, 5 + 3 the full one
        # the pools are written in place: no second state pool (0.43 GB) or K/V pool (1.24 GB) among the temporaries
        assert compiled.memory_analysis().temp_size_in_bytes < 96 * 1024 * 1024
        return
    kv = lambda: jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq, cfg.cache_width), bf16)
    column = on_chip(jax.eval_shape(lambda: StateColumn.zeros(cfg, kv(), kv(), bf16)))
    compiled = jax.jit(llama.forward, static_argnums=1, donate_argnums=(4,)).lower(
        params, cfg, _shape(one_chip, (1, 256), i32), _shape(one_chip, (), i32), column,
        _shape(one_chip, (), i32)).compile()
    text = compiled.as_text()
    kernels = mosaic_kernels(text)
    assert kernels.get("expert_chunk") == 6 and "gated_delta_step" not in kernels, kernels
    assert kernels.get("quant_matmul") == 15 and kernels.get("gated_delta_chunk") == 1, kernels
    assert "64,64,64]" not in text and "16,16,128]" not in text        # [N, B, H, C, C] pairs; a block's exponents
    assert compiled.memory_analysis().temp_size_in_bytes < 1536 * 1024 * 1024


def test_the_hybrids_tick_program_compiles_with_the_chunk_kernel_at_the_cells_size_for_v5e(one_chip, monkeypatch):
    """``hybrid.forward_and_step`` from olmo-hybrid-7b's own configuration at the
    widest bucket (a 256-token chunk into a 4096-token column, the 4 slots'
    decode rows beside it, pools donated): the rule's chunk form is ONE
    ``gated_delta_chunk`` and its step form ONE ``gated_delta_step``, the traced
    linear-layer body's, and none of the XLA form's ``[.., H, 64, 64]`` pair
    matrices is left in the program (PR 61)."""
    from dllama_tpu.models import hybrid
    from dllama_tpu.runtime.introspection import mosaic_kernels
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StateColumn, StatePool

    conf, cfg, params, on_chip = _cell_at_its_size(one_chip, monkeypatch, "olmo-hybrid-7b", "olmo_hybrid")
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    slots, seq, block = conf["engine"]["slots"], cfg.seq_len, conf["engine"]["kv_block_size"]
    pools = (on_chip(jax.eval_shape(lambda: PagedKVCache.create(cfg, slots * seq // block + 1, block, dtype=bf16))),
             on_chip(jax.eval_shape(lambda: StatePool.create(cfg, slots, bf16))))
    kv = lambda: jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq, cfg.cache_width), bf16)
    column = on_chip(jax.eval_shape(lambda: StateColumn.zeros(cfg, kv(), kv(), bf16)))
    rows = lambda dtype, *tail: _shape(one_chip, (slots, *tail), dtype)
    scalar = lambda dtype: _shape(one_chip, (), dtype)
    compiled = jax.jit(hybrid.forward_and_step, static_argnums=1, donate_argnums=(4,)).lower(
        params, cfg, rows(i32, 1), rows(i32), (column, pools), rows(i32, seq // block),
        _shape(one_chip, (1, 256), i32), scalar(i32), scalar(i32), scalar(f32)).compile()
    text = compiled.as_text()
    kernels = mosaic_kernels(text)
    assert kernels.get("gated_delta_chunk") == 1 and kernels.get("gated_delta_step") == 1, kernels
    assert kernels.get("quant_matmul") == 12, kernels           # five a linear layer's body, seven the full one's
    assert "30,64,64]" not in text                              # [N, B, H, C, C] pairs


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_on_cpu(chips):
    """The script end to end at a toy size with JAX_PLATFORMS=cpu children
    (``--rehearse``): every phase's control flow and the contract's last
    line, with ``platform`` relaxed to what a rehearsal can have."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse",
         "--chips", str(chips)],
        capture_output=True, text=True, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    assert "SIGTERM drained, exit 0" in p.stdout
    if chips == 4:
        assert "tp=4 text == tp=1 text" in p.stdout


def test_chip_smoke_refuses_to_run_without_a_chip():
    """The default run starts its children with JAX_PLATFORMS=tpu, so that jax
    itself refuses when there is no chip: nonzero exit, no result line."""
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, cwd=REPO, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


# -- mellum2-12b-a2.5b at its published widths (PR 60) --------------------------------------
# 8 of 64 experts a token, every one held, 896 wide HELD in 1024 (``cfg.expert_width_held``: at 896 a plane's
# scales are 28 rows, the HBM operand is tiled to 32 and Mosaic refuses the DMA's slice of 28: the first chip call
# of PR 60 died there). The step at 16 rows is past ``share.step_form`` (128 pairs over 64 experts) and takes the
# run form, as every chunk and every tick program does.


@pytest.mark.parametrize("rows", [16, 32 + 16, 256 + 16])
@pytest.mark.parametrize("kk,n,scatter", [(2304, 1024, False), (1024, 2304, True)])
def test_expert_chunk_compiles_at_mellums_experts_for_v5e(one_chip, kk, n, rows, scatter):
    """The grouped routed kernel at an expert's planes over 16 layers of 64
    held: the step's 16 rows, and a tick program's narrowest and widest
    bucket with every slot's row joined to it."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight

    held, k = 64, 8
    pairs = rows * k
    fed = ec.fed_rows(pairs, held)
    assert ec.stripe(rows, fed, kk, n, True, scatter) is not None
    stack = QuantizedWeight(scales=_shape(one_chip, (16, held, kk // 32, n), jnp.bfloat16),
                            codes=_shape(one_chip, (16, held, kk, n), jnp.int8))
    i32 = lambda *shape: _shape(one_chip, shape, jnp.int32)
    runs = (i32(),) + tuple(i32(held) for _ in range(4))
    if scatter:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r, at, w: ec.expert_chunk(x, st, layer, runs, r, (at, w), rows_out=rows, fast=True),
            _shape(one_chip, (fed, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs), i32(pairs),
            _shape(one_chip, (rows, k), jnp.float32))
    else:
        kernels = _compiled_kernels(
            lambda x, st, layer, runs, r: ec.expert_chunk(x, st, layer, runs, r, rows_out=fed, fast=True),
            _shape(one_chip, (rows, kk), jnp.bfloat16), stack, i32(), runs, i32(pairs))
    assert any("expert_chunk" in name for name in kernels), kernels


def test_paged_attention_compiles_at_mellums_tables_for_v5e(one_chip):
    """The step's walk: 16 slots of 11,776 in blocks of 16 (tables 736 wide,
    in both pools), 32:4 heads of 128 (a group of 8 query heads a K/V head)."""
    heads, group = _compile_paged_attention(one_chip, 16, 1, 32, 4, 128, 736, 16, jnp.bfloat16)
    assert 4 % heads == 0 and group >= 1

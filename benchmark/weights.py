"""Seeded weights made on the device, and the seam that hands them to the engine.

The benchmark writes no multi-GB ``.m``: it writes the header, truncates the
file (sparse) out to the size ``ModelFile``'s tensor walk expects, and replaces
the one call that reads tensors (``load_params_from_mfile``, as imported by
``dllama_tpu.runtime.engine``) with :func:`device_params`. Everything else in
``InferenceEngine.__init__`` runs as it does for a real file.

Started as a copy of ``bench.device_random_params`` (Llama-only, seed 0, no
mesh, one jit per tensor). Differences, and why:

* every array comes out of ONE jitted call keyed by ``--seed``, with the mesh
  plan's shardings as ``out_shardings`` (tp=4 places shards directly);
* Qwen3's per-head ``norm_q``/``norm_k`` are made where the header says so;
* scales are chosen per matrix as ``gain / sqrt(fan_in)`` and codes are
  symmetric around 0 (-7..7), so the residual stream neither collapses onto
  the codes' mean direction nor blows up over 32-40 layers: the bench's
  ``uniform(0.001, 0.011)`` scales with codes in ``[-8, 8)`` give every matrix
  a rank-one component of -0.5 * scale that grows with depth. The logits'
  spread then follows the head's gain alone (final RMSNorm makes the input
  unit-RMS); ``HEAD_GAIN`` = 1 gives a standard deviation near 1.
"""

from __future__ import annotations

import os
import struct

QUANT_BLOCK = 32        # Q40 block: 32 codes share one scale
CODE_RMS = 4.1833       # rms of codes drawn as below: sqrt(17.5)
SCALE_RMS = 1.0408      # rms of U(0.5, 1.5)
LAYER_GAIN = 1.0
HEAD_GAIN = 1.0

# .m header key ids, float types and magic (reference src/llm.hpp; the same
# numbers as dllama_tpu/formats/mfile.py and quants.py: the benchmark keeps
# its own so a header written here does not move with the program's enums)
_MAGIC = 0x0A00ABCD
_KEYS = {"version": 0, "arch_type": 1, "dim": 2, "hidden_dim": 3,
         "n_layers": 4, "n_heads": 5, "n_kv_heads": 6, "n_experts": 7,
         "n_active_experts": 8, "vocab_size": 9, "seq_len": 10,
         "hidden_act": 11, "rope_theta": 12, "weight_float_type": 13,
         "rope_type": 18, "head_dim": 19, "norm_epsilon": 20}
_ARCH = {"llama": 0xABCD00, "qwen3": 0xABCD01}
_ROPE = {"interleaved": 0, "half_split": 1}
_F32, _Q40 = 0, 2
_Q40_BLOCK_BYTES = 18   # f16 scale + 16 bytes of packed nibbles


def header_fields(model: dict) -> dict:
    """The integer header of a ``.m`` file for a configuration's ``model``."""
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": _ARCH[model["arch"]],
        "dim": model["hidden_size"], "hidden_dim": model["intermediate_size"],
        "n_layers": model["num_hidden_layers"],
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": 0, "n_active_experts": 0,
        "vocab_size": model["vocab_size"],
        "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": int(model["rope_theta"]),
        "weight_float_type": _Q40,
        "rope_type": _ROPE[model["rope_convention"]],
        "head_dim": model["head_dim"], "norm_epsilon": eps,
    }


def _tensor_bytes(n: int, float_type: int) -> int:
    return n * 4 if float_type == _F32 else n // QUANT_BLOCK * _Q40_BLOCK_BYTES


def walk_size(model: dict, header_size: int) -> int:
    """Bytes the program's tensor walk expects after the header (dense
    models: embedding f32, per layer q k v wo w1 w2 w3 [norm_q norm_k] norm_0
    norm_1, final norm, logits)."""
    d, h, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    v = model["vocab_size"]
    per_layer = sum(_tensor_bytes(n, _Q40) for n in
                    (q * d, kv * d, kv * d, d * q, h * d, d * h, h * d))
    per_layer += 2 * d * 4
    if model["arch"] == "qwen3":
        per_layer += 2 * hd * 4
    return (header_size + v * d * 4 + L * per_layer + d * 4
            + _tensor_bytes(v * d, _Q40))


def write_sparse_model(path: str, model: dict) -> None:
    """Header + a hole of the right size. No tensor byte is ever read: the
    seam below supplies the params."""
    fields = header_fields(model)
    data = b"".join(struct.pack("<ii", _KEYS[k], int(val)) for k, val in fields.items())
    header = struct.pack("<ii", _MAGIC, 8 + len(data)) + data
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(header)
        f.truncate(walk_size(model, len(header)))


def params_builder(cfg, plan):
    """``(build(key) -> Params, out_shardings)``: the engine's ``Params`` tree in
    the dtypes and shardings the streaming loader would have produced for a Q40
    file under this compute dtype and mesh plan."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import LayerParams, Params
    from dllama_tpu.ops.linear import QuantizedWeight, fast_numerics_resolved
    from dllama_tpu.parallel.api import make_tp_mesh
    from dllama_tpu.runtime.weights import dense_logits_resolved

    plan = plan if plan is not None else make_tp_mesh(1)
    fast = fast_numerics_resolved(cfg.compute_dtype)
    scale_dtype = jnp.bfloat16 if fast else jnp.float32
    cdt = jnp.dtype(cfg.compute_dtype)
    dense_head = dense_logits_resolved(cfg.compute_dtype)
    L, d, hdim = cfg.n_layers, cfg.dim, cfg.hidden_dim
    qwen3 = cfg.uses_qk_norm

    # (name, out, in, out_axis, in_axis): the loader's own table
    mats = [("wq", cfg.q_dim, d, "heads", None), ("wk", cfg.kv_dim, d, "kv_heads", None),
            ("wv", cfg.kv_dim, d, "kv_heads", None), ("wo", d, cfg.q_dim, None, "heads"),
            ("w1", hdim, d, "hidden", None), ("w2", d, hdim, None, "hidden"),
            ("w3", hdim, d, "hidden", None)]

    def qshard(out, in_, out_axis, in_axis, stacked=True):
        lead = ("layers",) if stacked else ()
        pre = (L,) if stacked else ()
        return QuantizedWeight(
            scales=plan.sharding_for(pre + (in_ // QUANT_BLOCK, out), *lead, in_axis, out_axis),
            codes=plan.sharding_for(pre + (in_, out), *lead, in_axis, out_axis))

    def rep(*shape):
        return plan.sharding_for(tuple(shape), *([None] * len(shape)))

    def stacked_rep(*tail):
        return plan.sharding_for((L, *tail), "layers", *([None] * len(tail)))

    layer_sh = LayerParams(
        **{n: qshard(o, i, oa, ia) for n, o, i, oa, ia in mats},
        norm_att=stacked_rep(d), norm_ffn=stacked_rep(d),
        norm_q=stacked_rep(cfg.head_dim) if qwen3 else None,
        norm_k=stacked_rep(cfg.head_dim) if qwen3 else None)
    head_sh = (plan.sharding_for((cfg.vocab_size, d), "vocab", None) if dense_head
               else qshard(cfg.vocab_size, d, "vocab", None, stacked=False))
    out_sh = Params(embedding=rep(cfg.vocab_size, d), layers=layer_sh,
                    final_norm=rep(d), logits=head_sh)

    def qw(key, out, in_, stacked=True, gain=LAYER_GAIN):
        pre = (L,) if stacked else ()
        ks, kc = jax.random.split(key)
        s0 = gain / (CODE_RMS * SCALE_RMS * in_ ** 0.5)
        scales = (jax.random.uniform(ks, pre + (in_ // QUANT_BLOCK, out), jnp.float32,
                                     0.5, 1.5) * s0).astype(scale_dtype)
        nib = (jax.random.bits(kc, pre + (in_, out), jnp.uint8) & jnp.uint8(0x0F)).astype(jnp.int8) - 8
        codes = jnp.where(nib == -8, jnp.int8(0), nib)   # -7..7, mean 0
        return QuantizedWeight(scales=scales, codes=codes)

    def build(key):
        keys = iter(jax.random.split(key, 16))
        layers = LayerParams(
            **{n: qw(next(keys), o, i) for n, o, i, _oa, _ia in mats},
            norm_att=jnp.ones((L, d), jnp.float32), norm_ffn=jnp.ones((L, d), jnp.float32),
            norm_q=jnp.ones((L, cfg.head_dim), jnp.float32) if qwen3 else None,
            norm_k=jnp.ones((L, cfg.head_dim), jnp.float32) if qwen3 else None)
        emb = jax.random.uniform(next(keys), (cfg.vocab_size, d), jnp.float32,
                                 -3 ** 0.5, 3 ** 0.5).astype(cdt)
        if dense_head:
            a = HEAD_GAIN * (3.0 / d) ** 0.5
            head = jax.random.uniform(next(keys), (cfg.vocab_size, d), jnp.float32,
                                      -a, a).astype(jnp.bfloat16)
        else:
            head = qw(next(keys), cfg.vocab_size, d, stacked=False, gain=HEAD_GAIN)
        return Params(embedding=emb, layers=layers,
                      final_norm=jnp.ones((d,), jnp.float32), logits=head)

    return build, out_sh


def device_params(cfg, plan, seed: int):
    """The tree of :func:`params_builder`, random from ``seed``, made on the
    device(s) in ONE jitted call."""
    import jax

    build, out_sh = params_builder(cfg, plan)
    # seeds run past 2**31: fold the high bits in instead of overflowing int32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.block_until_ready(jax.jit(build, out_shardings=out_sh)(key))


def install_seam(seed: int) -> None:
    """Replace the engine's tensor-reading call with the device-made params.
    One assignment, in the module that holds the name the engine calls."""
    import dllama_tpu.runtime.engine as engine_mod

    def load_params_from_mfile(mf, cfg, weight_mode="auto", plan=None):
        if weight_mode != "auto":
            raise ValueError("the benchmark serves Q40 planes (weight_mode auto) only")
        return device_params(cfg, plan, seed)

    engine_mod.load_params_from_mfile = load_params_from_mfile

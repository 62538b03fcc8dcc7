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

This file is the DEFAULT ``weights`` module (README, "Adding things"): the
dense decoders' header, walk size and ``Params`` tree. A configuration whose
layer equation is another names its own module, which owns its header, arch
id and walk size, and builds them from the parts here that no equation
changes: :func:`write_sparse` (the header writer, given a field dict),
:func:`qw` (the seeded Q40 plane), :class:`Trunk` (shardings and makers of the
attention planes, norms, embedding and head), :func:`device_params` (the one
jit) and :func:`install_seam`, the last two given the module's ``builder``.
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
HEADER_KEYS = {"version": 0, "arch_type": 1, "dim": 2, "hidden_dim": 3,
               "n_layers": 4, "n_heads": 5, "n_kv_heads": 6, "n_experts": 7,
               "n_active_experts": 8, "vocab_size": 9, "seq_len": 10,
               "hidden_act": 11, "rope_theta": 12, "weight_float_type": 13,
               "rope_type": 18, "head_dim": 19, "norm_epsilon": 20}
ARCH = {"llama": 0xABCD00, "qwen3": 0xABCD01}
_ROPE = {"interleaved": 0, "half_split": 1}
F32, Q40 = 0, 2
_Q40_BLOCK_BYTES = 18   # f16 scale + 16 bytes of packed nibbles


def header_fields(model: dict) -> dict:
    """The integer header of a ``.m`` file for a configuration's ``model``."""
    eps = {1e-5: 5, 1e-6: 6}[float(model["norm_epsilon"])]
    return {
        "version": 1, "arch_type": ARCH[model["arch"]],
        "dim": model["hidden_size"], "hidden_dim": model["intermediate_size"],
        "n_layers": model["num_hidden_layers"],
        "n_heads": model["num_attention_heads"],
        "n_kv_heads": model["num_key_value_heads"],
        "n_experts": 0, "n_active_experts": 0,
        "vocab_size": model["vocab_size"],
        "seq_len": model["max_position_embeddings"],
        "hidden_act": 1, "rope_theta": int(model["rope_theta"]),
        "weight_float_type": Q40,
        "rope_type": _ROPE[model["rope_convention"]],
        "head_dim": model["head_dim"], "norm_epsilon": eps,
    }


def tensor_bytes(n: int, float_type: int) -> int:
    return n * 4 if float_type == F32 else n // QUANT_BLOCK * _Q40_BLOCK_BYTES


def walk_size(model: dict, header_size: int, ffn_bytes: int | None = None) -> int:
    """Bytes the program's tensor walk expects after the header: embedding
    f32, per layer q k v wo, the feed-forward's tensors (``ffn_bytes``; absent,
    the dense w1 w2 w3), [norm_q norm_k] norm_0 norm_1, final norm, logits."""
    d, h, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    hd = model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    v = model["vocab_size"]
    if ffn_bytes is None:
        ffn_bytes = 3 * tensor_bytes(h * d, Q40)
    per_layer = sum(tensor_bytes(n, Q40) for n in (q * d, kv * d, kv * d, d * q)) + ffn_bytes + 2 * d * 4
    if model["arch"] == "qwen3":
        per_layer += 2 * hd * 4
    return (header_size + v * d * 4 + L * per_layer + d * 4
            + tensor_bytes(v * d, Q40))


def write_sparse(path: str, fields: dict, size_after) -> None:
    """Header + a hole of the right size. ``fields`` maps a name in
    :data:`HEADER_KEYS` (or a key id) to its integer; ``size_after(header_size)``
    is the whole file's size as the program's tensor walk will count it. No
    tensor byte is ever read: the seam below supplies the params."""
    data = b"".join(struct.pack("<ii", k if isinstance(k, int) else HEADER_KEYS[k], int(val))
                    for k, val in fields.items())
    header = struct.pack("<ii", _MAGIC, 8 + len(data)) + data
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(header)
        f.truncate(size_after(len(header)))


def write_sparse_model(path: str, model: dict) -> None:
    write_sparse(path, header_fields(model), lambda header_size: walk_size(model, header_size))


def qw(key, pre: tuple, out: int, in_: int, *, scale_dtype, gain: float = LAYER_GAIN):
    """One seeded Q40 plane (or a stack of them: ``pre`` are the leading axes)
    as the engine holds it: ``scales pre + [in/32, out]``, ``codes pre + [in, out]``."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops.linear import QuantizedWeight

    ks, kc = jax.random.split(key)
    s0 = gain / (CODE_RMS * SCALE_RMS * in_ ** 0.5)
    scales = (jax.random.uniform(ks, pre + (in_ // QUANT_BLOCK, out), jnp.float32,
                                 0.5, 1.5) * s0).astype(scale_dtype)
    nib = (jax.random.bits(kc, pre + (in_, out), jnp.uint8) & jnp.uint8(0x0F)).astype(jnp.int8) - 8
    codes = jnp.where(nib == -8, jnp.int8(0), nib)   # -7..7, mean 0
    return QuantizedWeight(scales=scales, codes=codes)


class Trunk:
    """What every decoder the program serves has around its feed-forward, for
    one ``cfg`` and mesh plan: the shardings the streaming loader would have
    produced for a Q40 file under this compute dtype, and the seeded makers.
    A builder draws its keys in its own fixed order."""

    def __init__(self, cfg, plan):
        import jax.numpy as jnp

        from dllama_tpu.ops.linear import fast_numerics_resolved
        from dllama_tpu.parallel.api import make_tp_mesh
        from dllama_tpu.runtime.weights import dense_logits_resolved

        self.cfg = cfg
        self.plan = plan if plan is not None else make_tp_mesh(1)
        self.scale_dtype = jnp.bfloat16 if fast_numerics_resolved(cfg.compute_dtype) else jnp.float32
        self.dense_head = dense_logits_resolved(cfg.compute_dtype)
        d = cfg.dim
        # (name, out, in, out_axis, in_axis): the loader's own table
        self.attention = [("wq", cfg.q_dim, d, "heads", None), ("wk", cfg.kv_dim, d, "kv_heads", None),
                          ("wv", cfg.kv_dim, d, "kv_heads", None), ("wo", d, cfg.q_dim, None, "heads")]

    # -- shardings -----------------------------------------------------------
    def qshard(self, out, in_, out_axis, in_axis, pre=None, lead=("layers",)):
        """Shardings of a plane stacked over ``pre`` (absent: the layers)
        whose leading axes carry the logical names ``lead``, one a leading axis."""
        from dllama_tpu.ops.linear import QuantizedWeight

        pre = (self.cfg.n_layers,) if pre is None else pre
        return QuantizedWeight(
            scales=self.plan.sharding_for(pre + (in_ // QUANT_BLOCK, out), *lead, in_axis, out_axis),
            codes=self.plan.sharding_for(pre + (in_, out), *lead, in_axis, out_axis))

    def rep(self, *shape):
        return self.plan.sharding_for(tuple(shape), *([None] * len(shape)))

    def stacked_rep(self, *tail):
        return self.plan.sharding_for((self.cfg.n_layers, *tail), "layers", *([None] * len(tail)))

    def norm_shardings(self) -> dict:
        cfg = self.cfg
        qk = self.stacked_rep(cfg.head_dim) if cfg.uses_qk_norm else None
        return {"norm_att": self.stacked_rep(cfg.dim), "norm_ffn": self.stacked_rep(cfg.dim),
                "norm_q": qk, "norm_k": qk}

    def params_shardings(self, layer_sh):
        from dllama_tpu.models.llama import Params

        cfg = self.cfg
        head_sh = (self.plan.sharding_for((cfg.vocab_size, cfg.dim), "vocab", None) if self.dense_head
                   else self.qshard(cfg.vocab_size, cfg.dim, "vocab", None, pre=(), lead=()))
        return Params(embedding=self.rep(cfg.vocab_size, cfg.dim), layers=layer_sh,
                      final_norm=self.rep(cfg.dim), logits=head_sh)

    # -- makers --------------------------------------------------------------
    def plane(self, key, out, in_, pre=None, gain=LAYER_GAIN):
        pre = (self.cfg.n_layers,) if pre is None else pre
        return qw(key, pre, out, in_, scale_dtype=self.scale_dtype, gain=gain)

    def norms(self) -> dict:
        import jax.numpy as jnp

        cfg, L = self.cfg, self.cfg.n_layers
        return {"norm_att": jnp.ones((L, cfg.dim), jnp.float32), "norm_ffn": jnp.ones((L, cfg.dim), jnp.float32),
                "norm_q": jnp.ones((L, cfg.head_dim), jnp.float32) if cfg.uses_qk_norm else None,
                "norm_k": jnp.ones((L, cfg.head_dim), jnp.float32) if cfg.uses_qk_norm else None}

    def params(self, key_emb, key_head, layers):
        """``Params`` around a layer stack: embedding, then head, each from its key."""
        import jax
        import jax.numpy as jnp

        from dllama_tpu.models.llama import Params

        cfg, d = self.cfg, self.cfg.dim
        emb = jax.random.uniform(key_emb, (cfg.vocab_size, d), jnp.float32,
                                 -3 ** 0.5, 3 ** 0.5).astype(jnp.dtype(cfg.compute_dtype))
        if self.dense_head:
            a = HEAD_GAIN * (3.0 / d) ** 0.5
            head = jax.random.uniform(key_head, (cfg.vocab_size, d), jnp.float32,
                                      -a, a).astype(jnp.bfloat16)
        else:
            head = self.plane(key_head, cfg.vocab_size, d, pre=(), gain=HEAD_GAIN)
        return Params(embedding=emb, layers=layers,
                      final_norm=jnp.ones((d,), jnp.float32), logits=head)


def params_builder(cfg, plan):
    """``(build(key) -> Params, out_shardings)``: the engine's ``Params`` tree in
    the dtypes and shardings the streaming loader would have produced for a Q40
    file under this compute dtype and mesh plan. The dense decoders': seven
    planes a layer, drawn in the order wq wk wv wo w1 w2 w3, embedding, head."""
    import jax

    from dllama_tpu.models.llama import LayerParams

    t = Trunk(cfg, plan)
    d, hdim = cfg.dim, cfg.hidden_dim
    mats = t.attention + [("w1", hdim, d, "hidden", None), ("w2", d, hdim, None, "hidden"),
                          ("w3", hdim, d, "hidden", None)]
    out_sh = t.params_shardings(LayerParams(
        **{n: t.qshard(o, i, oa, ia) for n, o, i, oa, ia in mats}, **t.norm_shardings()))

    def build(key):
        keys = iter(jax.random.split(key, 16))
        layers = LayerParams(**{n: t.plane(next(keys), o, i) for n, o, i, _oa, _ia in mats}, **t.norms())
        return t.params(next(keys), next(keys), layers)

    return build, out_sh


def device_params(cfg, plan, seed: int, builder=params_builder):
    """The tree of ``builder`` (the dense decoders' :func:`params_builder`, or a
    module's own), random from ``seed``, made on the device(s) in ONE jitted call."""
    import jax

    build, out_sh = builder(cfg, plan)
    # seeds run past 2**31: fold the high bits in instead of overflowing int32
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    return jax.block_until_ready(jax.jit(build, out_shardings=out_sh)(key))


def install_seam(seed: int, builder=params_builder) -> None:
    """Replace the engine's tensor-reading call with the device-made params.
    One assignment, in the module that holds the name the engine calls."""
    import dllama_tpu.runtime.engine as engine_mod

    def load_params_from_mfile(mf, cfg, weight_mode="auto", plan=None):
        if weight_mode != "auto":
            raise ValueError("the benchmark serves Q40 planes (weight_mode auto) only")
        return device_params(cfg, plan, seed, builder)

    engine_mod.load_params_from_mfile = load_params_from_mfile

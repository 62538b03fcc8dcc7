"""Digests of the dense decoders' lowered programs at a tiny size on the CPU:
the paged decode step and a prefill chunk, for the Llama and the Qwen3
equations. ``tests/test_olmo_hybrid.py`` holds them against
``tests/goldens/dense_hlo_sha256.json``, which PR 30 wrote from its PARENT
commit (PR 35 rewrote the two ``forward`` digests: a chunk scans the layer
index now; PR 39 the two step digests: the sampler's vocabulary-wide ops sit under a
``lax.cond``; PR 41 added the step as the server jits it, behind its packed
arguments, ``...packed``: the model function's own two digests stayed as they
were): a change to ``models/llama.py``, ``ModelConfig`` or
``runtime/steppack.py`` that alters what a
dense configuration compiles shows up as a mismatch. After a deliberate
change: ``python tools/dense_hlo_digest.py > tests/goldens/dense_hlo_sha256.json``.
"""

import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def digests() -> dict:
    import jax
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import llama
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.runtime import steppack
    from dllama_tpu.runtime.kvblocks import PagedKVCache
    from dllama_tpu.runtime.kvcache import KVCache

    out = {}
    for name, arch, rope in (("llama", ArchType.LLAMA, RopeType.LLAMA), ("qwen3", ArchType.QWEN3, RopeType.FALCON)):
        cfg = ModelConfig(arch=arch, dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                          vocab_size=128, seq_len=128, norm_epsilon=1e-5, rope_theta=10000.0, rope_type=rope,
                          compute_dtype="bfloat16")
        params = llama.init_random_params(cfg, quantized=True)
        shapes = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        i32, f32 = jnp.int32, jnp.float32
        S = jax.ShapeDtypeStruct
        chunk = jax.jit(llama.forward, static_argnums=1).lower(
            shapes(params), cfg, S((1, 32), i32), S((), i32), shapes(KVCache.create(cfg, dtype=jnp.bfloat16)))
        pool = shapes(PagedKVCache.create(cfg, 33, 16, dtype=jnp.bfloat16))
        fields = (S((4, 1), i32), S((4,), i32), S((4, 8), i32), S((4,), f32), S((4,), f32), S((4,), f32), S((), f32))
        step = jax.jit(llama.paged_sampled_step_guarded, static_argnums=1).lower(
            shapes(params), cfg, *fields[:2], pool, *fields[2:])
        # the same step as the server jits it: one packed vector, taken apart by the fields' layout
        packed = jax.jit(steppack.packed_program(llama.paged_sampled_step_guarded), static_argnums=(1, 4)).lower(
            shapes(params), cfg, S((sum(f.size for f in fields),), i32), pool, steppack.layout_of(fields))
        for program, lowered in (("forward", chunk), ("paged_sampled_step_guarded", step),
                                 ("paged_sampled_step_guarded.packed", packed)):
            out[f"{name}.{program}"] = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    return out


if __name__ == "__main__":
    print(json.dumps(digests(), indent=1))

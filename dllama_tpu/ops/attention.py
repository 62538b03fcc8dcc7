"""Causal GQA attention over a preallocated KV cache.

Semantics match the reference's OP_MULTIHEAD_ATT (reference: multiheadAtt_F32,
src/nn/nn-cpu-ops.cpp:751-786): per head, scores ``q·k / sqrt(head_dim)`` over
cache positions ``0..pos``, float32 softmax, weighted V sum; GQA via the
``kv_mul`` head-group factor. The serial per-position loop becomes one batched
einsum pair so XLA maps it onto the MXU; masking replaces the loop bound.

This XLA implementation is the semantics oracle; the Pallas flash-attention
kernel in :mod:`dllama_tpu.ops.flash_attention` must match it bit-for-bit in
f32 (tested the way nn-vulkan-test.cpp checks GPU ops against expectations).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
              positions: jax.Array, head_dim: int, *, window: int = 0,
              key_start: jax.Array | int = 0) -> jax.Array:
    """Attend ``q: [B, T, n_heads, head_dim]`` over cached
    ``k/v: [B, n_kv_heads, S, head_dim]`` (head-major, see runtime.kvcache).
    The lanes may be wider than ``head_dim`` (heads padded with zeros to
    whole lane tiles, models/lfm2.py): ``head_dim`` is the score's scale.

    ``positions: [B, T]`` is the absolute position of each query row; cache
    entries at ``s <= position`` are visible (the reference's ``t <= pos`` loop
    bound), which assumes the cache holds keys for positions ``0..pos``.
    ``window`` > 0 is a sliding-window layer: a query at position ``i`` sees
    keys ``i - window + 1 .. i`` only. ``key_start`` is the position of the
    cache's first row (a caller that cut the window's span out of a longer
    cache says where the cut starts).
    """
    B, T, n_heads, _ = q.shape
    n_kv = k_cache.shape[1]
    S = k_cache.shape[2]
    kv_mul = n_heads // n_kv

    qg = q.reshape(B, T, n_kv, kv_mul, q.shape[-1])
    scores = jnp.einsum("btkmh,bksh->btkms", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(head_dim))

    key_pos = jnp.arange(S)[None, None, :]
    if not (isinstance(key_start, int) and key_start == 0):
        key_pos = key_start + key_pos
    mask = key_pos <= positions[:, :, None]  # [B, T, S]
    if window:
        mask &= key_pos > positions[:, :, None] - window
    scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)

    out = jnp.einsum("btkms,bksh->btkmh", probs, v_cache.astype(jnp.float32))
    return out.reshape(B, T, n_heads, -1).astype(q.dtype)

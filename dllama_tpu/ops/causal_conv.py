"""The causal depthwise convolution over time that three mixers put in
front of (or in place of) their recurrence: the gated delta rule's
(models/hybrid.py, ``K`` taps then SiLU), the SSD mixer's (models/falcon_h1.py,
taps, a bias, SiLU) and the gated short convolution's (models/lfm2.py, three
taps and NO activation: the gates around it are the nonlinearity).

What a sequence carries from call to call is the TAIL, the last ``K - 1``
inputs: a row of :class:`~dllama_tpu.runtime.kvblocks.StatePool`'s ``conv``
between decode steps, :class:`~dllama_tpu.runtime.kvblocks.StateColumn`'s
between prefill chunks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x: jax.Array, tail: jax.Array, w: jax.Array,
                n_valid: jax.Array | None = None,
                bias: jax.Array | None = None, *, activation=jax.nn.silu):
    """Causal depthwise convolution over time, then ``activation`` (SiLU;
    ``None``: none): ``y_t = act(sum_j w[j] x_{t-(K-1)+j} + bias)``. ``x [B,
    T, C]``; ``tail [B, K-1, C]`` are the K-1 inputs before the chunk (zeros
    at a sequence's start); ``w [K, C]``; ``bias [C]`` where the mixer has
    one (ops/ssd.py's does, the gated delta rule's and the short
    convolution's do not). Returns float32 ``y [B, T, C]`` and the new tail:
    the last K-1 inputs at or before position ``n_valid`` (a scalar; absent,
    ``T``), so padding behind a chunk's valid length never enters it."""
    K, T = w.shape[0], x.shape[1]
    seq = jnp.concatenate([tail.astype(jnp.float32), x.astype(jnp.float32)],
                          axis=1)
    wf = w.astype(jnp.float32)
    y = sum(wf[j] * seq[:, j:j + T] for j in range(K))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    start = T if n_valid is None else n_valid
    new_tail = jax.lax.dynamic_slice_in_dim(seq, start, K - 1, axis=1)
    if activation is not None:
        y = activation(y)
    return y, new_tail.astype(tail.dtype)

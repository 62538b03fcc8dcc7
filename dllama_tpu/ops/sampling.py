"""On-device token sampling — the fused tail of the decode step.

Replaces the reference's host-side sample-after-transfer (reference:
``Sampler::sample`` over the gathered logits pipe, src/tokenizer.cpp:480-510;
our host oracle is :mod:`dllama_tpu.tokenizer.sampler`): the temperature
softmax, top-p truncation, and CDF pick all run on device inside the jitted
decode step, so a sampled token costs one dispatch and a 4-byte device→host
transfer — the same budget as greedy decode — instead of a vocab-row
download every token.

RNG stays host-side for reference parity: the xorshift* ``coin`` is computed
on host (one u64 step per token, bit-exact with tokenizer.cpp:25-36) and
passed in as a scalar. Semantics mirror the host oracle's reference quirks:

* cutoff pre-filter ``(1-topp)/(n-1)`` before the descending sort
  (tokenizer.cpp:432-441);
* renormalization by the truncated cumulative mass (``coin * cumulative``,
  tokenizer.cpp:455-459);
* ties keep ascending-index order (stable sort — the reference qsort
  comparator returns 0 for equal probs).

Float caveat: cumulative sums here and in numpy may associate differently,
so a coin landing exactly on a f32 boundary can pick a neighboring token;
tests sample many draws and require exact agreement on the oracle's RNG
stream (boundary hits are measure-zero in practice).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def topp_sample(probs: jax.Array, topp: jax.Array, coin: jax.Array) -> jax.Array:
    """Nucleus pick over ``probs [V]``; returns a scalar int32 token id."""
    n = probs.shape[0]
    cutoff = (1.0 - topp) / (n - 1)
    masked = jnp.where(probs >= cutoff, probs, 0.0)
    order = jnp.argsort(-masked, stable=True)
    ps = masked[order]
    return _nucleus_pick(ps, topp, coin, jnp.count_nonzero(ps), order)


def _nucleus_pick(ps: jax.Array, topp: jax.Array, coin: jax.Array,
                  n_kept, order: jax.Array) -> jax.Array:
    """The reference's truncate+renormalize+CDF walk over probabilities
    already sorted descending (``ps``); ``order`` maps positions back to
    token ids and ``n_kept`` is the count of nonzero survivors of the
    cutoff pre-filter (which may exceed ``ps``'s length in the windowed
    fast path — only ever used via min with the window bound)."""
    n = ps.shape[0]
    csum = jnp.cumsum(ps)
    over = csum > topp
    last = jnp.where(jnp.any(over), jnp.argmax(over),
                     jnp.minimum(jnp.maximum(n_kept - 1, 0), n - 1)
                     ).astype(jnp.int32)
    cumulative = csum[last]
    r = coin * cumulative
    inner = jnp.cumsum(
        jnp.where(jnp.arange(n, dtype=jnp.int32) <= last, ps, 0.0)) > r
    pick = jnp.where(jnp.any(inner), jnp.argmax(inner), last).astype(jnp.int32)
    return order[pick].astype(jnp.int32)


def mult_sample(probs: jax.Array, coin: jax.Array) -> jax.Array:
    """Multinomial CDF scan (reference: tokenizer.cpp:403-414)."""
    cdf = jnp.cumsum(probs)
    hit = coin < cdf
    n = probs.shape[0]
    return jnp.where(jnp.any(hit), jnp.argmax(hit), n - 1).astype(jnp.int32)


# top-p fast-path window: the nucleus of a typical top-p<=0.95 draw is a few
# dozen tokens; a 256-wide lax.top_k window replaces the full-vocab stable
# sort (the dominant cost of a fused sampled step: ~6 ms/step of a 128k-vocab
# argsort on the 1b preset, round-4 capture). The windowed math is the exact
# reference algorithm on the same descending prefix (lax.top_k breaks ties by
# lower index, like the stable argsort), so any draw whose nucleus fits the
# window is bit-identical; a batch with any row whose nucleus could overflow
# falls back to the full sort via a batch-level cond (a per-row cond would
# lower to select under vmap and run the full sort anyway).
TOPP_WINDOW = 256


def sampled_token(logits: jax.Array, temperature: jax.Array, topp: jax.Array,
                  coin: jax.Array) -> jax.Array:
    """Sample one token per row of ``logits [B, V]``.

    ``temperature``/``topp``/``coin`` are scalars (the single-sequence
    engine; temperature > 0 guaranteed by the caller) or ``[B]`` vectors
    (ragged batched serving): per-row knobs, with ``temperature <= 0`` rows
    taking the greedy argmax — one fused program covers a mixed batch.
    ``topp`` outside (0, 1) selects plain multinomial, matching the host
    oracle. A batch with no ``temperature > 0`` row runs the argmax alone
    (one ``lax.cond`` on the traced temperatures: same program, no
    recompile when a sampling request joins)."""
    logits = logits.astype(jnp.float32)
    B, V = logits.shape
    temp = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(temperature)), (B,))
    topp_v = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(topp)), (B,))
    coin_v = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(coin)), (B,))

    def greedy_path():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_path():
        safe_t = jnp.where(temp > 0.0, temp, 1.0)
        probs = jax.nn.softmax(logits / safe_t[:, None], axis=-1)
        # greedy rows (temp <= 0) never use their nucleus draw, so they must
        # not be able to force the full-vocab sort fallback for the whole
        # batch: a serving batch of mostly-greedy rows keeps the windowed
        # fast path
        topp_row = (topp_v > 0.0) & (topp_v < 1.0) & (temp > 0.0)

        if V > TOPP_WINDOW:
            K = TOPP_WINDOW
            cutoff = ((1.0 - topp_v) / (V - 1))[:, None]
            masked = jnp.where(probs >= cutoff, probs, 0.0)
            n_kept = jnp.count_nonzero(masked, axis=-1).astype(jnp.int32)
            vals, idxs = jax.lax.top_k(masked, K)
            # the window covers the nucleus iff it either exhausts the kept
            # set or its cumulative mass already crosses topp
            window_ok = ((jnp.cumsum(vals, axis=-1)[:, -1] > topp_v)
                         | (n_kept <= K))
            all_safe = jnp.all(window_ok | ~topp_row)

            def windowed():
                return jax.vmap(_nucleus_pick)(vals, topp_v, coin_v,
                                               jnp.minimum(n_kept, K), idxs)

            def full():
                return jax.vmap(topp_sample)(probs, topp_v, coin_v)

            nucleus = jax.lax.cond(all_safe, windowed, full)
        else:
            nucleus = jax.vmap(topp_sample)(probs, topp_v, coin_v)

        multi = jax.vmap(mult_sample)(probs, coin_v)
        sampled = jnp.where(topp_row, nucleus, multi)
        return jnp.where(temp > 0.0, sampled, greedy_path())

    # a batch in which no row samples reads none of the vocabulary-wide
    # softmax, top_k and cumulative sums; batch level like the cond above
    # (per row it would lower to a select under vmap and run both sides)
    return jax.lax.cond(jnp.any(temp > 0.0), sampled_path, greedy_path)

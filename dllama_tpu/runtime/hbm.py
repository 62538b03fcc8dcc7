"""Pre-staging HBM budget check — refuse loudly instead of OOM-wedging.

The reference prints its required-memory estimate before loading
(nn-core.cpp:162-176, "required memory" at graph-build time) and a malloc
failure is a clean abort. On this TPU stack the failure mode is much worse:
an HBM OOM can wedge the backend server-side for HOURS, so the engine
estimates device bytes up front and refuses with an actionable error when
the budget doesn't fit.

Estimates are deliberately simple shape algebra with a safety margin — the
goal is catching the 2x-and-worse misfits (8B f32 on a 16 GB chip, 70B on
anything single-chip), not byte-exact accounting.
"""

from __future__ import annotations

import os

from .kvcache import padded_cache_len

# dense-equivalent bytes per weight for each on-device representation
# (quantized planes carry f32 block scales in exact configs, bf16 in fast
# ones — the f32 value is kept as the conservative estimate either way)
_WEIGHT_BYTES = {
    "q40": 1.125,   # int8 codes (1 B) + f32 block scales (4/32 B)
    "q80": 1.125,
    "f16": 2.0,
    "bf16": 2.0,
    "f32": 4.0,
}

# headroom for XLA workspace, fusion temporaries, logits buffers, and the
# dispatch double-buffering the estimate can't see
_MARGIN = 1.15
_FIXED_OVERHEAD = 512 * 1024 * 1024


def device_memory_bytes() -> int | None:
    """The per-device memory limit, or None on a backend that has none to
    report (the CPU mesh). A TPU that cannot say its limit is an error, not
    an "unknown budget": every guard below would silently stand down on the
    one platform it exists for. ``DLLAMA_HBM_BYTES`` overrides (testing)."""
    env = os.environ.get("DLLAMA_HBM_BYTES")
    if env:
        return int(env)
    import jax

    dev = jax.local_devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is None and dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']: the HBM "
            f"budget cannot be checked (set DLLAMA_HBM_BYTES to state it)")
    return limit


def matmul_weight_count(cfg) -> int:
    """Total matmul-plane weights (the quantized payload): the decoder
    family's own arithmetic over what it HOLDS (models/family.py)."""
    from ..models.family import family_of

    return family_of(cfg).matmul_weight_count(cfg)


def estimate_device_bytes(cfg, *, weight_repr: str, kv_dtype_bytes: int,
                          batch: int = 1, n_shards: int = 1,
                          offload: bool = False) -> dict:
    """Per-device byte estimate. ``weight_repr`` names the on-device weight
    representation (q40/q80/f16/bf16/f32); ``n_shards`` divides the
    weight+KV payload (mesh sharding); ``offload`` keeps layer stacks in
    host DRAM, leaving only embeddings + head + a working set on device."""
    import numpy as np

    wbytes = _WEIGHT_BYTES[weight_repr]
    # embedding is stored at compute dtype (runtime.weights.load_params)
    emb_elem = np.dtype(getattr(cfg, "compute_dtype", "float32") or
                        "float32").itemsize
    emb_bytes = cfg.vocab_size * cfg.dim * emb_elem
    if wbytes < 2.0 and not getattr(cfg, "tied_embeddings", False):
        # fast configs load the logits head as resident dense bf16
        # (runtime.weights.dense_logits_wanted); charge the delta so the
        # budget check sees the real footprint. A tied head is the
        # embedding's own buffer, counted once above (its family's
        # ``matmul_weight_count`` leaves the head out)
        from .weights import dense_logits_resolved

        if dense_logits_resolved(getattr(cfg, "compute_dtype", "")):
            emb_bytes += int(cfg.vocab_size * cfg.dim * (2.0 - wbytes))
    if offload:
        # resident: embedding + head + ~2 layers of streamed working set
        per_layer = matmul_weight_count(cfg) // max(1, cfg.n_layers)
        weights = emb_bytes + int(2 * per_layer * wbytes)
    else:
        weights = emb_bytes + int(matmul_weight_count(cfg) * wbytes)
    kv = (cfg.n_kv_layers * padded_cache_len(cfg.seq_len)
          * cfg.cache_row_elems * batch * kv_dtype_bytes)
    need = int(((weights + kv) / max(1, n_shards)) * _MARGIN) + _FIXED_OVERHEAD
    return {"weights_bytes": weights, "kv_bytes": kv,
            "need_per_device": need}


def fit_batch_slots(cfg, n_slots: int, *, weight_repr: str,
                    kv_dtype_bytes: int, n_shards: int = 1, dp: int = 1,
                    offload: bool = False) -> tuple[int, dict]:
    """Largest slot-pool size ``<= n_slots`` (stepping by ``dp`` so the
    dp-sharded batch axis stays divisible) whose estimate fits the device
    limit — the HBM admission guard's DEGRADE path: a pool that would OOM
    shrinks instead of crashing the process at staging time. Returns
    ``(n_fit, estimate)``; ``n_fit == 0`` when even a ``dp``-slot pool
    doesn't fit (the caller refuses, same as before)."""
    limit = (None if os.environ.get("DLLAMA_SKIP_HBM_CHECK")
             else device_memory_bytes())
    n = max(dp, (n_slots // dp) * dp)
    while n >= dp:
        # +1: the engine's batch-1 cache stays allocated alongside the pool
        est = estimate_device_bytes(
            cfg, weight_repr=weight_repr, kv_dtype_bytes=kv_dtype_bytes,
            batch=n // dp + 1, n_shards=n_shards, offload=offload)
        if limit is None or est["need_per_device"] <= limit:
            return n, est
        n -= dp
    return 0, est


def admission_column_bytes(cfg, kv_dtype) -> int:
    """Device bytes of ONE admission's column as its decoder family makes it
    from a slot's gathered view (``Family.column``, shapes only): the view
    itself, or with window layers the full layers' view and the sliding
    layers' buffer of the window and the widest chunk, a recurrent state
    where there is one."""
    import math

    import jax

    from ..models.family import family_of

    view = jax.ShapeDtypeStruct(
        (cfg.n_kv_layers, 1, cfg.cache_heads, padded_cache_len(cfg.seq_len),
         cfg.cache_width), kv_dtype)
    col = jax.eval_shape(
        lambda k, v: family_of(cfg).column(cfg, k, v), view,
        None if cfg.has_latent_cache else view)
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(col))


def estimate_block_pool_bytes(cfg, n_blocks: int, block_size: int,
                              kv_dtype_bytes: int) -> int:
    """Device bytes of a paged KV block pool
    ``[L, n_blocks, n_kv, block_size, hd]`` ×2 (K and V), or of the one
    latent pool ``[L, n_blocks, 1, block_size, latent_row]``."""
    return cfg.n_kv_layers * n_blocks * cfg.cache_row_elems * block_size \
        * kv_dtype_bytes


def fit_block_pool(cfg, n_blocks: int, *, block_size: int, min_blocks: int,
                   weight_repr: str, kv_dtype_bytes: int, n_shards: int = 1,
                   offload: bool = False,
                   state_bytes: int = 0,
                   column_bytes: int = 0) -> tuple[int, dict]:
    """Largest paged block-pool size ``<= n_blocks`` whose estimate fits
    the device limit — the paged twin of :func:`fit_batch_slots`: blocks
    are the admission currency, so the pool shrinks block-granularly
    instead of by whole max-context slots. The base charge keeps the
    engine's batch-1 cache (still resident beside the pool). Returns
    ``(n_fit, estimate)``; ``n_fit == 0`` when even ``min_blocks`` (one
    full sequence + the null block) doesn't fit. With the host KV tier
    on (``--kv-host-blocks``, :func:`fit_host_pool`), a degraded device
    pool costs capacity for LIVE context only — cold (cached) blocks
    spill to the host mirror under pressure and page back at resume, so
    the device size stops bounding how many idle sessions keep their
    KV. ``state_bytes`` is the recurrent state pool of a decoder that has one
    (kvblocks.state_pool_bytes): it does not shrink with the blocks, so it
    is charged whole, beside them. So are ``column_bytes``: the admission
    columns of every slot at the family's column shape
    (:func:`admission_column_bytes`; every slot can be mid-prefill at once,
    as a start-up burst is), so that a configuration whose columns do not
    fit is degraded or refused HERE and not by an allocation failing under
    load."""
    limit = (None if os.environ.get("DLLAMA_SKIP_HBM_CHECK")
             else device_memory_bytes())
    base = estimate_device_bytes(
        cfg, weight_repr=weight_repr, kv_dtype_bytes=kv_dtype_bytes,
        batch=1, n_shards=n_shards, offload=offload)

    def est_for(k: int) -> dict:
        pool = estimate_block_pool_bytes(cfg, k, block_size, kv_dtype_bytes)
        est = dict(base)
        est["kv_pool_bytes"] = pool
        est["state_pool_bytes"] = state_bytes
        est["admission_columns_bytes"] = column_bytes
        est["need_per_device"] = (
            base["need_per_device"]
            + int((pool / max(1, n_shards) + state_bytes
                   + column_bytes / max(1, n_shards)) * _MARGIN))
        return est

    n = max(min_blocks, n_blocks)
    est = est_for(n)
    if limit is None or est["need_per_device"] <= limit:
        return n, est
    est = est_for(min_blocks)
    if est["need_per_device"] > limit:  # even the floor doesn't fit
        return 0, est
    # the estimate is monotone in the block count: bisect for the exact
    # largest fitting size (lo always fits, hi never does)
    lo, hi = min_blocks, n
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if est_for(mid)["need_per_device"] <= limit:
            lo = mid
        else:
            hi = mid
    return lo, est_for(lo)


def host_memory_bytes() -> int | None:
    """Total host DRAM, or None when the platform won't say.
    ``DLLAMA_HOST_KV_BYTES`` overrides with an explicit KV-tier budget
    (testing + containers whose cgroup limit the sysconf number can't
    see)."""
    env = os.environ.get("DLLAMA_HOST_KV_BYTES")
    if env:
        return int(env)
    try:
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


# the host KV mirror may take at most this share of host DRAM when the
# budget comes from the sysconf total (weights streaming, the OS, and the
# runtime need the rest); an explicit DLLAMA_HOST_KV_BYTES is taken as-is
_HOST_KV_FRACTION = 0.5


def fit_host_pool(cfg, n_blocks: int, *, block_size: int,
                  kv_dtype_bytes: int) -> int:
    """Largest host-tier mirror pool ``<= n_blocks`` that fits the host
    DRAM budget — the host twin of :func:`fit_block_pool`: host-resident
    blocks are *reclaimable session capacity* (a spilled idle session's
    KV pages back in at resume instead of re-prefilling), so the tier is
    sized the same block-granular way the device pool is. Returns the
    fitted count (0 = tier off); host capacity unknown ⇒ the request is
    granted as-is (host allocation failures surface as ordinary
    MemoryErrors at mirror-store time, which degrade to drop-evict).

    Granularity: the mirror stores spilled blocks in
    ``kvblocks.SPILL_BATCH``-wide chunks, so grants ≥ one batch round
    DOWN to a batch multiple (dangling sub-batch lanes could never
    carry a full spill and would sit dead against the chunk-accounted
    RAM cap); a sub-batch grant is kept as-is — its mirror may hold at
    most ONE chunk, a bounded absolute overshoot the operator accepted
    by asking for a tier that small."""
    from .kvblocks import SPILL_BATCH

    n = max(0, n_blocks)
    if n == 0:
        return 0
    limit = host_memory_bytes()
    if limit is not None:
        if not os.environ.get("DLLAMA_HOST_KV_BYTES"):
            limit = int(limit * _HOST_KV_FRACTION)
        per_block = max(1, estimate_block_pool_bytes(cfg, 1, block_size,
                                                     kv_dtype_bytes))
        n = min(n, limit // per_block)
    if n >= SPILL_BATCH:
        n = (n // SPILL_BATCH) * SPILL_BATCH
    return n


def estimate_prefill_temp_bytes(cfg, tokens: int) -> int:
    """Coarse XLA-temporary estimate for a ``tokens``-wide prefill chunk
    the engine has NOT compiled yet: per-layer activations (residual
    stream, QKV, FFN hidden) plus the logits row block, all f32. Like the
    rest of this module it aims at catching the 2x misfits, not byte
    accounting — once the program compiles, the measured
    ``memory_analysis()`` bytes supersede it (admission_check)."""
    act = tokens * (3 * cfg.dim + 2 * cfg.hidden_dim + cfg.q_dim
                    + 2 * cfg.kv_dim)
    return int((act + tokens * cfg.vocab_size) * 4)


def admission_check(*, need_bytes: int, measured_bytes: dict[str, int],
                    extra_bytes: int, what: str) -> tuple[bool, str]:
    """The HBM admission guard's verdict for one would-be admission:
    ``need_bytes`` (the staging-time shape-algebra estimate) is
    cross-checked against the compile ledger's measured per-program bytes
    (the estimate can only be RAISED by evidence, never lowered), plus
    ``extra_bytes`` for programs the admission would compile fresh.
    Returns ``(ok, reason)``; always ok when the device limit is unknown
    or ``DLLAMA_SKIP_HBM_CHECK`` is set."""
    if os.environ.get("DLLAMA_SKIP_HBM_CHECK"):
        return True, ""
    limit = device_memory_bytes()
    if limit is None:
        return True, ""
    measured_peak = max(measured_bytes.values(), default=0)
    need = max(need_bytes, measured_peak) + extra_bytes
    if need <= limit:
        return True, ""
    gb = 1024 ** 3
    src = ("measured per-program bytes"
           if measured_peak > need_bytes else "estimate")
    return False, (
        f"HBM admission guard: {what} needs ~{need / gb:.2f} GB per device "
        f"({src}"
        + (f" + ~{extra_bytes / gb:.2f} GB for an uncompiled program"
           if extra_bytes else "")
        + f") but the device reports {limit / gb:.2f} GB — refusing the "
        f"admission instead of risking an XLA OOM that can wedge the "
        f"backend (shrink the prompt, lower --batch-slots/--max-seq-len, "
        f"or set DLLAMA_SKIP_HBM_CHECK=1)")


def check_budget(need_per_device: int, what: str) -> int | None:
    """Raise a clean, actionable error when the estimate exceeds the device
    limit. Returns the limit (None = unknown, check skipped). Bypass with
    DLLAMA_SKIP_HBM_CHECK=1."""
    if os.environ.get("DLLAMA_SKIP_HBM_CHECK"):
        return None
    limit = device_memory_bytes()
    if limit is not None and need_per_device > limit:
        gb = 1024 ** 3
        raise RuntimeError(
            f"{what} needs ~{need_per_device / gb:.1f} GB per device but the "
            f"device reports {limit / gb:.1f} GB — refusing to stage (an HBM "
            f"OOM can wedge the TPU backend for hours). Shard over more "
            f"devices (--tp/--pp), quantize (Q40), shrink --max-seq-len, use "
            f"--weight-mode offload, or set DLLAMA_SKIP_HBM_CHECK=1 to "
            f"override.")
    return limit

"""Paged KV cache + continuous batching (runtime/kvblocks.py, the paged
program family in models/llama.py, and PagedGenerator/BatchScheduler in
runtime/serving.py).

Three tiers:

1. **Allocator properties** — pure host bookkeeping, no jax: thousands of
   alloc/free/share/copy-on-write cycles asserting the refcount invariants
   (no double free, freed blocks reusable, shared blocks never a write
   target, cached LRU eviction unregisters).
2. **Gather parity** — ``paged_forward`` through a deliberately scrambled
   block table is bit-identical to the dense slot-pool ``forward`` on the
   same inputs: the block-table indirection must be value-invisible.
3. **Serving acceptance** — the ISSUE-6 criteria: a request stream larger
   than the slot capacity completes under continuous batching token-exact
   vs fresh solo oracles; chunked prefill interleaves with decode; a
   shared-prefix workload shows ``dllama_kv_blocks_shared > 0`` with
   block-level reuse >= the dense pool's longest-prefix accounting, and
   zero post-steady compiles (ledger-asserted).
"""

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.kvblocks import (BlockPool, BlockPoolExhausted,
                                         PagedKVCache, blocks_per_seq,
                                         validate_block_size)
from dllama_tpu.runtime.kvcache import padded_cache_len
from dllama_tpu.runtime.serving import BatchScheduler, PagedGenerator, Request

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


# ---------------------------------------------------------------------------
# 1. BlockPool allocator properties (pure host, no jax)
# ---------------------------------------------------------------------------


def test_alloc_refcount_free_roundtrip():
    pool = BlockPool(8, 16)
    assert pool.free_blocks() == 7  # block 0 is the null block
    a = pool.alloc()
    b = pool.alloc()
    assert a != b and a != pool.NULL and b != pool.NULL
    assert pool.refcount(a) == 1 and pool.refcount(b) == 1
    assert pool.used_blocks() == 2
    pool.release(a)
    assert pool.refcount(a) == 0
    assert pool.free_blocks() == 6  # unregistered: straight back to free
    assert pool.used_blocks() == 1


def test_double_free_raises():
    pool = BlockPool(4, 8)
    a = pool.alloc()
    pool.release(a)
    with pytest.raises(ValueError, match="double free"):
        pool.release(a)


def test_null_block_is_never_sharable_or_releasable():
    pool = BlockPool(4, 8)
    with pytest.raises(ValueError):
        pool.share(pool.NULL)
    with pytest.raises(ValueError):
        pool.release(pool.NULL)


def test_share_free_block_raises():
    pool = BlockPool(4, 8)
    a = pool.alloc()
    pool.release(a)  # unregistered -> free, not cached
    with pytest.raises(ValueError, match="not shareable"):
        pool.share(a)


def test_exhaustion_raises_then_recovers_after_release():
    pool = BlockPool(4, 8)
    got = [pool.alloc() for _ in range(3)]
    with pytest.raises(BlockPoolExhausted):
        pool.alloc()
    pool.release(got[1])
    again = pool.alloc()  # freed block is reusable
    assert again == got[1]
    assert pool.used_blocks() == 3


def test_shared_blocks_counts_refcount_above_one():
    pool = BlockPool(8, 4)
    bids = [pool.alloc(), pool.alloc()]
    pool.register_prompt(bids, list(range(8)))  # two full blocks
    assert pool.shared_blocks() == 0
    shared, n, cow, cow_r = pool.match_prefix(list(range(8)))
    assert shared == bids and n == 8 and cow is None and cow_r == 0
    for b in shared:
        pool.share(b)
    assert pool.shared_blocks() == 2
    for b in shared:
        pool.release(b)
    assert pool.shared_blocks() == 0


def test_released_registered_blocks_park_in_cache_and_still_match():
    pool = BlockPool(8, 4)
    bids = [pool.alloc()]
    pool.register_prompt(bids, list(range(4)))
    pool.release(bids[0])
    assert pool.refcount(bids[0]) == 0
    assert pool.free_blocks() == 7  # cached blocks stay allocatable
    shared, n, _, _ = pool.match_prefix(list(range(4)))
    assert shared == bids and n == 4  # retired prompt still shareable
    pool.share(bids[0])  # resurrect from the cache
    assert pool.refcount(bids[0]) == 1


def test_lru_eviction_recycles_cached_blocks_and_unregisters():
    pool = BlockPool(4, 4)  # 3 usable blocks
    # register three single-block prompts, release all -> all cached
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
    bids = []
    for p in prompts:
        b = pool.alloc()
        pool.register_prompt([b], p)
        pool.release(b)
        bids.append(b)
    assert pool.free_blocks() == 3
    # allocation pressure: the OLDEST cached block (prompts[0]) is evicted
    fresh = pool.alloc()
    assert fresh == bids[0]
    shared, n, cow, cow_r = pool.match_prefix(prompts[0])
    assert shared == [] and n == 0 and cow is None  # evicted = unregistered
    shared, n, _, _ = pool.match_prefix(prompts[1])
    assert shared == [bids[1]] and n == 4  # younger entries survive


def test_match_prefix_cow_tail():
    pool = BlockPool(8, 4)
    bids = [pool.alloc(), pool.alloc()]
    # one full block [1,2,3,4] + a partial tail [5,6]
    pool.register_prompt(bids, [1, 2, 3, 4, 5, 6])
    pool.release(bids[0])
    pool.release(bids[1])
    # a new prompt sharing the full block and 1 token of the tail
    shared, n, cow, cow_r = pool.match_prefix([1, 2, 3, 4, 5, 99, 100])
    assert shared == [bids[0]] and n == 4
    assert cow == bids[1] and cow_r == 1
    # divergence inside the first block: nothing shared, CoW from pos 0
    shared, n, cow, cow_r = pool.match_prefix([1, 2, 99, 100])
    assert shared == [] and n == 0
    assert cow == bids[0] and cow_r == 2


def test_register_prompt_skips_already_indexed_blocks():
    pool = BlockPool(8, 4)
    a = pool.alloc()
    pool.register_prompt([a], [1, 2, 3, 4])
    # a second sequence SHARING block `a` re-registers the same chain
    pool.share(a)
    b = pool.alloc()
    pool.register_prompt([a, b], [1, 2, 3, 4, 5, 6, 7, 8])
    shared, n, _, _ = pool.match_prefix([1, 2, 3, 4, 5, 6, 7, 8])
    assert shared == [a, b] and n == 8


def test_reset_clears_refcounts_and_prefix_index():
    pool = BlockPool(8, 4)
    a = pool.alloc()
    pool.register_prompt([a], [1, 2, 3, 4])
    pool.reset()
    assert pool.free_blocks() == 7 and pool.used_blocks() == 0
    shared, n, cow, _ = pool.match_prefix([1, 2, 3, 4])
    assert shared == [] and n == 0 and cow is None


def test_validate_block_size():
    validate_block_size(96, 16)
    validate_block_size(96, 128)  # padded_cache_len(96) == 128
    with pytest.raises(ValueError, match="power of two"):
        validate_block_size(96, 24)
    with pytest.raises(ValueError, match="power of two"):
        validate_block_size(96, 0)
    with pytest.raises(ValueError, match="tile the padded context"):
        validate_block_size(96, 256)
    assert blocks_per_seq(96, 16) == padded_cache_len(96) // 16


def test_randomized_refcount_invariants():
    """Thousands of random alloc/share/release/register cycles against a
    model of the refcount state: no double allocation, conservation of
    blocks, free/cached/live partitions stay disjoint."""
    rng = np.random.default_rng(0xB10C)
    pool = BlockPool(16, 4)
    live: dict[int, int] = {}  # bid -> model refcount
    registered: set[int] = set()
    next_tok = [1000]

    for step in range(4000):
        op = rng.integers(0, 4)
        if op == 0:  # alloc
            try:
                b = pool.alloc()
            except BlockPoolExhausted:
                assert sum(live.values()) > 0  # only when everything is live
                continue
            assert b != pool.NULL
            assert b not in live, "double allocation of a live block"
            live[b] = 1
            registered.discard(b)  # eviction/recycle forgets the index
        elif op == 1 and live:  # share a live block
            b = int(rng.choice(list(live)))
            pool.share(b)
            live[b] += 1
        elif op == 2 and live:  # release
            b = int(rng.choice(list(live)))
            pool.release(b)
            live[b] -= 1
            if not live[b]:
                del live[b]
        elif op == 3 and live:  # register a fresh 1-block prompt
            b = int(rng.choice(list(live)))
            if b not in registered and pool.refcount(b) == 1:
                toks = [next_tok[0] + i for i in range(4)]
                next_tok[0] += 4
                pool.register_prompt([b], toks)
                registered.add(b)
        # invariants
        for b, r in live.items():
            assert pool.refcount(b) == r
        assert pool.used_blocks() == len(live)
        assert pool.free_blocks() == pool.n_blocks - 1 - len(live)
        assert pool.shared_blocks() == sum(1 for r in live.values() if r > 1)
    # drain: everything releasable exactly its refcount times, no more
    for b, r in list(live.items()):
        for _ in range(r):
            pool.release(b)
        with pytest.raises(ValueError):
            pool.release(b)
    assert pool.used_blocks() == 0 and pool.free_blocks() == pool.n_blocks - 1


# ---------------------------------------------------------------------------
# 1b. Tiered allocator properties (host spill tier; still pure host, no jax —
#     a stub spill_fn stands in for the device copies)
# ---------------------------------------------------------------------------


def _tiered_pool(n_blocks=4, bs=4, n_host=8):
    pool = BlockPool(n_blocks, bs, n_host_blocks=n_host)
    pool.spill_fn = lambda devs, hosts: True
    return pool


def _fill_cached(pool, n, bs=4, base=100):
    """Register n single-block prompts and retire them -> n cached."""
    bids = []
    for i in range(n):
        b = pool.alloc()
        pool.register_prompt([b], [base + bs * i + j for j in range(bs)])
        pool.release(b)
        bids.append(b)
    return bids


def test_spill_moves_cold_blocks_to_host_instead_of_dropping():
    pool = _tiered_pool()
    bids = _fill_cached(pool, 3)
    fresh = pool.alloc()  # pressure: free list dry, cached spill to host
    assert fresh in bids  # the device ids recycled
    assert pool.host_used_blocks() == 3
    # ALL three prompts still match — under host ids now
    for i in range(3):
        sh, n, _, _ = pool.match_prefix([100 + 4 * i + j for j in range(4)])
        assert n == 4 and len(sh) == 1 and pool.is_host(sh[0]), i


def test_pagein_restores_exact_trie_chain():
    """Page-back restores the exact chain: a two-block chain spilled and
    paged back matches the same prompt block-for-block, and the partial
    CoW tail candidacy survives the round trip too."""
    pool = BlockPool(4, 4, n_host_blocks=8)
    pool.spill_fn = lambda devs, hosts: True
    a, b = pool.alloc(), pool.alloc()
    toks = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full blocks + tail [9,10]
    c = pool.alloc()
    pool.register_prompt([a, b, c], toks)
    for x in (a, b, c):
        pool.release(x)
    taken = [pool.alloc() for _ in range(3)]  # spills the whole chain
    assert pool.host_used_blocks() == 3
    sh, n, cow, cow_r = pool.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9, 99])
    assert n == 8 and len(sh) == 2 and all(pool.is_host(x) for x in sh)
    assert cow is not None and pool.is_host(cow) and cow_r == 1
    for x in taken:
        pool.release(x)
    pairs = pool.begin_pagein(sh + [cow])
    pool.commit_pagein(pairs)
    sh2, n2, cow2, cow_r2 = pool.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9,
                                               99])
    assert n2 == 8 and cow_r2 == 1
    assert [pool.is_host(x) for x in sh2] == [False, False]
    assert not pool.is_host(cow2)
    assert sh2 == [dev for _, dev in pairs[:2]] and cow2 == pairs[2][1]
    # the caller owns rc 1 on each paged-in block (share()-equivalent)
    for _, dev in pairs:
        assert pool.refcount(dev) == 1


def test_spilled_then_paged_in_blocks_stay_refcount_correct():
    """A spilled block paged back in and shared by several sequences
    keeps exact refcounts through the whole cycle (the 'spilled shared
    blocks stay refcount-correct' invariant)."""
    pool = _tiered_pool()
    _fill_cached(pool, 3)
    fresh = pool.alloc()  # spill everything cached
    pool.release(fresh)
    sh, _, _, _ = pool.match_prefix([100, 101, 102, 103])
    pairs = pool.begin_pagein(sh)
    pool.commit_pagein(pairs)
    dev = pairs[0][1]
    assert pool.refcount(dev) == 1
    pool.share(dev)
    pool.share(dev)
    assert pool.refcount(dev) == 3 and pool.shared_blocks() == 1
    for _ in range(3):
        pool.release(dev)
    assert pool.refcount(dev) == 0
    # back in the device cached LRU, still matchable
    sh2, n2, _, _ = pool.match_prefix([100, 101, 102, 103])
    assert sh2 == [dev] and n2 == 4
    with pytest.raises(ValueError, match="double free"):
        pool.release(dev)


def test_host_blocks_never_sharable_or_releasable_directly():
    pool = _tiered_pool()
    _fill_cached(pool, 3)
    pool.alloc()  # spill
    sh, _, _, _ = pool.match_prefix([100, 101, 102, 103])
    hb = sh[0]
    assert pool.is_host(hb)
    with pytest.raises(ValueError, match="host-resident"):
        pool.share(hb)
    with pytest.raises(ValueError, match="host-resident"):
        pool.release(hb)


def test_spill_failure_degrades_to_drop_evict():
    """spill_fn returning False (or raising) falls back to the pre-tier
    contract: the LRU cached block is dropped and unregistered, nothing
    crashes, nothing leaks to the host tier."""
    for mode in ("false", "raise"):
        pool = BlockPool(4, 4, n_host_blocks=8)
        if mode == "false":
            pool.spill_fn = lambda d, h: False
        else:
            def _boom(d, h):
                raise RuntimeError("injected")
            pool.spill_fn = _boom
        bids = _fill_cached(pool, 3)
        fresh = pool.alloc()
        assert fresh == bids[0]  # LRU dropped, recycled
        assert pool.host_used_blocks() == 0
        sh, n, _, _ = pool.match_prefix([100, 101, 102, 103])
        assert n == 0  # dropped = unregistered, exactly the old behavior


def test_host_lru_eviction_drops_for_real_and_notifies():
    """When the host tier itself fills, ITS LRU drops for good (and the
    mirror hook is told which lanes died)."""
    pool = BlockPool(4, 4, n_host_blocks=2)
    pool.spill_fn = lambda d, h: True
    dropped = []
    pool.host_drop_fn = dropped.extend
    _fill_cached(pool, 3)
    pool.alloc()  # spill: only 2 host lanes -> 2 spill, 1 drop-evicted
    assert pool.host_used_blocks() == 2
    first = [b for b in list(pool._host_cached)]
    _fill_cached(pool, 2, base=500)
    pool.alloc()  # second spill wave: host full -> oldest host blocks drop
    assert dropped and all(pool.is_host(b) for b in dropped)
    assert dropped[0] == first[0]
    sh, n, _, _ = pool.match_prefix([100, 101, 102, 103])
    assert n == 0  # the host-dropped chain is gone for good


def test_spill_room_precheck_never_destroys_content_for_refused_spill():
    """Review regression: when the mirror's chunk budget has no room and
    the host LRU has nothing to drain, the spill must refuse WITHOUT
    evicting host content first — destroying idle sessions' KV for a
    spill that never happens is the exact anti-contract."""
    pool = BlockPool(4, 4, n_host_blocks=8)
    pool.spill_fn = lambda d, h: True
    pool.host_room_fn = lambda: False  # budget full, nothing drainable
    dropped = []
    pool.host_drop_fn = dropped.extend
    _fill_cached(pool, 3)
    fresh = pool.alloc()  # pressure: spill refused -> drop-evict
    assert fresh is not None
    assert pool.host_used_blocks() == 0 and not dropped
    sh, n, _, _ = pool.match_prefix([100, 101, 102, 103])
    assert n == 0  # device LRU dropped: the pre-tier contract, no worse


def test_spill_room_precheck_drains_host_lru_until_chunk_frees():
    """The budget-full-on-fragmented-chunks wedge: evicting the host LRU
    oldest-first frees a chunk (the drop hook fires per victim so the
    mirror can notice the moment its last lane dies), after which the
    spill PROCEEDS — the tier keeps cycling instead of refusing
    forever."""
    pool = BlockPool(4, 4, n_host_blocks=8)
    pool.spill_fn = lambda d, h: True
    chunk_lanes: set = set()  # the fake mirror's one resident chunk

    def drop(victims):
        chunk_lanes.difference_update(victims)
    pool.host_drop_fn = drop
    pool.host_room_fn = lambda: not chunk_lanes
    bids_a = _fill_cached(pool, 3, base=100)
    pool.alloc()  # first wave: room ok -> spills the 3 cached blocks
    assert pool.host_used_blocks() == 3
    chunk_lanes.update(b for b in pool._host_cached)  # chunk now "live"
    _fill_cached(pool, 2, base=500)
    pool.alloc()  # second wave: budget full -> drain host LRU, chunk
    #               frees, THEN the new cold blocks spill
    assert pool.host_used_blocks() == 2
    assert not any(pool.is_host(b) and b in pool._meta
                   for b in list(chunk_lanes))
    sh, n, _, _ = pool.match_prefix([500, 501, 502, 503])
    assert n == 4 and pool.is_host(sh[0])  # the NEW content made it out
    sh, n, _, _ = pool.match_prefix([100, 101, 102, 103])
    assert n == 0  # the stale chunk's content paid for it, oldest-first


def test_begin_pagein_exhaustion_rolls_back_atomically():
    pool = _tiered_pool()
    _fill_cached(pool, 3)
    pool.alloc()  # spill all three
    # occupy the remaining device blocks
    pool.alloc()
    pool.alloc()
    sh, _, _, _ = pool.match_prefix([100, 101, 102, 103])
    sh2, _, _, _ = pool.match_prefix([104, 105, 106, 107])
    with pytest.raises(BlockPoolExhausted):
        pool.begin_pagein(sh + sh2)
    # both host blocks still pinned-in-cache, still matchable
    for i in range(2):
        shx, n, _, _ = pool.match_prefix([100 + 4 * i + j for j in range(4)])
        assert n == 4 and pool.is_host(shx[0])
    assert pool.used_blocks() == 3  # no leaked device refcount


def test_randomized_tiered_invariants():
    """The randomized suite, tiered: random alloc/share/release/register
    cycles with a bookkeeping-only spill_fn and random page-ins, against
    a model of both tiers. Invariants: no logical block is ever device-
    AND host-live, refcounts exact, the free/cached/live/host partitions
    stay disjoint and conserve blocks, and every registered prompt keeps
    matching (from whichever tier) until genuinely dropped."""
    rng = np.random.default_rng(0x71E2)
    pool = BlockPool(10, 4, n_host_blocks=6)
    pool.spill_fn = lambda devs, hosts: True
    dropped_host: list[int] = []
    pool.host_drop_fn = dropped_host.extend
    live: dict[int, int] = {}
    next_tok = [1000]
    prompts: dict[int, list[int]] = {}  # bid -> registered tokens (model)

    for step in range(6000):
        op = rng.integers(0, 5)
        if op == 0:  # alloc (may spill)
            try:
                b = pool.alloc()
            except BlockPoolExhausted:
                assert sum(live.values()) > 0
                continue
            assert not pool.is_host(b)
            assert b not in live
            live[b] = 1
        elif op == 1 and live:  # share
            b = int(rng.choice(list(live)))
            pool.share(b)
            live[b] += 1
        elif op == 2 and live:  # release
            b = int(rng.choice(list(live)))
            pool.release(b)
            live[b] -= 1
            if not live[b]:
                del live[b]
        elif op == 3 and live:  # register a fresh 1-block prompt
            b = int(rng.choice(list(live)))
            if b not in pool._meta and pool.refcount(b) == 1:
                toks = [next_tok[0] + i for i in range(4)]
                next_tok[0] += 4
                pool.register_prompt([b], toks)
                prompts[b] = toks
        elif op == 4:  # page a random host-resident block back in
            host_live = [b for b in prompts if pool.is_host(b)]
            if not host_live:
                continue
            hb = int(rng.choice(host_live))
            toks = prompts[hb]
            try:
                pairs = pool.begin_pagein([hb])
            except BlockPoolExhausted:
                continue
            pool.commit_pagein(pairs)
            dev = pairs[0][1]
            prompts[dev] = prompts.pop(hb)
            live[dev] = 1
            sh, n, _, _ = pool.match_prefix(toks)
            assert sh == [dev] and n == 4

        # model sync (white-box): a spill REBINDS a registration to a
        # host id (same tokens, new key) and a drop removes it — rebuild
        # the id->tokens view from the pool's own meta so the match
        # invariant below checks every surviving registration, wherever
        # it lives now
        prompts = {bid: list(meta[2])
                   for bid, meta in pool._meta.items() if meta[0] == "full"}
        # invariants ------------------------------------------------------
        for b, r in live.items():
            assert pool.refcount(b) == r and not pool.is_host(b)
        assert pool.used_blocks() == len(live)
        n_dev_cached = len(pool._cached)
        assert pool.free_blocks() == len(pool._free) + n_dev_cached
        assert pool.used_blocks() + pool.free_blocks() == pool.n_blocks - 1
        # host partition: used lanes = cached host entries; disjoint ids
        assert pool.host_used_blocks() == len(pool._host_cached)
        assert all(pool.is_host(b) for b in pool._host_cached)
        dev_ids = set(pool._free) | set(pool._cached) | set(live)
        assert not (dev_ids & set(pool._host_cached))
        # NO logical block in both tiers: every registered bid is either
        # a device id or a host id, and each meta key appears once
        for bid in pool._meta:
            assert (bid in pool._host_cached) == pool.is_host(bid)
        # every surviving registered prompt still matches from its tier
        for bid, toks in prompts.items():
            sh, n, _, _ = pool.match_prefix(toks)
            assert n == 4 and sh == [bid], (bid, sh, n)

    # drain
    for b, r in list(live.items()):
        for _ in range(r):
            pool.release(b)
    assert pool.used_blocks() == 0


# ---------------------------------------------------------------------------
# 2. Gather parity: paged_forward ≡ dense forward through a scrambled table
# ---------------------------------------------------------------------------


def test_paged_forward_matches_dense_forward_bitwise():
    """The block-table indirection is value-invisible: a prefill-width
    ``paged_forward`` through a deliberately out-of-order block table
    produces bit-identical logits to the dense ``forward``, and the rows it
    scatters into the pool equal the dense cache's rows."""
    import jax.numpy as jnp

    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig
    from dllama_tpu.models.llama import forward, init_random_params, paged_forward
    from dllama_tpu.runtime.kvcache import KVCache

    cfg = ModelConfig(arch=ArchType.LLAMA, dim=32, hidden_dim=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, head_dim=8, vocab_size=64,
                      seq_len=64, norm_epsilon=1e-5, rope_theta=10000.0,
                      rope_type=RopeType.LLAMA)
    params = init_random_params(cfg, seed=7)
    T = 24
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab_size, (1, T)),
        jnp.int32)

    logits_d, kv = forward(params, cfg, tokens, jnp.int32(0),
                           KVCache.create(cfg))

    bs = 16
    M = blocks_per_seq(cfg.seq_len, bs)
    # scrambled physical placement: logical block j -> physical block
    # (descending from the top of the pool), so any row-order dependence
    # in the gather/scatter would break parity
    n_blocks = 2 * M + 1
    table = np.zeros((1, M), dtype=np.int32)
    table[0, :] = np.arange(n_blocks - 1, n_blocks - 1 - M, -1)
    pkv = PagedKVCache.create(cfg, n_blocks, bs)
    logits_p, pkv = paged_forward(params, cfg, tokens,
                                  jnp.asarray([0], jnp.int32), pkv,
                                  jnp.asarray(table))
    np.testing.assert_array_equal(np.asarray(logits_d), np.asarray(logits_p))

    # the scattered rows, gathered back through the table, equal the dense
    # cache rows the slot-pool forward produced
    k_p = np.asarray(pkv.k)[:, table[0]]       # [L, M, n_kv, bs, hd]
    k_p = np.moveaxis(k_p, 2, 1).reshape(cfg.n_layers, cfg.n_kv_heads,
                                         M * bs, cfg.head_dim)
    k_d = np.asarray(kv.k)[:, 0]               # [L, n_kv, S, hd]
    np.testing.assert_array_equal(k_p[:, :, :T], k_d[:, :, :T])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["distinct", "one-null-row", "null-rows",
                                  "all-null"])
def test_rowwise_kv_writes_equal_the_scatter_cell_for_cell(case, dtype):
    """The decode step (T == 1) writes its new K/V rows one
    ``dynamic_update_slice`` a row; wider dispatches scatter. Same cells,
    same bytes, every other cell untouched — rows parked on the null block
    included (several of them at one offset: the last row wins either way,
    on a pool nobody reads through)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import _write_kv_rows

    rng = np.random.default_rng(11)
    n_layers, l, n_blocks, n_kv, bs, hd, B = 3, 1, 9, 2, 16, 8, 5
    pool = jnp.asarray(rng.standard_normal((n_layers, n_blocks, n_kv, bs, hd)),
                       dtype)
    new = jnp.asarray(rng.standard_normal((B, 1, n_kv, hd)), jnp.float32)
    blk = np.asarray([[3], [7], [1], [8], [5]], np.int32)
    off = np.asarray([[0], [15], [4], [4], [9]], np.int32)
    if case == "one-null-row":
        blk[2, 0], off[2, 0] = 0, 0
    elif case == "null-rows":        # inactive rows: null block, offset 0
        blk[1:4, 0], off[1:4, 0] = 0, 0
    elif case == "all-null":
        blk[:], off[:] = 0, 0
    got = jax.jit(_write_kv_rows)(pool, jnp.int32(l), new, jnp.asarray(blk),
                                  jnp.asarray(off))
    want = pool.at[l, jnp.asarray(blk), :, jnp.asarray(off), :].set(
        new.astype(pool.dtype))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # and against plain numpy, row after row: layer l's cells, no other's
    ref = np.asarray(pool, np.float32).copy()
    for b in range(B):
        ref[l, blk[b, 0], :, off[b, 0], :] = np.asarray(
            new.astype(pool.dtype), np.float32)[b, 0]
    np.testing.assert_array_equal(np.asarray(got, np.float32), ref)


def test_wide_kv_writes_keep_the_scatter():
    """T > 1 (the verify step, write_lens): the scatter, as before."""
    import jax.numpy as jnp

    from dllama_tpu.models.llama import _write_kv_rows

    rng = np.random.default_rng(12)
    pool = jnp.zeros((2, 6, 2, 16, 8), jnp.float32)
    new = jnp.asarray(rng.standard_normal((2, 3, 2, 8)), jnp.float32)
    blk = jnp.asarray([[1, 1, 2], [4, 0, 0]], jnp.int32)
    off = jnp.asarray([[14, 15, 0], [3, 4, 5]], jnp.int32)
    got = _write_kv_rows(pool, jnp.int32(1), new, blk, off)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(pool.at[1, blk, :, off, :].set(new)))
    assert not np.asarray(got)[0].any()


def _three_layer_cfg():
    """A three-layer toy Llama for the program-level cases below."""
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig

    return ModelConfig(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=3,
                       n_heads=8, n_kv_heads=2, head_dim=8, vocab_size=128,
                       seq_len=64, norm_epsilon=1e-5, rope_theta=10000.0,
                       rope_type=RopeType.LLAMA)


def _written_cells(shape, tables, pos, lanes):
    """The cells ``[L, n_blocks, bs]`` a dispatch writes: in every layer,
    for live row ``b`` and lane ``t < lanes[b]``, position ``pos[b] + t``
    of the row's table (rows parked on the null block write block 0)."""
    L, _, _, bs, _ = shape
    want = np.zeros((L, shape[1], bs), bool)
    for b, n in enumerate(lanes):
        for t in range(n):
            want[:, tables[b, (pos[b] + t) // bs], (pos[b] + t) % bs] = True
    return want


@pytest.mark.parametrize("width", ["step", "verify"])
def test_a_dispatch_changes_only_the_cells_it_writes(width):
    """The pool rides the layer scan's carry and is written in place: after
    a decode step (row-wise writes) or a verify dispatch (the scatter,
    ``write_lens``) every layer's pool differs from what went in at the
    rows' own cells and nowhere else, the null block (where inactive rows
    and lanes past a draft land) aside."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import init_random_params, paged_forward

    cfg = _three_layer_cfg()
    params = init_random_params(cfg, seed=7)
    rng = np.random.default_rng(5)
    B, M, bs = 4, 4, 16
    T = 1 if width == "step" else 4
    tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32)
    tables[1] = 0                                   # an inactive row
    pos = np.asarray([5, 40, 30, 0], np.int32)      # row 2 crosses a block edge
    lens = None if T == 1 else np.asarray([3, 0, 2, 1], np.int32)
    lanes = [1, 0, 1, 1] if T == 1 else [4, 0, 3, 2]
    shape = (cfg.n_layers, 1 + B * M, cfg.n_kv_heads, bs, cfg.head_dim)
    pkv = PagedKVCache(k=jnp.asarray(rng.standard_normal(shape), jnp.float32),
                       v=jnp.asarray(rng.standard_normal(shape), jnp.float32))
    toks = jnp.asarray(rng.integers(1, 127, (B, T)).astype(np.int32))
    # a lambda of its own: jits of one function object share an executable
    # cache, and tests/test_paged_attention.py counts paged_forward's entries
    _, out = jax.jit(lambda *a: paged_forward(a[0], cfg, *a[1:]))(
        params, toks, jnp.asarray(pos), pkv, jnp.asarray(tables),
        None if lens is None else jnp.asarray(lens))
    want = _written_cells(shape, tables, pos, lanes)
    for got, was in ((out.k, pkv.k), (out.v, pkv.v)):
        changed = (np.asarray(got) != np.asarray(was)).any(axis=(2, 4))
        np.testing.assert_array_equal(changed[:, 1:], want[:, 1:])


def test_the_compiled_step_holds_no_second_pool():
    """Structure, not time: with the pool donated (as the server's wrapper
    jits it) the compiled decode step's temporaries stay far under ONE
    pool's bytes at a geometry where the pool (2 x 12.6 MB) dwarfs the
    rest. As the scan's stacked output the pool was a temporary of both."""
    import jax.numpy as jnp

    from dllama_tpu.models.llama import init_random_params
    from helpers import compile_paged_step

    cfg = _three_layer_cfg()
    params = init_random_params(cfg, seed=7, quantized=True)
    compiled, pool = compile_paged_step(cfg, params, n_slots=4, n_blocks=4096,
                                        block_size=16, table_width=4,
                                        pool_dtype=jnp.float32)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert pool == 3 * 4096 * 2 * 16 * 8 * 4
    assert temp < pool // 2, (temp, pool)


@pytest.mark.parametrize("mode,path", [("fused", "fused"), ("pallas", "tiled")])
def test_paged_step_tokens_equal_the_xla_modes(monkeypatch, mode, path):
    """The paged decode step over Q40 planes, scanned by layer index with
    the stack closed over: under the fused kernel (stack + index entry,
    interpret mode here) and under the tiled one (plain slices) it emits the
    tokens the XLA mode emits, writes the same pool, and the program's
    Q40 matmuls are all noted on the path the mode names."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import init_random_params, paged_forward

    cfg = _three_layer_cfg()
    params = init_random_params(cfg, seed=7, quantized=True)
    rng = np.random.default_rng(3)
    B, M = 4, 4
    tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M)
    tables[3] = 0                                   # an inactive row
    tables = jnp.asarray(tables.astype(np.int32))
    pos0 = np.asarray([5, 0, 33, 0], np.int32)
    toks0 = jnp.asarray(rng.integers(1, 127, (B, 1)).astype(np.int32))

    def run(kernel_mode):
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel_mode)
        scope = f"paged-step-{mode}-{kernel_mode}"
        step = introspection.observe(
            jax.jit(lambda p, c, t, s, kv, tb: paged_forward(p, c, t, s, kv, tb),
                    static_argnums=1), scope=scope, program="step")
        pkv = PagedKVCache.create(cfg, n_blocks=1 + B * M, block_size=16)
        toks, out = toks0, []
        for i in range(4):
            logits, pkv = step(params, cfg, toks, jnp.asarray(pos0 + i), pkv,
                               tables)
            toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out.append(np.asarray(logits))
        return out, pkv, introspection.ledger().q40_paths(scope)["step"]

    want, pkv_x, paths_x = run("xla")
    got, pkv_k, paths_k = run(mode)
    n = 7 * 1 + 1      # the scanned body's seven planes, and the Q40 head
    assert paths_x == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": n}
    assert paths_k == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 0, path: n}
    # the inactive row is nobody's to read: the paged kernel writes zeros for
    # a row whose table starts with the null block (PR 31), the oracle attends
    # over whatever the null block holds; both stay finite
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a[:3].argmax(-1), b[:3].argmax(-1))
        np.testing.assert_allclose(a[:3], b[:3], rtol=1e-5, atol=1e-6)
        assert np.all(np.isfinite(b))
    # block 0 is the null block: the inactive row's ride-along writes land
    # there, and follow its (unread) hidden state
    np.testing.assert_allclose(np.asarray(pkv_x.k)[:, 1:], np.asarray(pkv_k.k)[:, 1:],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(pkv_x.v)[:, 1:], np.asarray(pkv_k.v)[:, 1:],
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# 3. Serving acceptance (PagedGenerator / BatchScheduler)
# ---------------------------------------------------------------------------

PATHS = {}


@pytest.fixture(scope="module")
def paged_engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("kvblocks")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    PATHS["m"], PATHS["t"] = str(mpath), str(tpath)
    return InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=16)


def solo(temperature=0.0, seed=7, **kw):
    """Fresh single-sequence engine on the same files — the oracle."""
    return InferenceEngine(PATHS["m"], PATHS["t"], tp=1,
                           temperature=temperature, seed=seed, **kw)


def _enc(engine, text):
    return engine.tokenizer.encode(text, is_start=True)


def test_engine_validates_block_size_and_combos(tmp_path_factory):
    d = tmp_path_factory.mktemp("kvblocks_val")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(1))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    with pytest.raises(ValueError, match="power of two"):
        InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=24)
    with pytest.raises(ValueError, match="tile the padded context"):
        InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=512)
    # spec composes with paged KV now (ISSUE 14) — only a verify width
    # past the decode regime refuses (spec_lookup + 1 > 16)
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=16,
                          spec_lookup=3)
    eng.close()
    with pytest.raises(ValueError, match="--spec-lookup > 15"):
        InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=16,
                        spec_lookup=16)
    with pytest.raises(ValueError, match="--decode-chunk"):
        InferenceEngine(str(mpath), str(tpath), tp=1, kv_block_size=16,
                        decode_chunk=4)
    with pytest.raises(ValueError, match="--dp"):
        InferenceEngine(str(mpath), str(tpath), tp=1, dp=2, kv_block_size=16)


def test_continuous_stream_exceeds_slot_capacity_token_exact(paged_engine):
    """THE tentpole acceptance: a stream of 6 mixed requests through 2
    slots completes under continuous batching — sequences admit and retire
    mid-batch — and every transcript equals a fresh solo run."""
    prompts = ["hello world", "hello there", "abc",
               "hello world how are you", "xyzzy", "hello hello hello"]
    specs = [dict(temperature=0.0, seed=1), dict(temperature=0.8, seed=2),
             dict(temperature=0.0, seed=3), dict(temperature=1.2, seed=4),
             dict(temperature=0.0, seed=5), dict(temperature=0.6, seed=6)]
    want = []
    for p, s in zip(prompts, specs):
        e = solo(**s)
        want.append(e.generate(p, 8, stop_on_eos=False).tokens)
        e.close()

    admissions = tm.registry().counter(tm.ADMISSIONS)
    retires = tm.registry().counter(tm.RETIRES)
    a0, r0 = admissions.total(), retires.total()
    sched = BatchScheduler(paged_engine, n_slots=2)
    assert isinstance(sched.gen, PagedGenerator)
    try:
        reqs = [sched.submit(_enc(paged_engine, p), 8, stop_on_eos=False,
                             temperature=s["temperature"], seed=s["seed"])
                for p, s in zip(prompts, specs)]
        for r in reqs:
            assert r.done.wait(timeout=300)
            assert r.error is None, r.error
        for r, w, p in zip(reqs, want, prompts):
            assert r.tokens == w, p
    finally:
        sched.close()
    assert admissions.total() - a0 == len(prompts)
    assert retires.total() - r0 >= len(prompts)


def test_block_sharing_live_and_cow_write_isolation(paged_engine):
    """Block-level prefix sharing: a second live sequence with a >= 1-block
    common prefix SHARES physical blocks (``dllama_kv_blocks_shared`` > 0
    while both run; reuse counted at block granularity), the shared bytes
    are never rewritten, and both transcripts stay solo-exact."""
    # 26 distinct chars -> BOS + 26 ids; rest = 26 >= one full 16-block
    base = "abcdefghijklmnopqrstuvwxy "
    e1 = solo()
    want_a = e1.generate(base + "111", 6, stop_on_eos=False).tokens
    e1.close()
    e2 = solo()
    want_b = e2.generate(base + "222", 6, stop_on_eos=False).tokens
    e2.close()

    gen = PagedGenerator(paged_engine, n_slots=2)
    reuse = tm.registry().counter(tm.PREFIX_REUSE_TOKENS)
    shared_gauge = tm.registry().gauge(tm.KV_BLOCKS_SHARED)

    r_a = Request(rid=0, prompt_ids=_enc(paged_engine, base + "111"),
                  max_tokens=6, stop_on_eos=False)
    gen.admit(r_a, 0)
    gen.step()  # r_a live and decoding; its prompt blocks are registered

    ids_b = _enc(paged_engine, base + "222")
    n_common = 0
    for x, y in zip(ids_b[:-1], r_a.prompt_ids[:-1]):
        if x != y:
            break
        n_common += 1
    assert n_common >= gen.block_size, "workload must share a full block"

    c0 = reuse.total()
    shared_before = gen.pool.shared_blocks()
    r_b = Request(rid=1, prompt_ids=ids_b, max_tokens=6, stop_on_eos=False)
    gen.admit(r_b, 1)

    # both sequences live: physical sharing is visible in pool + telemetry
    assert gen.pool.shared_blocks() > shared_before
    assert shared_gauge.value() > 0
    # block-level reuse >= the dense pool's longest-prefix token accounting
    # (full shared blocks + the copy-on-write tail cover the whole prefix)
    assert reuse.total() - c0 >= n_common

    # copy-on-write safety: the shared block's device bytes never change
    shared_bids = [b for b in gen._seq_bids[1] if gen.pool.refcount(b) > 1]
    assert shared_bids
    before = np.asarray(gen.pkv.k[:, shared_bids[0]]).copy()
    while gen.n_active:
        gen.step()
    np.testing.assert_array_equal(
        before, np.asarray(gen.pkv.k[:, shared_bids[0]]))

    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_paged_prefill_interleaves_with_decode(tmp_path_factory):
    """Chunked prefill interleaves with decode on the paged pool: an active
    slot keeps emitting between a newcomer's prefill chunks, and both
    match their solo runs."""
    d = tmp_path_factory.mktemp("kvblocks_inc")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4,
                          kv_block_size=16)
    long_ids = [int(x) for x in np.random.default_rng(3).integers(1, 200, 40)]

    solo_a = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4)
    want_a = solo_a.generate("hello world", 16, stop_on_eos=False).tokens
    solo_a.close()
    solo_b = InferenceEngine(str(mpath), str(tpath), tp=1, n_batches=4)
    want_b = solo_b.generate(long_ids, 4, stop_on_eos=False).tokens
    solo_b.close()

    gen = PagedGenerator(eng, n_slots=2)
    r_a = Request(rid=0, prompt_ids=_enc(eng, "hello world"),
                  max_tokens=16, stop_on_eos=False)
    gen.admit(r_a, 0)
    gen.step()
    a_before = len(r_a.tokens)

    r_b = Request(rid=1, prompt_ids=long_ids, max_tokens=4,
                  stop_on_eos=False)
    adm = gen.begin_admit(r_b, 1)
    interleaved = 0
    while not gen.continue_admit(adm):
        gen.step()  # active slot decodes between the newcomer's chunks
        interleaved += 1
    assert interleaved >= 5  # 39 prompt tokens / 4-token chunks
    assert len(r_a.tokens) > a_before
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_shared_prefix_workload_is_ledger_quiet_post_steady(paged_engine):
    """Zero post-steady compiles across a CoW + sharing + admit/retire
    wave: the paged program family is jitted once per pool geometry, so
    block-table contents, occupancy, and sharing must never retrace."""
    sched = BatchScheduler(paged_engine, n_slots=2)
    scope = paged_engine.introspection_scope
    try:
        # steady-state warmup: the full program family (prefill buckets,
        # paged step, CoW copy) compiles here
        warm = [sched.submit(_enc(paged_engine, p), 4, stop_on_eos=False)
                for p in ["abcdefghijklmnopqrstuvwxy 0",
                          "abcdefghijklmnopqrstuvwxy 1", "hello"]]
        for r in warm:
            assert r.done.wait(timeout=300) and r.error is None
        c0 = introspection.ledger().compile_count(scope)
        wave = [sched.submit(_enc(paged_engine, p), 4, stop_on_eos=False)
                for p in ["abcdefghijklmnopqrstuvwxy 2",
                          "abcdefghijklmnopqrstuvwxy 3",
                          "abcdefghijklmnopqrstuvwxy 4", "hello there"]]
        for r in wave:
            assert r.done.wait(timeout=300) and r.error is None
        assert introspection.ledger().compile_count(scope) == c0, \
            "post-steady recompile on the paged path"
    finally:
        sched.close()


def test_paged_under_tp_matches_solo(tmp_path_factory):
    """The paged pool composes with tensor parallelism: kv-heads shard over
    tp (parallel/sharding.paged_kv_sharding), transcripts equal solo tp
    runs."""
    d = tmp_path_factory.mktemp("kvblocks_tp")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(41)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())

    s1 = InferenceEngine(str(mpath), str(tpath), tp=2)
    want_a = s1.generate("hello world", 8, stop_on_eos=False).tokens
    s1.close()
    s2 = InferenceEngine(str(mpath), str(tpath), tp=2, temperature=0.8,
                         seed=6)
    want_b = s2.generate("hello", 8, stop_on_eos=False).tokens
    s2.close()

    eng = InferenceEngine(str(mpath), str(tpath), tp=2, kv_block_size=16)
    gen = PagedGenerator(eng, n_slots=2)
    r_a = Request(rid=0, prompt_ids=_enc(eng, "hello world"), max_tokens=8,
                  stop_on_eos=False)
    r_b = Request(rid=1, prompt_ids=_enc(eng, "hello"), max_tokens=8,
                  stop_on_eos=False, temperature=0.8, seed=6)
    gen.admit(r_a, 0)
    gen.admit(r_b, 1)
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_mid_decode_block_growth_is_lazy(paged_engine):
    """A sequence only holds the blocks its live context spans: decoding
    across a block boundary allocates exactly one more block, at the
    boundary — the continuous-batching memory win."""
    gen = PagedGenerator(paged_engine, n_slots=2)
    # prompt of 10 ids -> rest 9 -> 1 block; decode grows past row 16
    r = Request(rid=0, prompt_ids=_enc(paged_engine, "hello w"),
                max_tokens=24, stop_on_eos=False)
    gen.admit(r, 0)
    assert len(gen._seq_bids[0]) == 1
    grew_at = None
    while gen.n_active:
        pos_before = int(gen.pos[0])
        blocks_before = len(gen._seq_bids[0])
        gen.step()
        if gen.n_active and len(gen._seq_bids[0]) > blocks_before:
            assert grew_at is None, "grew more than once before row 32"
            grew_at = pos_before
    assert grew_at is not None and grew_at % gen.block_size == 0


def test_walk_counters_follow_the_live_rows_and_their_depths(paged_engine):
    """``dllama_paged_walk_blocks_total`` over ``dllama_paged_table_blocks_total``
    is the share of the block tables paged attention walks: a step adds
    ``ceil((pos + 1) / block_size)`` for each row whose table starts with a
    real block and the whole table's entries to the denominator; a retired
    slot (stale ``pos``, null table) adds nothing."""
    from dllama_tpu.runtime import telemetry

    reg = telemetry.registry()
    walk = reg.counter(telemetry.PAGED_WALK_BLOCKS)
    table = reg.counter(telemetry.PAGED_TABLE_BLOCKS)
    gen = PagedGenerator(paged_engine, n_slots=3)
    bs = gen.block_size
    gen.admit(Request(rid=0, prompt_ids=_enc(paged_engine, "hello w"),
                      max_tokens=12, stop_on_eos=False), 1)
    w0, t0 = walk.total(), table.total()
    gen.step()
    assert table.total() - t0 == 3 * gen.table_width
    assert walk.total() - w0 == -(-(int(gen.pos[1]) + 1) // bs) == 1
    gen.admit(Request(rid=1, prompt_ids=_enc(paged_engine, "a" * 40),
                      max_tokens=40, stop_on_eos=False), 2)
    w0 = walk.total()
    gen.step()
    both = sum(-(-(int(gen.pos[i]) + 1) // bs) for i in (1, 2))
    assert walk.total() - w0 == both == 1 + 3
    while gen.slots[1] is not None:         # rid 0 retires first
        gen.step()
    assert gen.pos[1] > 0 and not gen.tables[1].any()   # stale depth, null table
    w0, t0 = walk.total(), table.total()
    gen.step()
    assert walk.total() - w0 == -(-(int(gen.pos[2]) + 1) // bs)
    assert table.total() - t0 == 3 * gen.table_width
    while gen.n_active:
        gen.step()


def test_fit_block_pool_tests_the_min_blocks_floor(monkeypatch):
    """The degrade loop must test min_blocks itself even when the step
    sequence would skip past it (want - min not divisible by the step):
    a budget that fits exactly the floor returns the floor, not 0."""
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig
    from dllama_tpu.runtime.hbm import (estimate_block_pool_bytes,
                                        estimate_device_bytes,
                                        fit_block_pool)

    cfg = ModelConfig(arch=ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=128,
                      seq_len=64, norm_epsilon=1e-5, rope_theta=10000.0,
                      rope_type=RopeType.LLAMA)
    want, mn, bs = 53, 14, 16  # step = (53-14)//16 = 2: 53, 51, ... 15, SKIPS 14
    base = estimate_device_bytes(cfg, weight_repr="q40", kv_dtype_bytes=4,
                                 batch=1, n_shards=1)["need_per_device"]
    floor_pool = estimate_block_pool_bytes(cfg, mn, bs, 4)
    above_pool = estimate_block_pool_bytes(cfg, mn + 1, bs, 4)
    # a limit between the floor pool's need and one-block-more's need
    monkeypatch.setenv("DLLAMA_HBM_BYTES",
                       str(base + (int(floor_pool * 1.15)
                                   + int(above_pool * 1.15)) // 2))
    n_fit, est = fit_block_pool(cfg, want, block_size=bs, min_blocks=mn,
                                weight_repr="q40", kv_dtype_bytes=4)
    assert n_fit == mn, (n_fit, est)
    # and a limit below even the floor still refuses with 0
    monkeypatch.setenv("DLLAMA_HBM_BYTES", str(base))
    n_fit, _ = fit_block_pool(cfg, want, block_size=bs, min_blocks=mn,
                              weight_repr="q40", kv_dtype_bytes=4)
    assert n_fit == 0


def test_fully_shared_prompt_skips_prefill_and_stays_token_exact(
        paged_engine):
    """Resubmitting an identical prompt (the repeated-system-prompt hot
    path) reuses EVERY prefill position — no prefill dispatch, no column
    gather/scatter (adm.col is None) — and still decodes token-exactly."""
    gen = PagedGenerator(paged_engine, n_slots=2)
    ids = _enc(paged_engine, "abcdefghijklmnopqrstuvwxy!")
    r_a = Request(rid=0, prompt_ids=ids, max_tokens=6, stop_on_eos=False)
    gen.admit(r_a, 0)
    while gen.n_active:
        gen.step()

    reuse = tm.registry().counter(tm.PREFIX_REUSE_TOKENS)
    c0 = reuse.total()
    r_b = Request(rid=1, prompt_ids=list(ids), max_tokens=6,
                  stop_on_eos=False)
    adm = gen.begin_admit(r_b, 1)
    assert adm.col is None  # zero device work beyond the one CoW copy
    assert adm.pos == len(ids) - 1  # nothing left to prefill
    assert reuse.total() - c0 == len(ids) - 1
    assert gen.continue_admit(adm)
    while gen.n_active:
        gen.step()
    assert r_b.tokens == r_a.tokens


def test_mid_admission_ride_along_never_writes_shared_blocks(paged_engine):
    """The slot table must stay all-null until the admission COMMITS: a
    slot mid-admission still rides along decode dispatches with whatever
    stale ``pos`` its previous occupant left, and that ride-along write
    must land in the null block — publishing shared bids early would let
    it corrupt prefix KV other live sequences attend to."""
    base = "abcdefghijklmnopqrstuvwxy "  # rest >= one full 16-block
    e1 = solo()
    want_a = e1.generate(base + "111", 8, stop_on_eos=False).tokens
    e1.close()
    e2 = solo()
    want_b = e2.generate(base + "222", 6, stop_on_eos=False).tokens
    e2.close()

    gen = PagedGenerator(paged_engine, n_slots=2)
    # previous occupant of slot 1: retires with stale pos INSIDE block 0,
    # so a published shared bids[0] would be the ride-along write target
    r0 = Request(rid=0, prompt_ids=_enc(paged_engine, "hi"),
                 max_tokens=12, stop_on_eos=False)
    gen.admit(r0, 1)
    while gen.n_active:
        gen.step()
    stale = int(gen.pos[1])
    assert 0 < stale < gen.block_size

    r_a = Request(rid=1, prompt_ids=_enc(paged_engine, base + "111"),
                  max_tokens=8, stop_on_eos=False)
    gen.admit(r_a, 0)
    gen.step()  # r_a live; its prompt blocks registered for sharing

    r_b = Request(rid=2, prompt_ids=_enc(paged_engine, base + "222"),
                  max_tokens=6, stop_on_eos=False)
    adm = gen.begin_admit(r_b, 1)
    shared_bids = [b for b in gen._seq_bids[1] if gen.pool.refcount(b) > 1]
    assert shared_bids  # the base prefix really is physically shared
    assert (gen.tables[1] == gen.pool.NULL).all()  # not published yet
    before = np.asarray(gen.pkv.k[:, shared_bids[0]]).copy()
    gen.step()  # slot 1 rides along with its stale pos mid-admission
    np.testing.assert_array_equal(
        before, np.asarray(gen.pkv.k[:, shared_bids[0]]))
    while not gen.continue_admit(adm):
        gen.step()
    while gen.n_active:
        gen.step()
    assert r_a.tokens == want_a
    assert r_b.tokens == want_b


def test_admission_reserves_decode_growth_no_organic_exhaustion(
        paged_engine):
    """Block-priced admission holds across the BATCH: every live
    sequence's worst-case decode growth stays reserved, so a second
    request that would double-spend the same free blocks queues instead
    of admitting — and nobody ever hits organic mid-decode exhaustion
    (503) on a pool the admission gate said was affordable."""
    from dllama_tpu.runtime.kvblocks import BlockPool
    from dllama_tpu.runtime.serving import BatchScheduler

    exhaustion = tm.registry().counter(tm.KV_BLOCK_EXHAUSTION)
    e0 = exhaustion.total()
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        # shrink the allocatable pool to 9 blocks (< two 6-block worst
        # cases); bids 1..9 stay valid indices into the larger device pool
        sched.gen.pool = BlockPool(10, sched.gen.block_size)
        ids = _enc(paged_engine, "hello wor")  # rest 9 -> 1 block held
        # worst case: 9 + 85 = 94 rows -> 6 blocks per request
        r1 = sched.submit(ids, 85, stop_on_eos=False)
        r2 = sched.submit(list(ids), 85, stop_on_eos=False)
        max_active = 0
        for _ in range(500):
            sched._tick()
            max_active = max(max_active, sched.gen.n_active)
            if r1.done.is_set() and r2.done.is_set():
                break
        assert r1.done.is_set() and r2.done.is_set()
        assert r1.error is None and r2.error is None
        assert len(r1.tokens) == 85 and len(r2.tokens) == 85
        assert max_active == 1  # the second request QUEUED, not gambled
        assert exhaustion.total() == e0  # and nothing ever ran dry
    finally:
        sched.close()


def test_begin_admit_rolls_back_blocks_on_any_failure(paged_engine):
    """A device error mid-admission (not just exhaustion) must release
    every block taken — a leaked refcount would shrink the allocatable
    pool forever on a healthy server."""
    gen = PagedGenerator(paged_engine, n_slots=2)
    free0 = gen.pool.free_blocks()
    orig = gen._take

    def boom(*a):
        raise RuntimeError("device boom")

    gen._take = boom
    r = Request(rid=0, prompt_ids=_enc(paged_engine, "hello world"),
                max_tokens=4, stop_on_eos=False)
    with pytest.raises(RuntimeError, match="device boom"):
        gen.begin_admit(r, 0)
    assert gen.pool.free_blocks() == free0  # atomic rollback
    gen._take = orig
    gen.admit(r, 0)  # the pool is intact: the same request admits fine
    while gen.n_active:
        gen.step()
    assert len(r.tokens) == 4


def test_cancelled_mid_admission_releases_blocks(paged_engine):
    """A client cancel between prefill chunks aborts the admission AND
    returns its blocks to the pool (dense slots had nothing to release;
    paged refcounts would leak without abort_admit)."""
    from dllama_tpu.runtime.serving import BatchScheduler

    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        free0 = sched.gen.pool.free_blocks()
        # rest of 79 ids needs 2 chunks (64-bucket + tail) -> the cancel
        # window between ticks exists
        ids = [int(x) for x in np.random.default_rng(9).integers(1, 200, 80)]
        req = sched.submit(ids, 8, stop_on_eos=False)
        sched._tick()
        assert sched._admissions  # still prefilling
        assert sched.gen.pool.free_blocks() < free0
        req.cancel.set()
        sched._tick()
        assert req.done.is_set()
        assert not sched._admissions
        assert sched.gen.pool.free_blocks() == free0  # all blocks back
    finally:
        sched.close()


def test_cancel_behind_prefill_budget_releases_immediately(paged_engine):
    """The cancel sweep runs over EVERY in-flight admission before the
    budgeted prefill loop: a cancelled client queued behind the budget
    cutoff must not keep blocks/reservation/slot for the remaining ticks
    of the admissions ahead of it."""
    from dllama_tpu.runtime.serving import BatchScheduler

    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    sched.prefill_budget = 1  # only the FIRST admission advances per tick
    try:
        free0 = sched.gen.pool.free_blocks()
        rng = np.random.default_rng(11)
        a = sched.submit([int(x) for x in rng.integers(1, 200, 80)], 4,
                         stop_on_eos=False)
        b = sched.submit([int(x) for x in rng.integers(1, 200, 80)], 4,
                         stop_on_eos=False)
        sched._tick()  # both begin; only A's prefill advances
        assert len(sched._admissions) == 2
        held = free0 - sched.gen.pool.free_blocks()
        b.cancel.set()
        sched._tick()  # cancel sweep precedes the budget break
        assert b.done.is_set()
        assert all(adm.req is not b for adm in sched._admissions)
        assert free0 - sched.gen.pool.free_blocks() < held  # B's came back
        while not a.done.is_set():
            sched._tick()
        assert a.error is None and len(a.tokens) == 4
    finally:
        sched.close()


# -- window layers: the second pool's return rule -----------------------------


def test_window_first_block_is_the_oldest_position_still_seen():
    from dllama_tpu.runtime.kvblocks import window_blocks_cap, window_first_block

    # a window of 512 counts the query's own position: at 511 the oldest key seen is 0, at 512 it is 1
    assert [window_first_block(p, 512, 16) for p in (0, 511, 512, 526, 527, 528, 1039)] == [0, 0, 0, 0, 1, 1, 33]
    assert window_first_block(100, 32, 16) == 4 and window_first_block(79, 32, 16) == 3
    assert window_blocks_cap(512, 16) == 34 and window_blocks_cap(32, 16) == 4


@pytest.mark.parametrize("seed", range(5))
def test_window_pool_churn_never_passes_its_cap_and_comes_back_whole(seed):
    """The window pool under the return rule, host bookkeeping alone: rows of
    random prompt lengths decode at random, each returning what falls behind
    its window before it takes the block its next position opens. No row ever
    holds more than the cap, a pool of slots x cap never runs dry, a returned
    block is handed out again while its first owner lives, and after every row
    retires the free count is what it started at."""
    from dllama_tpu.runtime.kvblocks import BlockPool, window_blocks_cap, window_first_block

    rng = np.random.default_rng(seed)
    window, bs, slots = 48, 16, 3
    cap = window_blocks_cap(window, bs)
    pool = BlockPool(slots * cap + 1, bs)
    free0 = pool.free_blocks()
    held = [dict() for _ in range(slots)]
    pos = [int(p) for p in rng.integers(1, 200, size=slots)]
    owners: dict[int, set] = {}
    for i in range(slots):                       # admission: the blocks the first step's window reaches
        for idx in range(window_first_block(pos[i], window, bs), (pos[i] - 1) // bs + 1):
            held[i][idx] = pool.alloc()
    for _ in range(600):
        i = int(rng.integers(slots))
        first = window_first_block(pos[i], window, bs)
        for idx in [j for j in held[i] if j < first]:
            pool.release(held[i].pop(idx))
        if pos[i] // bs not in held[i]:
            bid = pool.alloc()
            held[i][pos[i] // bs] = bid
            owners.setdefault(bid, set()).add(i)
        assert len(held[i]) <= cap and min(held[i]) >= first
        assert all(pool.refcount(b) == 1 for b in held[i].values())
        pos[i] += 1
    assert any(len(rows) > 1 for rows in owners.values())          # a block served more than one row
    for i in range(slots):
        for bid in held[i].values():
            pool.release(bid)
    assert pool.free_blocks() == free0 and pool.used_blocks() == 0


# -- window layers: a matched prefix brings its window ------------------------------


def _commit(pool, wpool, tokens, window, bs):
    """What an admission and its commit do to the two allocators, host
    bookkeeping alone: the full pool's blocks registered, the window blocks of
    the prompt's last boundary registered under the same chain ids. Returns the
    blocks the sequence holds in each pool."""
    from dllama_tpu.runtime.kvblocks import window_first_block

    bids = [pool.alloc() for _ in range(-(-len(tokens) // bs))]
    pool.register_prompt(bids, tokens)
    _, chain = pool.match_chain(tokens)
    n_full = len(tokens) // bs
    wbids = {idx: wpool.alloc() for idx in range(window_first_block(n_full * bs, window, bs), -(-len(tokens) // bs))}
    for idx, b in wbids.items():
        if idx < len(chain):
            wpool.register_keyed(b, chain[idx])
    return bids, wbids


def test_a_match_is_as_long_as_both_pools_hold_it():
    from dllama_tpu.runtime.kvblocks import BlockPool, match_windowed, window_first_block

    window, bs = 12, 4                       # a window of 12 reaches back three or four blocks
    pool, wpool = BlockPool(64, bs), BlockPool(32, bs)
    tokens = list(range(100, 140))           # ten blocks
    bids, wbids = _commit(pool, wpool, tokens, window, bs)
    assert sorted(wbids) == [7, 8, 9] and window_first_block(40, window, bs) == 7
    shared, wshared, chain = match_windowed(pool, wpool, tokens + [1, 2, 3], window)
    assert shared == bids and wshared == wbids and len(chain) == 10
    # a prompt that leaves the chain at block 6 matches six blocks in the full pool; their window (blocks 3-5) was
    # never kept, so nothing is usable: the window pool missed
    shared, wshared, chain = match_windowed(pool, wpool, tokens[:24] + [9] * 8, window)
    assert (shared, wshared, len(chain)) == ([], {}, 6)
    # the boundary's window left behind under the chain's ids (what the admission that missed does): now it is whole
    saved = {idx: wpool.alloc() for idx in range(window_first_block(24, window, bs), 6)}
    for idx, b in saved.items():
        assert wpool.register_keyed(b, chain[idx])
        wpool.release(b)                                    # parked at once
    shared, wshared, chain = match_windowed(pool, wpool, tokens[:24] + [9] * 8, window)
    assert shared == bids[:6] and wshared == saved
    # one parked block of that window evicted: the walk goes back to a boundary whose window is whole, here none
    wpool._unregister(saved[4])
    wpool._cached.pop(saved[4])
    wpool._free.append(saved[4])
    assert match_windowed(pool, wpool, tokens[:24] + [9] * 8, window)[:2] == ([], {})
    # a second registration under a key already held, or of a block already registered, changes nothing
    other = wpool.alloc()
    assert not wpool.register_keyed(other, chain[3]) and not wpool.register_keyed(saved[3], 12345)
    # under a window shorter than a block every boundary is usable from its own last block
    assert match_windowed(pool, wpool, tokens, 1)[0] == bids


@pytest.mark.parametrize("seed", range(5))
def test_two_pools_under_sessions_never_match_what_is_not_registered_behind_the_same_chain(seed):
    """Sessions of growing prompts over two allocators, host bookkeeping alone:
    every turn matches, shares, allocates, commits, decodes (sliding its
    window) and retires at random, other sessions evicting what it parked. At
    every step: a returned match's window blocks are all registered under the
    chain ids of the SAME prefix and cover the boundary's whole window; a
    parked block is never in a live sequence's hands, so never a write target;
    a block a sequence writes has refcount 1; both pools' counts balance (free
    + parked + live = all) and come back whole once every sequence retires."""
    from dllama_tpu.runtime.kvblocks import (BlockPool, BlockPoolExhausted, match_windowed, window_blocks_cap,
                                             window_first_block)

    rng = np.random.default_rng(seed)
    window, bs, slots = 24, 4, 3
    cap = window_blocks_cap(window, bs)
    pool, wpool = BlockPool(slots * 40 + 1, bs), BlockPool(2 * slots * cap + 1, bs)
    all_full, all_win = pool.free_blocks(), wpool.free_blocks()
    system = [int(t) for t in rng.integers(0, 50, size=20)]
    sessions = [list(system) for _ in range(5)]
    live: dict[int, tuple] = {}                  # slot -> (full bids, window bids by index, position)
    hits = {"system": 0, "turn": 0}

    def balanced():
        for p, total in ((pool, all_full), (wpool, all_win)):
            held = sum(1 for b in range(1, p.n_blocks) if p.refcount(b) > 0)
            assert len(p._free) + len(p._cached) + held == total
            assert not set(p._free) & set(p._cached) and all(p.refcount(b) == 0 for b in p._cached)
        mine = [b for _f, w, _p in live.values() for b in w.values()]
        assert not set(mine) & set(wpool._cached) and not set(mine) & set(wpool._free)

    for step in range(300):
        slot = int(rng.integers(slots))
        if slot in live:
            bids, wbids, pos = live[slot]
            if rng.random() < 0.3:                                   # retire
                for b in bids:
                    pool.release(b)
                for b in wbids.values():
                    wpool.release(b)
                del live[slot]
            else:                                                    # a few decode steps: the window slides
                for _ in range(int(rng.integers(1, 9))):
                    first = window_first_block(pos, window, bs)
                    for idx in [j for j in wbids if j < first]:
                        wpool.release(wbids.pop(idx))
                    if pos // bs not in wbids:
                        wbids[pos // bs] = wpool.alloc()
                    if pos // bs >= len(bids):
                        bids.append(pool.alloc())
                    assert wpool.refcount(wbids[pos // bs]) == 1 and pool.refcount(bids[pos // bs]) == 1
                    pos += 1
                live[slot] = (bids, wbids, pos)
            balanced()
            continue
        s = int(rng.integers(len(sessions)))
        sessions[s] = sessions[s] + [int(t) for t in rng.integers(0, 50, size=int(rng.integers(3, 30)))]
        if len(sessions[s]) > 120:
            sessions[s] = list(system) + [int(t) for t in rng.integers(0, 50, size=5)]       # a new session
        tokens = sessions[s]
        shared, wshared, chain = match_windowed(pool, wpool, tokens, window)
        n = len(shared)
        # the match's window is whole and registered behind the same chain
        assert sorted(wshared) == list(range(window_first_block(n * bs, window, bs), n))
        assert all(wpool.keyed(chain[idx]) == b for idx, b in wshared.items()) and pool.match_chain(tokens)[1] == chain
        hits["system" if n * bs == len(system) else "turn" if n else "none"] = \
            hits.get("system" if n * bs == len(system) else "turn" if n else "none", 0) + 1
        bids, wbids = list(shared), dict(wshared)
        try:
            for b in shared:
                pool.share(b)
            for b in wshared.values():
                wpool.share(b)
            n_full = len(tokens) // bs
            while len(bids) < -(-len(tokens) // bs):
                bids.append(pool.alloc())
            for idx in range(max(n, window_first_block(n_full * bs, window, bs)), -(-len(tokens) // bs)):
                wbids[idx] = wpool.alloc()
        except BlockPoolExhausted:
            for b in bids:
                pool.release(b)
            for b in wbids.values():
                wpool.release(b)
            balanced()
            continue
        # a block this sequence will WRITE (at or past the match) is its own
        assert all(pool.refcount(b) == 1 for b in bids[n:]) and all(wpool.refcount(b) == 1
                                                                    for i, b in wbids.items() if i >= n)
        if len(chain) > n:                                           # the window pool missed: leave it behind
            try:
                got = {idx: wpool.alloc() for idx in range(window_first_block(len(chain) * bs, window, bs), len(chain))
                       if wpool.keyed(chain[idx]) is None}
            except BlockPoolExhausted:
                got = {}
            for idx, b in got.items():
                wpool.register_keyed(b, chain[idx])
                wpool.release(b)
        pool.register_prompt(bids, tokens)
        _, chain = pool.match_chain(tokens)
        for idx, b in wbids.items():
            if idx < len(chain):
                wpool.register_keyed(b, chain[idx])
        first = window_first_block(len(tokens), window, bs)
        for idx in [j for j in wbids if j < first]:
            wpool.release(wbids.pop(idx))
        live[slot] = (bids, wbids, len(tokens))
        balanced()
    assert hits["turn"] > 5 and hits["system"] > 0          # both kinds of boundary matched
    for bids, wbids, _pos in live.values():
        for b in bids:
            pool.release(b)
        for b in wbids.values():
            wpool.release(b)
    assert (pool.free_blocks(), wpool.free_blocks()) == (all_full, all_win)
    assert pool.used_blocks() == 0 and wpool.used_blocks() == 0


def test_window_column_rows_hold_the_window_the_widest_chunk_and_a_commits_blocks():
    from dllama_tpu.runtime.kvblocks import window_column_rows

    buckets = (256, 128, 64, 32)
    assert window_column_rows(1024, 16, buckets, 11776) == 1280        # mellum2: the window and the widest chunk
    assert window_column_rows(512, 16, buckets, 4096) == 768           # laguna
    assert window_column_rows(32, 16, buckets, 512) == 384
    assert window_column_rows(1024, 128, buckets, 11776) == 1408       # blocks of 128: two of them and the padding
    assert window_column_rows(1024, 16, buckets, 1024) == 1024         # never more than the slot's padded length

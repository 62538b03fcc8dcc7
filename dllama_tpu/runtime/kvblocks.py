"""Block-granular paged KV cache — allocator + device pool.

The slot-pool serving cache (runtime/kvcache.py used via runtime/serving.py)
reserves max-context HBM per sequence: a 50-token request holds the same
``[S, kv_dim]`` column as a 5000-token one, and prefix reuse is token-count
accounting against whole slot columns. This module replaces that with the
vLLM/"Ragged Paged Attention" memory model (PAPERS.md) expressed portably
in XLA:

* **Device pool** — :class:`PagedKVCache` stores KV as
  ``[L, n_blocks, n_kv, block_size, hd]``; a sequence's logical cache is a
  *block table* (host ``int32[max_blocks]``) of physical block ids, and the
  paged decode program (models/llama.py ``paged_forward``) gathers K/V
  through it. Physical block 0 is the **null block**: never allocated,
  the write target for inactive ride-along rows and the gather target for
  unallocated table tail entries (masked by position, so its garbage is
  value-invisible — the same argument as padded prefill tails).

* **Host allocator** — :class:`BlockPool` refcounts physical blocks.
  Prefix reuse becomes *block-level sharing*: full blocks of prefill-built
  prompt ids register under a hash chain (tuple-exact, no collisions), a
  new prompt walks the chain and shares every matching physical block
  (refcount++, zero prefill work). Shared blocks are full and positions
  only advance, so a shared block is **never written in place**; the tail
  of the match is handled copy-on-write — the best partially-matching
  registered block is *copied* into a fresh block (one device copy), then
  the new sequence overwrites its own rows from the divergence point.
  Retired sequences' registered blocks park in an LRU "cached" state:
  still shareable (cross-request system-prompt reuse, the batched analogue
  of the single-sequence NaiveCache) until allocation pressure evicts
  them. Only prefill-built tokens register — decode-built rows are
  deliberately never matched (a decode-shaped dispatch can differ in the
  last ulp from the prefill a solo run would execute; golden_assets
  documents ulp flips becoming token flips).

* **Window layers** — a block id addresses ``pool[l, bid]`` for every
  layer of a pool at once, so a sliding-window layer cannot give a block
  back while a full-attention layer keeps it. A model with both
  (models/laguna.py, models/mellum.py) has TWO pools and two tables a
  sequence: the full layers' here, and the window layers' in a second
  :class:`PagedKVCache` over a second :class:`BlockPool`, whose blocks a
  sequence gives back as soon as every position in them is more than
  ``window - 1`` behind the row's next position
  (:func:`window_first_block`); their table entries become the null
  block, which paged attention never reads (its walk starts at the first
  live block). **A matched prefix brings its window with it.** A match of
  ``m`` tokens in the full pool is usable only if the window pool still
  holds the sliding layers' K/V of positions ``m - window + 1 .. m - 1``
  computed behind the same tokens (they cannot be rebuilt from the last
  window's tokens: a sliding layer's K/V at a position is a function of
  every lower layer's output there, whose reach grows by a window a
  sliding layer). So the window pool has registrations of its own, under
  the FULL pool's chain ids (:meth:`BlockPool.register_keyed`: a chain id
  names a whole prefix, and K/V at a block's positions is a function of
  that prefix alone): a commit registers the prompt's last window, an
  admission that passes a boundary the full pool matched and the window
  pool missed leaves that boundary's window behind
  (``PagedGenerator._save_window``), a registered block a sequence gives
  back parks refcount-0 in the LRU (still shareable) and is what an
  allocation takes back once the free list is dry. :func:`match_windowed`
  returns the longest block boundary BOTH pools hold. No copy-on-write
  tail: a boundary is a whole block.

* **Host tier** — with ``n_host_blocks > 0`` (``--kv-host-blocks``), the
  LRU cached machinery becomes a *spill point* instead of a drop point:
  under allocation pressure the coldest cached blocks move to a
  pinned-host mirror pool (:class:`HostKVMirror`; batched block-granular
  device→host copies) and their prefix-trie registrations follow — an
  idle chat session's KV survives HBM pressure in host DRAM. A later
  prefix-matched admission (the resumed session) *pages the blocks back
  in*: fresh device blocks are allocated, the host copies are restored
  bit-exactly, and the trie rebinds to the device ids — zero re-prefill
  work, transcripts identical to a never-spilled run. Every logical
  block lives in exactly ONE tier at a time (device ids
  ``1..n_blocks-1``, host ids ``n_blocks..n_blocks+n_host_blocks-1``);
  host-resident blocks are never refcounted live, never write targets,
  and never appear in a published block table. Only COLD blocks spill:
  live blocks are attended by every decode dispatch (full-context
  attention each tick), so there is no "cold live block" — the idle
  sessions the tier exists for are retired requests whose blocks park
  in the cached LRU, longest-idle first out. Spill failure (the
  ``spill`` failpoint, or a real copy error) degrades to the old
  drop-evict contract; page-in failure fails only the resuming request
  (503-shaped), bystanders untouched.

The allocator is pure host bookkeeping (no jax import; the device↔host
copies run through a ``spill_fn`` hook the generator installs and the
:class:`HostKVMirror` gates its jax imports), so the property tests in
tests/test_kvblocks.py drive thousands of alloc/free/share/CoW/spill
cycles in microseconds.
"""

from __future__ import annotations

import os
from collections import OrderedDict
from typing import TYPE_CHECKING, NamedTuple

from . import failpoints
from .kvcache import padded_cache_len

if TYPE_CHECKING:  # jax only needed for the device pool, not the allocator
    import jax

# the root chain id of every prefix trie (the empty prefix)
_ROOT = 0

# blocks per batched device↔host copy (and per HostKVMirror chunk): the
# spill/page-in transfer programs are jitted at this fixed width so tier
# traffic never retraces — short batches pad with the null block
SPILL_BATCH = 4


class BlockPoolExhausted(RuntimeError):
    """No free or evictable block is available. The batch scheduler treats
    this as back-pressure — the request stays queued (429/503-shaped under
    load shedding/deadlines), never a crash."""


class PageInError(RuntimeError):
    """A host→device page-in failed (the ``pagein`` failpoint, or a real
    copy error). Fails ONLY the resuming request, 503-shaped — the host
    copies stay intact and bystander slots keep decoding."""


_HOST_KIND = None  # (kind | None, reason) once probed


def probe_host_memory_kind() -> tuple[str | None, str]:
    """CAPABILITY probe (once per process, no overrides): the jax host
    memory kind this backend can actually place arrays in —
    ``pinned_host`` (TPU DMA-able host DRAM) with an ``unpinned_host``
    fallback (the only kind CPU jaxlib exposes — it IS host DRAM there,
    so the CPU tier exercises the real spill/page-back path instead of
    capability-skipping), else ``(None, reason)``. The test helpers
    (tests/helpers.pinned_host_probe) delegate here, NOT to
    :func:`host_memory_kind` — a forced serving knob must never change
    which capability-gated tests run or skip."""
    global _HOST_KIND
    if _HOST_KIND is not None:
        return _HOST_KIND
    reasons = []
    for kind in ("pinned_host", "unpinned_host"):
        try:
            import jax
            import jax.numpy as jnp

            dev = jax.local_devices()[0]
            s = jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)
            jax.block_until_ready(
                jax.device_put(jnp.zeros((8,), jnp.float32), s))
            _HOST_KIND = (kind, "")
            return _HOST_KIND
        except Exception as e:  # noqa: BLE001 — any failure = "not this kind here"
            reasons.append(f"{kind}: {type(e).__name__}: {e}")
    _HOST_KIND = (None, "; ".join(reasons))
    return _HOST_KIND


def host_memory_kind() -> tuple[str | None, str]:
    """The kind the KV mirror USES: ``DLLAMA_KV_HOST_KIND`` overrides
    (``pinned_host`` / ``unpinned_host`` / ``none`` = numpy-buffer
    fallback — a forced kind the backend can't place fails at the
    mirror's warmup, which degrades the tier off loudly), else the
    :func:`probe_host_memory_kind` capability result."""
    forced = os.environ.get("DLLAMA_KV_HOST_KIND")
    if forced:
        return ((None, "forced off via DLLAMA_KV_HOST_KIND")
                if forced == "none" else (forced, "forced via env"))
    return probe_host_memory_kind()


def validate_block_size(seq_len: int, block_size: int) -> None:
    """``--kv-block-size`` validation: power of two, and it must tile the
    padded physical context exactly (every power of two <= 128 does; larger
    sizes must divide the padded row count)."""
    padded = padded_cache_len(seq_len)
    if block_size < 1 or block_size & (block_size - 1):
        raise ValueError(
            f"--kv-block-size must be a power of two, got {block_size}")
    if block_size > padded or padded % block_size:
        raise ValueError(
            f"--kv-block-size {block_size} must tile the padded context "
            f"({padded} rows for seq_len {seq_len}); use a power of two "
            f"<= {min(padded, 128)} or a divisor of {padded}")


def blocks_per_seq(seq_len: int, block_size: int) -> int:
    """Block-table width: blocks covering the padded physical context."""
    return padded_cache_len(seq_len) // block_size


def window_first_block(pos: int, window: int, block_size: int) -> int:
    """Table index of the oldest block a sliding-window layer still reads
    when the query sits at position ``pos``: the block that holds position
    ``pos - window + 1`` (the window counts the query's own position).
    Every block before it holds only positions more than ``window - 1``
    behind, and goes back to the window pool's free list
    (``PagedGenerator._slide_window``); ``ops/paged_attention.walk_bounds``
    starts the row's walk at the same index."""
    return max(0, pos - window + 1) // block_size


def window_blocks_cap(window: int, block_size: int) -> int:
    """Blocks of the window pool one sequence can hold at once, at the
    most: the window's, one more where it starts mid-block, and the block
    the next position opens before the oldest is returned."""
    return window // block_size + 2


def window_column_rows(window: int, block_size: int, buckets,
                       seq_len: int) -> int:
    """Rows of the sliding layers' buffer in an admission's column
    (models/laguna.LagunaColumn): the keys the widest chunk's windows reach
    (``window - 1`` behind it and itself), and what a commit or a saved
    boundary still writes to blocks behind a chunk that was padded to the
    narrowest bucket (the window's first block may start ``block_size - 1``
    before the window, a boundary ``block_size - 1`` before the chunk's
    end). Whole tiles of 128; never more than the slot's padded length."""
    need = window + max(max(buckets) - 1,
                        2 * block_size + min(buckets) - 4)
    return min(padded_cache_len(seq_len), -(-need // 128) * 128)


def match_windowed(pool: "BlockPool", wpool: "BlockPool", tokens,
                   window: int) -> tuple[list[int], dict[int, int], list[int]]:
    """Longest block boundary of ``tokens`` that BOTH pools hold, for a model
    with window layers: ``(shared, window_bids, chain)``.

    ``chain`` are the chain ids of every full block the FULL pool matched
    (``len(chain) * block_size`` tokens: what it alone would share).
    ``shared`` are the first ``b`` of its blocks, ``b`` the largest count
    whose boundary's window is whole in the window pool: every table index
    from :func:`window_first_block` of position ``b * block_size`` up to ``b
    - 1`` registered there under the SAME chain id (``window_bids``: index
    -> window block). ``b`` is 0 where no boundary's window is whole; no
    refcount is taken here."""
    bs = pool.block_size
    bids, chain = pool.match_chain(tokens)
    held = [wpool.keyed(cid) for cid in chain]
    run = best = 0          # consecutive held blocks ending at j; best boundary
    for j, wbid in enumerate(held):
        run = run + 1 if wbid is not None else 0
        b = j + 1
        if run >= b - window_first_block(b * bs, window, bs):
            best = b
    first = window_first_block(best * bs, window, bs)
    return (bids[:best], {j: held[j] for j in range(first, best)}, chain)


class PagedKVCache(NamedTuple):
    """Device-side block pool: ``[L, n_blocks, n_kv, block_size, hd]``.

    The block axis replaces the slot-pool batch axis; under a mesh plan the
    kv-head axis shards over tp exactly like the dense cache (the block and
    row axes stay replicated — parallel/sharding.paged_kv_sharding).

    The block geometry is the ARCHITECTURE's (``cfg.cache_heads``,
    ``cfg.cache_width``): K and V of every K/V head, or, with latent
    attention (models/axk1.py), ONE pool ``k [L, n_blocks, 1, block_size,
    latent_row]`` of compressed rows and ``v`` None. It is one list of blocks
    by token range either way, so the allocator, the prefix index and
    copy-on-write are the same."""

    k: "jax.Array"
    v: "jax.Array | None"

    @classmethod
    def create(cls, cfg, n_blocks: int, block_size: int,
               dtype=None) -> "PagedKVCache":
        import jax.numpy as jnp

        dtype = dtype if dtype is not None else jnp.float32
        # every layer, or a hybrid decoder's full ones only
        shape = (cfg.n_kv_layers, n_blocks, cfg.cache_heads, block_size,
                 cfg.cache_width)
        return cls(k=jnp.zeros(shape, dtype=dtype),
                   v=(None if cfg.has_latent_cache
                      else jnp.zeros(shape, dtype=dtype)))

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]


class StatePool(NamedTuple):
    """Device-side recurrent state of a decoder whose layers carry one
    (``cfg.has_state``), slot-indexed, beside the K/V block pool: ``s
    [state layers, rows, heads, ...]`` float32 and the causal convolution's
    last ``K - 1`` inputs ``conv [state layers, rows, K - 1, channels]``.
    The shapes are the ARCHITECTURE's (``cfg.state_shape`` /
    ``cfg.conv_shape``): a gated delta-rule state ``[H, dk, dv]`` in a
    hybrid's linear layers (models/hybrid.py), an SSD state ``[H, P, N]`` in
    every layer where the mixer sits beside attention (models/falcon_h1.py),
    or NONE where the convolution's tail is the whole state (a gated
    short-convolution layer, models/lfm2.py: ``cfg.state_shape`` is None, ``s``
    is None, and no float32 row is allocated, committed or counted).
    Row 0 is the NULL row, as block 0 is the null block: inactive slots
    riding along a decode step read and write it, and its contents are
    value-invisible. Slot ``i`` owns row ``i + 1``.

    Its rules are not the block pool's. A state is one slot's, never
    shared: it is a function of the whole prefix, so a matched prefix block
    brings no state with it, and block-level prefix sharing is off for a
    model that has one. It starts at zero in the admission
    (:meth:`StateColumn.zeros`), is carried from prefill chunk to chunk
    there, is written to the slot's row ONCE, at commit, goes through every
    decode step in place, and is simply left behind at retirement: the next
    admission's commit overwrites the row."""

    s: "jax.Array | None"
    conv: "jax.Array"

    NULL = 0

    @classmethod
    def create(cls, cfg, n_slots: int, conv_dtype) -> "StatePool":
        import jax.numpy as jnp

        return cls(s=_zero_state(cfg, n_slots + 1),
                   conv=jnp.zeros(cfg.conv_shape(n_slots + 1), conv_dtype))

    @property
    def n_bytes(self) -> int:
        return state_bytes(self)


def _zero_state(cfg, rows: int):
    """The float32 state of ``rows`` sequences at a sequence's start, or
    None where the architecture has none beside the tail."""
    import jax.numpy as jnp

    shape = cfg.state_shape(rows)
    return None if shape is None else jnp.zeros(shape, jnp.float32)


def state_bytes(state) -> int:
    """Bytes of the state a :class:`StatePool` or :class:`StateColumn`
    holds: the tail, and the float32 state where there is one."""
    return state.conv.nbytes + (0 if state.s is None else state.s.nbytes)


class StateColumn(NamedTuple):
    """One slot's context gathered for chunked prefill where it is more
    than K/V (the ``KVCache`` of a decoder with a recurrent state): K/V of
    the layers that cache it, the state layers' state and the convolution's
    tail. What an admission carries from chunk to chunk and its commit
    writes: K/V through the block table, ``s`` (None where the tail is the
    whole state) and ``conv`` to the slot's row of :class:`StatePool`, and,
    where the decoder also routes over experts (models/lfm2.py), the chunks'
    routing counters ``stats`` into the generator's running totals."""

    k: "jax.Array"      # [n_kv_layers, B, n_kv, S, cache_width]
    v: "jax.Array"
    s: "jax.Array | None"   # cfg.state_shape(B), float32
    conv: "jax.Array"   # cfg.conv_shape(B)
    stats: "jax.Array | None" = None   # models/share.zero_stats

    @classmethod
    def zeros(cls, cfg, k: "jax.Array", v: "jax.Array",
              conv_dtype=None) -> "StateColumn":
        """A sequence's start: the given K/V column, zero state and tail
        (in the compute dtype, as :class:`StatePool`'s, unless given)."""
        import jax.numpy as jnp

        B = k.shape[1]
        if conv_dtype is None:
            conv_dtype = jnp.dtype(cfg.compute_dtype)
        stats = None
        if cfg.has_expert_share:
            from ..models.share import zero_stats

            stats = zero_stats(cfg)
        return cls(k=k, v=v, s=_zero_state(cfg, B),
                   conv=jnp.zeros(cfg.conv_shape(B), conv_dtype), stats=stats)


def state_pool_bytes(cfg, n_slots: int, conv_dtype_bytes: int) -> int:
    """Device bytes of :class:`StatePool` for ``n_slots`` (0 for a model
    without a recurrent state): what ``runtime/hbm.py``'s fit counts."""
    if not cfg.has_state:
        return 0
    import math

    shape = cfg.state_shape(n_slots + 1)
    return ((0 if shape is None else math.prod(shape) * 4)
            + math.prod(cfg.conv_shape(n_slots + 1)) * conv_dtype_bytes)


class HostKVMirror:
    """Host-DRAM side of the KV tier: chunk-granular storage for spilled
    blocks plus the device↔host transfer machinery.

    A spill moves up to :data:`SPILL_BATCH` blocks in ONE batched hop:
    one jitted gather (models.llama.gather_kv_blocks) pulls the blocks
    out of the pool as a contiguous chunk, one ``jax.device_put`` moves
    the chunk into pinned host memory (``pinned_host`` on TPU;
    ``unpinned_host`` on CPU jaxlib — same code path, host DRAM either
    way; plain numpy when neither kind places). The transfers are
    dispatched async, so a spill overlaps the decode ticks that follow it
    — jax array immutability keeps the gathered chunk valid even after
    the pool recycles the source blocks. Page-in reverses the hop per
    chunk (device_put back + one jitted scatter,
    models.llama.scatter_kv_blocks; unwanted lanes target the null
    block) and frees the lanes — a logical block is host- OR
    device-resident, never both.

    Owned by the PagedGenerator (loop thread), like the pool it mirrors.
    """

    def __init__(self, max_chunks: int = 0):
        import jax

        from ..models.llama import gather_kv_blocks, scatter_kv_blocks

        # raw jit is deliberate: plan-independent data movement (no
        # constrain()), the same argument as the generator's take/put/copy
        self._gather = jax.jit(gather_kv_blocks)  # dlint: disable=jit-entry
        self._scatter = jax.jit(scatter_kv_blocks,  # dlint: disable=jit-entry
                                donate_argnums=(0,))
        self.kind, self.kind_reason = host_memory_kind()
        self._chunks: dict[int, dict] = {}
        self._where: dict[int, tuple[int, int]] = {}  # host bid -> (cid, lane)
        self._next_cid = 0
        # the HARD host-RAM bound: chunks are SPILL_BATCH blocks of
        # buffer whether or not every lane is live, and interleaved
        # session lifetimes can keep a chunk alive on one lane — so the
        # budget is enforced in CHUNKS, not lanes. At the cap,
        # :meth:`has_room` refuses and the spill degrades to drop-evict
        # (capacity loss under fragmentation, never an overshoot past
        # the DLLAMA_HOST_KV_BYTES / fit_host_pool budget). 0 = uncapped
        # (tests driving the mirror directly).
        self.max_chunks = max(0, max_chunks)

    def has_room(self) -> bool:
        """Whether a new spill chunk fits the chunk-accounted budget."""
        return not self.max_chunks or len(self._chunks) < self.max_chunks

    def _pad_ids(self, bids: list[int]):
        import numpy as np

        ids = np.zeros(SPILL_BATCH, dtype=np.int32)  # pad = null block
        ids[:len(bids)] = bids
        return ids

    def _to_host(self, arr):
        """One chunk array → host memory: ``device_put`` onto the probed
        host memory kind (async D2H DMA), or a numpy copy when no host
        kind places on this backend."""
        import jax

        if self.kind is None:
            import numpy as np

            return np.asarray(arr)
        return jax.device_put(arr, arr.sharding.with_memory_kind(self.kind))

    def store(self, pkv, dev_bids: list[int], host_bids: list[int]) -> None:  # dlint: owner=loop-thread
        """Execute one spill batch: gather ``dev_bids`` from the pool and
        park the chunk under ``host_bids``' lanes."""
        import jax.numpy as jnp

        ck, cv = self._gather(pkv, jnp.asarray(self._pad_ids(dev_bids)))
        dev_shard = (ck.sharding, cv.sharding)
        hk, hv = self._to_host(ck), self._to_host(cv)
        cid = self._next_cid
        self._next_cid += 1
        self._chunks[cid] = {"k": hk, "v": hv, "dev_shard": dev_shard,
                             "live": set(host_bids)}
        for lane, hb in enumerate(host_bids):
            self._where[hb] = (cid, lane)

    def load(self, pkv_ref: list, pairs: list[tuple[int, int]]) -> None:  # dlint: owner=loop-thread
        """Execute one page-in batch: restore each ``(host_bid, dev_bid)``
        pair's content into the pool (grouped per chunk — one H2D hop +
        one scatter per touched chunk) and free the lanes.

        ``pkv_ref`` is a one-element list holding the pool; it is updated
        in place after every scatter so the CALLER always holds a live
        pool even if a later step raises — the scatter donates its pool
        input, and losing the updated reference mid-batch would leave
        the generator pointing at a deleted buffer (crashing every
        bystander, not just the resumer). Staged for the same reason:
        ALL host→device transfers (the failure-prone hop) run before the
        first donation, and the mirror's lane bookkeeping mutates only
        after every copy landed — a failed batch leaves the lanes intact
        and consistent with the pool's restored host pins, so the retry
        resume finds its content."""
        import jax
        import jax.numpy as jnp

        by_chunk: dict[int, list[tuple[int, int, int]]] = {}
        for hb, dev in pairs:
            cid, lane = self._where[hb]
            by_chunk.setdefault(cid, []).append((hb, lane, dev))
        staged = []
        for cid, entries in by_chunk.items():
            ch = self._chunks[cid]
            ids = self._pad_ids([])  # all-null: unwanted lanes are no-ops
            for _, lane, dev in entries:
                ids[lane] = dev
            if self.kind is None:
                dk, dv = jnp.asarray(ch["k"]), jnp.asarray(ch["v"])
            else:
                dk = jax.device_put(ch["k"], ch["dev_shard"][0])
                dv = jax.device_put(ch["v"], ch["dev_shard"][1])
            staged.append((cid, entries, dk, dv, ids))
        for cid, entries, dk, dv, ids in staged:
            pkv_ref[0] = self._scatter(pkv_ref[0], dk, dv,
                                       jnp.asarray(ids))
        for cid, entries, _, _, _ in staged:
            ch = self._chunks[cid]
            for hb, _, _ in entries:
                del self._where[hb]
                ch["live"].discard(hb)
            if not ch["live"]:
                del self._chunks[cid]

    def drop(self, host_bids: list[int]) -> None:  # dlint: owner=loop-thread
        """Forget lanes the pool evicted from the host LRU (their content
        is gone for good — the tier's own drop-evict under host
        pressure)."""
        for hb in host_bids:
            loc = self._where.pop(hb, None)
            if loc is None:
                continue
            ch = self._chunks.get(loc[0])
            if ch is not None:
                ch["live"].discard(hb)
                if not ch["live"]:
                    del self._chunks[loc[0]]

    def drop_all(self) -> None:  # dlint: owner=loop-thread
        """Crash recovery twin of BlockPool.reset."""
        self._chunks.clear()
        self._where.clear()

    def warmup(self, pkv):  # dlint: owner=loop-thread
        """Compile the gather/scatter programs and exercise both transfer
        hops on the null block BEFORE serving reaches steady state — a
        first spill under pressure must be a copy, not a compile (the same
        discipline as the generator's copy-on-write warmup). Returns the
        pool (a jit output, keeping the canonical-sharding story)."""
        import jax.numpy as jnp

        ids = jnp.asarray(self._pad_ids([]))
        ck, cv = self._gather(pkv, ids)
        hk, hv = self._to_host(ck), self._to_host(cv)
        if self.kind is None:
            dk, dv = jnp.asarray(hk), jnp.asarray(hv)
        else:
            import jax

            dk = jax.device_put(hk, ck.sharding)
            dv = jax.device_put(hv, cv.sharding)
        return self._scatter(pkv, dk, dv, ids)


class BlockPool:
    """Refcounted physical-block allocator with block-level prefix sharing.

    States of a physical block (id ``1..n_blocks-1``; 0 is the null block):

    * **free** — on the free list; contents meaningless.
    * **live** — refcount >= 1; owned by that many sequences. A block with
      refcount > 1 is *shared* and is never a write target (writes land at
      positions past the shared prefix, in refcount-1 blocks).
    * **cached** — refcount 0 but registered in the prefix index; contents
      preserved for future sharing until LRU eviction recycles it.

    Not thread-safe on its own — the batch scheduler's loop thread owns it,
    the same single-writer discipline as the generator it serves.
    """

    NULL = 0

    def __init__(self, n_blocks: int, block_size: int,
                 n_host_blocks: int = 0):
        if n_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 null + 1 usable), "
                             f"got {n_blocks}")
        self.n_blocks = n_blocks
        self.block_size = block_size
        self._ref = [0] * (n_blocks + max(0, n_host_blocks))
        # LIFO free list: recently freed (cache-warm) blocks recycle first
        self._free = list(range(n_blocks - 1, 0, -1))
        self._cached: OrderedDict[int, None] = OrderedDict()  # LRU, oldest first
        # -- host tier (n_host_blocks > 0): ids n_blocks..n_blocks+H-1 ----
        # host blocks hold COLD registered content only: refcount stays 0,
        # they are never write targets and never appear in block tables —
        # a prefix match returning a host id is the page-in signal
        self.n_host_blocks = max(0, n_host_blocks)
        self._host_free = list(range(n_blocks + self.n_host_blocks - 1,
                                     n_blocks - 1, -1))
        self._host_cached: OrderedDict[int, None] = OrderedDict()  # LRU
        # installed by the generator (the only component allowed to touch
        # the device): spill_fn(dev_bids, host_bids) -> bool executes the
        # batched device→host copies (False/raise = degrade to drop-evict);
        # host_drop_fn(host_bids) tells the mirror to forget lanes the
        # host LRU evicted for good; host_room_fn() -> bool reports
        # whether the mirror's chunk-accounted RAM budget has room for
        # one more spill chunk (fragmented chunks hold buffer on a few
        # live lanes — lane counts alone can't see that)
        self.spill_fn = None  # dlint: owner=loop-thread
        self.host_drop_fn = None  # dlint: owner=loop-thread
        self.host_room_fn = None  # dlint: owner=loop-thread
        # prefix index as a trie over INTEGER chain ids: node key =
        # (parent_chain_id, block_tokens) so every lookup hashes one
        # block's tokens, O(block_size) — a cumulative tuple-of-tuples key
        # would re-hash the whole prefix at every chain step, O(prefix²)
        # per admission on long prompts. Matching stays tuple-EXACT (dict
        # equality on the block tokens), no hash-collision sharing.
        self._nodes: dict[tuple, tuple[int, int]] = {}  # (pcid, blk) -> (cid, bid)
        self._by_parent: dict[int, list[int]] = {}      # pcid -> candidate tails
        self._meta: dict[int, tuple] = {}               # bid -> (kind, pcid, tokens)
        self._next_cid = 1  # 0 is _ROOT (the empty prefix)
        # a window pool's registrations: the FULL pool's chain id -> block
        # (``_meta[bid] = ("keyed", key, None)``); its own trie stays empty
        self._keyed: dict[int, int] = {}

    # -- accounting ----------------------------------------------------------

    def refcount(self, bid: int) -> int:
        return self._ref[bid]

    def is_host(self, bid: int) -> bool:
        """Whether ``bid`` is a host-tier id (cold content in the mirror
        pool; must be paged in before it can be shared or attended)."""
        return bid >= self.n_blocks

    def free_blocks(self) -> int:
        """DEVICE blocks allocatable right now (free + evictable cached —
        with a host tier the cached ones spill instead of dropping, so
        they stay reclaimable capacity either way; host-resident blocks
        are NOT device capacity, paging them in costs device blocks)."""
        return len(self._free) + len(self._cached)

    def used_blocks(self) -> int:
        """Device blocks held by live sequences (refcount >= 1)."""
        return self.n_blocks - 1 - self.free_blocks()

    def shared_blocks(self) -> int:
        """Physical blocks referenced by more than one live sequence."""
        return sum(1 for r in self._ref[1:] if r > 1)

    def host_total_blocks(self) -> int:
        return self.n_host_blocks

    def host_used_blocks(self) -> int:
        """Host-tier blocks holding spilled (cold, registered) content."""
        return self.n_host_blocks - len(self._host_free)

    # -- alloc / free --------------------------------------------------------

    def alloc(self) -> int:  # dlint: owner=loop-thread
        """One fresh DEVICE block (refcount 1). When the free list is dry,
        pressure resolves against the cached LRU: with a host tier armed
        (``spill_fn`` + ``n_host_blocks``), the coldest cached blocks
        SPILL to host (one batched device→host copy, registrations
        rebound — content survives for later page-in); without one — or
        when the spill fails — the LRU cached block is dropped (evicted +
        unregistered), the pre-tier contract. Raises
        :class:`BlockPoolExhausted` when nothing is allocatable —
        including via the ``kv_alloc`` failpoint (chaos-injected
        exhaustion, runtime/failpoints.py)."""
        try:
            failpoints.fire("kv_alloc")
        except failpoints.FailpointError as e:
            raise BlockPoolExhausted(f"injected block-pool exhaustion: {e}") \
                from e
        if not self._free and self._cached:
            self._try_spill()
        if self._free:
            bid = self._free.pop()
        elif self._cached:
            bid, _ = self._cached.popitem(last=False)  # LRU
            self._unregister(bid)
        else:
            raise BlockPoolExhausted(
                f"KV block pool exhausted ({self.n_blocks - 1} blocks, "
                f"block size {self.block_size}) — request stays queued")
        assert self._ref[bid] == 0, (bid, self._ref[bid])
        self._ref[bid] = 1
        return bid

    def share(self, bid: int) -> None:  # dlint: owner=loop-thread
        """Take one more reference on a live or cached DEVICE block. A
        host-resident block cannot be shared directly — the caller must
        page it in first (its content is not attendable)."""
        if bid == self.NULL:
            raise ValueError("cannot share the null block")
        if self.is_host(bid):
            raise ValueError(f"block {bid} is host-resident — page it in "
                             f"before sharing")
        if self._ref[bid] == 0:
            if bid not in self._cached:
                raise ValueError(f"block {bid} is free, not shareable")
            del self._cached[bid]
        self._ref[bid] += 1

    def release(self, bid: int) -> None:  # dlint: owner=loop-thread
        """Drop one reference. At zero, a registered block parks in the
        cached LRU (still shareable); an unregistered one returns to the
        free list. Releasing a free block is a double free and raises."""
        if bid == self.NULL:
            raise ValueError("cannot release the null block")
        if self.is_host(bid):
            raise ValueError(f"block {bid} is host-resident (never "
                             f"refcounted live)")
        if self._ref[bid] <= 0:
            raise ValueError(f"double free of block {bid}")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            if bid in self._meta:
                self._cached[bid] = None  # most-recently-used end
            else:
                self._free.append(bid)

    def reset(self) -> None:  # dlint: owner=loop-thread
        """Forget everything (crash recovery): all blocks free, the prefix
        index cleared so nothing can match rows a half-finished dispatch may
        have corrupted. Host-tier bookkeeping clears too (the mirror's
        buffers are dropped by the generator alongside this)."""
        self._ref = [0] * (self.n_blocks + self.n_host_blocks)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._cached.clear()
        self._host_free = list(range(
            self.n_blocks + self.n_host_blocks - 1, self.n_blocks - 1, -1))
        self._host_cached.clear()
        self._nodes.clear()
        self._by_parent.clear()
        self._meta.clear()
        self._keyed.clear()
        self._next_cid = 1

    def cached_blocks(self) -> int:
        """Registered blocks parked at refcount 0 (shareable until an
        allocation takes them back)."""
        return len(self._cached)

    # -- tiering: spill (device→host) and page-in (host→device) -------------

    def _rebind(self, old_bid: int, new_bid: int) -> None:  # dlint: owner=loop-thread
        """Move one registered block's identity (trie node, CoW candidacy,
        meta) from ``old_bid`` to ``new_bid`` — chain ids are untouched, so
        the prefix chain matches exactly the same prompts afterward."""
        kind, pcid, blk = self._meta.pop(old_bid)
        self._meta[new_bid] = (kind, pcid, blk)
        if kind == "full":
            node = self._nodes.get((pcid, blk))
            if node is not None and node[1] == old_bid:
                self._nodes[(pcid, blk)] = (node[0], new_bid)
        sibs = self._by_parent.get(pcid)
        if sibs is not None:
            for i, b in enumerate(sibs):
                if b == old_bid:
                    sibs[i] = new_bid
                    break

    def _try_spill(self) -> None:  # dlint: owner=loop-thread
        """Spill up to :data:`SPILL_BATCH` LRU cached device blocks to the
        host tier via ``spill_fn``. Host-pool pressure evicts the host
        LRU first (drop for real — the tier's own pre-tier contract). Any
        failure leaves the cached set untouched; the caller falls back to
        drop-evict."""
        if self.spill_fn is None or not self.n_host_blocks:
            return
        want = min(SPILL_BATCH, len(self._cached))
        dropped: list[int] = []

        def _drop_host_lru() -> bool:
            if not self._host_cached:
                return False
            victim, _ = self._host_cached.popitem(last=False)
            self._unregister(victim)
            self._host_free.append(victim)
            dropped.append(victim)
            if self.host_drop_fn is not None:
                # per-victim so the mirror frees a chunk the moment its
                # last lane dies — host_room_fn below watches for that
                self.host_drop_fn([victim])
            return True
        # chunk-budget room FIRST — before any content is destroyed for
        # lane room: a spill the mirror would refuse anyway must not
        # cost the oldest idle sessions their KV. When the budget is
        # full on fragmented chunks (live lanes scattered across them),
        # evicting the host LRU oldest-first eventually kills a whole
        # chunk and frees its buffer; if even draining the whole host
        # LRU can't make chunk room, refuse without touching anything
        # else.
        if self.host_room_fn is not None and not self.host_room_fn():
            while not self.host_room_fn():
                if not _drop_host_lru():
                    return
        # then lane room: the host tier's own LRU drops for real
        while len(self._host_free) < want and self._host_cached:
            _drop_host_lru()
        want = min(want, len(self._host_free))
        if want <= 0:
            return
        devs = [b for b, _ in zip(self._cached, range(want))]  # LRU first
        hosts = [self._host_free.pop() for _ in range(want)]
        try:
            ok = bool(self.spill_fn(devs, hosts))
        except Exception:  # noqa: BLE001 — degrade to drop-evict, never crash alloc
            ok = False
        if not ok:
            self._host_free.extend(reversed(hosts))
            return
        for dev, host in zip(devs, hosts):
            del self._cached[dev]
            self._rebind(dev, host)
            self._host_cached[host] = None  # MRU end
            self._free.append(dev)

    def begin_pagein(self, host_bids: list[int]) -> list[tuple[int, int]]:  # dlint: owner=loop-thread
        """Stage a page-in of ``host_bids`` (host-resident registered
        blocks): pins each out of the host LRU (so a concurrent spill's
        host-room eviction can't drop it) and allocates one fresh device
        block per entry — which may itself spill OTHER cold blocks.
        Returns ``(host_bid, dev_bid)`` pairs; the caller copies the
        content and then :meth:`commit_pagein` (rebinding registrations to
        the device ids, caller owns refcount 1) or :meth:`abort_pagein`
        (restoring the host pins). Atomic: exhaustion mid-way rolls
        everything back and re-raises (the request stays queued)."""
        pairs: list[tuple[int, int]] = []
        pinned: list[int] = []
        try:
            for hb in host_bids:
                if not self.is_host(hb) or hb not in self._host_cached:
                    raise ValueError(f"block {hb} is not host-resident")
                del self._host_cached[hb]  # pin across the allocs below
                pinned.append(hb)
            for hb in pinned:
                pairs.append((hb, self.alloc()))
        except BaseException:
            for _, dev in pairs:
                self.release(dev)
            for hb in pinned:
                self._host_cached[hb] = None
            raise
        return pairs

    def commit_pagein(self, pairs: list[tuple[int, int]]) -> None:  # dlint: owner=loop-thread
        """The copies landed: rebind each registration host→device (the
        exact trie chain survives — chain ids never moved) and return the
        host lanes to the free list. The device blocks keep the refcount 1
        taken in :meth:`begin_pagein` — the caller owns them like
        freshly-shared blocks and releases them at retire, parking them
        back in the (device) cached LRU."""
        for hb, dev in pairs:
            self._rebind(hb, dev)
            self._host_free.append(hb)

    def abort_pagein(self, pairs: list[tuple[int, int]]) -> None:  # dlint: owner=loop-thread
        """The copies failed: free the device blocks (their content never
        materialized) and unpin the host blocks — content intact, still
        registered, a retry can page them in again."""
        for hb, dev in pairs:
            self.release(dev)
            self._host_cached[hb] = None

    # -- prefix sharing ------------------------------------------------------

    def register_prompt(self, bids: list[int], tokens: list[int]) -> None:  # dlint: owner=loop-thread
        """Index a committed prompt's blocks for future sharing. ``tokens``
        are the prefill-built prompt ids (``prompt_ids[:-1]``); ``bids`` must
        cover them (``len(bids) >= ceil(len(tokens)/block_size)``). Full
        blocks chain into the exact-match trie; a partial tail block
        registers as a copy-on-write candidate under its parent chain.
        Blocks already registered (shared prefixes) are skipped."""
        bs = self.block_size
        n_full, tail = divmod(len(tokens), bs)
        cid = _ROOT
        for j in range(n_full):
            blk = tuple(tokens[j * bs:(j + 1) * bs])
            node = self._nodes.get((cid, blk))
            if node is not None:
                cid = node[0]  # chain already indexed (shared or duplicate)
                continue
            bid = bids[j]
            if bid in self._meta:
                # registered under a different chain (cannot normally
                # happen — a block holds one prompt's rows); skip it
                continue
            new_cid = self._next_cid
            self._next_cid += 1
            self._nodes[(cid, blk)] = (new_cid, bid)
            self._by_parent.setdefault(cid, []).append(bid)
            self._meta[bid] = ("full", cid, blk)
            cid = new_cid
        if tail:
            bid = bids[n_full]
            if bid not in self._meta:
                self._by_parent.setdefault(cid, []).append(bid)
                self._meta[bid] = ("partial", cid,
                                   tuple(tokens[n_full * bs:]))

    def register_keyed(self, bid: int, key: int) -> bool:  # dlint: owner=loop-thread
        """Register live block ``bid`` under ``key`` (a window pool's block
        under the full pool's chain id of the same prefix). False, and
        nothing done, where the key or the block is registered already (the
        same content committed twice: the first stays)."""
        if key in self._keyed or bid in self._meta:
            return False
        if self._ref[bid] <= 0:
            raise ValueError(f"block {bid} is not live")
        self._keyed[key] = bid
        self._meta[bid] = ("keyed", key, None)
        return True

    def keyed(self, key: int) -> int | None:
        """The block registered under ``key``, live or parked, or None."""
        return self._keyed.get(key)

    def _unregister(self, bid: int) -> None:  # dlint: owner=loop-thread
        kind, pcid, blk = self._meta.pop(bid)
        if kind == "keyed":
            del self._keyed[pcid]
            return
        if kind == "full":
            node = self._nodes.get((pcid, blk))
            if node is not None and node[1] == bid:
                # descendants become unreachable (match stops at the gap)
                # but each still owns exactly one node entry, freed when
                # ITS block is evicted — the trie stays O(n_blocks)
                del self._nodes[(pcid, blk)]
        sibs = self._by_parent.get(pcid)
        if sibs is not None:
            try:
                sibs.remove(bid)
            except ValueError:
                pass
            if not sibs:
                del self._by_parent[pcid]

    def match_chain(self, tokens) -> tuple[list[int], list[int]]:  # dlint: owner=loop-thread
        """The full blocks of ``tokens`` the index holds, as ``(bids,
        chain ids)``: block ``j``'s chain id names the prefix up to and with
        it, and is what a window pool registers its block ``j`` under."""
        bs = self.block_size
        cid = _ROOT
        bids: list[int] = []
        chain: list[int] = []
        i = 0
        while i + bs <= len(tokens):
            node = self._nodes.get((cid, tuple(tokens[i:i + bs])))
            if node is None:
                break
            cid, bid = node
            bids.append(bid)
            chain.append(cid)
            i += bs
        return bids, chain

    def match_prefix(self, tokens) -> tuple[list[int], int, int | None, int]:  # dlint: owner=loop-thread
        """Longest block-level match of ``tokens`` against the index:
        ``(shared_bids, n_shared_tokens, cow_src_bid, cow_tokens)``.

        ``shared_bids`` are full blocks covering ``n_shared_tokens`` (a
        multiple of block_size) — the caller :meth:`share`\\ s them (no
        refcounts are taken here). ``cow_src_bid``, when not None, is the
        registered block whose first ``cow_tokens`` ids extend the match —
        the caller allocates a fresh block, device-copies the source into
        it, and resumes prefill at ``n_shared_tokens + cow_tokens``."""
        shared, chain = self.match_chain(tokens)
        cid = chain[-1] if chain else _ROOT
        i = len(shared) * self.block_size
        tail = tuple(tokens[i:])
        best_bid, best_r = None, 0
        if tail:
            for bid in self._by_parent.get(cid, ()):
                cand = self._meta[bid][2]
                r = 0
                for a, b in zip(tail, cand):
                    if a != b:
                        break
                    r += 1
                if r > best_r:
                    best_bid, best_r = bid, r
        return shared, i, best_bid, best_r

"""Continuous batched serving — multiple independent sequences, one program.

New capability with no reference analogue (the reference is strictly
single-sequence: one KV cache, one position, SURVEY.md §2.2 "prefill
micro-batching ... Not multi-request batching"). Decode on TPU at batch 1 is
HBM-bandwidth-bound — the whole weight set streams per token for ONE row of
output — so serving throughput scales almost linearly with concurrent
sequences until compute saturates. This module adds that axis:

* a fixed pool of ``n_slots`` sequence slots sharing one KV cache
  ``[L, n_slots, n_kv, S, hd]`` and ONE jitted ragged decode step (per-row
  positions, per-row temperature/top-p/coin — temp 0 rows take argmax), so
  a mixed greedy/sampled batch is a single dispatch;
* per-slot prefill that gathers the slot's cache column, runs the ordinary
  chunked prefill on it, and scatters it back — new requests join without
  recompiling anything (all shapes static);
* a :class:`BatchScheduler` that queues requests beyond the pool, retires
  slots on EOS/limits, and streams tokens per request — the engine-room of
  an OpenAI-style serving front end (serve/api.py ``--batch-slots``).

Determinism: each request carries its own xorshift seed and consumes its own
coin stream, so a request's output is independent of what shares the batch
with it (tested in test_serving.py) — the serving twin of the reference's
fixed-seed reproducibility.
"""

from __future__ import annotations

import os
import statistics
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import (failpoints, flightrec, introspection, numerics, steppack,
               telemetry, tenancy)

from ..models.family import family_of
from ..models.llama import forward, ragged_verify_step_guarded
from ..parallel.api import plan_scoped_jit, use_plan
from ..parallel.multihost import (
    CTRL_SRV_COMMIT,
    CTRL_SRV_INIT,
    CTRL_SRV_PREFILL,
    CTRL_SRV_STEP,
    CTRL_SRV_STEP_CHUNK,
    CTRL_SRV_TAKE,
    CTRL_SRV_VERIFY,
    replicated,
)
from ..tokenizer.sampler import xorshift_random_f32
from ..models.share import N_COUNTS
from .kvblocks import (SPILL_BATCH, BlockPoolExhausted, PageInError,
                       match_windowed, state_bytes, window_blocks_cap,
                       window_first_block)
from .kvcache import KVCache

if TYPE_CHECKING:
    from .engine import InferenceEngine

_MASK64 = (1 << 64) - 1

# tick phases (flightrec.FlightRecorder.tick_phase) open profiler
# annotations from here on: flightrec itself stays importable without jax
flightrec.set_annotation_factory(jax.profiler.TraceAnnotation)

# chunk-free step_wait samples kept for the prefill-cost estimate's
# baseline (_GeneratorCore._settle_prefill)
STEP_WAIT_SAMPLES = 64


class SchedulerError(RuntimeError):
    """Base for admission-time scheduler failures (serve/api.py maps each
    subclass to an HTTP status)."""


class QueueFullError(SchedulerError):
    """Bounded admission: the wait queue is at --max-queue (HTTP 429)."""


class TenantOverBudgetError(QueueFullError):
    """Per-tenant admission: THIS tenant's --tenant-limits token-rate
    bucket ran dry (HTTP 429 with the same backpressure headers as a
    queue-full shed — the subclassing is the contract). Other tenants
    are unaffected; the caller retries after Retry-After."""


class SchedulerUnavailableError(SchedulerError):
    """The scheduler is draining, closed, or crashed past its restart
    budget (HTTP 503)."""


class RequestTimeoutError(SchedulerError):
    """A request's deadline expired before it produced any output
    (HTTP 408). Deadline expiry mid-generation instead finishes the
    request with ``finish_reason="timeout"`` and partial output."""


class HbmAdmissionError(SchedulerError):
    """The HBM admission guard refused the request: estimated + measured
    per-device bytes would exceed the HBM limit (HTTP 503 with the
    reason; ``dllama_hbm_admission_rejects_total``)."""


def check_hbm_admission(engine, n_prompt: int, need_bytes: int) -> None:
    """HBM admission guard, shared by the batch scheduler's ``submit`` and
    the single-sequence API path: before admitting a prompt, cross-check
    the staging-time estimate against the compile ledger's measured
    per-program bytes (PR 3's ``memory_analysis()`` data), plus a
    workspace estimate for any prefill bucket the engine has not
    dispatched yet — a fresh program means fresh XLA temporaries, which is
    exactly where an over-budget admission would OOM the process. Raises
    :class:`HbmAdmissionError` instead of letting that happen; a no-op
    when the device limit is unknown or ``DLLAMA_SKIP_HBM_CHECK`` is
    set."""
    from . import introspection
    from .hbm import admission_check, estimate_prefill_temp_bytes

    scope = getattr(engine, "introspection_scope", None)
    measured = (introspection.ledger().measured_hbm_bytes(scope)
                if scope else {})
    bucket = engine._prefill_chunk_size(max(1, n_prompt - 1))
    extra = (0 if bucket in engine.seen_buckets
             else estimate_prefill_temp_bytes(engine.cfg, bucket))
    ok, reason = admission_check(
        need_bytes=need_bytes, measured_bytes=measured, extra_bytes=extra,
        what=f"admitting a {n_prompt}-token request")
    if not ok:
        telemetry.registry().counter(telemetry.HBM_ADMISSION_REJECTS).inc()
        raise HbmAdmissionError(reason)


@dataclass
class Request:
    rid: int
    prompt_ids: list[int]
    max_tokens: int
    temperature: float = 0.0
    topp: float = 0.9
    seed: int = 0xB1A5
    stop_on_eos: bool = True
    on_token: Callable[[int, str | None], None] | None = None
    # tenant observatory (runtime/tenancy): the canonical tenant label
    # this request's tokens/latency/KV residency are attributed to —
    # already resolved through TenantRegistry.resolve() at submit (the
    # cardinality bound), so accounting sites use it verbatim
    tenant: str = tenancy.ANON
    # filled by the generator:
    tokens: list[int] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    # True when `error` was set by a SERVER-side failure (scheduler crash,
    # shutdown) rather than a per-request reject — the HTTP layer maps
    # these to 503, not 400
    server_error: bool = False
    # set by the CLIENT to stop decoding early (e.g. a stop STRING matched in
    # the emitted text — the raw-token EOS check can't see those); the slot
    # is retired at the next step boundary
    cancel: threading.Event = field(default_factory=threading.Event)
    rng_state: int = 0
    error: str | None = None
    decoder: object = None  # per-request streaming UTF-8 decoder
    # deadline (monotonic ns; 0 = none): past it the scheduler fails the
    # request if still queued, or cancels its slot at the next step
    # boundary — done is ALWAYS set within one loop tick + one step
    deadline_ns: int = 0
    timed_out: bool = False
    # telemetry timeline (monotonic ns; 0 = not reached): submit → admission
    # start → decode armed. Spans derived from these feed the --trace-out
    # JSONL stream and the queue-wait histogram.
    t_submit: int = 0
    t_admit: int = 0
    t_decode: int = 0
    # latency attribution (runtime/flightrec): first-token stamp plus
    # per-phase wall accumulators (ms) the generator fills — queue/
    # admission/prefill/first_decode are derived from these at the first
    # emitted token and must sum to wall TTFT by construction
    t_first_token: int = 0
    # last emitted-run stamp (monotonic ns): the per-tenant ITL
    # histogram records each emit-run's mean inter-token gap from it
    t_last_emit: int = 0
    ms_prefill: float = 0.0       # own prefill chunks' settled cost (_settle_prefill)
    ms_decode_steps: float = 0.0  # decode dispatch wall while slot active
    ms_preempt: float = 0.0       # others' interleaved prefill wall while
    #                               this slot was decode-armed (tick-budget
    #                               preemption share of inter-token stalls)
    ms_verify: float = 0.0        # speculative verify dispatch wall (the
    #                               `verify` ITL attribution cause)
    ms_pagein: float = 0.0        # KV-tier page-in wall during admission
    #                               (resumed sessions restoring spilled
    #                               blocks — the `pagein` TTFT phase)
    ms_kvmigrate: float = 0.0     # peer-KV migration wall while parked
    #                               pre-admission (runtime/kvwire fetch +
    #                               scatter — the `kvmigrate` TTFT phase)
    # KV migration (runtime/kvwire): a peer replica URL whose paged pool
    # holds this prompt's prefix. The scheduler fetches the blocks over
    # the checksummed Q80 wire before admission; ANY failure clears the
    # field and the request admits normally (recompute fallback) — a
    # migration is an optimization, never a correctness dependency.
    kv_peer: str | None = None
    # mid-stream resume (serve/router.py failover): the TAIL of
    # prompt_ids carries this many already-emitted tokens from the dead
    # replica's stream. Admission treats them like any prompt prefix
    # (match/share/chunked prefill, kv_peer migration included); the
    # sampled-coin stream is fast-forwarded by the same count so the
    # continuation draws exactly the coins the dead replica would have
    # (coin i == emitted token i, the spec_coins_consumed invariant).
    resume_from: int = 0
    # speculative accounting (paged/dense spec serving): drafted tokens
    # offered to verify dispatches and the accepted count — the per-request
    # accept rate surfaced in the opt-in `timing` response block
    spec_drafted: int = 0
    spec_accepted: int = 0
    # quality observatory (runtime/evalharness): a teacher-forced eval
    # sequence — admitted and chunk-prefilled like any request, but every
    # chunk dispatches the fused prefill_nll program, the per-chunk NLL
    # values accumulate here (float32, position order), and the sequence
    # retires at end of prefill: no decode, no prefix-index registration.
    score: bool = False
    nll_parts: list = field(default_factory=list)

    def __post_init__(self):
        self.rng_state = self.seed & _MASK64
        for _ in range(self.resume_from):
            _, self.rng_state = xorshift_random_f32(self.rng_state)

    def ttft_breakdown(self) -> dict | None:
        """This request's TTFT decomposition (ms) via the one shared
        phase formula (:func:`flightrec.ttft_phases`), or None until the
        first token (or for direct-generator use with no submit stamp)."""
        if not (self.t_first_token and self.t_submit and self.t_admit
                and self.t_decode):
            return None
        return flightrec.ttft_phases(self.t_submit, self.t_admit,
                                     self.t_decode, self.t_first_token,
                                     self.ms_prefill, self.ms_pagein,
                                     self.ms_kvmigrate)


@dataclass
class _Admission:
    """In-flight incremental prefill of one request into one slot.

    ``pos`` doubles as the prompt cursor: exactly ``pos`` prompt tokens have
    been prefilled, at positions ``[0, pos)``."""

    req: Request
    slot: int
    col: KVCache  # the slot's gathered cache column, being filled
    pos: int = 0
    reused: int = 0  # prefix tokens skipped via cross-slot KV reuse
    bucket: int = 0  # the last chunk's DISPATCHED (padded) width
    # KV tier (paged pool with --kv-host-blocks): outstanding page-in
    # pairs (host_bid, dev_bid) — drained in SPILL_BATCH batches, one per
    # continue_admit call, so a long resume's restore interleaves with
    # the other slots' decode ticks instead of stalling one tick
    pagein: list = field(default_factory=list)
    # device work deferred until the paged-in content is resident: the
    # copy-on-write block copy (src_dev, dst_dev) and — when the source
    # came from the host tier — the rc-1 reference on it to release after
    # the copy; plus the column gather (need_take) for partial reuse
    cow: tuple | None = None
    cow_release: int = 0
    need_take: bool = False
    # window layers: a boundary (in blocks) the full pool matched and the
    # window pool missed, with the match's chain ids: a chunk is cut to end
    # on it and its window is left behind (PagedGenerator._save_window)
    wsave: int = 0
    wchain: list | None = None


class _PendingChunk(NamedTuple):
    """A prefill chunk enqueued and not yet waited for
    (:meth:`_GeneratorCore._settle_prefill`)."""

    req: Request
    slot: int
    width: int      # dispatched (padded) tokens
    n_valid: int
    t_enqueue_ns: int


class _StepIO:
    """One step's way through its three phases
    (:meth:`_GeneratorCore._step_io`): ``step_upload`` while :meth:`call`
    makes the device arguments, ``step_dispatch`` (the plan context and
    the jitted call alone) until :meth:`fetch`, ``step_wait`` from there.
    Every step path goes through this one object, so the phases' names
    stay at the same boundaries on all of them."""

    __slots__ = ("_gen", "span")

    def __init__(self, gen: "_GeneratorCore", span):
        self._gen = gen
        self.span = span  # the open phase: step_wait's once fetch() ran

    def call(self, program, cache, *host, static=()):
        """``program`` is a :func:`steppack.packed_program`: ``host``
        (tokens, positions, then the rest, as the model's step function
        takes them) and the tripwire's poison selector go up as ONE packed
        transfer that the program takes apart again. While a profiler
        listens ``step_upload`` carries that transfer's count and its host
        bytes. The device argument dies with this frame, as the call's
        temporaries did."""
        gen = self._gen
        fields = (*host, gen._poison())
        # fresh every tick: never a buffer a transfer still reads
        words = steppack.pack(fields)
        dev = jnp.asarray(words)
        if self.span.traced:
            self.span.set(arrays=1, bytes=words.nbytes)
        self.span.next_phase("step_dispatch")
        with gen._plan_ctx():
            return program(gen.eng.params, gen.cfg, dev, cache,
                           steppack.layout_of(fields), *static)

    def fetch(self, **outs) -> tuple:
        """The step's outputs on the host, in the order named. Every
        output's copy to the host is started before the first wait, then
        each is fetched under its own ``dllama.step.fetch`` span inside
        ``step_wait``: the first waits for the device, the others find
        their bytes on the host. No sync but the fetches themselves."""
        self.span.next_phase("step_wait")
        for out in outs.values():
            out.copy_to_host_async()
        got = []
        for what, out in outs.items():
            with flightrec.fetch_span(what):
                got.append(np.asarray(out))
        return tuple(got)


@dataclass
class _KVMigration:
    """One in-flight peer-KV pull (runtime/kvwire): the request parks
    here — popped from the queue, not yet admitted — while a daemon
    thread streams frames from the peer. The fetch thread writes ONLY
    this holder (blocks/error/finished) and never touches scheduler or
    pool state; the loop thread commits or falls back in
    ``_service_migrations`` once ``finished`` flips."""

    req: Request
    peer: str
    t0_ns: int
    blocks: list = field(default_factory=list)
    error: BaseException | None = None
    finished: bool = False


@dataclass
class _KVExportJob:
    """One pending ``/v1/kv/export`` gather: the HTTP handler thread
    parks on ``done`` while the loop thread (the pool's owner) runs
    :meth:`PagedGenerator.export_prefix` between ticks."""

    tokens: list[int]
    done: threading.Event = field(default_factory=threading.Event)
    n_tokens: int = 0
    blocks: list = field(default_factory=list)
    error: BaseException | None = None


class _GeneratorCore:
    """Slot-lifecycle machinery shared by the dense slot-pool generator
    (:class:`BatchedGenerator`) and the paged block-pool generator
    (:class:`PagedGenerator`): request emit/retire rules, the non-finite
    tripwire tail, and per-dispatch telemetry. Subclasses own the KV
    storage and the admit/step programs."""

    def _init_core(self, engine: "InferenceEngine", n_slots: int) -> None:
        self.eng = engine
        self.cfg = engine.cfg
        self.n_slots = n_slots
        self.pos = np.zeros(n_slots, dtype=np.int32)
        self.next_token = np.zeros(n_slots, dtype=np.int32)
        self.slots: list[Request | None] = [None] * n_slots
        self.spec = 0
        self._proposers: list = [None] * n_slots
        # telemetry: cached handles (no registry lookups per step)
        self._tm = telemetry.registry()
        self._tm.gauge(telemetry.BATCH_SLOTS).set(n_slots)
        self._m_step_ms = self._tm.histogram(telemetry.BATCH_STEP_MS)
        self._m_occupancy = self._tm.gauge(telemetry.BATCH_OCCUPANCY)
        self._m_tokens = self._tm.counter(telemetry.BATCH_TOKENS)
        self._m_sampler = self._tm.counter(telemetry.SAMPLER_STEPS)
        self._m_kv = self._tm.gauge(telemetry.KV_OCCUPANCY)
        # flight recorder (runtime/flightrec): the scheduler opens/closes
        # ticks; the generator records decisions and dispatch/prefill wall
        # into the open tick — pure host bookkeeping, trace-invisible
        self.flight = flightrec.recorder()
        self._m_ttft_attrib = self._tm.histogram(telemetry.TTFT_ATTRIB_MS)
        self._m_itl_attrib = self._tm.histogram(telemetry.ITL_ATTRIB_MS)
        self._m_prefill_ms = self._tm.histogram(telemetry.PREFILL_CHUNK_MS)
        # prefill chunks enqueued and not yet waited for, and the recent
        # step_wait walls of chunk-free steps (_settle_prefill)
        self._chunks_pending: list[_PendingChunk] = []
        self._step_waits: deque = deque(maxlen=STEP_WAIT_SAMPLES)
        # what recent waits behind queued chunks cost a dispatched token:
        # the next such wait's usual length, which is no stall
        self._chunk_ms_per_token: deque = deque(maxlen=STEP_WAIT_SAMPLES)
        # running totals: plain prefill chunks dispatched, and those of them
        # whose program also stepped at least one live decode row
        # (PagedGenerator._run_rows)
        self._n_chunks = self._n_chunks_rows = 0
        self._m_chunks = self._tm.counter(telemetry.PREFILL_CHUNKS)
        for rows in ("live", "none"):
            self._m_chunks.inc(0, rows=rows)
        # tenant observatory (runtime/tenancy): every accounting site
        # below notes the SAME value it publishes globally, so per-tenant
        # sums reconcile with the global counters bit-exactly
        self._tenancy = tenancy.registry()

    # -- slot lifecycle -----------------------------------------------------

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def can_admit(self, req: Request) -> bool:
        """Whether admission-side capacity exists for ``req`` right now
        (beyond a free slot). The dense pool always says yes; the paged
        pool prices the request in blocks."""
        return True

    def prefix_totals(self) -> tuple[int, int]:
        """``(matched, prompt)``: the prompt tokens admitted since start-up
        and those of them that came from matched blocks. The dense pool
        shares nothing."""
        return 0, 0

    # with window layers: (prompt tokens the full pool matched, admissions
    # whose whole match was used, bytes of the last admission's column);
    # None where a slot's context has no window pool
    def window_totals(self) -> tuple[int, int, int] | None:
        return None

    def take_rows_rode(self) -> bool:  # dlint: owner=loop-thread
        """Whether a prefill chunk's program has stepped the decode rows
        since the last step (asked once a tick: it forgets). Then the tick
        dispatches no step of its own: a tick that carries a chunk is one
        program, and a slot that chunk's commit armed takes its first step
        in the next tick. Only the paged generator of a dense decoder has
        such a program (:meth:`PagedGenerator._step_with_chunk`)."""
        return False

    def abort_admit(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Roll back an admission that will never commit (client cancel
        mid-prefill, or a prefill dispatch raised). The dense pool has
        nothing to undo — the slot column is pool-owned; the paged pool
        releases the blocks taken in ``begin_admit``."""

    def _plan_ctx(self):
        return (use_plan(self.eng.plan) if self.eng.plan is not None
                else nullcontext())

    def _poison(self) -> np.float32:
        """The tripwire's poison selector for one ragged dispatch, the last
        word of the step's packed arguments: always 0 under multihost (root
        AND mirrors — a one-sided injection would desync the replicated
        outputs), else driven by the `logits` failpoint (runtime/numerics)."""
        return np.float32(0.0 if self.eng.multihost
                          else numerics.poison_code())

    @contextmanager
    def _step_io(self, guard: str):
        """``with self._step_io("batch_step") as io:`` — one step or
        verify dispatch under the watchdog's ``guard``, which covers the
        uploads, the call and the wait: ``io.call(program, cache,
        *host_arrays)`` uploads and dispatches, ``io.fetch(...)`` brings
        the outputs back (:class:`_StepIO`)."""
        with self.flight.tick_phase("step_upload") as span, \
                self.eng.watchdog.guard(guard):
            failpoints.fire("step_hang")
            yield _StepIO(self, span)

    def _retire(self, slot: int, reason: str = "done") -> None:  # dlint: owner=loop-thread
        req = self.slots[slot]
        self.slots[slot] = None
        self._proposers[slot] = None
        self._tm.counter(telemetry.RETIRES).inc()
        if req.t_decode:
            telemetry.tracer().emit(req.rid, "decode", req.t_decode,
                                    telemetry.now_ns(), slot=slot,
                                    n_tokens=len(req.tokens))
        self.flight.note("retire", req.rid, reason=reason, slot=slot,
                         n_tokens=len(req.tokens), tenant=req.tenant)
        # speculative accounting charges once, at retire — the same
        # place the per-request accept rate becomes final
        self._tenancy.note_spec(req.tenant, req.spec_drafted,
                                req.spec_accepted)
        # ITL attribution (once per request, at retire): total decode
        # dispatch wall vs the tick-budget preemption stall other
        # admissions' prefill chunks imposed while this slot waited
        if req.t_first_token and len(req.tokens) > 1:
            self._m_itl_attrib.record(req.ms_decode_steps, cause="step")
            self._m_itl_attrib.record(req.ms_preempt, cause="preempt")
            if req.ms_verify:
                # speculative serving: verify dispatch walls are their own
                # cause — a spec-on ITL regression must name the verify
                # widening, not hide inside `step`
                self._m_itl_attrib.record(req.ms_verify, cause="verify")
        req.done.set()

    def _arm_decode(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Shared commit tail: arm ``adm``'s slot for decode (position,
        seed token, per-request streaming decoder, telemetry span)."""
        req = adm.req
        self.pos[adm.slot] = adm.pos
        self.next_token[adm.slot] = req.prompt_ids[-1]
        if self.eng.tokenizer is not None:
            # per-request streaming decoder: a shallow copy shares the vocab
            # tables but owns its UTF-8 carry-over, so interleaved slots
            # can't corrupt each other's multi-byte sequences
            import copy

            req.decoder = copy.copy(self.eng.tokenizer)
            req.decoder._pending = bytearray()
            # resumed stream: replay the already-emitted history through
            # the fresh decoder (output discarded) so its UTF-8 carry-over
            # matches the dead replica's state at the splice point —
            # a kill inside a multi-byte character still decodes exactly
            for t in req.prompt_ids[len(req.prompt_ids) - req.resume_from:]:
                req.decoder.decode(t)
        req.t_decode = telemetry.now_ns()
        if req.t_admit:
            # n_tokens = positions actually prefilled (after prefix reuse),
            # so span counts cross-check dllama_prefix_reuse_tokens_total
            telemetry.tracer().emit(req.rid, "prefill", req.t_admit,
                                    req.t_decode, slot=adm.slot,
                                    n_tokens=adm.pos - adm.reused)
        self.flight.note("decode_armed", req.rid, slot=adm.slot,
                         pos=adm.pos, reused=adm.reused)
        self.slots[adm.slot] = req

    def _note_admitted(self, req: Request, slot: int, reused: int) -> None:
        """Shared admission telemetry, called AFTER the last failable call
        of begin_admit so a reject never skews admissions - retires."""
        req.t_admit = telemetry.now_ns()
        self._tm.counter(telemetry.ADMISSIONS).inc()
        self.flight.note("admit", req.rid, slot=slot, reused=reused,
                         n_prompt=len(req.prompt_ids), tenant=req.tenant)
        if reused:
            self._tm.counter(telemetry.PREFIX_REUSE_TOKENS).inc(reused)
        if req.t_submit:
            wait_ms = (req.t_admit - req.t_submit) / 1e6
            self._tm.histogram(telemetry.QUEUE_WAIT_MS).record(wait_ms)
            # the SAME wait value feeds the tenant's queue-wait histogram
            # (per-tenant count/sum must reconcile with the global one)
            self._tenancy.note_admission(req.tenant, wait_ms)
            telemetry.tracer().emit(req.rid, "queue", req.t_submit,
                                    req.t_admit, slot=slot)
        else:
            self._tenancy.note_admission(req.tenant)

    # -- emit/tripwire tails shared by every dispatch kind ------------------

    def _handle_nonfinite(self, active: list[int], nf) -> set[int]:  # dlint: owner=loop-thread
        """Non-finite tripwire tail for one ragged dispatch: count each
        poisoned row's event (``dllama_nonfinite_total{site="batch"}``);
        with fail-fast armed, fail THAT request explicitly (503-shaped —
        an explicit numerics error instead of garbage tokens) and retire
        its slot, leaving the rest of the batch untouched. Returns the
        retired rows."""
        failed: set[int] = set()
        for i in active:
            n = int(nf[i])
            if n <= 0:
                continue
            numerics.record_nonfinite(n, "batch")
            if getattr(self.eng, "nf_failfast", False):
                req = self.slots[i]
                req.error = str(numerics.nonfinite_error("batch", n))
                req.server_error = True
                self._retire(i, "nonfinite")
                failed.add(i)
        return failed

    def _kv_fraction(self) -> float:
        """Live-context share of the KV storage for the occupancy gauge —
        subclass-specific (rows over the slot pool, blocks over the block
        pool)."""
        raise NotImplementedError

    def _sweep_cancelled(self) -> list[int]:  # dlint: owner=loop-thread
        """Retire client-cancelled slots; return the active row list."""
        for i, s in enumerate(self.slots):
            if s is not None and s.cancel.is_set():
                self._retire(i, "cancel")
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _sampling_rows(self, active: list[int]):
        """Per-row sampling knobs for ONE ragged dispatch (single-step
        form: one xorshift coin drawn and committed per temperature>0
        row — multi-step dispatches pre-draw from a COPY instead, see
        step_chunk). Shared so the coin-stream rules can never diverge
        between the dense and paged paths. The temperatures also decide
        the step's sampler path (``ops.sampling.sampled_token``: the argmax
        alone unless a row samples), counted here once a dispatch."""
        temps = np.zeros(self.n_slots, dtype=np.float32)
        topps = np.zeros(self.n_slots, dtype=np.float32)
        coins = np.zeros(self.n_slots, dtype=np.float32)
        for i in active:
            req = self.slots[i]
            temps[i] = req.temperature
            topps[i] = req.topp
            if req.temperature > 0.0:
                coins[i], req.rng_state = xorshift_random_f32(req.rng_state)
        self._m_sampler.inc(
            path="sampled" if (temps > 0.0).any() else "greedy")
        return temps, topps, coins

    def _record_step(self, n_active: int, ms: float, emitted: int) -> None:
        """Per-dispatch telemetry: occupancy, step latency, emitted tokens,
        KV occupancy (see :meth:`_kv_fraction`), the tick's dispatch
        record."""
        self._m_occupancy.set(n_active)
        self._m_step_ms.record(ms)
        if emitted:
            self._m_tokens.inc(emitted)
            # analytic col-split wire bytes per emitted token (the batched
            # twin of the engine decode paths' accounting)
            self.eng.count_collective_bytes(emitted)
        self._m_kv.set(self._kv_fraction())
        self.flight.note_dispatch(ms, n_active, emitted)

    def _attrib_decode(self, active: list[int], ms: float) -> None:
        """Charge one decode dispatch's wall to every active request
        (called BEFORE tripwire/emit retires can clear slots)."""
        for i in active:
            req = self.slots[i]
            if req is not None:
                req.ms_decode_steps += ms

    def _attrib_verify(self, active: list[int], ms: float) -> None:
        """Charge one speculative verify dispatch's wall to every active
        request under the ``verify`` ITL cause (published at retire)."""
        for i in active:
            req = self.slots[i]
            if req is not None:
                req.ms_verify += ms

    def _safe_draft(self, i: int) -> list[int] | None:  # dlint: owner=loop-thread
        """Slot ``i``'s proposer draft, through the ``draft`` failpoint:
        a poisoned/raising proposer DEGRADES the slot to plain decode for
        this step (returns None; ``dllama_spec_degraded_total``) instead
        of failing the request — the request completes, bystanders are
        untouched, and the proposer stays armed for later steps."""
        try:
            failpoints.fire("draft")
            return self._proposers[i].draft()
        except Exception as e:  # noqa: BLE001 — degrade, never fail the request
            self._tm.counter(telemetry.SPEC_DEGRADED).inc()
            self.flight.note("spec_degraded", self.slots[i].rid,
                             reason=type(e).__name__, slot=i)
            return None

    def _advance_traced(self, adm: "_Admission", span) -> bool:  # dlint: owner=loop-thread
        """``_advance_prefill`` under its ``prefill_dispatch`` phase; while
        a profiler listens the phase names its cause: the request, the
        valid tokens of the chunk this call enqueued, the padded width
        dispatched (both 0 for a call that only paged blocks in) and the
        position the chunk starts at (the context it attends behind)."""
        pos0 = adm.pos
        done = self._advance_prefill(adm)
        if span.traced:
            tokens = adm.pos - pos0
            span.set(rid=adm.req.rid, tokens=tokens,
                     bucket=adm.bucket if tokens else 0, start=pos0)
        return done

    def _prefill_chunk(self, adm: "_Admission", padded, n_valid: int) -> None:
        """One prefill chunk dispatch for ``adm``. The dispatch only
        ENQUEUES the program (nothing here waits for the device, and no
        sync is added for telemetry's sake), so its cost is attributed
        later, by :meth:`_settle_prefill`, when the next step's fetch has
        waited for it."""
        self._enqueue_chunk(adm, padded, n_valid, adm.pos)

    def _enqueue_chunk(self, adm: "_Admission", padded, n_valid: int,
                       pos: int) -> None:
        """The chunk at ``pos`` alone, no decode row beside it."""
        t0 = telemetry.now_ns()
        adm.col = self._exec_prefill(adm.col, padded, pos, n_valid)
        self._note_chunk(adm, len(padded), n_valid, t0, rows="none")

    def _note_chunk(self, adm: "_Admission", width: int, n_valid: int,
                    t_enqueue_ns: int, *, rows: str) -> None:
        """The books of one plain chunk just enqueued: pending until a
        wait settles it, the tenant's tokens, the tick record, and the
        chunk totals (``rows`` is ``live`` where the chunk's program also
        stepped the tick's decode rows, ``none`` otherwise)."""
        self._chunks_pending.append(
            _PendingChunk(adm.req, adm.slot, width, n_valid, t_enqueue_ns))
        self._tenancy.note_prefill_tokens(adm.req.tenant, n_valid)
        # the tick that dispatched the chunk spent the tokens; the wall
        # lands in the tick whose step waited for it (usually this one)
        self.flight.note_prefill(adm.req.rid, 0.0, n_valid)
        self._n_chunks += 1
        self._n_chunks_rows += rows == "live"
        self._m_chunks.inc(rows=rows)

    def _settle_prefill(self, t_wait0_ns: int, t_wait1_ns: int, *,
                        rode: bool = False) -> None:  # dlint: owner=loop-thread
        """Attribute the prefill chunks enqueued since the last step,
        now that a step's ``step_wait`` (``t_wait0_ns``..``t_wait1_ns``)
        has waited for them. The device runs programs in dispatch order,
        so the wall from the first pending chunk's enqueue to the end of
        this wait holds the chunks AND the step; less the running median
        wait of chunk-free steps it is the chunks' device-inclusive
        cost, split over them by dispatched width. Each share goes to
        the admission's own ``ms_prefill``, to every decode-armed
        request's ``ms_preempt`` (the chunk ran in front of their next
        token), to the tick record, to ``dllama_prefill_chunk_ms`` and a
        ``prefill_chunk`` span. With no chunk pending the wait is a
        baseline sample. Until a chunk-free step has been seen (a cold
        server's first request) the baseline is 0 and the first step's
        own time is charged with the chunks, once.

        ``rode``: the wait was for a chunk's program that stepped the rows
        itself (:meth:`PagedGenerator._run_rows`), so no step's own wait
        lies in the wall behind the chunks. They are charged the whole
        wall, shares that sum to it; the bystanders, whose token came out
        of the same program, are stalled by what the wall holds beyond a
        step of their own, as before."""
        pending = self._chunks_pending
        if not pending:
            self._step_waits.append((t_wait1_ns - t_wait0_ns) / 1e6)
            return
        self._chunks_pending = []
        base = statistics.median(self._step_waits) if self._step_waits else 0.0
        t0 = pending[0].t_enqueue_ns
        wall = (t_wait1_ns - t0) / 1e6
        stall = max(0.0, wall - base)
        total = wall if rode else stall
        width = sum(c.width for c in pending)
        # a prompt's chunks are enqueued in a burst and the step waits for
        # all of them: the wait is as long as they are many, and only what
        # it lasted beyond their usual cost is held against the stall limit
        if self._chunk_ms_per_token:
            self.flight.note_queued(
                width * statistics.median(self._chunk_ms_per_token))
        self._chunk_ms_per_token.append(total / width)
        for c in pending:
            ms = total * c.width / width
            c.req.ms_prefill += ms
            for s in self.slots:
                if s is not None and s is not c.req:
                    s.ms_preempt += stall * c.width / width
            self._m_prefill_ms.record(ms)
            self.flight.note_prefill(c.req.rid, ms, 0)
            t1 = t0 + int(ms * 1e6)
            telemetry.tracer().emit(c.req.rid, "prefill_chunk", t0, t1,
                                    slot=c.slot, n_tokens=c.n_valid)
            t0 = t1

    def _record_ttft_attrib(self, req: Request) -> None:
        """Publish the TTFT decomposition (:meth:`Request.ttft_breakdown`)
        at the first emitted token."""
        bd = req.ttft_breakdown()
        if bd is None:
            return  # direct-generator use (tests) has no submit stamp
        flightrec.record_ttft(self._m_ttft_attrib, bd)

    # -- teacher-forced eval (the quality observatory) ----------------------

    def _exec_prefill_nll(self, col, padded, targets, pos: int):
        """One teacher-forced NLL chunk over a slot column: the engine's
        jitted ``prefill_nll`` program (fused log-softmax-gather — the
        chunk's full-vocab logits never leave the device) on the SAME
        padded chunk the plain prefill would dispatch, so eval chunking
        stays bit-comparable to the engine oracle's."""
        with self.eng.watchdog.guard("batch_prefill"):
            failpoints.fire("step_hang")
            with self._plan_ctx():
                nll, col = self.eng._nll_step(
                    self.eng.params, self.cfg,
                    jnp.asarray(np.asarray(padded).reshape(1, -1), jnp.int32),
                    jnp.asarray(np.asarray(targets).reshape(1, -1),
                                jnp.int32),
                    jnp.int32(pos), col)
            return nll, col

    def _prefill_nll_chunk(self, adm: "_Admission", padded, targets,
                           n_valid: int) -> None:
        """The scoring twin of :meth:`_prefill_chunk`: same timing,
        attribution (own prefill wall, bystanders' preempt stall), and
        ``prefill_chunk`` span, plus the chunk's host-fetched NLL values
        appended to the request — sliced to the valid positions, so the
        padding rows' garbage never reaches a sum."""
        t0 = telemetry.now_ns()
        nll, adm.col = self._exec_prefill_nll(adm.col, padded, targets,
                                              adm.pos)
        vals = np.asarray(nll[0, :n_valid], dtype=np.float32)
        t1 = telemetry.now_ns()
        ms = (t1 - t0) / 1e6
        adm.req.ms_prefill += ms
        for s in self.slots:
            if s is not None:
                s.ms_preempt += ms
        bad = int(vals.size - np.count_nonzero(np.isfinite(vals)))
        if bad:
            numerics.record_nonfinite(bad, "eval")
        adm.req.nll_parts.append(vals)
        self._tenancy.note_prefill_tokens(adm.req.tenant, n_valid)
        self.flight.note_prefill(adm.req.rid, ms, n_valid)
        telemetry.tracer().emit(adm.req.rid, "prefill_chunk", t0, t1,
                                slot=adm.slot, n_tokens=n_valid)

    def _finish_score(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Retire a teacher-forced eval admission at end of prefill: eval
        sequences never decode — the scored chunks ARE the work. RETIRES
        balances begin_admit's ADMISSIONS increment, and the ``eval``
        span covers admission start → last NLL chunk so eval traffic is
        attributable in timelines next to user requests."""
        req = adm.req
        self._tm.counter(telemetry.RETIRES).inc()
        n = max(0, len(req.prompt_ids) - 1)
        telemetry.tracer().emit(req.rid, "eval",
                                req.t_admit or telemetry.now_ns(),
                                telemetry.now_ns(), slot=adm.slot,
                                n_tokens=n)
        self.flight.note("eval_done", req.rid, slot=adm.slot, n_tokens=n)
        req.done.set()

    def flight_blocks(self) -> dict | None:
        """Block-pool occupancy for the tick record (paged pool only)."""
        return None

    def kv_blocks_by_slot(self, slot: int) -> float:
        """KV blocks slot ``slot`` holds right now, for the tenant
        observatory's device block-second charging. The dense pool has
        no blocks — one synthetic block per slot column (the whole
        column is reserved whether short or long); the paged pool
        reports the slot's real block count."""
        return 1.0

    def _emit_run(self, i: int, run: list[int]) -> int:  # dlint: owner=loop-thread
        """Deliver a run of tokens to slot ``i``'s request: append, stream,
        advance position, retire on EOS / limits. Returns tokens emitted.
        The run is pre-truncated to the ACCEPTED prefix; EOS/max_tokens
        truncation happens here so both step paths share the exact rules."""
        req = self.slots[i]
        tok = self.eng.tokenizer
        n_keep = min(len(run), req.max_tokens - len(req.tokens))
        if n_keep <= 0:  # belt: the scheduler retires at max_tokens
            self._retire(i, "max_tokens")
            return 0
        retire = n_keep < len(run)
        hit_eos = False
        for j in range(n_keep):
            t = run[j]
            if req.stop_on_eos and tok is not None and tok.is_eos(t):
                n_keep, retire, hit_eos = j + 1, True, True
                break
        run = run[:n_keep]
        self.pos[i] += len(run)
        self.next_token[i] = run[-1]
        t_emit = telemetry.now_ns()
        if req.t_first_token == 0:
            # first emitted token: stamp + publish the TTFT decomposition
            req.t_first_token = t_emit
            self.flight.note("first_token", req.rid, slot=i)
            self._record_ttft_attrib(req)
            if req.t_submit:
                self._tenancy.note_ttft(
                    req.tenant, (t_emit - req.t_submit) / 1e6)
        elif req.t_last_emit:
            # later runs: the run's mean inter-token gap, weighted by its
            # token count — a spec-accepted burst records its true
            # per-token latency, not one misleading burst-sized gap
            self._tenancy.note_itl(
                req.tenant, (t_emit - req.t_last_emit) / 1e6 / len(run),
                n=len(run))
        req.t_last_emit = t_emit
        self._tenancy.note_decode_tokens(req.tenant, len(run))
        req.tokens.extend(run)
        if self._proposers[i] is not None:
            self._proposers[i].extend(run)
        for t in run:
            piece = req.decoder.decode(t) if req.decoder is not None else None
            if req.on_token is not None:
                req.on_token(t, piece)
        if (retire or len(req.tokens) >= req.max_tokens
                or self.pos[i] >= self.cfg.seq_len):
            self._retire(i, "eos" if hit_eos
                         else "max_tokens" if len(req.tokens) >= req.max_tokens
                         else "ctx_full")
        return len(run)


class BatchedGenerator(_GeneratorCore):
    """Slot pool + the ragged batched decode step. Not thread-safe by itself
    (the scheduler serializes access)."""

    def __init__(self, engine: "InferenceEngine", n_slots: int = 4, *,
                 _mirror: bool = False):
        if getattr(engine, "dp", 1) > 1 and n_slots % engine.dp != 0:
            raise ValueError(
                f"--batch-slots {n_slots} must divide over dp={engine.dp} "
                f"(the slot pool is the dp-sharded batch axis)")
        # multihost: the ROOT's generator broadcasts every device-mutating op
        # over the control channel (parallel.multihost CTRL_SRV_*) and
        # workers replay them on a mirror generator built by worker_serve —
        # the reference's API-server-drives-the-worker-mesh shape
        # (dllama-api.cpp:599-613). A worker must not construct one directly.
        if engine.multihost and not engine._is_root and not _mirror:
            raise ValueError("on worker processes batched serving runs via "
                             "worker_serve's mirror, not directly")
        # the engine's admission-time HBM check budgeted a batch-1 KV; the
        # slot pool multiplies that by n_slots, so re-check before
        # allocating (runtime.hbm — a staging OOM can wedge the TPU
        # backend for hours). The check now DEGRADES instead of refusing:
        # the largest dp-divisible pool that fits serves (with a loud
        # warning), and only a pool where even dp slots don't fit still
        # raises. KV per device: the slot pool is dp-sharded, so a device
        # holds n_slots/dp columns — plus ONE more for the engine's
        # still-resident batch-1 cache; weights and the layer-stacked KV
        # shard over tp×pp (same n_shards as the engine's load-time
        # check; dp replicates weights). Computed BEFORE the worker
        # broadcast so every process builds the same (possibly degraded)
        # pool; worker mirrors take the packet's count as-is.
        from .hbm import check_budget, estimate_device_bytes, fit_batch_slots

        dp = max(1, getattr(engine, "dp", 1))
        if _mirror:
            # a mirror takes the packet's (possibly root-degraded) slot
            # count as-is — degrading independently would desync the
            # replay — but still refuses a pool ITS device can't hold
            est = estimate_device_bytes(
                engine.cfg,
                weight_repr=getattr(engine, "hbm_weight_repr", "q40"),
                kv_dtype_bytes=engine.kv_dtype.itemsize,
                batch=n_slots // dp + 1, n_shards=engine.tp * engine.pp,
                offload=(engine.weight_mode == "offload"))
            check_budget(est["need_per_device"],
                         f"batched serving ({n_slots} slots)")
        else:
            n_fit, est = fit_batch_slots(
                engine.cfg, n_slots,
                weight_repr=getattr(engine, "hbm_weight_repr", "q40"),
                kv_dtype_bytes=engine.kv_dtype.itemsize,
                n_shards=engine.tp * engine.pp, dp=dp,
                offload=(engine.weight_mode == "offload"))
            if n_fit == 0:
                check_budget(est["need_per_device"],
                             f"batched serving ({n_slots} slots)")
            if n_fit < n_slots:
                print(f"⚠️ HBM admission guard: --batch-slots {n_slots} "
                      f"does not fit the device budget — degrading to "
                      f"{n_fit} slots instead of risking an OOM "
                      f"(runtime/hbm.py)", flush=True)
                n_slots = n_fit
        self._root_bcast = engine.multihost and engine._is_root
        if self._root_bcast:
            # FIRST thing before any device work: the slot-pool KV below is
            # device_put onto a sharding that spans every process, which
            # blocks until all processes participate — the worker must be
            # building its mirror generator concurrently, not still waiting
            # in its packet loop
            engine._ctrl.send(engine._ctrl.encode_raw(CTRL_SRV_INIT,
                                                      n_slots, ()))
        self._init_core(engine, n_slots)
        # the staging-time pool estimate the submit-time admission guard
        # cross-checks against measured per-program bytes
        self.hbm_need = est["need_per_device"]
        kv = KVCache.create(self.cfg, batch_size=n_slots,
                            dtype=engine.kv_dtype)
        if engine.plan is not None:
            from ..parallel.sharding import kv_cache_sharding

            kv = jax.device_put(kv, kv_cache_sharding(engine.plan, kv))
        self.kv = kv
        # per-slot PREFILL context: _ctx[s][p] is the prompt token whose KV
        # row sits at position p of slot s, for the prefill-built region
        # only. Survives retirement: retired slots DO keep riding every
        # dispatch as temp-0 rows writing at pos[i] (clamped for the
        # K+1-wide spec write), but those writes land at/above pos[i],
        # which never goes below the prefill-built region — the invariant
        # pos[i] >= len(_ctx[i]) (debug-asserted in step()) is what keeps
        # the reusable prefix rows intact. So a new request whose prompt
        # shares a prefix with ANY slot's prompt — live or retired — skips
        # prefilling that prefix (cross-slot KV reuse: the batched analogue
        # of the API's single-sequence NaiveCache, amortizing shared system
        # prompts). Exact: the reused rows were computed by the same
        # prefill-shaped program a solo run would use; decode-built rows are
        # deliberately NOT matched (a decode-shaped dispatch may differ in
        # the last ulp from the prefill that solo-C would run — golden_assets
        # documents ulp flips becoming token flips).
        self._ctx: list[list[int] | None] = [None] * n_slots

        # one fused ragged step: forward + per-row sample (greedy rows mixed
        # in via temperature 0), and the chunked ragged decode (engine
        # --decode-chunk composed with --batch-slots: K fused steps over the
        # whole pool per dispatch, K× fewer dispatches, host-loop ticks and,
        # under multihost, control packets, when every active slot has K rows
        # of headroom): the model's step functions behind their packed
        # arguments (steppack.jit_packed_step). The ENGINE owns both, so a
        # second generator on this engine (a supervised restart builds one)
        # shares the executables the first compiled: a fresh wrapper here
        # would recompile a full-model program (minutes on real models).
        # Under multihost the host-read outputs (picked tokens, verify accept
        # counts) must be REPLICATED or np.asarray on a non-addressable
        # global array throws: the programs are
        # parallel.multihost.replicated's, as the engine's solo ones are.
        _sc = getattr(engine, "introspection_scope", None) or "default"
        self._step = engine._packed_sampled_step
        self._steps = engine._packed_sampled_steps
        # speculative serving (engine --spec-lookup): per-slot prompt-lookup
        # drafts verified in the ragged program. Greedy rows accept runs;
        # sampled rows keep their exact one-token/one-coin behavior, so every
        # request's output still matches its solo run.
        self.spec = max(0, getattr(engine, "spec_lookup", 0))
        self._proposers: list = [None] * n_slots
        if self.spec:
            self._verify = steppack.jit_packed_step(
                replicated(ragged_verify_step_guarded) if engine.multihost
                else ragged_verify_step_guarded, scope=_sc,
                name=("_replicated_ragged_verify" if engine.multihost
                      else "ragged_verify_step"))
        # non-multihost engine._step IS jit(forward) with these options;
        # multihost needs plain forward (the engine's replicated_forward
        # constrains logits this path discards, but matching the seed's
        # prefill program exactly keeps worker mirrors bit-identical)
        self._prefill_fwd = (plan_scoped_jit(forward, scope=_sc,
                                             static_argnums=1,
                                             donate_argnums=(4,))
                             if engine.multihost else engine._step)
        # slot-column gather/scatter for per-slot prefill. Raw jit is
        # deliberate: these lambdas are plan-independent data movement
        # (no constrain() in the bodies), so the plan-scoped per-engine
        # cache argument does not apply and sharing their executables
        # across engines is correct.
        self._take = jax.jit(  # dlint: disable=jit-entry
            lambda kv, b: KVCache(
                k=jax.lax.dynamic_slice_in_dim(kv.k, b, 1, axis=1),
                v=jax.lax.dynamic_slice_in_dim(kv.v, b, 1, axis=1)))
        self._put = jax.jit(  # dlint: disable=jit-entry
            lambda kv, col, b: KVCache(
                k=jax.lax.dynamic_update_slice_in_dim(kv.k, col.k, b, axis=1),
                v=jax.lax.dynamic_update_slice_in_dim(kv.v, col.v, b, axis=1)),
            donate_argnums=(0,))
    # -- multihost mirror plumbing ------------------------------------------
    #
    # Every method below that touches device state is split root/worker
    # style: the public caller broadcasts the op (root only), then both
    # sides run the SAME _exec_* body — one code path, no drift.

    def _bcast(self, kind: int, aux: int = 0, payload=()) -> None:
        if self._root_bcast:
            self.eng._ctrl.send(self.eng._ctrl.encode_raw(kind, aux, payload))

    @staticmethod
    def _f32bits(*vecs) -> np.ndarray:
        return np.concatenate(
            [np.asarray(v, np.float32) for v in vecs]).view(np.int32)

    def _exec_take(self, src: int):
        return self._take(self.kv, src)

    def _exec_prefill(self, col, padded, pos: int, n_valid: int):
        # a dense decoder pads freely (padded K/V rows are overwritten
        # later): n_valid is the paged generator's, for a recurrent state
        del n_valid
        with self.eng.watchdog.guard("batch_prefill"):
            failpoints.fire("step_hang")
            with self._plan_ctx():
                _, col = self._prefill_fwd(
                    self.eng.params, self.cfg,
                    jnp.asarray(np.asarray(padded).reshape(1, -1), jnp.int32),
                    jnp.int32(pos), col)
            return col

    def _exec_commit(self, slot: int, col) -> None:
        self.kv = self._put(self.kv, col, slot)

    def _exec_step(self, tokens, pos, temps, topps, coins):
        with self._step_io("batch_step") as io:
            self._wait = io.span
            (nxt, nf), self.kv = io.call(
                self._step, self.kv, np.asarray(tokens, np.int32)[:, None],
                np.asarray(pos, np.int32), np.asarray(temps, np.float32),
                np.asarray(topps, np.float32), np.asarray(coins, np.float32))
            return io.fetch(tokens=nxt, nonfinite=nf)

    def _exec_step_chunk(self, tokens, pos, temps, topps, coins, k: int):
        with self._step_io("batch_chunk") as io:
            self._wait = io.span
            (toks, nf), self.kv = io.call(
                self._steps, self.kv, np.asarray(tokens, np.int32),
                np.asarray(pos, np.int32), np.asarray(temps, np.float32),
                np.asarray(topps, np.float32), np.asarray(coins, np.float32),
                static=(k,))
            return io.fetch(tokens=toks, nonfinite=nf)  # [B, k], [B]

    def _exec_verify(self, toks_2d, pos, temps, topps, coins):
        with self._step_io("batch_verify") as io:
            self._wait = io.span
            (n_acc, preds, nf), self.kv = io.call(
                self._verify, self.kv, np.asarray(toks_2d, np.int32),
                np.asarray(pos, np.int32), np.asarray(temps, np.float32),
                np.asarray(topps, np.float32), np.asarray(coins, np.float32))
            return io.fetch(accepted=n_acc, tokens=preds, nonfinite=nf)

    # -- slot lifecycle -----------------------------------------------------

    def begin_admit(self, req: Request, slot: int) -> "_Admission":  # dlint: owner=loop-thread
        """Start admitting a request into ``slot``: the slot's cache column
        is gathered to a [L, 1, ...] view and prefilled INCREMENTALLY — one
        n_batches chunk per :meth:`continue_admit` call — so a long prompt
        never stalls the active slots' decode steps (the scheduler
        interleaves chunks with :meth:`step`)."""
        ids = req.prompt_ids
        assert ids, "empty prompt"
        limit = self.cfg.seq_len - self.spec  # spec: the K+1-wide dispatch
        # needs spec+1 free rows past the prompt or it could never run once
        if len(ids) >= limit:
            raise ValueError(
                f"prompt of {len(ids)} tokens exceeds the usable context "
                f"({limit} = seq_len {self.cfg.seq_len}"
                + (f" - spec-lookup {self.spec}" if self.spec else "") + ")")
        # teacher-forced eval (runtime/evalharness): every position must
        # be scored, so cross-slot prefix reuse is disabled — a matched
        # prefix would skip its NLL terms and the run would no longer be
        # bit-comparable to the single-sequence oracle
        src, k = (0, 0) if req.score else self._best_prefix(ids[:-1])
        self._bcast(CTRL_SRV_TAKE, src if k else slot, [slot])
        adm = _Admission(req=req, slot=slot,
                         col=self._exec_take(src if k else slot),
                         reused=k)
        adm.pos = k  # prefill resumes after the reused prefix
        # telemetry AFTER the last failable call: a raise anywhere above
        # (prompt too long, device error) leaves ADMISSIONS untouched, so
        # the scheduler's reject path never skews admissions - retires
        self._note_admitted(req, slot, k)
        return adm

    def _best_prefix(self, rest: list[int]) -> tuple[int, int]:
        """(source slot, longest shared context prefix) over all slots."""
        best, best_k = 0, 0
        for s, ctx in enumerate(self._ctx):
            if not ctx:
                continue
            k = 0
            for a, b in zip(rest, ctx):
                if a != b:
                    break
                k += 1
            if k > best_k:
                best, best_k = s, k
        return best, best_k

    def continue_admit(self, adm: "_Admission") -> bool:  # dlint: owner=loop-thread
        """Run one prefill chunk; True when the slot is armed for decode."""
        with self.flight.tick_phase("prefill_dispatch") as span:
            if not self._advance_traced(adm, span):
                return False
        with self.flight.tick_phase("admit_commit") as span:
            self._commit_admit(adm)
            if span.traced:
                span.set(rid=adm.req.rid)
        return True

    def _advance_prefill(self, adm: "_Admission") -> bool:  # dlint: owner=loop-thread
        """Dispatch the next chunk; True once the prompt is prefilled."""
        rest = adm.req.prompt_ids[:-1]
        if adm.pos < len(rest):
            # same bucketed chunk sizing as engine.prefill (TPU-sized
            # dispatches; pinned --nbatches pins it here too)
            n_b = self.eng._prefill_chunk_size(len(rest) - adm.pos)
            chunk = rest[adm.pos:adm.pos + n_b]
            pad_to = min(n_b, self.cfg.seq_len - adm.pos)
            padded = chunk + [0] * (pad_to - len(chunk))
            if adm.req.score:
                # teacher-forced eval chunk: NO worker broadcast (eval is
                # gated off multihost at submit) — the fused NLL program
                # replaces the plain prefill on the same padded chunk
                tgt = adm.req.prompt_ids[adm.pos + 1:
                                         adm.pos + 1 + len(chunk)]
                tgt = tgt + [0] * (len(padded) - len(chunk))
                self._prefill_nll_chunk(adm, padded, tgt, len(chunk))
            else:
                self._bcast(CTRL_SRV_PREFILL, adm.slot, [adm.pos] + padded)
                self._prefill_chunk(adm, padded, len(chunk))
            adm.bucket = len(padded)
            self.eng.seen_buckets.add(adm.bucket)  # the DISPATCHED width
            adm.pos += len(chunk)
            if adm.pos < len(rest):
                return False
        return True

    def _commit_admit(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Commit the prefilled column and arm the slot for decode."""
        if adm.req.score:
            # eval sequences are done at end of prefill: no commit (the
            # scored column is discarded — the slot's pool rows and any
            # recorded prefix context stay exactly as the previous
            # occupant left them), no proposer, no decode arming
            self._finish_score(adm)
            return
        self._bcast(CTRL_SRV_COMMIT, adm.slot)
        self._exec_commit(adm.slot, adm.col)
        self._ctx[adm.slot] = list(adm.req.prompt_ids[:-1])
        if self.spec:
            from .speculative import NgramProposer

            self._proposers[adm.slot] = NgramProposer(self.spec)
            self._proposers[adm.slot].extend(adm.req.prompt_ids)
        self._arm_decode(adm)

    def admit(self, req: Request, slot: int) -> None:  # dlint: owner=loop-thread
        """Admit in one go (tests / non-interleaved callers)."""
        adm = self.begin_admit(req, slot)
        while not self.continue_admit(adm):
            pass

    def reset_state(self) -> None:  # dlint: owner=loop-thread
        """Forget every slot, cached prefix, and proposer — crash
        recovery. The pool restarts logically empty: ``_ctx`` is cleared
        so no later admission can prefix-match rows a half-finished
        dispatch may have corrupted, and positions return to 0 (the next
        prefill overwrites the rows it needs). Device buffers are kept;
        if a crash left ``self.kv`` donated/invalid, the next dispatch
        raises and the supervisor's restart budget converges to unready."""
        self.slots = [None] * self.n_slots
        self._ctx = [None] * self.n_slots
        self._proposers = [None] * self.n_slots
        self._chunks_pending = []
        self.pos[:] = 0
        self.next_token[:] = 0
        self._m_occupancy.set(0)
        self._m_kv.set(0.0)

    # -- the batched step ---------------------------------------------------

    def step(self) -> int:  # dlint: owner=loop-thread
        """One ragged decode step for every active slot; returns the number
        of tokens emitted. Inactive slots ride along as temp-0 rows writing
        into their own (unused) cache positions — static shapes, one
        compiled program regardless of occupancy."""
        with self.flight.tick_phase("step_prepare"):
            active = self._sweep_cancelled()
            if self.spec:
                # the K+1-wide cache write would CLAMP (and corrupt
                # earlier rows) past seq_len - spec - 1: retire slots that
                # close to the cap before dispatching (non-spec mode
                # retires at seq_len; spec trades the last few positions
                # of capacity for run dispatches)
                for i in list(active):
                    if self.pos[i] + self.spec + 1 > self.cfg.seq_len:
                        self._retire(i, "ctx_full")
                        active.remove(i)
            if not active:
                return 0
            if __debug__:
                # cross-slot prefix-reuse safety: every slot with a
                # recorded prefill context must have its write cursor
                # at/above that region, or a ride-along write could
                # corrupt reusable rows
                for i, ctx in enumerate(self._ctx):
                    assert ctx is None or self.pos[i] >= len(ctx), (
                        i, int(self.pos[i]), len(ctx))
            temps, topps, coins = self._sampling_rows(active)
            if self._root_bcast and not self.spec:
                # payload built only when it will be sent
                self._bcast(CTRL_SRV_STEP, 0, np.concatenate([
                    self.next_token.astype(np.int32),
                    self.pos.astype(np.int32),
                    self._f32bits(temps, topps, coins)]))
        if self.spec:
            return self._spec_step(active, temps, topps, coins)
        t0 = time.perf_counter()
        nxt, nf = self._exec_step(self.next_token, self.pos, temps, topps,
                                  coins)
        ms = (time.perf_counter() - t0) * 1000.0
        with self.flight.tick_phase("emit"):
            self._settle_prefill(self._wait.t0_ns, self._wait.t1_ns)
            self._attrib_decode(active, ms)
            poisoned = self._handle_nonfinite(active, nf)
            emitted = 0
            for i in active:
                if i in poisoned:
                    continue
                emitted += self._emit_run(i, [int(nxt[i])])
        with self.flight.tick_phase("bookkeeping"):
            self._record_step(len(active), ms, emitted)
        return emitted

    def step_chunk(self, k: int) -> int:  # dlint: owner=loop-thread
        """K fused ragged decode steps in one dispatch (models.sampled_steps_guarded, ragged form).

        Falls back to :meth:`step` when chunking can't apply this tick:
        k<=1, speculative mode (spec already multiplies tokens/dispatch), or
        an active slot without k rows of context headroom (the tail runs
        single steps — same policy as the engine's chunked decode). Each
        row's host xorshift coins are pre-drawn from a COPY of its RNG
        state; after EOS/limit truncation the state is committed by exactly
        the kept count, so every request's coin stream stays bit-identical
        to its solo run."""
        if k <= 1 or self.spec:
            return self.step()
        with self.flight.tick_phase("step_prepare"):
            active = self._sweep_cancelled()
            fits = active and not (
                any(self.pos[i] + k > self.cfg.seq_len for i in active)
                or any(self.slots[i].max_tokens - len(self.slots[i].tokens)
                       < k for i in active))
            if fits:
                temps = np.zeros(self.n_slots, dtype=np.float32)
                topps = np.zeros(self.n_slots, dtype=np.float32)
                coins = np.zeros((k, self.n_slots), dtype=np.float32)
                for i in active:
                    req = self.slots[i]
                    temps[i] = req.temperature
                    topps[i] = req.topp
                    if req.temperature > 0.0:
                        st = req.rng_state  # COPY: committed after truncation
                        for j in range(k):
                            coins[j, i], st = xorshift_random_f32(st)
                if self._root_bcast:
                    self._bcast(CTRL_SRV_STEP_CHUNK, k, np.concatenate([
                        self.next_token.astype(np.int32),
                        self.pos.astype(np.int32),
                        self._f32bits(temps, topps, coins.reshape(-1))]))
        if not active:
            return 0
        if not fits:
            return self.step()
        t0 = time.perf_counter()
        toks, nf = self._exec_step_chunk(self.next_token, self.pos, temps,
                                         topps, coins, k)
        step_ms = (time.perf_counter() - t0) * 1000.0
        with self.flight.tick_phase("emit"):
            self._settle_prefill(self._wait.t0_ns, self._wait.t1_ns)
            self._attrib_decode(active, step_ms)
            poisoned = self._handle_nonfinite(active, nf)
            emitted = 0
            for i in active:
                if i in poisoned:
                    continue
                req = self.slots[i]
                sampled = req.temperature > 0.0
                n = self._emit_run(i, [int(t) for t in toks[i]])
                emitted += n
                if sampled:
                    st = req.rng_state
                    for _ in range(n):  # commit exactly the kept draws
                        _, st = xorshift_random_f32(st)
                    req.rng_state = st
        with self.flight.tick_phase("bookkeeping"):
            self._record_step(len(active), step_ms, emitted)
        return emitted

    def _kv_fraction(self) -> float:
        """Pooled KV occupancy: rows holding LIVE requests' context / total
        rows — retired slots keep stale pos for prefix reuse but their rows
        are reclaimable, so they must not count as occupied."""
        live = sum(int(self.pos[i]) for i, s in enumerate(self.slots)
                   if s is not None)
        return live / (self.n_slots * self.cfg.seq_len)

    def _spec_step(self, active: list[int], temps, topps, coins) -> int:  # dlint: owner=loop-thread
        """One ragged speculative verify dispatch (models.ragged_verify_step_guarded):
        greedy rows emit their accepted run, sampled rows exactly one token."""
        with self.flight.tick_phase("step_prepare"):
            toks = np.zeros((self.n_slots, self.spec + 1), dtype=np.int32)
            degraded: set[int] = set()
            for i in active:
                toks[i, 0] = self.next_token[i]
                if self.slots[i].temperature <= 0.0:
                    d = self._safe_draft(i)
                    if d is None:
                        # degraded: the program's K+1 width is static, so
                        # the row still carries filler (the committed
                        # token — acceptance-neutral for greedy verify),
                        # but the slot emits only its verified token and
                        # counts NO drafts — plain decode for this step,
                        # same as the paged path's lens=0
                        degraded.add(i)
                        toks[i, 1:] = int(toks[i, 0])
                    else:
                        toks[i, 1:] = d
            if self._root_bcast:
                self._bcast(CTRL_SRV_VERIFY, self.spec, np.concatenate([
                    toks.reshape(-1), self.pos.astype(np.int32),
                    self._f32bits(temps, topps, coins)]))
        t0 = time.perf_counter()
        n_acc, preds, nf = self._exec_verify(toks, self.pos, temps, topps,
                                             coins)
        ms = (time.perf_counter() - t0) * 1000.0
        with self.flight.tick_phase("emit"):
            self._settle_prefill(self._wait.t0_ns, self._wait.t1_ns)
            self._attrib_verify(active, ms)
            drafted = sum(self.spec for i in active
                          if self.slots[i].temperature <= 0.0
                          and i not in degraded)
            if drafted:
                self._tm.counter(telemetry.SPEC_DRAFT_TOKENS).inc(
                    drafted, generator="dense")
            poisoned = self._handle_nonfinite(active, nf)
            emitted = 0
            accepted = 0
            for i in active:
                if i in poisoned:
                    continue
                req = self.slots[i]
                # a degraded slot's filler draft must not count as drafted
                # OR accepted — it emits exactly its verified token
                acc = 0 if i in degraded else int(n_acc[i])
                if req.temperature <= 0.0 and i not in degraded:
                    req.spec_drafted += self.spec
                    req.spec_accepted += acc
                    accepted += acc
                    if acc:
                        self._tm.counter(
                            telemetry.SPEC_ACCEPTED_TOKENS).inc(
                                acc, generator="dense")
                run = [int(t) for t in preds[i, : acc + 1]]
                emitted += self._emit_run(i, run)
        with self.flight.tick_phase("bookkeeping"):
            self.flight.note_spec(drafted, accepted)
            self._record_step(len(active), ms, emitted)
        return emitted


class PagedGenerator(_GeneratorCore):
    """Block-granular paged KV + the paged ragged decode step
    (runtime/kvblocks.py, models.llama.paged_forward) — the continuous
    batching engine room behind ``--kv-block-size``.

    Differences from the dense slot pool:

    * KV lives in a block pool ``[L, n_blocks, n_kv, block_size, hd]``; a
      sequence holds exactly the blocks its context needs (lazy growth at
      decode time), not a max-context column — admission is priced in
      BLOCKS, so many short requests fit where the dense pool would hold
      worst-case HBM for each.
    * Prefix reuse is block-level sharing: full prompt blocks are shared
      physically (refcount, zero prefill work, zero copy), the partial
      tail is copy-on-write (one block copy). Retired sequences' blocks
      stay shareable in an LRU cache until allocation pressure evicts
      them — reuse now survives pool churn instead of riding retired
      slots' leftover columns.
    * Prefill reuses the ENGINE's own prefill program over the sequence's
      gathered dense column (take → chunked forward → scatter back), so
      the paged path adds the paged decode step plus — under
      ``--spec-lookup`` — the paged verify step, each jitted once per
      pool geometry.
    * Speculative decoding is first-class (``--spec-lookup K``): every
      slot owns an :class:`~dllama_tpu.runtime.speculative.NgramProposer`
      and each tick runs ONE ragged verify dispatch
      (models.llama.paged_verify_step_guarded) with per-slot draft
      lengths — greedy rows emit their exact accepted run, sampled rows
      run rejection-sampling acceptance (distribution-preserving,
      runtime/speculative.spec_decide). Block growth covers the verify
      width ``pos..pos+lens`` up front and admission prices the worst
      case ``+spec`` rows, so organic mid-verify exhaustion stays
      impossible; rejected lanes' writes sit at/above ``pos`` in
      refcount-1 blocks, so rollback is pure pos/table bookkeeping.

    Unsupported combinations (validated at engine construction): fused
    decode chunks, multihost, sp/pp/dp meshes, forced Pallas attention
    (the paged gather runs the XLA oracle), spec lookup past the decode
    regime's verify width.
    """

    def __init__(self, engine: "InferenceEngine", n_slots: int = 4):
        from ..runtime.kvblocks import (BlockPool, PagedKVCache, StatePool,
                                        blocks_per_seq, state_pool_bytes)
        from .hbm import (admission_column_bytes, check_budget,
                          fit_block_pool)

        block_size = int(getattr(engine, "kv_block_size", 0) or 0)
        if block_size <= 0:
            raise ValueError("PagedGenerator needs an engine built with "
                             "kv_block_size (--kv-block-size N)")
        if engine.multihost:
            raise ValueError("--kv-block-size is single-host only (the "
                             "worker mirror protocol has no paged ops yet)")
        self._init_core(engine, n_slots)
        t_phase = time.monotonic()
        self.block_size = block_size
        self.table_width = blocks_per_seq(self.cfg.seq_len, block_size)
        # pool sizing through the HBM guard: want the dense pool's worst
        # case (every slot at max context) + the null block; degrade to the
        # largest pool that fits the device budget (>= one full sequence)
        want = n_slots * self.table_width + 1
        # sliding-window layers live in a pool of their own (a block id
        # addresses every layer of its pool at once, so a window layer
        # cannot give a block back while a full layer keeps it): a slot
        # holds at most the window's blocks, one for the position being
        # written and one for a window that starts mid-block
        self.window = (self.cfg.sliding_window
                       if self.cfg.has_window_layers else 0)
        self._wcap = (window_blocks_cap(self.window, block_size)
                      if self.window else 0)
        # ... and as many again for the windows that registered boundaries
        # leave parked (a session's last prompt, a shared prompt's end):
        # what a matched prefix brings with it (runtime/kvblocks.py)
        n_wblocks = 2 * n_slots * self._wcap + 1 if self.window else 0
        wpool_bytes = (2 * self.cfg.n_window_layers * n_wblocks
                       * self.cfg.kv_dim * block_size
                       * engine.kv_dtype.itemsize)
        n_blocks, est = fit_block_pool(
            self.cfg, want, block_size=block_size,
            min_blocks=self.table_width + 1,
            weight_repr=getattr(engine, "hbm_weight_repr", "q40"),
            kv_dtype_bytes=engine.kv_dtype.itemsize,
            n_shards=engine.tp * engine.pp,
            offload=(engine.weight_mode == "offload"),
            state_bytes=wpool_bytes + state_pool_bytes(
                self.cfg, n_slots, jnp.dtype(self.cfg.compute_dtype).itemsize),
            # every slot can be mid-prefill at once (a start-up burst is).
            # The dense decoders' columns are NOT charged yet: an accepted
            # configuration of theirs (16 slots of 6144) would lose a third
            # of its pool to them (PERF.md section 7)
            column_bytes=(n_slots * admission_column_bytes(
                self.cfg, engine.kv_dtype) if self.cfg.paged_only else 0))
        if n_blocks == 0:
            check_budget(est["need_per_device"],
                         f"paged serving ({want} blocks of {block_size})")
        if n_blocks < want:
            print(f"⚠️ HBM admission guard: {want} KV blocks do not fit the "
                  f"device budget — degrading to {n_blocks} blocks "
                  f"({(n_blocks - 1) * block_size} cache rows) beside "
                  f"{n_slots} admission columns of "
                  f"{est['admission_columns_bytes'] / n_slots / 2**20:.0f} "
                  f"MiB instead of risking an OOM (runtime/hbm.py)",
                  flush=True)
        self.hbm_need = est["need_per_device"]
        # tiered KV memory (--kv-host-blocks, runtime/kvblocks.py): a
        # host-DRAM mirror pool sized through the host budget — cold
        # cached blocks spill there under pressure instead of dropping,
        # and resumed sessions page them back in at admission
        from .hbm import fit_host_pool

        want_host = int(getattr(engine, "kv_host_blocks", 0) or 0)
        n_host = fit_host_pool(self.cfg, want_host, block_size=block_size,
                               kv_dtype_bytes=engine.kv_dtype.itemsize)
        if n_host < want_host:
            print(f"⚠️ host KV tier: {want_host} host blocks exceed the "
                  f"host DRAM budget — degrading to {n_host} "
                  f"(runtime/hbm.py fit_host_pool)", flush=True)
        self.pool = BlockPool(n_blocks, block_size, n_host_blocks=n_host)
        t_phase = engine._stamp_startup("pool_fit", t_phase)
        pkv = PagedKVCache.create(self.cfg, n_blocks, block_size,
                                  dtype=engine.kv_dtype)
        if engine.plan is not None:
            from ..parallel.sharding import paged_kv_sharding

            pkv = jax.device_put(pkv, paged_kv_sharding(engine.plan, pkv))
        else:
            # where every step will leave it (see _pin_home): a pool that
            # starts elsewhere keys a second trace of whatever touches it
            # before and after the first step
            pkv = self._pin_home(pkv)
        self.pkv = pkv
        # a recurrent state, slot-indexed, beside the blocks, in the shape
        # the architecture gives it (kvblocks.StatePool has its rules:
        # never shared, written once at commit, in place through every step)
        self.spool = None
        if self.cfg.has_state:
            self.spool = StatePool.create(self.cfg, n_slots,
                                          jnp.dtype(self.cfg.compute_dtype))
            if engine.plan is None:
                # pinned as the blocks are: a tick program takes it before
                # any step or commit has left it there
                self.spool = self._pin_home(self.spool)
        # window layers: the second pool, its allocator and its tables,
        # and the routing counters the step and the chunks accumulate on
        # the device (models/laguna.py)
        self.wpool = self.wkv = self.wtables = self.moe_stats = None
        # latent attention (models/axk1.py): ONE pool of compressed rows,
        # the same list of blocks by token range, so prefix reuse is carried
        self.latent = self.cfg.has_latent_cache
        if self.cfg.has_expert_share:
            from ..models.share import zero_totals

            self.moe_stats = zero_totals(self.cfg)
            if engine.plan is None:
                # pinned as the pools are (a tick program takes the totals
                # before any step or commit has), in the spelling every
                # program hands them back in: they are a NEW array each
                # time, replicated with its axes named to its rank
                self.moe_stats = self._pin_home(self.moe_stats, by_rank=True)
            self._moe_seen = np.zeros(self.moe_stats.shape, np.int64)
            # tokens a held expert since this generator was built: the
            # running total a traced step's span carries (the registry's
            # series would cost one lookup an expert a step to join)
            self._moe_tokens_total = np.zeros(self.cfg.n_experts, np.int64)
            # held planes a step's routed layers COULD fetch, added a step:
            # what ``moe_planes`` is a share of
            self._moe_plane_slots = 0
        # what the step's cache is made of, in the order every step program
        # takes it and gives it back (models/*.paged_forward): ONE
        # description of what the architecture carries, so that a decoder
        # with a state pool AND routing counters is no case of its own
        self._cache_parts = tuple(
            name for name, has in (
                ("pkv", True), ("wkv", bool(self.window)),
                ("spool", self.spool is not None),
                ("moe_stats", self.moe_stats is not None)) if has)
        if self.window:
            self.wpool = BlockPool(n_wblocks, block_size)
            wshape = (self.cfg.n_window_layers, n_wblocks,
                      self.cfg.n_kv_heads, block_size, self.cfg.head_dim)
            # pinned as the blocks are: a tick program takes the window
            # pool before any step or commit has left it there
            self.wkv = self._pin_home(
                PagedKVCache(k=jnp.zeros(wshape, engine.kv_dtype),
                             v=jnp.zeros(wshape, engine.kv_dtype)))
        # window blocks a slot owns, by table index: host truth from
        # begin_admit on (the table row is published at commit)
        self._wbids: list[dict[int, int]] = [{} for _ in range(n_slots)]
        # per-slot block tables (host truth; shipped per dispatch as a
        # traced [n_slots, table_width] int32 — values never recompile)
        self.tables = np.zeros((n_slots, self.table_width), dtype=np.int32)
        if self.window:
            # a row's two tables side by side, as the step takes them:
            # ``tables`` and ``wtables`` are the halves of one array
            self._both_tables = np.zeros((2,) + self.tables.shape, np.int32)
            self.tables, self.wtables = self._both_tables
        self._seq_bids: list[list[int]] = [[] for _ in range(n_slots)]
        # shared-prefix length (in blocks) per slot: the commit scatter
        # redirects those entries to the null block so a shared block is
        # never written, even with identical bytes
        self._n_shared = [0] * n_slots
        # per-slot RESERVATION: worst-case blocks the slot's request may
        # still allocate at decode boundaries. can_admit subtracts the
        # outstanding total so concurrent sequences can't double-spend
        # the same free blocks and hit mid-decode exhaustion — the
        # block-priced admission guarantee holds across the whole batch,
        # not just per request
        self._reserve = [0] * n_slots
        # prompt tokens admitted and those of them that came from matched
        # blocks (or a copy-on-write block's reused rows), since start-up
        self._n_prefix_tokens = self._n_prompt_tokens = 0
        # with window layers: prompt tokens the FULL pool matched (those of
        # them whose window was found are the prefix tokens above),
        # admissions whose whole match was used, and the bytes of the last
        # admission's column as allocated
        self._n_full_matched = self._n_window_hits = self._column_bytes = 0

        _sc = getattr(engine, "introspection_scope", None) or "default"
        from ..models.llama import paged_sampled_step_guarded

        self._step = steppack.jit_packed_step(
            paged_sampled_step_guarded, scope=_sc, name="paged_sampled_step")
        # speculative serving (--spec-lookup composed with --kv-block-size):
        # ONE ragged paged verify program per pool geometry — K+1 width,
        # table width, and batch width are static; per-slot draft lengths,
        # coins, and knobs are traced, so admit/retire churn and varying
        # lens never retrace (ledger-asserted in tests)
        self.spec = max(0, getattr(engine, "spec_lookup", 0))
        if self.spec:
            from ..models.llama import paged_verify_step_guarded

            self._verify = steppack.jit_packed_step(
                paged_verify_step_guarded, scope=_sc,
                name="paged_verify_step")
        # prefill rides the ENGINE's jitted forward over the gathered
        # column (same program its solo path compiles — shared cache)
        self._prefill_fwd = engine._step
        # ... except where a chunk and the tick's decode rows can be ONE
        # program (the layers' planes read once for both): a decoder family
        # that brings such a program (models/family.py: ``tick``; the dense
        # decoders' is models.llama.forward_and_step), one device, a plain
        # step a tick, and a chunk
        # regime of the Q40 kernel wide enough for the widest bucket with
        # every slot's row joined to it. Then EVERY plain chunk goes through
        # it, its rows dead (null tables, as an inactive slot rides a step)
        # where nobody decodes: one executable a bucket, no variant that
        # only some ticks reach. Everything else keeps its two programs.
        self._tick = None
        self._riding = None       # a chunk waiting for the tick's rows
        self._rows_rode = False   # ... which rode one since the last step()
        from ..ops.quant_matmul import CHUNK_MAX_M

        family = family_of(self.cfg)
        if (family.tick is not None and engine.plan is None
                and not self.spec
                and getattr(engine, "decode_chunk", 1) == 1
                and max(engine.prefill_buckets) + n_slots <= CHUNK_MAX_M):
            from ..ops.sampling import sampled_token

            self._tick = steppack.jit_packed_step(
                family.tick, scope=_sc, name="forward_and_step")
            # the tables in the shape a step takes them (_run_rows): with a
            # window pool, a row's two side by side
            self._dead_rows = (
                np.zeros((n_slots, 1), np.int32), np.zeros(n_slots, np.int32),
                np.zeros_like(self._both_tables if self.window
                              else self.tables))
            # the tick program ends in an argmax (what the step's sampler
            # gives a batch in which no row samples) and hands back the
            # rows' logits; where a row does sample, the sampler runs over
            # them as a program of its own, one for every bucket. Met here
            # once, so that the first sampled row beside a chunk finds it
            # loaded
            self._sample_rows = plan_scoped_jit(sampled_token, scope=_sc,
                                                program="sample_rows")
            none = np.zeros(n_slots, np.float32)
            self._sample_rows(
                jnp.zeros((n_slots, self.cfg.vocab_size), jnp.float32),
                none, none, none)
        M, bs = self.table_width, block_size

        heads, width = self.cfg.cache_heads, self.cfg.cache_width

        def view(pool, table):
            g = pool[:, table]                    # [L, M, n_kv, bs, hd]
            g = jnp.moveaxis(g, 1, 2)             # [L, n_kv, M, bs, hd]
            return g.reshape(g.shape[0], 1, heads, M * bs, width)

        def _take_fn(pkv, table):
            # an admission's column from the slot's gathered view, as the
            # decoder family makes it (models/family.py): the view itself,
            # matched prefix blocks included, or a sequence's start where
            # prefix blocks are never shared; a latent pool has no V plane
            return family.column(
                self.cfg, view(pkv.k, table),
                None if pkv.v is None else view(pkv.v, table))

        def back(pool, c, table):
            L = c.shape[0]
            c = c[:, 0].reshape(L, heads, M, bs, width)
            c = jnp.moveaxis(c, 2, 1)                 # [L, M, n_kv, bs, hd]
            return pool.at[:, table].set(c.astype(pool.dtype))

        # window blocks one program moves between the window pool and a
        # column's sliding buffer: a window's, and the two a cap counts
        lanes = self._wcap
        in_block = jnp.arange(bs, dtype=jnp.int32)[None, :]

        def _window_rows(col, widx):
            # [lanes * bs]: where table index widx's rows lie in the buffer
            return (widx[:, None] * bs + in_block - col.base).reshape(-1)

        def _save_window_fn(wkv, col, widx, wdst):
            # the buffer's rows of table indices ``widx`` into window
            # blocks ``wdst`` (a lane not in use: the null block)
            rows = jnp.clip(_window_rows(col, widx), 0, col.wk.shape[3] - 1)

            def put(pool, buf):
                g = buf[:, 0][:, :, rows]          # [NS, n_kv, lanes*bs, hd]
                g = g.reshape(g.shape[0], heads, lanes, bs, width)
                return pool.at[:, wdst].set(
                    jnp.moveaxis(g, 2, 1).astype(pool.dtype))

            return PagedKVCache(k=put(wkv.k, col.wk), v=put(wkv.v, col.wv))

        def _take_window_fn(wkv, col, wsrc, widx, base):
            # a matched boundary's window blocks ``wsrc`` (table indices
            # ``widx``; a lane not in use names an index past the buffer)
            # into the column's sliding buffer, which starts at ``base``
            col = col._replace(base=base)
            rows = _window_rows(col, widx)

            def fill(buf, pool):
                g = jnp.moveaxis(pool[:, wsrc], 2, 1)  # [NS, n_kv, lanes, bs, hd]
                g = g.reshape(g.shape[0], heads, lanes * bs, width)
                return buf.at[:, 0, :, rows].set(
                    jnp.moveaxis(g, 2, 0).astype(buf.dtype), mode="drop")

            return col._replace(wk=fill(col.wk, wkv.k), wv=fill(col.wv, wkv.v))

        def _put_window_fn(pkv, wkv, stats, col, table, widx, wdst):
            # the column's full layers through the slot's table (matched
            # entries null there), its sliding layers' newest rows into the
            # slot's own window blocks, and the chunks' routing counters
            # into the running totals' chunk row
            return (PagedKVCache(k=back(pkv.k, col.k, table),
                                 v=back(pkv.v, col.v, table)),
                    _save_window_fn(wkv, col, widx, wdst),
                    stats.at[1].add(col.stats))

        def _state_put_fn(spool, s, conv, row):
            put = jax.lax.dynamic_update_index_in_dim
            # where the tail is the whole state there is no ``s`` to write
            return StatePool(s=(None if s is None
                                else put(spool.s, s[:, 0], row, 1)),
                             conv=put(spool.conv, conv[:, 0], row, 1))

        def _add_chunk_stats_fn(totals, stats):
            # an admission's routing counters into the totals' chunk row
            return totals.at[1].add(stats)

        def _put_fn(pkv, col, table):
            return PagedKVCache(k=back(pkv.k, col.k, table),
                                v=back(pkv.v, col.v, table))

        def _put_latent_fn(pkv, stats, col, table):
            # the column's rows through the slot's OWN entries (matched
            # ones are null there), the chunks' routing counters into the
            # running totals' chunk row
            return (PagedKVCache(k=back(pkv.k, col.c, table), v=None),
                    stats.at[1].add(col.stats))

        def _copy_fn(pkv, src, dst):
            def cp(pool):
                blk = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(pool, blk, dst,
                                                           axis=1)
            # a latent pool has no V plane: the tree holds one array
            return jax.tree.map(cp, pkv)

        # raw jit is deliberate for the three block-movement programs:
        # plan-independent gather/scatter/copy (no constrain()), safe to
        # share across engines — same argument as the dense pool's pair
        self._take = jax.jit(_take_fn)  # dlint: disable=jit-entry
        self._put_latent = jax.jit(_put_latent_fn, donate_argnums=(0, 1))  # dlint: disable=jit-entry
        self._put_window = jax.jit(_put_window_fn, donate_argnums=(0, 1, 2))  # dlint: disable=jit-entry
        self._take_window = jax.jit(_take_window_fn, donate_argnums=(1,))  # dlint: disable=jit-entry
        self._save_window_blocks = jax.jit(_save_window_fn, donate_argnums=(0,))  # dlint: disable=jit-entry
        # a recurrent state's commit writes the admission's state to the
        # slot's row of the state pool, in place
        self._state_put = jax.jit(_state_put_fn, donate_argnums=(0,))  # dlint: disable=jit-entry
        self._add_chunk_stats = jax.jit(_add_chunk_stats_fn,  # dlint: disable=jit-entry
                                        donate_argnums=(0,))
        self._put = jax.jit(_put_fn, donate_argnums=(0,))  # dlint: disable=jit-entry
        self._copy_block = jax.jit(_copy_fn, donate_argnums=(0,))  # dlint: disable=jit-entry
        # KV migration wire (runtime/kvwire): export gathers one block at
        # a time, import scatters one block at a time — ids is a traced
        # 1-element array, so a migration of ANY length reuses the same
        # two executables (the tier's gather/scatter transfer programs,
        # shape-stable by construction). Cold path: raw jit, same
        # plan-independence argument as the trio above.
        from ..models.llama import gather_kv_blocks, scatter_kv_blocks

        self._wire_take = jax.jit(gather_kv_blocks)  # dlint: disable=jit-entry
        self._wire_put = jax.jit(scatter_kv_blocks, donate_argnums=(0,))  # dlint: disable=jit-entry
        # warm-up normalization: pass the freshly created (committed) pool
        # through one no-op jitted copy (null block onto itself). Two birds:
        # the copy-on-write program is compiled BEFORE serving reaches
        # steady state (a first CoW admission must not be a latency cliff),
        # and every program only ever sees jit-OUTPUT sharding on the pool
        # — a committed input would key a second executable for the same
        # shapes on the first post-decode admission (the donated-output
        # recompile the canary docs measured)
        self.pkv = self._copy_block(self.pkv, jnp.int32(0), jnp.int32(0))
        if self.window:
            # ... and the two programs that move a boundary's window between
            # the window pool and a column's sliding buffer, on lanes that
            # name no block: the first admission behind a match, and the
            # first boundary left behind, must not be a compile either
            nobody = self._window_lanes({})
            col = self._exec_take_window(self._exec_take([]), {}, 0)
            self.wkv = self._save_window_blocks(self.wkv, col, *nobody)
        # host KV tier: the mirror owns the host buffers + transfer
        # programs; its warmup compiles the gather/scatter pair and
        # exercises both device_put hops on the null block NOW, so the
        # first under-pressure spill is a copy, never a compile. The
        # spill hook is installed only after a successful warmup — a
        # backend that can't run the transfers serves untiered instead
        # of degrading on every alloc.
        self.mirror = None
        # the one per-block size formula (hbm sizes the budget with it;
        # the spill/pagein byte counters must price identically)
        from .hbm import estimate_block_pool_bytes

        self._block_bytes = estimate_block_pool_bytes(
            self.cfg, 1, block_size, engine.kv_dtype.itemsize)
        if self.pool.n_host_blocks:
            from ..runtime.kvblocks import HostKVMirror

            # chunk-accounted RAM cap: fragmentation (a chunk alive on
            # one lane) must cost capacity, never overshoot the host
            # budget fit_host_pool granted
            mirror = HostKVMirror(max_chunks=max(1, n_host // SPILL_BATCH))
            try:
                self.pkv = mirror.warmup(self.pkv)
            except Exception as e:  # noqa: BLE001 — tier off, serving must start
                print(f"⚠️ host KV tier disabled: transfer warmup failed "
                      f"({type(e).__name__}: {e})", flush=True)
                self.pool.n_host_blocks = 0
                self.pool._host_free.clear()
            else:
                self.mirror = mirror
                self.pool.spill_fn = self._exec_spill
                self.pool.host_drop_fn = mirror.drop
                self.pool.host_room_fn = mirror.has_room
        # the pool's sharding flips ONCE after the first plan-scoped step
        # dispatch (raw-jit outputs carry SingleDeviceSharding, the model
        # programs' outputs the plan's NamedSharding) — re-warm the tier
        # transfer programs (and the CoW copy) against the steady
        # sharding right after that first step, so the first
        # under-pressure spill / resume page-in post-steady is a copy,
        # never a compile cliff
        self._tier_rewarmed = self.mirror is None
        self._m_blocks_total = self._tm.gauge(telemetry.KV_BLOCKS_TOTAL)
        self._m_blocks_used = self._tm.gauge(telemetry.KV_BLOCKS_USED)
        self._m_blocks_shared = self._tm.gauge(telemetry.KV_BLOCKS_SHARED)
        self._m_host_total = self._tm.gauge(telemetry.KV_BLOCKS_HOST_TOTAL)
        self._m_host_used = self._tm.gauge(telemetry.KV_BLOCKS_HOST_USED)
        self._m_spill_blocks = self._tm.counter(telemetry.KV_SPILL_BLOCKS)
        self._m_spill_bytes = self._tm.counter(telemetry.KV_SPILL_BYTES)
        self._m_spill_ms = self._tm.counter(telemetry.KV_SPILL_MS)
        self._m_pagein_blocks = self._tm.counter(telemetry.KV_PAGEIN_BLOCKS)
        self._m_pagein_bytes = self._tm.counter(telemetry.KV_PAGEIN_BYTES)
        self._m_pagein_ms = self._tm.counter(telemetry.KV_PAGEIN_MS)
        self._m_walk_blocks = self._tm.counter(telemetry.PAGED_WALK_BLOCKS)
        self._m_table_blocks = self._tm.counter(telemetry.PAGED_TABLE_BLOCKS)
        self._m_blocks_total.set(n_blocks - 1)
        self._m_host_total.set(self.pool.n_host_blocks)
        self._m_state_used = self._tm.gauge(telemetry.STATE_SLOTS_USED)
        self._m_skipped = self._tm.counter(telemetry.PREFIX_REUSE_SKIPPED)
        self._tm.gauge(telemetry.STATE_SLOTS_TOTAL).set(
            n_slots if self.spool is not None else 0)
        self._tm.gauge(telemetry.STATE_POOL_BYTES).set(
            self.spool.n_bytes if self.spool is not None else 0)
        self._m_wblocks_used = self._tm.gauge(telemetry.KV_WINDOW_BLOCKS_USED)
        self._m_wblocks_alloc = self._tm.counter(
            telemetry.KV_WINDOW_BLOCKS_ALLOCATED)
        self._m_wblocks_returned = self._tm.counter(
            telemetry.KV_WINDOW_BLOCKS_RETURNED)
        self._m_wblocks_parked = self._tm.gauge(
            telemetry.KV_WINDOW_BLOCKS_PARKED)
        self._tm.gauge(telemetry.KV_WINDOW_BLOCKS_TOTAL).set(
            max(0, n_wblocks - 1))
        self._m_moe_pairs = self._tm.counter(telemetry.MOE_PAIRS)
        self._m_moe_tokens = self._tm.counter(telemetry.MOE_EXPERT_TOKENS)
        self._m_moe_fed = self._tm.counter(telemetry.MOE_CHUNK_ROWS_FED)
        if self.moe_stats is not None:
            for where in ("held", "absent"):
                self._m_moe_pairs.inc(0, where=where)
            self._m_moe_fed.inc(0)
        self._update_block_gauges()
        engine._stamp_startup("generator", t_phase)

    # -- pool bookkeeping ---------------------------------------------------

    def prefix_totals(self) -> tuple[int, int]:
        return int(self._n_prefix_tokens), int(self._n_prompt_tokens)

    def window_totals(self) -> tuple[int, int, int] | None:
        if self.wpool is None:
            return None
        return (int(self._n_full_matched), int(self._n_window_hits),
                self._column_bytes)

    def _update_block_gauges(self) -> None:
        self._m_blocks_used.set(self.pool.used_blocks())
        if self.wpool is not None:
            self._m_wblocks_used.set(self.wpool.used_blocks())
            self._m_wblocks_parked.set(self.wpool.cached_blocks())
        if self.spool is not None:
            self._m_state_used.set(self.n_active)
        self._m_blocks_shared.set(self.pool.shared_blocks())
        if self.pool.n_host_blocks:
            self._m_host_used.set(self.pool.host_used_blocks())

    def _kv_fraction(self) -> float:
        return self.pool.used_blocks() / max(1, self.pool.n_blocks - 1)

    def _record_step(self, n_active: int, ms: float, emitted: int) -> None:
        """Beside the core's record: the share of the block tables that
        paged attention walks (ops/paged_attention.py bounds its walk by
        the same two arrays: a row is live where its table starts with a
        real block, and walks ``ceil((pos + 1) / block_size)`` entries)."""
        super()._record_step(n_active, ms, emitted)
        live = self.tables[:, 0] != self.pool.NULL
        self._m_walk_blocks.inc(int(np.sum(
            -(-(self.pos[live] + 1) // self.block_size))))
        self._m_table_blocks.inc(self.tables.size)

    def flight_blocks(self) -> dict | None:
        d = {"total": self.pool.n_blocks - 1,
             "used": self.pool.used_blocks(),
             "shared": self.pool.shared_blocks(),
             "reserved": sum(self._reserve)}
        if self.pool.n_host_blocks:
            d["host_total"] = self.pool.n_host_blocks
            d["host_used"] = self.pool.host_used_blocks()
        return d

    # -- KV tier: spill (device→host) and page-in (host→device) -------------

    def _tier_rewarm(self) -> None:  # dlint: owner=loop-thread
        """One-shot, after the first decode dispatch: re-run the transfer
        (and CoW) warmups now that the pool carries the steady
        NamedSharding the step programs output — executables key on
        input shardings, and the init-time warmup could only see the
        fresh pool's. Same failure contract as the init warmup: a
        backend that can't run the transfers against the steady
        sharding degrades to UNTIERED serving (nothing has spilled yet
        — spills need retired sessions, which need decode steps), it
        must never crash the batch."""
        self._tier_rewarmed = True
        try:
            self.pkv = self._copy_block(self.pkv, jnp.int32(0),
                                        jnp.int32(0))
            self.pkv = self.mirror.warmup(self.pkv)
        except Exception as e:  # noqa: BLE001 — tier off, serving continues
            print(f"⚠️ host KV tier disabled: steady-sharding transfer "
                  f"re-warm failed ({type(e).__name__}: {e})", flush=True)
            self.pool.spill_fn = None
            self.pool.host_drop_fn = None
            self.pool.host_room_fn = None
            self.pool.n_host_blocks = 0
            self.pool._host_free.clear()
            self.mirror = None
            self._m_host_total.set(0)

    def _exec_spill(self, devs: list[int], hosts: list[int]) -> bool:  # dlint: owner=loop-thread
        """The pool's ``spill_fn``: one batched device→host copy moving
        the LRU cached blocks ``devs`` into the mirror's ``hosts`` lanes.
        Any failure — the ``spill`` failpoint or a real transfer error —
        returns False, and the pool falls back to the pre-tier
        drop-evict contract (content lost, allocation proceeds): a
        broken host tier costs resume work, never availability."""
        if not self.mirror.has_room():
            # chunk-accounted budget full (fragmented chunks alive on a
            # few lanes): capacity loss, never an overshoot — the pool
            # drop-evicts exactly as if the tier were off
            self.flight.note("spill_failed", reason="host_budget_full",
                             n_blocks=len(devs))
            return False
        t0 = telemetry.now_ns()
        try:
            failpoints.fire("spill")
            self.mirror.store(self.pkv, devs, hosts)
        except Exception as e:  # noqa: BLE001 — degrade to drop-evict
            self.flight.note("spill_failed", reason=type(e).__name__,
                             n_blocks=len(devs))
            return False
        ms = (telemetry.now_ns() - t0) / 1e6
        self._m_spill_blocks.inc(len(devs))
        self._m_spill_bytes.inc(len(devs) * self._block_bytes)
        self._m_spill_ms.inc(ms)
        self.flight.note("spill", n_blocks=len(devs), ms=round(ms, 3))
        return True

    def _rollback_pagein(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Undo every UNcopied page-in pair of ``adm`` — THE one rollback
        for both failure paths (a failed restore in :meth:`_exec_pagein`
        and a cancelled admission in :meth:`abort_admit`): the staged
        device blocks leave ``_seq_bids`` (they were never content-
        carrying), a CoW whose source never materialized is cancelled,
        and ``abort_pagein`` frees the devices and restores the host
        pins — content intact and registered for the next attempt."""
        uncopied = list(adm.pagein)
        adm.pagein = []
        if not uncopied:
            return
        pair_devs = {dev for _, dev in uncopied}
        self._seq_bids[adm.slot] = [b for b in self._seq_bids[adm.slot]
                                    if b not in pair_devs]
        if adm.cow_release in pair_devs:
            adm.cow_release = 0
            adm.cow = None  # its source never materialized
        self.pool.abort_pagein(uncopied)

    def _exec_pagein(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Drain one SPILL_BATCH batch of ``adm``'s pending page-in pairs:
        restore the host copies into the freshly allocated device blocks
        and commit the rebind. Failure (the ``pagein`` failpoint or a
        real transfer error) rolls back every UNcopied pair — host
        content stays intact and registered for a retry — and raises
        :class:`PageInError`, which fails only this request (503-shaped);
        committed earlier batches stay owned via ``_seq_bids`` and are
        released with the slot. The pool rides a one-element holder
        through the mirror so a mid-batch failure can never strand the
        generator on a donated (deleted) buffer."""
        batch = adm.pagein[:SPILL_BATCH]
        req = adm.req
        t0 = telemetry.now_ns()
        ref = [self.pkv]
        try:
            failpoints.fire("pagein")
            self.mirror.load(ref, batch)
        except Exception as e:
            self.pkv = ref[0]  # whatever scatters landed, stay live
            self._rollback_pagein(adm)
            self._update_block_gauges()
            raise PageInError(
                f"KV page-in failed for request {req.rid}: "
                f"{type(e).__name__}: {e}") from e
        self.pkv = ref[0]
        self.pool.commit_pagein(batch)
        adm.pagein = adm.pagein[len(batch):]
        t1 = telemetry.now_ns()
        ms = (t1 - t0) / 1e6
        req.ms_pagein += ms
        self._m_pagein_blocks.inc(len(batch))
        self._m_pagein_bytes.inc(len(batch) * self._block_bytes)
        self._m_pagein_ms.inc(ms)
        self.flight.note("pagein", req.rid, slot=adm.slot,
                         n_blocks=len(batch), ms=round(ms, 3))
        telemetry.tracer().emit(req.rid, "pagein", t0, t1, slot=adm.slot,
                                n_tokens=len(batch) * self.block_size)
        self._update_block_gauges()

    def _worst_case_blocks(self, prompt_len: int, max_tokens: int) -> int:
        """Admission price in blocks: every position the request could
        ever write (prompt prefill + decode growth, capped at seq_len) —
        conservative (sharing only reduces the real need). Under
        speculative serving each decode boundary writes up to
        ``pos + lens`` (``lens <= spec``), so the frontier can run
        ``spec`` rows past the committed need — the ``+spec`` keeps
        organic mid-VERIFY exhaustion impossible, not just mid-decode
        (lens is clamped to ``seq_len - 1 - pos``, so the cap holds)."""
        rows = min(prompt_len - 1 + max_tokens + self.spec,
                   self.cfg.seq_len)
        return max(1, -(-rows // self.block_size))

    def can_admit(self, req: Request) -> bool:
        """Free (+ evictable) blocks minus every live sequence's
        outstanding worst-case growth must cover this request's own
        worst case — admission never over-commits the pool, so organic
        mid-decode exhaustion cannot happen (only injected exhaustion
        and early-retire slack remain). With the host tier on, the
        cached share of ``free_blocks()`` is RECLAIMABLE rather than
        disposable capacity — allocating over it spills the cold
        content to host instead of dropping it, so saying yes here
        costs idle sessions a page-in at resume, not their KV; the
        worst-case price already covers the device blocks a
        prefix-matched (possibly host-resident) prompt pages back
        into."""
        price = self._worst_case_blocks(len(req.prompt_ids), req.max_tokens)
        if self.wpool is not None:
            # BOTH pools are asked: a window slot may yet grow to its cap
            # (or to all its request will ever write, if that is less)
            owed = sum(max(0, self._wprice(i) - len(w))
                       for i, w in enumerate(self._wbids))
            if self.wpool.free_blocks() - owed < min(self._wcap, price):
                return False
        return self.pool.free_blocks() - sum(self._reserve) >= price

    def _wprice(self, slot: int) -> int:
        """Window blocks ``slot`` may hold at once, at the most."""
        return min(self._wcap, len(self._seq_bids[slot]) + self._reserve[slot])

    # -- KV migration wire: export (peer pull) / ingest (local commit) ------

    def wire_geometry(self) -> dict:  # dlint: owner=any
        """The layout facts a KV-wire transfer must agree on bit-for-bit
        (``runtime/kvwire.GEOMETRY_KEYS``) — pure config reads, safe from
        any thread."""
        import numpy as _np

        return {"n_layers": self.cfg.n_layers,
                "n_kv_heads": self.cfg.n_kv_heads,
                "block_size": self.block_size,
                "head_dim": self.cfg.head_dim,
                "dtype": str(_np.dtype(self.eng.kv_dtype))}

    def _refuse_wire(self) -> None:
        if self.latent:
            raise ValueError(
                "kvwire export/ingest frames a block as K and V planes of "
                "[layers, kv heads, block, head width]; a latent pool's "
                "block is one plane of compressed rows, which has no wire "
                "format yet")
        if self.wpool is not None:
            raise ValueError(
                "kvwire export/ingest moves one list of K/V blocks by token "
                "range; with window layers a sequence's context is blocks of "
                "two pools, the window pool's already returned behind the "
                "window, which has no wire format")
        if self.spool is not None:
            raise ValueError(
                "kvwire export/ingest moves K/V blocks between replicas; "
                "the blocks of a decoder with a recurrent state are useless "
                "without that state, which has no wire format")

    def export_prefix(self, tokens: list[int]) -> tuple[int, list]:  # dlint: owner=loop-thread
        """Gather the device-resident shared-prefix blocks matching
        ``tokens`` for a peer's ``/v1/kv/export`` pull: ``(n_tokens,
        [(k, v), ...])`` with each plane ``[L, n_kv, bs, hd]`` float32
        numpy. The match truncates at the first HOST-resident block (a
        cold block would need a page-in the exporter must not spend on a
        peer's behalf); blocks are pinned via :meth:`BlockPool.share`
        across the gather so a concurrent admission's pressure cannot
        spill or evict them mid-read, and released after — refcounts
        balance exactly."""
        self._refuse_wire()
        shared, _n_tok, _cow, _cow_r = self.pool.match_prefix(list(tokens))
        dev: list[int] = []
        for b in shared:
            if self.pool.is_host(b):
                break
            dev.append(b)
        if not dev:
            return 0, []
        for b in dev:
            self.pool.share(b)
        try:
            out = []
            for b in dev:
                k, v = self._wire_take(self.pkv,
                                       jnp.asarray([b], jnp.int32))
                out.append((np.asarray(k[:, 0], np.float32),
                            np.asarray(v[:, 0], np.float32)))
        finally:
            for b in dev:
                self.pool.release(b)
        return len(dev) * self.block_size, out

    def ingest_prefix(self, tokens: list[int], blocks: list) -> int:  # dlint: owner=loop-thread
        """Commit peer-migrated KV into the pool: one fresh device block
        per received ``(k, v)`` pair, scattered via the wire transfer
        program and registered under the prompt's prefix — the very next
        ``begin_admit`` finds them through ``match_prefix`` and reuses
        them exactly like locally computed blocks. Atomic: exhaustion
        mid-allocation releases every staged block and re-raises
        (``BlockPoolExhausted`` → the caller's ``exhaustion`` fallback
        reason); nothing is registered until every block is resident, so
        a failed ingest leaves the pool untouched. Returns the number of
        prefix tokens now resident (0 when already matched locally —
        a duplicate migration must not burn blocks)."""
        self._refuse_wire()
        n_tokens = len(blocks) * self.block_size
        usable = list(tokens[:n_tokens])
        if len(usable) < n_tokens:
            # peer sent more blocks than this prompt has prefill
            # positions (mismatched transfer): refuse the surplus
            n_full = len(usable) // self.block_size
            blocks = blocks[:n_full]
            n_tokens = n_full * self.block_size
            usable = usable[:n_tokens]
        if not blocks:
            return 0
        _, already, _c, _r = self.pool.match_prefix(usable)
        if already >= n_tokens:
            return 0
        bids: list[int] = []
        try:
            for _ in blocks:
                bids.append(self.pool.alloc())
        except BlockPoolExhausted:
            for b in bids:
                self.pool.release(b)
            raise
        for b, (k, v) in zip(bids, blocks):
            self.pkv = self._wire_put(
                self.pkv, jnp.asarray(k[:, None]), jnp.asarray(v[:, None]),
                jnp.asarray([b], jnp.int32))
        self.pool.register_prompt(bids, usable)
        for b in bids:
            # rc → 0 parks each registered block in the cached LRU:
            # matchable by the admission that triggered the migration,
            # evictable/spillable under pressure like any cached prefix
            self.pool.release(b)
        self._update_block_gauges()
        return n_tokens

    # -- admission ----------------------------------------------------------

    def begin_admit(self, req: Request, slot: int) -> "_Admission":  # dlint: owner=loop-thread
        """Start admitting into ``slot``: match the prompt against the
        block-level prefix index (share full blocks, copy-on-write the
        partial tail), allocate the remaining prompt blocks, and gather
        the sequence's column for incremental chunked prefill. Allocation
        is atomic: any exhaustion mid-way releases everything taken and
        raises :class:`~dllama_tpu.runtime.kvblocks.BlockPoolExhausted`
        (the scheduler keeps the request QUEUED)."""
        ids = req.prompt_ids
        assert ids, "empty prompt"
        if len(ids) >= self.cfg.seq_len:
            raise ValueError(
                f"prompt of {len(ids)} tokens exceeds the usable context "
                f"(seq_len {self.cfg.seq_len})")
        t_begin = telemetry.now_ns()  # the "admit" span: block bookkeeping
        rest = ids[:-1]
        wshared: dict[int, int] = {}   # table index -> matched window block
        chain: list[int] = []          # chain ids of the full pool's match
        if req.score:
            # teacher-forced eval (runtime/evalharness): every position
            # must be scored, so block-level prefix reuse is disabled —
            # a matched prefix would skip its NLL terms and the run would
            # no longer be bit-comparable to the single-sequence oracle
            shared, n_tok, cow_src, cow_r = [], 0, None, 0
        elif self.wpool is not None:
            # the longest block boundary BOTH pools hold: a match is used
            # as far back as its window is whole in the window pool (no
            # copy-on-write tail: a boundary is a whole block)
            shared, wshared, chain = match_windowed(self.pool, self.wpool,
                                                    rest, self.window)
            n_tok, cow_src, cow_r = len(shared) * self.block_size, None, 0
        else:
            shared, n_tok, cow_src, cow_r = self.pool.match_prefix(rest)
        if req.score and self.latent:
            raise ValueError(
                "teacher-forced scoring is not carried to a latent column "
                "(its chunks carry routing counters, not scores)")
        skip = self.cfg.prefix_reuse_skipped
        if req.score and (skip is not None or self.wpool is not None):
            raise ValueError(
                "teacher-forced scoring is not carried to a "
                "recurrent state (its chunks run unmasked), "
                "nor to window layers (their column carries routing "
                "counters, not scores)")
        if skip is not None:
            # a matched block holds K/V this request did not compute and
            # NO state of the linear layers: nothing is reused, and the
            # counter says a match was passed over
            if n_tok or (cow_src is not None and cow_r > 0):
                self._m_skipped.inc(reason=skip)
            shared, n_tok, cow_src, cow_r = [], 0, None, 0
        if len(chain) > len(shared):
            # the full pool matched a boundary whose window the window pool
            # no longer holds: prefilled from the longest boundary both
            # hold (or 0), and this admission leaves the missed boundary's
            # window behind as its chunks pass it (_save_window)
            self._m_skipped.inc(reason="window_miss")
        # KV tier: matched blocks may be HOST-resident (a resumed /
        # prefix-matched session whose cold blocks spilled under
        # pressure). Stage their page-in NOW — device blocks allocated
        # atomically, same exhaustion→requeue contract — but defer the
        # copies (and everything depending on the restored content: the
        # CoW block copy, the column gather) to continue_admit, which
        # drains one batch per tick so a long resume interleaves with
        # bystander decode steps instead of stalling one tick.
        cow_wanted = cow_src is not None and cow_r > 0
        host_need = [b for b in shared if self.pool.is_host(b)]
        cow_host = cow_wanted and self.pool.is_host(cow_src)
        if cow_host:
            host_need.append(cow_src)
        pairs: list[tuple[int, int]] = []
        bids: list[int] = []
        pinned: list[int] = []  # device shares taken before bids exist
        cow_exec: tuple | None = None
        cow_release = 0
        wbids: dict[int, int] = {}
        try:
            if self.wpool is not None and rest:
                # the matched boundary's window FIRST, pinned across the
                # allocations below (refcount >= 1: no allocation takes a
                # parked block back from under this admission)
                for idx, b in wshared.items():
                    self.wpool.share(b)
                    wbids[idx] = b
                # the window pool's share of the prompt: the window of the
                # prompt's LAST block boundary, which the commit registers
                # for the session's next turn, and so the blocks the first
                # decode step's window still reaches (the earlier positions
                # live in the admission's column only, and are never
                # written to a block)
                bs = self.block_size
                for idx in range(
                        max(len(shared), window_first_block(
                            len(rest) // bs * bs, self.window, bs)),
                        (len(rest) - 1) // bs + 1):
                    wbids[idx] = self.wpool.alloc()
            # pin every DEVICE-resident matched block FIRST: the page-in
            # (and CoW/growth) allocations below resolve pressure against
            # the cached LRU, and an unpinned match sitting there could
            # be spilled out (rebound to host — its dev id recycled as
            # someone else's block) or drop-evicted (then share() raises)
            # right out from under this admission. refcount >= 1 makes a
            # block untouchable by either path — the pre-tier code had
            # this property implicitly because share() ran before any
            # alloc.
            for b in shared:
                if not self.pool.is_host(b):
                    self.pool.share(b)
                    pinned.append(b)
            if cow_wanted and not cow_host:
                self.pool.share(cow_src)  # pin across ALL allocs below
                pinned.append(cow_src)
            if host_need:
                pairs = self.pool.begin_pagein(host_need)
            devmap = dict(pairs)
            for b in shared:
                # paged-in blocks carry rc 1 from begin_pagein; device
                # ones carry the pin taken above
                bids.append(devmap.get(b, b))
            reused = n_tok
            if cow_wanted:
                # copy-on-write: the partially-matching block cannot be
                # shared (this sequence will overwrite rows >= cow_r), so
                # copy it physically and reuse its first cow_r rows
                if cow_host:
                    src = devmap[cow_src]  # rc 1 held; release post-copy
                    dst = self.pool.alloc()
                    bids.append(dst)
                    cow_exec, cow_release = (src, dst), src
                else:
                    try:
                        dst = self.pool.alloc()
                    finally:
                        # copy next, so the pin can drop now (parks the
                        # source back in the cached LRU on rc 0)
                        self.pool.release(cow_src)
                        pinned.remove(cow_src)
                    bids.append(dst)
                    self.pkv = self._copy_block(self.pkv, jnp.int32(cow_src),
                                                jnp.int32(dst))
                reused += cow_r
            while len(bids) < -(-len(rest) // self.block_size):
                bids.append(self.pool.alloc())
            # a fully-reused prompt (shared blocks + CoW tail cover every
            # prefill position) has no rows to build: skip the column
            # gather/scatter round-trip entirely — THE hot path of
            # repeated system prompts, where reuse must mean zero device
            # work beyond the one CoW copy
            # (a decoder with a recurrent state always takes one: its
            # column carries the zero state its commit writes to the
            # slot's row)
            need_take = reused < len(rest) or self.spool is not None
            col = (self._exec_take(bids)
                   if need_take and not pairs else None)
            if col is not None and wshared:
                col = self._exec_take_window(col, wshared, n_tok)
        except Exception as e:  # noqa: BLE001 — atomic rollback, re-raised
            # ANY failure before the slot owns the blocks (exhaustion, a
            # device error in the CoW copy or the column gather) releases
            # every reference taken EXACTLY once — a leaked refcount
            # would shrink the pool forever. The pinned list covers the
            # device shares (whether or not they made it into bids);
            # paged-in devices roll back through abort_pagein (which
            # also restores the host pins); fresh blocks are whatever
            # remains in bids.
            pair_devs = {dev for _, dev in pairs}
            for b in wbids.values():
                self.wpool.release(b)
            for b in bids:
                if b not in pair_devs and b not in pinned:
                    self.pool.release(b)
            for b in pinned:
                self.pool.release(b)
            if pairs:
                self.pool.abort_pagein(pairs)
            if isinstance(e, BlockPoolExhausted):
                telemetry.registry().counter(
                    telemetry.KV_BLOCK_EXHAUSTION).inc()
            raise
        self._seq_bids[slot] = bids
        if wshared:
            # a matched block behind the prompt's last window was pinned for
            # the gather alone (enqueued above, in front of whatever writes
            # the block next): it parks again, registered as it was
            first = window_first_block(len(rest), self.window,
                                       self.block_size)
            for idx in [i for i in wshared if i < first]:
                self.wpool.release(wbids.pop(idx))
        self._wbids[slot] = wbids
        self._m_wblocks_alloc.inc(len(wbids) - sum(i in wbids for i in wshared))
        self._n_shared[slot] = len(shared)
        self._reserve[slot] = max(
            0, self._worst_case_blocks(len(ids), req.max_tokens) - len(bids))
        # the slot's table is NOT published yet: until the commit in
        # continue_admit the slot still rides along decode dispatches as
        # an INACTIVE row (with whatever stale pos the previous occupant
        # left), and its ride-along writes must keep landing in the null
        # block — publishing shared bids here would let a stale-pos
        # ride-along write corrupt a shared block other live sequences
        # attend to. Prefill runs over a locally-built table instead.
        self.tables[slot, :] = self.pool.NULL
        adm = _Admission(req=req, slot=slot, col=col, reused=reused)
        adm.pagein = pairs
        adm.cow = cow_exec
        adm.cow_release = cow_release
        adm.need_take = col is None and need_take
        adm.pos = reused  # prefill resumes after the reused prefix
        if len(chain) > len(shared):
            adm.wsave, adm.wchain = len(chain), chain
        if col is not None and self.wpool is not None:
            self._column_bytes = sum(a.nbytes for a in jax.tree.leaves(col))
        # paged-lifecycle span: the admission's block match/share/alloc +
        # column gather work (n_tokens = prefix positions reused)
        telemetry.tracer().emit(req.rid, "admit", t_begin,
                                telemetry.now_ns(), slot=slot,
                                n_tokens=reused)
        self._note_admitted(req, slot, reused)
        # matched against prompt tokens, as running totals: a traced step's
        # ``step_wait`` span carries both, ``admit_begin`` its own admissions'
        self._n_prefix_tokens += reused
        self._n_prompt_tokens += len(rest)
        self._n_full_matched += len(chain) * self.block_size
        self._n_window_hits += bool(chain) and len(chain) == len(shared)
        self._update_block_gauges()
        return adm

    def _exec_take(self, bids: list[int]):
        table = np.full(self.table_width, self.pool.NULL, dtype=np.int32)
        table[:len(bids)] = bids
        return self._pin_home(self._take(self.pkv, jnp.asarray(table)))

    def _window_lanes(self, blocks: dict[int, int]):
        """``(table indices, window blocks)`` as the window programs take
        them: ``_wcap`` lanes, one not in use an index past every buffer and
        the null block."""
        widx = np.full(self._wcap, 1 << 24, np.int32)
        wbid = np.zeros(self._wcap, np.int32)
        widx[:len(blocks)] = list(blocks)
        wbid[:len(blocks)] = list(blocks.values())
        return jnp.asarray(widx), jnp.asarray(wbid)

    def _exec_take_window(self, col, wshared: dict[int, int], n_tok: int):
        """The matched boundary's window gathered into the column's sliding
        buffer, which then ends at the boundary."""
        widx, wsrc = self._window_lanes(wshared)
        base = max(0, n_tok - col.wk.shape[3])
        return self._pin_home(self._take_window(self.wkv, col, wsrc, widx,
                                                jnp.int32(base)))

    def _save_window(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Leave a boundary's window behind: the chunks have just reached
        the boundary the full pool matched and the window pool missed
        (``adm.wsave`` blocks), so the column's sliding buffer ends with its
        window. Those rows go into fresh window blocks registered under the
        boundary's chain ids and parked at once: the next request that
        matches this far finds them. Where the window pool has no block to
        spare nothing is kept."""
        n, chain, bs = adm.wsave, adm.wchain, self.block_size
        adm.wsave, adm.wchain = 0, None
        got: dict[int, int] = {}
        try:
            for idx in range(window_first_block(n * bs, self.window, bs), n):
                if self.wpool.keyed(chain[idx]) is None:
                    got[idx] = self.wpool.alloc()
        except BlockPoolExhausted:
            for b in got.values():
                self.wpool.release(b)
            return
        if not got:
            return
        self.wkv = self._save_window_blocks(self.wkv, adm.col,
                                            *self._window_lanes(got))
        for idx, b in got.items():
            self.wpool.register_keyed(b, chain[idx])
            self.wpool.release(b)
        self._m_wblocks_alloc.inc(len(got))

    def _pin_home(self, col, by_rank: bool = False):
        """Pin ONE canonical sharding on an admission's column (and on the
        pool it is gathered from, at its creation): the prefill
        executable is keyed on its input's sharding (and its trace on the
        mesh that sharding names), and a column is either gathered from a
        pool that cycles through jit outputs whose resolved
        sharding/commitment varies with the ops that produced them
        (copy-on-write vs step vs create), or is the previous chunk's
        output, which takes the weights' mesh. Without the pin an
        identical-shape column keys a second forward trace and executable
        a bucket: AFTER steady state if the variant first shows up then (a
        latency cliff on TPU), and in every warm-up otherwise (a second
        walk of every bucket, 0.5-1.3 s each once a bucket carries Pallas
        kernels: PERF.md section 6, PR 35). device_put on a matching
        layout is a no-copy alias."""
        if self.eng.plan is not None:
            from ..parallel.sharding import kv_cache_sharding

            return jax.device_put(col, kv_cache_sharding(self.eng.plan, col))
        # no plan: where the weights live. Weights placed through a
        # one-device mesh name that mesh in every output computed from
        # them, a chunk's column included; a bare device otherwise
        s = jax.tree.leaves(self.eng.params)[0].sharding
        if isinstance(s, jax.sharding.NamedSharding):
            # ``by_rank``: the same placement spelled ``(None,) * ndim``,
            # which is a different cache key
            at = lambda a: jax.sharding.NamedSharding(
                s.mesh, jax.sharding.PartitionSpec(
                    *(None,) * (a.ndim if by_rank else 0)))
        else:
            one = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
            at = lambda _: one
        return jax.device_put(col, jax.tree.map(at, col))

    def _exec_prefill(self, col, padded, pos: int, n_valid: int):
        # a recurrent state would keep what padding wrote into it: the
        # chunk of a decoder that has one carries its valid length;
        # a dense decoder pads freely and is passed none
        valid = (jnp.int32(n_valid),) if self.cfg.paged_only else ()
        with self.eng.watchdog.guard("batch_prefill"):
            failpoints.fire("step_hang")
            if self._tick is not None:
                # the tick's program with every row dead: nobody decodes,
                # or the rows rode an earlier chunk of this tick. Enqueued
                # and not waited for, as the plain forward is; no row's
                # logits to poison, so the failpoint is not asked
                fields = (*self._dead_rows,
                          *self._chunk_fields(padded, pos, n_valid),
                          np.float32(0.0))
                _, (col, cache) = self._tick(
                    self.eng.params, self.cfg,
                    jnp.asarray(steppack.pack(fields)),
                    (col, self._step_cache()), steppack.layout_of(fields))
                self._keep_step_cache(cache)
                return self._pin_home(col)
            with self._plan_ctx():
                _, col = self._prefill_fwd(
                    self.eng.params, self.cfg,
                    jnp.asarray(np.asarray(padded).reshape(1, -1), jnp.int32),
                    jnp.int32(pos), col, *valid)
            return self._pin_home(col)

    def _chunk_fields(self, padded, pos: int, n_valid: int) -> tuple:
        """A chunk as the tick program's last host fields: its tokens, its
        position and, where a recurrent state would keep what padding wrote
        (as :meth:`_exec_prefill` passes ``forward``), its valid length."""
        valid = (np.int32(n_valid),) if self.cfg.paged_only else ()
        return (np.asarray(padded, np.int32).reshape(1, -1), np.int32(pos),
                *valid)

    def _step_cache(self):
        """What the step's cache is made of (``_cache_parts``: the blocks,
        then a window pool, a state pool, the routing counters) as a step
        or tick program takes it: the one part, or the tuple of them."""
        cache = tuple(getattr(self, name) for name in self._cache_parts)
        return cache if len(cache) > 1 else cache[0]

    def _keep_step_cache(self, cache) -> None:
        """... and as the program gave it back (all of it was donated)."""
        parts = self._cache_parts
        for name, part in zip(parts, cache if len(parts) > 1 else (cache,)):
            setattr(self, name, part)

    def _prefill_chunk(self, adm: "_Admission", padded, n_valid: int) -> None:
        if self._tick is None or self._rows_rode or not self.n_active:
            return super()._prefill_chunk(adm, padded, n_valid)
        # live rows, and no chunk has carried them since the last step():
        # this one waits for continue_admit to dispatch it WITH them, under
        # the step's own phases (adm.pos moves on before that)
        self._riding = (adm, padded, n_valid, adm.pos)

    def continue_admit(self, adm: "_Admission") -> bool:  # dlint: owner=loop-thread
        """One admission step: drain a page-in batch (KV tier, resumed
        sessions — one SPILL_BATCH restore per tick so bystander decode
        interleaves), then the deferred CoW copy / column gather once the
        content is resident, then one prefill chunk over the gathered
        column; commit scatters it back through the block table
        (shared-prefix entries redirected to the null block — a shared
        block is never a write target) and registers the prompt's blocks
        for future sharing."""
        with self.flight.tick_phase("prefill_dispatch") as span:
            done = self._advance_traced(adm, span)
        if self._riding is not None:
            self._step_with_chunk()
        if adm.wsave and adm.pos >= adm.wsave * self.block_size:
            self._save_window(adm)
        if not done:
            return False
        with self.flight.tick_phase("admit_commit") as span:
            self._commit_admit(adm)
            if span.traced:
                span.set(rid=adm.req.rid)
            if self.spool is not None and not adm.req.score:
                span.set(state_bytes=state_bytes(adm.col))
            if self.wpool is not None:
                span.set(window_blocks=len(self._wbids[adm.slot]))
            if self.latent and adm.col is not None:
                # the latent rows this commit wrote: the slot's own blocks
                own = len(self._seq_bids[adm.slot]) - self._n_shared[adm.slot]
                span.set(latent_bytes=own * self._block_bytes)
        return True

    def _advance_prefill(self, adm: "_Admission") -> bool:  # dlint: owner=loop-thread
        """The dispatch half of :meth:`continue_admit`; True once every
        prompt position is prefilled (nothing here waits for the
        device)."""
        if adm.pagein:
            self._exec_pagein(adm)  # raises PageInError on failure
            if adm.pagein:
                return False  # more batches: keep interleaving
        if adm.cow is not None:
            # the deferred copy-on-write block copy: its source is a
            # paged-in block, resident only now
            src, dst = adm.cow
            self.pkv = self._copy_block(self.pkv, jnp.int32(src),
                                        jnp.int32(dst))
            adm.cow = None
            if adm.cow_release:
                # drop our page-in reference: the source parks in the
                # (device) cached LRU, registered and shareable again
                self.pool.release(adm.cow_release)
                adm.cow_release = 0
        if adm.need_take:
            adm.col = self._exec_take(self._seq_bids[adm.slot])
            adm.need_take = False
        rest = adm.req.prompt_ids[:-1]
        if adm.pos < len(rest):
            # a chunk ends on a boundary whose window is to be left behind
            end = len(rest)
            if adm.pos < adm.wsave * self.block_size:
                end = adm.wsave * self.block_size
            n_b = self.eng._prefill_chunk_size(end - adm.pos)
            chunk = rest[adm.pos:min(adm.pos + n_b, end)]
            pad_to = min(n_b, self.cfg.seq_len - adm.pos)
            padded = chunk + [0] * (pad_to - len(chunk))
            if adm.req.score:
                # teacher-forced eval chunk: the fused NLL program
                # replaces the plain prefill on the same padded chunk
                tgt = adm.req.prompt_ids[adm.pos + 1:
                                         adm.pos + 1 + len(chunk)]
                tgt = tgt + [0] * (len(padded) - len(chunk))
                self._prefill_nll_chunk(adm, padded, tgt, len(chunk))
            else:
                self._prefill_chunk(adm, padded, len(chunk))
            adm.bucket = len(padded)
            self.eng.seen_buckets.add(adm.bucket)
            adm.pos += len(chunk)
            if adm.pos < len(rest):
                return False
        return True

    def _commit_admit(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """The commit half of :meth:`continue_admit`, after the last
        chunk."""
        rest = adm.req.prompt_ids[:-1]
        slot = adm.slot
        if adm.req.score:
            # eval sequences are done at end of prefill: no commit
            # scatter, no register_prompt (eval KV must never seed the
            # prefix index), no proposer, no decode arming — the blocks
            # release now and the scored column is discarded
            self._release_blocks(slot)
            self._finish_score(adm)
            return
        bids = self._seq_bids[slot]
        if adm.col is not None:
            # scatter only the slot's OWN blocks back: shared-prefix
            # entries stay null — a shared block is never a write target
            put_table = np.full(self.table_width, self.pool.NULL,
                                dtype=np.int32)
            n_sh = self._n_shared[slot]
            put_table[n_sh:len(bids)] = bids[n_sh:]
            if self.wpool is not None:
                # the slot's OWN window blocks: a matched one is shared
                self.pkv, self.wkv, totals = self._put_window(
                    self.pkv, self.wkv, self.moe_stats, adm.col,
                    jnp.asarray(put_table),
                    *self._window_lanes({i: b for i, b
                                         in self._wbids[slot].items()
                                         if i >= n_sh}))
                # the commit hands the totals back spelled ``()``, the step
                # and the tick program by rank: one spelling, or the chunk
                # behind a commit keys a second executable a bucket
                self.moe_stats = self._pin_home(totals, by_rank=True)
            elif self.latent:
                self.pkv, self.moe_stats = self._put_latent(
                    self.pkv, self.moe_stats, adm.col,
                    jnp.asarray(put_table))
            else:
                self.pkv = self._put(
                    self.pkv, KVCache(k=adm.col.k, v=adm.col.v),
                    jnp.asarray(put_table))
        if self.spool is not None:
            # the state's one write: the admission's carry becomes the
            # slot's row (the previous occupant's state goes with it)
            self.spool = self._state_put(self.spool, adm.col.s, adm.col.conv,
                                         jnp.int32(slot + 1))
            if adm.col.stats is not None:
                self.moe_stats = self._add_chunk_stats(self.moe_stats,
                                                       adm.col.stats)
        self.pool.register_prompt(bids, rest)
        if self.wpool is not None:
            # the prompt's last boundary keeps its window: the slot's full
            # window blocks under the chain ids the full pool gave their
            # prefixes (one already registered, shared or a duplicate, stays
            # as it is). They are never written again: positions only
            # advance, and decode writes past the last full block
            _, chain = self.pool.match_chain(rest)
            for idx, b in self._wbids[slot].items():
                if idx < len(chain):
                    self.wpool.register_keyed(b, chain[idx])
        # the table goes live only NOW, with the committed pos riding in
        # _arm_decode — no dispatch ever sees this slot's real table
        # paired with a stale position
        self.tables[slot, :len(bids)] = bids
        if self.wpool is not None:
            self.wtables[slot] = self._wtable_row(slot)
        adm.pos = len(rest)
        if self.spec:
            from .speculative import NgramProposer

            # EVERY slot drafts — sampled rows cash the check through
            # rejection sampling, not just greedy ones (the dense pool's
            # greedy-only restriction does not apply here)
            self._proposers[slot] = NgramProposer(self.spec)
            self._proposers[slot].extend(adm.req.prompt_ids)
        self._arm_decode(adm)

    def admit(self, req: Request, slot: int) -> None:  # dlint: owner=loop-thread
        """Admit in one go (tests / non-interleaved callers)."""
        adm = self.begin_admit(req, slot)
        while not self.continue_admit(adm):
            pass

    def _release_blocks(self, slot: int) -> None:  # dlint: owner=loop-thread
        """Drop every block reference ``slot`` holds and forget its
        bookkeeping (shared count, growth reservation, table row — the
        all-null row sends ride-along writes to the null block)."""
        for b in self._seq_bids[slot]:
            self.pool.release(b)
        for b in self._wbids[slot].values():
            self.wpool.release(b)
        self._wbids[slot] = {}
        if self.wtables is not None:
            self.wtables[slot, :] = self.pool.NULL
        self._seq_bids[slot] = []
        self._n_shared[slot] = 0
        self._reserve[slot] = 0
        self.tables[slot, :] = self.pool.NULL
        self._update_block_gauges()

    def _retire(self, slot: int, reason: str = "done") -> None:  # dlint: owner=loop-thread
        super()._retire(slot, reason)
        self._release_blocks(slot)

    def kv_blocks_by_slot(self, slot: int) -> float:
        return float(len(self._seq_bids[slot]))

    def abort_admit(self, adm: "_Admission") -> None:  # dlint: owner=loop-thread
        """Release everything ``begin_admit`` took for an admission that
        will never commit. Safe in every abort window: blocks this
        admission allocated fresh are unregistered (they free outright),
        shared/CoW sources just drop the extra reference — registered
        contents stay valid for other sequences. KV tier: page-in pairs
        whose copies never ran roll back through
        :meth:`_rollback_pagein` (host content stays registered for the
        next resume attempt); a paged-in CoW source we still hold
        releases into the cached LRU."""
        self._rollback_pagein(adm)
        if adm.cow_release:
            self.pool.release(adm.cow_release)
            adm.cow_release = 0
        self._release_blocks(adm.slot)

    def reset_state(self) -> None:  # dlint: owner=loop-thread
        """Crash recovery: every slot forgotten, the whole pool (refcounts
        AND the prefix index) reset — nothing can match blocks a
        half-finished dispatch may have corrupted."""
        self.slots = [None] * self.n_slots
        self._proposers = [None] * self.n_slots
        self._chunks_pending = []
        self._riding, self._rows_rode = None, False
        self._seq_bids = [[] for _ in range(self.n_slots)]
        self._n_shared = [0] * self.n_slots
        self._reserve = [0] * self.n_slots
        self.pool.reset()
        if self.wpool is not None:
            self.wpool.reset()
            self.wtables[:, :] = self.pool.NULL
        self._wbids = [{} for _ in range(self.n_slots)]
        if self.mirror is not None:
            self.mirror.drop_all()  # host buffers follow the pool's reset
        self.tables[:, :] = self.pool.NULL
        self.pos[:] = 0
        self.next_token[:] = 0
        self._m_occupancy.set(0)
        self._m_kv.set(0.0)
        self._update_block_gauges()

    # -- decode -------------------------------------------------------------

    def _ensure_blocks(self, i: int, last_pos: int) -> None:  # dlint: owner=loop-thread
        """Lazy block growth: guarantee slot ``i`` has physical blocks for
        every write position up to ``last_pos`` (inclusive) before the
        dispatch — one block at ``pos`` for plain decode, the blocks
        covering ``pos..pos+lens`` for a speculative verify (the
        continuous-batching memory win holds either way: a sequence only
        ever holds the blocks its live context — plus the verify
        frontier — spans)."""
        for idx in range(int(self.pos[i]) // self.block_size,
                         last_pos // self.block_size + 1):
            if self.tables[i, idx] == self.pool.NULL:
                bid = self.pool.alloc()
                self._seq_bids[i].append(bid)
                self._reserve[i] = max(0, self._reserve[i] - 1)
                self.tables[i, idx] = bid
        if self.wpool is not None:
            self._slide_window(i, last_pos)

    def _wtable_row(self, slot: int) -> np.ndarray:
        row = np.full(self.table_width, self.pool.NULL, dtype=np.int32)
        for idx, bid in self._wbids[slot].items():
            row[idx] = bid
        return row

    def _slide_window(self, i: int, pos: int) -> None:  # dlint: owner=loop-thread
        """The window pool's lazy growth AND its return: before the step
        that writes position ``pos``, every block of slot ``i`` whose
        positions all lie more than ``window - 1`` behind ``pos`` goes back
        to the free list (its table entry becomes the null block; paged
        attention starts the row's walk past it), and the block ``pos``
        falls in is allocated where the table has none."""
        held = self._wbids[i]
        first = window_first_block(pos, self.window, self.block_size)
        gone = [idx for idx in held if idx < first]
        for idx in gone:
            self.wpool.release(held.pop(idx))
            self.wtables[i, idx] = self.pool.NULL
        if gone:
            self._m_wblocks_returned.inc(len(gone))
        idx = pos // self.block_size
        if idx not in held:
            held[idx] = self.wpool.alloc()
            self.wtables[i, idx] = held[idx]
            self._m_wblocks_alloc.inc()

    def _grow_or_fail(self, active: list[int], grow: list[int]) -> None:  # dlint: owner=loop-thread
        """Lazy growth for one dispatch: ensure every active slot's write
        range ``pos..pos+grow[i]`` has blocks; a slot whose growth finds
        no block (injected exhaustion — admission reservations make the
        organic case impossible) fails THAT request explicitly
        (503-shaped), keeps the rest of the batch, and leaves a black-box
        postmortem naming the victim and the tick decisions leading in."""
        for i in list(active):
            try:
                self._ensure_blocks(i, int(self.pos[i]) + int(grow[i]))
            except BlockPoolExhausted as e:
                telemetry.registry().counter(
                    telemetry.KV_BLOCK_EXHAUSTION).inc()
                req = self.slots[i]
                req.error = str(e)
                req.server_error = True
                self._retire(i, "kv_block_exhaustion")
                active.remove(i)
                self.flight.dump("kv_block_exhaustion", victims=[req.rid],
                                 info={"error": str(e), "slot": i})

    def _assert_writable(self, active: list[int], grow: list[int]) -> None:
        if __debug__:
            # copy-on-write safety: a write target is never a shared
            # block — over the WHOLE verify width under speculation
            for i in active:
                for p in range(int(self.pos[i]),
                               int(self.pos[i]) + int(grow[i]) + 1):
                    bid = int(self.tables[i, p // self.block_size])
                    assert self.pool.refcount(bid) == 1, (i, p, bid)

    def take_rows_rode(self) -> bool:  # dlint: owner=loop-thread
        rode, self._rows_rode = self._rows_rode, False
        return rode

    def _prepare_rows(self):  # dlint: owner=loop-thread
        """The ``step_prepare`` of a plain step, alone or behind a chunk:
        the live rows once the cancelled are swept, each with a block for
        the position it writes, and their sampling knobs (None under
        ``--spec-lookup``, whose verify prepares its own, and where no row
        is left)."""
        active = self._sweep_cancelled()
        if not active or self.spec:
            return active, None
        zeros = [0] * self.n_slots
        self._grow_or_fail(active, zeros)
        if not active:
            return active, None
        self._assert_writable(active, zeros)
        return active, self._sampling_rows(active)

    def step(self) -> int:  # dlint: owner=loop-thread
        """One paged ragged decode step for every active slot. Inactive
        slots ride along with all-null tables (their writes land in the
        null block) — static shapes, one compiled program regardless of
        occupancy or block-table contents. Under ``--spec-lookup`` the
        dispatch is the ragged paged VERIFY step instead
        (:meth:`_spec_step`)."""
        self._rows_rode = False
        with self.flight.tick_phase("step_prepare"):
            active, rows = self._prepare_rows()
        if not active:
            return 0
        if self.spec:
            return self._spec_step(active)
        return self._run_rows(active, rows)

    def _step_with_chunk(self) -> None:  # dlint: owner=loop-thread
        """The chunk :meth:`_prefill_chunk` left waiting and the tick's
        decode rows as ONE dispatch: the step's preparation first, then
        ``forward_and_step`` through the helper every step path uses, the
        emit after it. The scheduler's tick then dispatches no step
        (:meth:`take_rows_rode`)."""
        (adm, padded, n_valid, pos), self._riding = self._riding, None
        with self.flight.tick_phase("step_prepare") as span:
            active, rows = self._prepare_rows()
            if not active:
                # every row left in the sweep: the chunk alone, unwaited
                span.next_phase("prefill_dispatch")
                self._enqueue_chunk(adm, padded, n_valid, pos)
                return
        self._rows_rode = True
        self._run_rows(active, rows, chunk=(adm, padded, n_valid, pos))

    def _run_rows(self, active: list[int], rows, chunk=None) -> int:  # dlint: owner=loop-thread
        """Dispatch, wait for and emit one token a live row: the step
        program, or with ``chunk`` (an admission, its padded tokens, their
        valid count and position) the tick program, which runs that chunk
        into the admission's column in the same pass over the weights."""
        temps, topps, coins = rows
        t0 = time.perf_counter()
        with self._step_io("batch_step") as io:
            wait = io.span
            tables = (self.tables if self.wpool is None
                      else self._both_tables)
            host = (self.next_token.astype(np.int32)[:, None],
                    self.pos.astype(np.int32), tables)
            # either program takes what the architecture carries
            # (_step_cache), all donated, and gives all of it back
            if chunk is None:
                (nxt, nf), cache = io.call(
                    self._step, self._step_cache(), *host,
                    temps, topps, coins)
                self._keep_step_cache(cache)
            else:
                adm, padded, n_valid, pos = chunk
                t_enqueue = telemetry.now_ns()
                (nxt, nf, logits), (col, cache) = io.call(
                    self._tick, (adm.col, self._step_cache()), *host,
                    *self._chunk_fields(padded, pos, n_valid))
                self._keep_step_cache(cache)
                if (temps > 0.0).any():
                    nxt = self._sample_rows(logits, temps, topps, coins)
                adm.col = self._pin_home(col)
                self._note_chunk(adm, len(padded), n_valid, t_enqueue,
                                 rows="live")
            if self.moe_stats is None:
                nxt, nf = io.fetch(tokens=nxt, nonfinite=nf)
            else:
                # the routing counters are the same program's output as
                # the tokens
                nxt, nf, totals = io.fetch(tokens=nxt, nonfinite=nf,
                                           moe_stats=self.moe_stats)
                self._note_moe(totals, wait)
            if chunk is None:
                # blocks this step's walk over the cache reads, over the
                # live rows (ops/paged_attention.py and ops/mla.py both walk
                # ceil((pos + 1) / block_size) entries a row). The STEP
                # program's walk alone, as the routing counters above: the
                # walk's roofline share divides it by that program's kernel
                # time, and a carried tick's rows walk inside the tick program
                walk_blocks = int(sum(
                    -(-(int(self.pos[i]) + 1) // self.block_size)
                    for i in active))
                wait.set(**{"mla_walk_blocks" if self.latent
                            else "kv_walk_blocks": walk_blocks})
            if wait.traced:
                # running totals, as the routing counters': a reader of a
                # traced slice takes last less first
                matched, prompt = self.prefix_totals()
                wait.set(prefix_tokens=matched, prompt_tokens=prompt,
                         chunks=self._n_chunks,
                         chunks_with_rows=self._n_chunks_rows)
                if self.wpool is not None:
                    # what the full pool matched (the prefix tokens above
                    # are those of them whose window was found), and the
                    # parked windows beside the window pool's blocks
                    wait.set(full_matched_tokens=self._n_full_matched,
                             wblocks_parked=self.wpool.cached_blocks(),
                             wblocks_total=self.wpool.n_blocks - 1)
        ms = (time.perf_counter() - t0) * 1000.0
        with self.flight.tick_phase("emit"):
            self._settle_prefill(wait.t0_ns, wait.t1_ns,
                                 rode=chunk is not None)
            if not self._tier_rewarmed:
                self._tier_rewarm()
            self._attrib_decode(active, ms)
            poisoned = self._handle_nonfinite(active, nf)
            emitted = 0
            for i in active:
                if i in poisoned:
                    continue
                emitted += self._emit_run(i, [int(nxt[i])])
        with self.flight.tick_phase("bookkeeping"):
            self._record_step(len(active), ms, emitted)
            self._update_block_gauges()
        return emitted

    def _note_moe(self, totals: np.ndarray, wait) -> None:  # dlint: owner=loop-thread
        """The device's running routing counters (row 0 the steps', row 1
        the committed chunks'; held pairs, absent pairs, rows the chunk form
        fed its planes, tokens a held expert) into the registry: what was
        added since the last step's fetch (int32 on the device: the
        difference is taken modulo 2**32). The step's ``step_wait`` span
        gets the held pairs
        THIS step computed (``moe_pairs``: the routed kernel's roofline
        share reads them) and, while a profiler listens, the counters'
        running totals, so that a reader of a traced slice takes what the
        slice added and not what the process has counted since it started
        (``moe_held`` / ``moe_absent``, ``moe_tokens`` a held expert joined
        by ``/``, ``moe_step_held`` / ``moe_planes`` the steps' own pairs
        and the distinct held experts their layers chose: pairs over planes
        is how often the decode kernel, a plane a pair, reads a plane,
        ``moe_plane_slots`` the routed layers times the held experts added a
        step: planes over them is the share of its held planes a step
        fetched, ``moe_chunk_held`` / ``moe_chunk_fed`` /
        ``moe_chunk_planes`` the chunks' own pairs, the rows they fed and
        the distinct held experts their layers chose (what a prefill
        chunk's grouped kernel fetched), ``wblocks_allocated`` /
        ``wblocks_returned``)."""
        delta = (totals.astype(np.int64) - self._moe_seen) % (1 << 32)
        self._moe_seen = totals.astype(np.int64)
        both = delta.sum(axis=0)
        if both[0]:
            self._m_moe_pairs.inc(int(both[0]), where="held")
        if both[1]:
            self._m_moe_pairs.inc(int(both[1]), where="absent")
        if both[2]:
            self._m_moe_fed.inc(int(both[2]))
        for e in np.nonzero(both[N_COUNTS:])[0]:
            self._m_moe_tokens.inc(int(both[N_COUNTS + e]),
                                   expert=str(int(e)))
        self._moe_tokens_total += both[N_COUNTS:]
        wait.set(moe_pairs=int(delta[0, 0]))
        if delta[0, :2].any():
            # the STEP program ran: a tick program's one joined dispatch is a
            # chunk-form one and counts on the chunk row alone
            # (models/lfm2.py), so planes over slots stays a step's share
            self._moe_plane_slots += (self.cfg.n_moe_layers
                                      * self.cfg.n_experts)
        if wait.traced:
            pairs = self._m_moe_pairs
            wait.set(moe_held=int(pairs.total(where="held")),
                     moe_absent=int(pairs.total(where="absent")),
                     moe_tokens="/".join(
                         map(str, self._moe_tokens_total.tolist())),
                     moe_step_held=int(self._moe_seen[0, 0]),
                     moe_planes=int(self._moe_seen[0, 3]),
                     moe_plane_slots=self._moe_plane_slots,
                     moe_chunk_held=int(self._moe_seen[1, 0]),
                     moe_chunk_fed=int(self._moe_seen[1, 2]),
                     moe_chunk_planes=int(self._moe_seen[1, 3]),
                     wblocks_allocated=int(self._m_wblocks_alloc.total()),
                     wblocks_returned=int(self._m_wblocks_returned.total()))

    def _spec_step(self, active: list[int]) -> int:  # dlint: owner=loop-thread
        """One ragged paged speculative verify dispatch
        (models.llama.paged_verify_step_guarded) over the whole pool.

        Per-slot draft lengths are RAGGED: each row's ``lens[i]`` is its
        proposer's draft clamped to the context tail
        (``seq_len - 1 - pos``) and the request's remaining token budget,
        with 0 for degraded proposers (``draft`` failpoint) — so near-cap
        and near-done slots keep decoding at width 1 instead of retiring
        early, and a varying-lens batch never retraces (lens is traced).
        Greedy rows emit their exact accepted run; sampled rows emit the
        exact-match-verified run, drawing coins in POSITION order (the
        K draft-position coins, then the bonus coin) from a COPY of
        their RNG state and committing one coin per emitted token
        (``speculative.spec_coins_consumed``), so coin ``i`` of a
        request's stream always belongs to emitted token ``i`` — the
        invariant mid-stream resume fast-forwards on — and every
        request's stream stays independent of its batch-mates."""
        from .speculative import spec_coins_consumed

        with self.flight.tick_phase("step_prepare"):
            drafted, rows = self._spec_rows(active)
        if not active:
            return 0
        toks, lens, temps, topps, acoins, fcoins = rows
        t0 = time.perf_counter()
        with self._step_io("batch_verify") as io:
            wait = io.span
            (n_acc, out, nf), self.pkv = io.call(
                self._verify, self.pkv, toks, self.pos.astype(np.int32),
                self.tables, lens, temps, topps, acoins, fcoins)
            n_acc, out, nf = io.fetch(accepted=n_acc, tokens=out,
                                      nonfinite=nf)
        ms = (time.perf_counter() - t0) * 1000.0
        with self.flight.tick_phase("emit"):
            self._settle_prefill(wait.t0_ns, wait.t1_ns)
            if not self._tier_rewarmed:
                self._tier_rewarm()
            self._attrib_verify(active, ms)
            if drafted:
                self._tm.counter(telemetry.SPEC_DRAFT_TOKENS).inc(
                    drafted, generator="paged")
            poisoned = self._handle_nonfinite(active, nf)
            emitted = 0
            accepted = 0
            for i in active:
                if i in poisoned:
                    continue
                req = self.slots[i]
                acc = int(n_acc[i])
                accepted += acc
                req.spec_drafted += int(lens[i])
                req.spec_accepted += acc
                if req.temperature > 0.0:
                    st = req.rng_state
                    for _ in range(spec_coins_consumed(acc, int(lens[i]))):
                        _, st = xorshift_random_f32(st)
                    req.rng_state = st
                emitted += self._emit_run(
                    i, [int(t) for t in out[i, :acc + 1]])
        with self.flight.tick_phase("bookkeeping"):
            if accepted:
                self._tm.counter(telemetry.SPEC_ACCEPTED_TOKENS).inc(
                    accepted, generator="paged")
            self.flight.note_spec(drafted, accepted)
            self._record_step(len(active), ms, emitted)
            self._update_block_gauges()
        return emitted

    def _spec_rows(self, active: list[int]):  # dlint: owner=loop-thread
        """The verify dispatch's host rows (drafts, ragged lengths,
        sampling knobs, pre-drawn coins) with block growth over each
        row's verify width; ``active`` shrinks in place when growth
        fails a row. Returns ``(drafted, rows)``."""
        spec = self.spec
        B = self.n_slots
        toks = np.zeros((B, spec + 1), dtype=np.int32)
        lens = np.zeros(B, dtype=np.int32)
        temps = np.zeros(B, dtype=np.float32)
        topps = np.zeros(B, dtype=np.float32)
        acoins = np.zeros((B, spec), dtype=np.float32)
        fcoins = np.zeros(B, dtype=np.float32)
        drafted = 0
        for i in active:
            req = self.slots[i]
            toks[i, 0] = self.next_token[i]
            temps[i] = req.temperature
            topps[i] = req.topp
            cap = min(spec, self.cfg.seq_len - 1 - int(self.pos[i]),
                      max(0, req.max_tokens - len(req.tokens) - 1))
            if cap > 0:
                d = self._safe_draft(i)
                if d is None:
                    cap = 0  # degraded: plain decode for this step
                else:
                    toks[i, 1:cap + 1] = d[:cap]
            lens[i] = cap
            drafted += cap
            if req.temperature > 0.0:
                # pre-draw from a COPY (committed post-dispatch by the
                # consumed count) in POSITION order: all K draft-slot
                # coins then the bonus coin, so stream coin i is always
                # emitted-token i's coin (a zero-length draft's position
                # 0 is acoins[0] — the very draw plain decode would make)
                st = req.rng_state
                for j in range(spec):
                    acoins[i, j], st = xorshift_random_f32(st)
                fcoins[i], st = xorshift_random_f32(st)
        self._grow_or_fail(active, lens)
        if active:
            self._assert_writable(active, lens)
        return drafted, (toks, lens, temps, topps, acoins, fcoins)

    def step_chunk(self, k: int) -> int:  # dlint: owner=loop-thread
        """Fused multi-step decode is not built for the paged path yet
        (engine validation rejects --decode-chunk with --kv-block-size);
        direct callers degrade to single steps."""
        return self.step()


class BatchScheduler:
    """Thread-safe front end: queue beyond the slot pool + a step loop.

    HTTP handler threads call :meth:`generate` (blocking) or submit+wait;
    a single background thread owns the generator and runs admit/step.

    Fault tolerance (the serving layer's explicit failure semantics —
    nothing in here may leave a waiter hanging on ``done.wait()``):

    * **deadlines** — ``submit(..., timeout_s=...)`` stamps a monotonic
      deadline; past it, a queued request fails immediately and an
      in-flight one is cancelled at the next step boundary, both marked
      ``timed_out`` (``dllama_request_timeouts_total``).
    * **bounded admission** — ``max_queue > 0`` sheds submits beyond the
      bound with :class:`QueueFullError` (``dllama_requests_shed_total``).
    * **supervision** — an unexpected exception in the loop fails every
      queued and in-flight request with the error, resets the generator
      pool, and restarts (``dllama_scheduler_crashes_total`` /
      ``_restarts_total``); past ``max_restarts`` — or on any crash under
      multihost, where a restart would desync the worker mirrors — the
      scheduler goes permanently unready and further submits raise
      :class:`SchedulerUnavailableError`.
    * **graceful drain** — :meth:`close` (optionally after
      :meth:`begin_drain`) stops admitting, lets active slots finish up
      to ``drain_s``, then fails the remainder explicitly.
    """

    def __init__(self, engine: "InferenceEngine", n_slots: int = 4, *,
                 max_queue: int = 0, max_restarts: int = 3,
                 tenant_limits: dict | None = None,
                 _start_thread: bool = True):
        # --kv-block-size selects the paged block-pool generator; the
        # scheduler's queue/deadline/supervision machinery is identical
        # over both (they share _GeneratorCore's lifecycle contract)
        if getattr(engine, "kv_block_size", 0):
            self.gen: _GeneratorCore = PagedGenerator(engine, n_slots)
        else:
            self.gen = BatchedGenerator(engine, n_slots)
        self.n_slots = self.gen.n_slots  # may be HBM-degraded below n_slots
        # token-budget policy for interleaved chunked prefill: per loop
        # tick, at least one admission advances one chunk, and further
        # admissions only run while the tick's prefill-token budget lasts
        # — decode latency for active slots stays bounded no matter how
        # many long prompts are admitting
        self.prefill_budget = max(engine.prefill_buckets)
        # flight recorder (runtime/flightrec): the scheduler owns the tick
        # framing; every decision in _tick lands in the open tick record
        self.flight = self.gen.flight
        # a scrape shows every tick phase (and what lies between two phases
        # and between two ticks) from start-up, not only those a tick has
        # reached yet
        for ph in (*telemetry.TICK_PHASES, *telemetry.LOOP_GAPS):
            telemetry.registry().counter(telemetry.TICK_PHASE_MS).inc(
                0.0, phase=ph)
        # this loop's first tick follows no tick of its own
        self.flight.loop_edge()
        self.max_queue = max_queue
        self.max_restarts = max_restarts
        # tenant observatory (runtime/tenancy): the process-wide
        # accounting registry plus this scheduler's fair-share knobs —
        # --tenant-limits (weight/max_slots/tokens_per_s) applied here so
        # tests can construct a limited scheduler without CLI plumbing
        self._tenancy = tenancy.registry()
        if tenant_limits is not None:
            self._tenancy.set_limits(tenant_limits)
        # shared scheduler state: mutated by handler threads (submit),
        # the loop thread, the closer, and the watchdog monitor — every
        # write outside __init__ must hold _lock (machine-checked by
        # dlint's lock-guard rule via the guarded-by declarations)
        # The wait queue is per-tenant FIFOs drained by weighted
        # round-robin (tenancy.FairQueue — FIFO within a tenant, WRR
        # across tenants); it supports len/iter/remove/clear, so the
        # deadline sweep and fail-all treat it like the list it replaced.
        self._queue = tenancy.FairQueue(         # dlint: guarded-by=_lock
            weight_of=lambda t: self._tenancy.limit_for(t).weight)
        self._admissions: list[_Admission] = []  # dlint: guarded-by=_lock
        # KV migration (runtime/kvwire): requests parked mid-transfer +
        # peer export gathers awaiting the loop thread. Guarded so
        # _fail_all (any thread) can drain the parked requests without
        # racing the loop's service sweep.
        self._migrating: list[_KVMigration] = []   # dlint: guarded-by=_lock
        self._export_jobs: list[_KVExportJob] = []  # dlint: guarded-by=_lock
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._next_rid = 0                       # dlint: guarded-by=_lock
        self._stop = False                       # dlint: guarded-by=_lock
        self._draining = False                   # dlint: guarded-by=_lock
        self._drain_ended = False                # dlint: guarded-by=_lock
        self._healthy = True                     # dlint: guarded-by=_lock
        self._crashes = 0
        # retrace sentinel (runtime.introspection): after STEADY_TICKS
        # consecutive work-carrying loop ticks with zero compiles in this
        # engine's scope, serving is declared steady — any later compile is
        # an unexpected retrace (WARNed + dllama_retrace_unexpected_total)
        self._introspect_scope = getattr(engine, "introspection_scope", None)
        self._quiet_ticks = 0
        # the ledger's (built, loaded) counts at the open tick's start
        self._built_before = self._build_counts()  # dlint: owner=loop-thread
        # step watchdog (runtime.watchdog): a wedged dispatch blocks the
        # loop thread inside step(), so supervision can't run there — the
        # watchdog's monitor thread calls _on_stall instead
        self._watchdog = getattr(engine, "watchdog", None)
        if self._watchdog is not None:
            self._watchdog.on_stall.append(self._on_stall)
        # tick-usage clock: KV block-seconds and fairness-window slot
        # occupancy are charged per tick as (now - last tick) — the idle
        # path resets it so a long quiet stretch never bills anyone
        self._t_last_tick = time.monotonic()     # dlint: owner=loop-thread
        self._thread: threading.Thread | None = None
        if _start_thread:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    # -- admission-side API (handler threads) -------------------------------

    def submit(self, prompt_ids: list[int], max_tokens: int, *,  # dlint: owner=any
               temperature: float = 0.0, topp: float = 0.9,
               seed: int = 0xB1A5, stop_on_eos: bool = True,
               timeout_s: float | None = None, on_token=None,
               kv_peer: str | None = None, score: bool = False,
               resume_from: int = 0, tenant: str = tenancy.ANON) -> Request:
        if score and getattr(self.gen.eng, "_nll_step", None) is None:
            raise ValueError(
                "eval scoring is unsupported on this engine: no "
                "prefill_nll program (multihost has no replicated twin)")
        # the request's clock starts at the call: the loop thread holds
        # _lock through admit_begin (block match, gather dispatch, a first
        # compile), and a submit waiting behind it is queueing already
        t_submit = telemetry.now_ns()
        # resolve BEFORE the lock: the cardinality bound + overflow
        # counter live in the tenancy registry, not scheduler state
        tenant = self._tenancy.resolve(tenant)
        with self._lock:
            if self._stop or self._draining or not self._healthy or (
                    self._thread is not None and not self._thread.is_alive()):
                raise SchedulerUnavailableError(
                    "scheduler is draining" if self._draining
                    else "scheduler is not running")
            if self.max_queue and len(self._queue) >= self.max_queue:
                telemetry.registry().counter(telemetry.REQUESTS_SHED).inc()
                self._tenancy.note_shed(tenant, "queue_full")
                self.flight.note("shed", reason="queue_full", tenant=tenant)
                raise QueueFullError(
                    f"queue full ({len(self._queue)} waiting, "
                    f"--max-queue {self.max_queue}); retry later")
            # per-tenant token-rate budget (--tenant-limits): cost is the
            # request's worst case (prompt + decode limit), charged up
            # front — a 429 here sheds only THIS tenant's request; the
            # global queue bound above takes precedence so a full queue
            # never reads as a tenant-budget problem
            if not self._tenancy.try_charge_tokens(
                    tenant, len(prompt_ids) + max_tokens):
                telemetry.registry().counter(telemetry.REQUESTS_SHED).inc()
                self._tenancy.note_shed(tenant, "tenant_rate_budget")
                self.flight.note("shed", reason="tenant_rate_budget",
                                 tenant=tenant)
                raise TenantOverBudgetError(
                    f"tenant {tenant!r} is over its token-rate budget "
                    f"({self._tenancy.limit_for(tenant).tokens_per_s:g} "
                    f"tok/s); retry later")
            # HBM admission guard: refuse a request that would push the
            # device past its limit (measured-bytes cross-check +
            # uncompiled-bucket workspace) instead of OOM-crashing later
            check_hbm_admission(self.gen.eng, len(prompt_ids),
                                self.gen.hbm_need)
            rid = self._next_rid
            self._next_rid += 1
            if not 0 <= resume_from < len(prompt_ids):
                raise ValueError(
                    f"resume_from {resume_from} out of range for a "
                    f"{len(prompt_ids)}-token prompt+history")
            req = Request(rid=rid, prompt_ids=list(prompt_ids),
                          max_tokens=max_tokens, temperature=temperature,
                          topp=topp, seed=seed, stop_on_eos=stop_on_eos,
                          on_token=on_token, score=score,
                          resume_from=resume_from, tenant=tenant)
            if kv_peer and hasattr(self.gen, "wire_geometry"):
                # peer-KV migration is paged-pool-only; a dense pool (or
                # an empty peer) just recomputes — no error, no field
                req.kv_peer = kv_peer
            req.t_submit = t_submit
            if timeout_s is not None and timeout_s > 0:
                req.deadline_ns = req.t_submit + int(timeout_s * 1e9)
            # the span tracer binds rid → tenant BEFORE the request is
            # findable by the loop thread, so every span it ever emits —
            # queue, prefill, decode, the --trace-out JSONL — carries
            # the attribution
            telemetry.tracer().bind_tenant(rid, tenant)
            self._queue.push(req)
            telemetry.registry().gauge(telemetry.QUEUE_DEPTH).set(
                len(self._queue))
            self.flight.note("submit", rid, n_prompt=len(prompt_ids),
                             max_tokens=max_tokens, tenant=tenant)
            if resume_from:
                self.flight.note("resume", rid, n_history=resume_from,
                                 peer=kv_peer or "", tenant=tenant)
        self._wake.set()
        return req

    def generate(self, prompt_ids: list[int], max_tokens: int,  # dlint: owner=any
                 **kw) -> list[int]:
        req = self.submit(prompt_ids, max_tokens, **kw)
        req.done.wait()
        return req.tokens

    def is_alive(self) -> bool:  # dlint: owner=any
        """Loop thread running and not crash-exhausted."""
        return (self._healthy and not self._stop
                and (self._thread is None or self._thread.is_alive()))

    def eval_resident(self) -> int:  # dlint: owner=any
        """Teacher-forced eval sequences currently queued or mid-prefill
        (runtime/evalharness). Surfaced on ``/readyz`` and the api banner
        so the fleet router's least-loaded dispatch can SEE why this
        replica's queue depth is elevated — eval sequences already count
        in dllama_queue_depth; this makes the reason observable."""
        with self._lock:
            return (sum(1 for r in self._queue if r.score)
                    + sum(1 for a in self._admissions if a.req.score))

    def readiness(self) -> tuple[bool, str, str]:  # dlint: owner=any
        """(ready, human reason, machine code) for ``GET /readyz``:
        scheduler alive ∧ not draining ∧ queue below the shed threshold
        ∧ no watchdog stall. The code comes from the closed vocabulary
        ``serve/api.py READY_CODES`` — machines (the fleet router)
        branch on it, humans read the reason."""
        if self._watchdog is not None and self._watchdog.stalled:
            return (False, "step watchdog tripped (wedged device dispatch)",
                    "crashed")
        if not self._healthy:
            return (False, "scheduler crashed (restart budget exhausted)",
                    "crashed")
        if self._thread is not None and not self._thread.is_alive():
            return False, "scheduler thread is not running", "crashed"
        if self._stop or self._draining:
            return False, "draining", "draining"
        if self.max_queue and len(self._queue) >= self.max_queue:
            return False, "queue full (shedding)", "queue_full"
        return True, "ok", "ok"

    # -- shutdown ------------------------------------------------------------

    def begin_drain(self) -> None:  # dlint: owner=any
        """Stop admitting (submits raise 503-shaped errors, ``/readyz``
        flips) while in-flight work keeps stepping — phase one of a
        graceful shutdown. The flag flips under the lock so no submit
        can interleave between its availability check and the enqueue.
        Idempotent: only the FIRST call opens the flight recorder's
        ``drain_begin``/``drain_end`` bracket, so a postmortem can tell
        a drained death from a crash."""
        with self._lock:
            already = self._draining
            self._draining = True
            n_queued = len(self._queue)
        telemetry.registry().gauge(telemetry.SERVER_DRAINING).set(1)
        if not already:
            self.flight.note("drain_begin", n_queued=n_queued,
                             n_active=self.gen.n_active)
        self._wake.set()

    def _pending(self) -> int:  # dlint: owner=any
        with self._lock:
            return len(self._queue) + len(self._admissions)

    def close(self, drain_s: float = 0.0) -> None:  # dlint: owner=any
        """Stop admitting, drain active work up to ``drain_s`` seconds,
        then stop the loop and fail whatever remains — every waiter's
        ``done`` is set by the time this returns."""
        self.begin_drain()
        if drain_s > 0 and self._thread is not None \
                and self._thread.is_alive():
            deadline = time.monotonic() + drain_s
            while time.monotonic() < deadline and (
                    self._pending() or self.gen.n_active):
                time.sleep(0.01)
        with self._lock:
            self._stop = True
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        # close the drain bracket BEFORE failing the remainder: the
        # lifecycle ring then reads drain_begin → … → drain_end, and a
        # postmortem can say "drained clean" vs "drain deadline failed
        # N requests" instead of guessing from a bare process death
        # (once — close() is idempotent for the test fixtures)
        with self._lock:
            ended, self._drain_ended = self._drain_ended, True
        if not ended:
            remainder = self._pending() + self.gen.n_active
            # "drain_timeout" is reserved for an actual expired drain
            # window — a close(drain_s=0) that failed survivors was an
            # intentional hard stop, and the postmortem must say so
            reason = ("clean" if remainder == 0
                      else "drain_timeout" if drain_s > 0 else "aborted")
            self.flight.note("drain_end", n_failed=remainder,
                             reason=reason)
            # final ledger line at drain: the cumulative totals a billing
            # pipeline reconciles against are never lost to the interval
            tenancy.ledger().maybe_write(self._tenancy, force=True)
        # the remainder fails EXPLICITLY (the close() that used to leak
        # waiters would leave these threads in done.wait() forever)
        self._fail_all("server shutting down")

    # -- failure plumbing ----------------------------------------------------

    def _fail_request(self, req: Request, msg: str) -> None:  # dlint: owner=any
        if not req.done.is_set():
            if not req.timed_out:
                req.error = msg
                req.server_error = True
            req.done.set()

    def _timeout_request(self, req: Request) -> None:  # dlint: owner=any
        req.timed_out = True
        telemetry.registry().counter(telemetry.REQUEST_TIMEOUTS).inc()
        # same site, same count: per-tenant timeouts reconcile exactly
        # with dllama_request_timeouts_total
        self._tenancy.note_timeout(req.tenant)

    def _fail_all(self, msg: str) -> None:  # dlint: owner=any
        """Fail every queued, admitting, and in-flight request with
        ``msg`` (idempotent; timed-out requests keep their flag)."""
        with self._lock:
            victims = list(self._queue)
            self._queue.clear()
            # NOT abort_admit'ed here: _fail_all runs on foreign threads
            # (close(), the watchdog monitor) that must not touch the
            # loop-thread-owned BlockPool; every _fail_all path either
            # resets the pool right after (crash restart) or stops
            # serving for good (stall, drain), so nothing is leaked to a
            # live pool
            victims += [a.req for a in self._admissions]
            self._admissions.clear()
            # parked migrations hold NO pool state (the fetch thread
            # writes only its holder) — failing them here leaks nothing,
            # and the orphaned fetch thread's result is simply dropped
            victims += [m.req for m in self._migrating]
            self._migrating.clear()
            telemetry.registry().gauge(telemetry.QUEUE_DEPTH).set(0)
        for s in list(self.gen.slots):
            if s is not None:
                victims.append(s)
        for req in victims:
            self._fail_request(req, msg)

    def _check_deadlines(self) -> None:  # dlint: owner=loop-thread
        """Queued requests past deadline fail now; in-flight ones are
        cancelled (their slot retires at the next step boundary)."""
        now = telemetry.now_ns()
        expired: list[Request] = []
        with self._lock:
            for req in list(self._queue):
                if req.deadline_ns and now >= req.deadline_ns:
                    self._queue.remove(req)
                    expired.append(req)
            if expired:
                telemetry.registry().gauge(telemetry.QUEUE_DEPTH).set(
                    len(self._queue))
        for req in expired:
            self._timeout_request(req)
            self.flight.note("timeout", req.rid, reason="queued",
                             tenant=req.tenant)
            req.done.set()
        for holder in (a.req for a in self._admissions):
            if holder.deadline_ns and now >= holder.deadline_ns \
                    and not holder.timed_out:
                self._timeout_request(holder)
                self.flight.note("timeout", holder.rid, reason="admitting",
                                 tenant=holder.tenant)
                holder.cancel.set()
        for s in self.gen.slots:
            if s is not None and s.deadline_ns and now >= s.deadline_ns \
                    and not s.timed_out:
                self._timeout_request(s)
                self.flight.note("timeout", s.rid, reason="in_flight",
                                 tenant=s.tenant)
                s.cancel.set()

    # -- KV migration (runtime/kvwire): peer pull before admission -----------

    def _spawn_migration(self, mig: _KVMigration) -> None:  # dlint: owner=loop-thread
        """Launch the fetch thread for a freshly parked migration. The
        per-transfer deadline is bounded by the request's own remaining
        deadline — a migration may never park a request past the point
        its recompute fallback could still finish in time."""
        from . import kvwire

        deadline_s = float(os.environ.get("DLLAMA_KVWIRE_DEADLINE_S", 0)
                           or 0) or kvwire.DEFAULT_DEADLINE_S
        if mig.req.deadline_ns:
            remaining = (mig.req.deadline_ns - telemetry.now_ns()) / 1e9
            deadline_s = max(0.05, min(deadline_s, remaining))
        self.flight.note("kvmigrate_begin", mig.req.rid, peer=mig.peer)
        threading.Thread(target=self._migrate_worker,
                         args=(mig, deadline_s), daemon=True,
                         name=f"dllama-kvwire-{mig.req.rid}").start()

    def _migrate_worker(self, mig: _KVMigration,
                        deadline_s: float) -> None:  # dlint: owner=any
        """The fetch thread body: stream + verify the peer's frames.
        Writes ONLY the migration holder — never scheduler or pool
        state — so a fetch outliving a fail-all sweep (its holder
        already dropped) is harmless."""
        from . import kvwire

        try:
            _, blocks = kvwire.fetch_kv(mig.peer, mig.req.prompt_ids[:-1],
                                        self.gen.wire_geometry(),
                                        deadline_s=deadline_s)
            mig.blocks = [(k, v) for _i, k, v
                          in sorted(blocks, key=lambda t: t[0])]
        except BaseException as e:  # noqa: BLE001 — every failure class falls back to recompute
            mig.error = e
        mig.finished = True
        self._wake.set()

    def _service_migrations(self) -> None:  # dlint: owner=loop-thread
        """Commit or fall back every finished migration: success ingests
        the blocks (scatter + prefix registration — the request's own
        admission then reuses them like any shared prefix); ANY failure
        — wire error, injected chaos, destination exhaustion — counts
        its reason in ``dllama_kvwire_fallback_total`` and requeues the
        request at the head for ordinary chunked-prefill recompute.
        Either way the wall spent parked lands in the request's
        ``kvmigrate`` TTFT phase and span; a user-visible failure is
        impossible by construction."""
        from . import kvwire

        with self._lock:
            finished = [m for m in self._migrating if m.finished]
            for m in finished:
                self._migrating.remove(m)
        for mig in finished:
            req = mig.req
            if req.done.is_set():
                continue  # failed (shutdown/deadline sweep) while parked
            n_tokens, reason = 0, None
            if mig.error is None:
                try:
                    n_tokens = self.gen.ingest_prefix(req.prompt_ids[:-1],
                                                      mig.blocks)
                except BlockPoolExhausted:
                    reason = "exhaustion"
                except Exception as e:  # noqa: BLE001 — a bad ingest degrades to recompute
                    reason = kvwire.classify_failure(e)
            else:
                reason = kvwire.classify_failure(mig.error)
            now = telemetry.now_ns()
            req.ms_kvmigrate += (now - mig.t0_ns) / 1e6
            telemetry.tracer().emit(req.rid, "kvmigrate", mig.t0_ns, now,
                                    n_tokens=n_tokens)
            reg = telemetry.registry()
            if reason is None:
                reg.counter(telemetry.KVWIRE_MIGRATIONS).inc(
                    outcome="migrated")
                self.flight.note("kvmigrate", req.rid, n_tokens=n_tokens,
                                 peer=mig.peer)
            else:
                reg.counter(telemetry.KVWIRE_MIGRATIONS).inc(
                    outcome="fallback")
                reg.counter(telemetry.KVWIRE_FALLBACK).inc(reason=reason)
                self.flight.note("kvmigrate_fallback", req.rid,
                                 reason=reason, peer=mig.peer)
            with self._lock:
                # head of its tenant's queue: the request was at the
                # front when it parked, and its prefix (migrated or not)
                # admits through the one ordinary path — match, share,
                # chunked prefill. push_front also refunds the WRR pass
                # the park's pop charged, so a migration isn't billed as
                # two turns against the tenant's share.
                self._queue.push_front(req)
                telemetry.registry().gauge(telemetry.QUEUE_DEPTH).set(
                    len(self._queue))
            self._wake.set()

    # -- KV export (the peer-pull source side) -------------------------------

    def request_kv_export(self, tokens: list[int],
                          timeout_s: float = 5.0) -> tuple[int, list]:  # dlint: owner=any
        """Gather the device-resident prefix blocks matching ``tokens``
        for a peer's ``/v1/kv/export`` pull: parks the calling handler
        thread while the loop thread (the pool's owner) runs
        :meth:`PagedGenerator.export_prefix` between ticks. Returns
        ``(n_tokens, [(k, v), ...])``; raises
        :class:`SchedulerUnavailableError` when the loop cannot service
        the gather (stopped, crashed, or past ``timeout_s``)."""
        if not hasattr(self.gen, "export_prefix"):
            raise SchedulerUnavailableError(
                "KV export needs the paged block pool (--kv-block-size)")
        job = _KVExportJob(tokens=list(tokens))
        with self._lock:
            if self._stop or not self._healthy or (
                    self._thread is not None
                    and not self._thread.is_alive()):
                raise SchedulerUnavailableError("scheduler is not running")
            self._export_jobs.append(job)
        self._wake.set()
        if not job.done.wait(timeout_s):
            raise SchedulerUnavailableError(
                f"KV export gather timed out after {timeout_s:g}s")
        if job.error is not None:
            raise job.error
        return job.n_tokens, job.blocks

    def _service_exports(self) -> None:  # dlint: owner=loop-thread
        """Drain pending export gathers (loop thread — the only thread
        allowed to touch the block pool). A gather failure answers THAT
        export request with the error; serving is untouched."""
        with self._lock:
            jobs, self._export_jobs = list(self._export_jobs), []
        for job in jobs:
            try:
                job.n_tokens, job.blocks = self.gen.export_prefix(
                    job.tokens)
            except Exception as e:  # noqa: BLE001 — the export answers with the error, serving continues
                job.error = e
            job.done.set()

    def _on_stall(self, info: dict) -> None:  # dlint: owner=monitor-thread
        """Watchdog trip (runs on the MONITOR thread — the loop thread is
        the one wedged inside a dispatch, so it cannot supervise itself):
        flip unready first, under the lock, so no submit slips in after
        the fail sweep; then fail every queued/admitting/in-flight
        request explicitly (their handlers get 503s, never a hang). The
        stall is permanent — even if the dispatch eventually returns, the
        device just proved it can wedge, and restarting the pool on top
        of a possibly half-executed program is exactly the implicit
        failure mode this PR removes."""
        with self._lock:
            self._healthy = False
            self._stop = True
            victims = ([r.rid for r in self._queue]
                       + [a.req.rid for a in self._admissions])
        victims += [s.rid for s in self.gen.slots if s is not None]
        self._fail_all(
            f"step watchdog: device dispatch {info.get('label')!r} stalled "
            f"past its {info.get('budget_s') or 0:.1f}s budget")
        # black-box postmortem: the wedged dispatch plus the last N ticks
        # of scheduler decisions that led into it
        self.flight.dump("watchdog_stall", victims=victims,
                         info={"label": info.get("label"),
                               "budget_s": info.get("budget_s"),
                               "waited_s": info.get("waited_s")})
        self._wake.set()

    def _on_crash(self, exc: BaseException) -> None:  # dlint: owner=loop-thread
        """Supervision: surface the crash to every pending request, then
        restart with a fresh pool — or go permanently unready once the
        restart budget is spent (or under multihost, where replaying a
        reset through the worker mirrors isn't implemented)."""
        self._crashes += 1
        telemetry.registry().counter(telemetry.SCHEDULER_CRASHES).inc()
        msg = f"scheduler crashed: {type(exc).__name__}: {exc}"
        print(f"🛑 {msg} (crash {self._crashes}/{self.max_restarts})",
              flush=True)
        with self._lock:
            victims = ([r.rid for r in self._queue]
                       + [a.req.rid for a in self._admissions])
        victims += [s.rid for s in self.gen.slots if s is not None]
        self.flight.dump("scheduler_crash", victims=victims,
                         info={"error": msg, "crash_n": self._crashes})
        dead = self._crashes > self.max_restarts or self.gen.eng.multihost

        def _go_unready() -> None:
            # flags flip UNDER the lock and BEFORE _fail_all: a submit
            # racing in after the fail sweep would otherwise enqueue a
            # request nobody ever fails — a hung done.wait()
            with self._lock:
                self._healthy = False
                self._stop = True

        if dead:
            _go_unready()
        self._fail_all(msg)
        if dead:
            print("🛑 scheduler restart budget exhausted — marking unready",
                  flush=True)
            return
        try:
            self.gen.reset_state()
        except Exception as e:  # noqa: BLE001 — reset failed: go unready
            _go_unready()
            self._fail_all(msg)  # submits that raced in during the reset
            print(f"🛑 scheduler state reset failed ({e}) — marking unready",
                  flush=True)
            return
        telemetry.registry().counter(telemetry.SCHEDULER_RESTARTS).inc()

    # -- the loop ------------------------------------------------------------

    def _loop(self) -> None:  # dlint: owner=loop-thread
        try:
            while not self._stop:
                try:
                    self._tick()
                except Exception as exc:  # noqa: BLE001 — supervised: fail-all + bounded restart
                    self._on_crash(exc)
        finally:
            self.flight.loop_edge()    # nothing follows the last tick

    STEADY_TICKS = 2  # compile-quiet work ticks before steady is declared

    def _mark_steady_if_quiet(self, compiles_before: int) -> None:  # dlint: owner=loop-thread
        scope = self._introspect_scope
        led = introspection.ledger()
        if scope is None or led.steady(scope):
            return
        if led.compile_count(scope) == compiles_before:
            self._quiet_ticks += 1
            if self._quiet_ticks >= self.STEADY_TICKS:
                led.mark_steady(scope)
        else:
            self._quiet_ticks = 0

    def _tick(self) -> None:  # dlint: owner=loop-thread
        """One loop tick under flight-recorder framing: the tick record
        (runtime/flightrec) captures every decision, dispatch, and the
        block-pool state — idle ticks are dropped by ``end_tick``, so the
        ring stays signal-dense. The finally also closes the tick on a
        crash, so the postmortem dump includes the dying tick."""
        self.flight.begin_tick(queue_depth=len(self._queue),
                               n_admissions=len(self._admissions),
                               n_active=self.gen.n_active)
        try:
            self._tick_body()
        except BaseException as e:
            # a crash before any decision/dispatch would otherwise read as
            # an idle tick and be dropped — note it so the dying tick
            # survives into the postmortem, named
            self.flight.note("crash", reason=type(e).__name__)
            raise
        finally:
            with self.flight.tick_phase("bookkeeping"):
                blocks = self.gen.flight_blocks()
                slots = [s.rid if s is not None else None
                         for s in self.gen.slots]
                # programs built inside the tick: a stall record of it is
                # attributed by them (a load from the program store on a
                # warm start is no jax compile event)
                built, loaded = (b - a for a, b in zip(
                    self._built_before, self._build_counts()))
            self.flight.end_tick(blocks=blocks, slots=slots,
                                 prefill_budget=self.prefill_budget,
                                 compiles=built - loaded, loads=loaded)

    def _build_counts(self) -> tuple[int, int]:  # dlint: owner=loop-thread
        scope = self._introspect_scope
        return introspection.ledger().build_counts(scope) if scope else (0, 0)

    def _tick_body(self) -> None:  # dlint: owner=loop-thread
        """One tick, divided into ``telemetry.TICK_PHASES`` spans
        (``flight.tick_phase``): the phases tile the tick, so whatever
        the device's idle gaps overlap in a profile names what the host
        was doing."""
        with self.flight.tick_phase("deadlines"):
            self._built_before = self._build_counts()
            compiles_before = self._built_before[0]
            self._check_deadlines()
            # KV migration service points (runtime/kvwire): peer export
            # gathers run here (the loop thread owns the pool), and
            # finished peer pulls commit or fall back before this tick's
            # admissions — a just-migrated prefix is matchable by its own
            # request's begin_admit below
            if self._export_jobs:
                self._service_exports()
            if self._migrating:
                self._service_migrations()
        with self.flight.tick_phase("admit_begin") as span:
            before = self.gen.prefix_totals()
            wbefore = self.gen.window_totals()
            rids = self._begin_admissions()
            span.set(admitted=len(rids))
            if rids:
                # of the prompt tokens admitted here, those that came from
                # matched blocks (the paged generator's running totals)
                matched, prompt = (a - b for a, b in zip(
                    self.gen.prefix_totals(), before))
                span.set(prefix_tokens=matched, prompt_tokens=prompt)
                if wbefore is not None:
                    # with window layers: the matched length in each pool
                    # (the window pool's is what was used), the admissions
                    # whose whole match was, and a column as allocated
                    after = self.gen.window_totals()
                    span.set(matched_full=after[0] - wbefore[0],
                             matched_window=matched,
                             window_hit=after[1] - wbefore[1],
                             column_bytes=after[2])
            if rids and span.traced:
                span.set(rids="/".join(map(str, rids)))
        self._advance_admissions()
        # a chunk's program stepped this tick's rows (asked here, before
        # any return: the answer is this tick's alone)
        rows_rode = self.gen.take_rows_rode()
        # golden canary drift sentinel (runtime/numerics): time-gated
        # fixed-seed replay on this thread — the same thread that owns
        # every device dispatch, so it can never race a batch step. Its
        # golden was recorded at startup (run_api_server), so replays are
        # compile-cache hits and cannot trip the retrace sentinel.
        canary = getattr(self.gen.eng, "canary", None)
        if canary is not None:
            with self.flight.tick_phase("canary"):
                canary.maybe_run()
        if self.gen.n_active == 0 and not self._admissions:
            with self.flight.tick_phase("idle_wait"):
                # idle: nobody holds KV, so reset the usage clock (a quiet
                # hour must not be billed to whoever admits next) — but
                # the ledger keeps its cadence so consumers see liveness
                self._t_last_tick = time.monotonic()
                tenancy.ledger().maybe_write(self._tenancy)
                self._wake.wait(timeout=0.05)
                self._wake.clear()
            return
        failpoints.fire("step")
        # --decode-chunk composes with batched serving: K fused steps
        # per tick (admissions then interleave per-K-tokens instead of
        # per-token — the same latency/throughput trade as the engine's
        # chunked decode)
        chunk = getattr(self.gen.eng, "decode_chunk", 1)
        if rows_rode:
            pass    # one program a tick: no step behind a carried chunk
        elif chunk > 1:
            self.gen.step_chunk(chunk)
        else:
            self.gen.step()
        with self.flight.tick_phase("bookkeeping"):
            # only work-carrying ticks advance the steady countdown: an
            # idle server must not declare itself steady before ever
            # compiling
            self._mark_steady_if_quiet(compiles_before)
            self._note_tick_usage()

    def _begin_admissions(self) -> list[int]:  # dlint: owner=loop-thread
        """The ``admit_begin`` phase: drain the queue into free slots
        (``gen.begin_admit`` under the scheduler lock), launch parked
        peer-KV pulls, sweep cancelled admissions. Returns the ids of
        the requests that began admission."""
        begun: list[int] = []
        reserved = {a.slot for a in self._admissions}
        started: list[_KVMigration] = []
        with self._lock:
            # start admissions into free, unreserved slots, drained in
            # weighted-round-robin order across tenants (FairQueue —
            # FIFO within a tenant); on the paged pool each request is
            # priced in BLOCKS first (worst-case need vs free+evictable
            # blocks) — an unaffordable request stays queued at its
            # tenant's head. A tenant at its --tenant-limits slot cap is
            # SKIPPED (blocked for this tick), not a barrier: the other
            # tenants keep admitting past it.
            blocked: set[str] = set()
            while True:
                head = self._queue.peek(blocked)
                if head is None:
                    break
                if head.kv_peer:
                    # peer-KV pull: park the request while a fetch
                    # thread streams frames across ticks — bystanders
                    # keep admitting and decoding untouched; any wire
                    # failure requeues it for ordinary recompute
                    self._queue.pop(head)
                    mig = _KVMigration(req=head, peer=head.kv_peer,
                                       t0_ns=telemetry.now_ns())
                    head.kv_peer = None  # one attempt, ever
                    self._migrating.append(mig)
                    started.append(mig)
                    continue
                free = [s for s in self.gen.free_slots()
                        if s not in reserved]
                if not free:
                    break
                lim = self._tenancy.limit_for(head.tenant)
                if lim.max_slots and self._tenant_active(
                        head.tenant, reserved) >= lim.max_slots:
                    self.flight.note("defer", head.rid,
                                     reason="tenant_slot_cap",
                                     tenant=head.tenant)
                    blocked.add(head.tenant)
                    continue
                if not self.gen.can_admit(head):
                    # blocks unaffordable: the head stays queued (FIFO) —
                    # the tick record says WHY nothing admitted this tick
                    self.flight.note("defer", head.rid,
                                     reason="blocks_unaffordable",
                                     tenant=head.tenant)
                    break
                req = self._queue.pop(head)
                try:
                    failpoints.fire("admit")
                    adm = self.gen.begin_admit(req, free[0])
                except BlockPoolExhausted:
                    # block-pool exhaustion (organic or kv_alloc-injected)
                    # DEGRADES TO QUEUEING: the request goes back to its
                    # tenant's head and waits for retirements to free
                    # blocks — back-pressure surfaces as 429s (queue
                    # full) or 408s (deadline), never a crash or a
                    # silent drop
                    self._queue.push_front(req)
                    now = telemetry.now_ns()
                    telemetry.tracer().emit(req.rid, "requeue", now, now)
                    self.flight.note("requeue", req.rid,
                                     reason="kv_block_exhaustion",
                                     tenant=req.tenant)
                    break
                except Exception as e:  # noqa: BLE001 — reject, don't wedge
                    req.error = f"{type(e).__name__}: {e}"
                    # a failed KV page-in is a SERVER-side failure (the
                    # host tier broke, not the request) — 503-shaped
                    req.server_error = isinstance(e, PageInError)
                    self.flight.note("reject", req.rid,
                                     reason=type(e).__name__,
                                     tenant=req.tenant)
                    req.done.set()
                    continue
                self._admissions.append(adm)
                reserved.add(adm.slot)
                begun.append(req.rid)
            telemetry.registry().gauge(telemetry.QUEUE_DEPTH).set(
                len(self._queue))
        # fetch threads launch OUTSIDE the admission lock (the spawn
        # takes no scheduler state, and _migrate_worker's first wake
        # could otherwise re-enter a non-reentrant lock path)
        for mig in started:
            self._spawn_migration(mig)
        # interleaved chunked prefill under the token-budget policy: the
        # FIRST admission always advances one chunk (progress guarantee);
        # further admissions run only while the tick's budget lasts, so a
        # pile-up of long prompts can't starve active decode steps
        # cancel sweep over EVERY admission first — a cancelled client
        # behind the budget cutoff must not keep blocks/reservation/slot
        # for the remaining ticks of the admissions ahead of it
        for adm in list(self._admissions):
            if adm.req.cancel.is_set():
                # mutation under the lock: _fail_all (any thread) clears
                # this list concurrently — an unlocked remove could race
                # the clear and raise into the crash supervisor
                with self._lock:
                    if adm not in self._admissions:
                        continue  # a concurrent _fail_all already took it
                    self._admissions.remove(adm)
                self.gen.abort_admit(adm)  # paged: release the blocks
                # counted as admitted in begin_admit: balance the pair so
                # admissions_total - retires_total stays "live requests"
                telemetry.registry().counter(telemetry.RETIRES).inc()
                self.flight.note("cancel", adm.req.rid, reason="admitting",
                                 tenant=adm.req.tenant)
                adm.req.done.set()
        return begun

    def _advance_admissions(self) -> None:  # dlint: owner=loop-thread
        """One chunk for the first admission, more while the tick's
        prefill budget lasts (``prefill_dispatch`` / ``admit_commit``
        phases inside ``gen.continue_admit``)."""
        spent = 0
        for adm in list(self._admissions):
            if spent >= self.prefill_budget:
                # over budget: this admission prefills on later ticks —
                # the preempt decision is what ITL attribution's
                # tick-budget story is built from
                self.flight.note("preempt", adm.req.rid,
                                 reason="prefill_budget",
                                 tenant=adm.req.tenant)
                continue
            remaining = len(adm.req.prompt_ids) - 1 - adm.pos
            spent += self.gen.eng._prefill_chunk_size(max(1, remaining))
            try:
                if self.gen.continue_admit(adm):
                    with self._lock:
                        if adm in self._admissions:
                            self._admissions.remove(adm)
            except Exception as e:  # noqa: BLE001 — reject, don't wedge
                with self._lock:
                    if adm in self._admissions:
                        self._admissions.remove(adm)
                self.gen.abort_admit(adm)
                telemetry.registry().counter(telemetry.RETIRES).inc()
                adm.req.error = f"{type(e).__name__}: {e}"
                # a failed KV page-in fails ONLY the resuming request,
                # 503-shaped — bystander slots keep decoding untouched
                adm.req.server_error = isinstance(e, PageInError)
                self.flight.note("reject", adm.req.rid,
                                 reason=type(e).__name__)
                adm.req.done.set()

    def _tenant_active(self, tenant: str, reserved: set) -> int:  # dlint: owner=loop-thread
        """Slots ``tenant`` currently occupies or is admitting into —
        the count its --tenant-limits ``max_slots`` cap gates on.
        Caller holds ``_lock`` (the admission loop)."""
        return (sum(1 for s in self.gen.slots
                    if s is not None and s.tenant == tenant)
                + sum(1 for a in self._admissions
                      if a.req.tenant == tenant))

    def _note_tick_usage(self) -> None:  # dlint: owner=loop-thread
        """Tenant observatory tick accounting: charge this tick's wall to
        each tenant's KV residency (device tier: blocks its live slots
        hold — one synthetic block per slot on the dense pool; host
        tier: spilled blocks its admissions' outstanding page-ins still
        reference), feed the fairness window, and give the usage ledger
        its periodic chance to append. Pure host bookkeeping — dict
        updates and at most one small file append — so steady-state
        dispatch traces are untouched."""
        now = time.monotonic()
        dt = now - self._t_last_tick
        self._t_last_tick = now
        device: dict[str, float] = {}
        for i, s in enumerate(self.gen.slots):
            if s is not None:
                device[s.tenant] = (device.get(s.tenant, 0.0)
                                    + self.gen.kv_blocks_by_slot(i))
        host: dict[str, float] = {}
        with self._lock:
            for a in self._admissions:
                n = len(a.pagein)
                if n:
                    host[a.req.tenant] = host.get(a.req.tenant, 0.0) + n
        if device or host:
            self._tenancy.note_tick(dt, device, host)
        tenancy.ledger().maybe_write(self._tenancy)

"""InferenceEngine — the root driver, TPU-style.

Replaces the reference's RootLlmInference + NnExecutor + worker control flow
(reference: src/app.cpp:164-226, nn-executor.cpp:134-187): instead of
broadcasting a control packet and spin-barrier-stepping an op list on every
node, the engine holds sharded params + KV cache and dispatches jitted SPMD
programs — a chunked prefill (the reference's nBatches positions-as-batch
micro-batching, app.cpp:28) and fused single-token decode steps (greedy
argmax or temperature/top-p sample on device, ops.sampling) with donated KV
buffers. The sampling semantics match the reference Sampler
(tokenizer.cpp:480-510), with the xorshift* coin stepped on host.

Padded prefill tails are safe without masking: pad-position garbage lands in
KV slots strictly beyond the current position, is invisible to the causal
mask (``s <= pos``), and every slot is rewritten by its real token's
``update_layer`` before it ever becomes visible.
"""

from __future__ import annotations

import itertools
import os
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.mfile import ModelFile
from ..formats.quants import F32, Q80
from ..models.config import ModelConfig
from ..models.family import family_of
from ..models.llama import (
    Params,
    forward,
    forward_with_taps,
    greedy_step_guarded,
    greedy_steps_guarded,
    load_params_from_mfile,
    prefill_nll,
    sampled_step_guarded,
    sampled_steps_guarded,
    verify_step_guarded,
)
from ..parallel.api import MeshPlan, make_mesh, plan_scoped_jit, use_plan
from ..parallel.sharding import kv_cache_sharding, shard_params, validate_tp
from ..tokenizer.bpe import Tokenizer
from ..tokenizer.sampler import Sampler, xorshift_random_f32
from . import failpoints, flightrec, numerics, steppack, telemetry
from .kvcache import KVCache
from .watchdog import StepWatchdog

DEFAULT_N_BATCHES = 32  # reference default nBatches (app.cpp:28)

# compile-ledger scope names (engine-1, engine-2, ...): per process, so two
# engines' programs never share a retrace-sentinel namespace
_ENGINE_SEQ = itertools.count(1)

# TPU-sized prefill chunking: the reference's 32-token default is a
# Pi-cluster constant — on a TPU a 32-token dispatch leaves the MXU idle, so
# when the user keeps the default the engine buckets prompt evaluation into
# the largest of these chunk sizes that fits (largest-first; the tail pads
# into the smallest bucket). One compiled program per bucket, absorbed by
# the compile cache. An explicit --nbatches pins a single fixed chunk size.
PREFILL_BUCKETS = (256, 128, 64, 32)


@dataclass
class StepMetrics:
    """Per-token timing, mirroring the reference's console metrics
    (dllama.cpp:59-67, 88-97). ``ms`` is whole-step wall time. On TPU the
    eval/sync seam lives inside one fused XLA program, so ``sync_ms`` (the
    collective share) comes from a one-off profiler capture whose measured
    sync fraction is applied to each step's wall time — populated when the
    engine runs with ``profile_split=True`` (runtime.profiling)."""

    kind: str  # "eval" (prefill chunk) or "pred" (decode)
    ms: float
    n_tokens: int
    sync_ms: float | None = None
    # token WIDTH of the dispatch that produced this step (a speculative
    # verify always runs K+1 columns even when only 1 draft is accepted; a
    # fused chunk always scans its full k) — what per-step wire traffic
    # scales with, unlike n_tokens (the kept count)
    width: int = 1

    @property
    def eval_only_ms(self) -> float | None:
        return None if self.sync_ms is None else self.ms - self.sync_ms


@dataclass
class GenerationResult:
    tokens: list[int]
    text: str
    prompt_tokens: int
    steps: list[StepMetrics] = field(default_factory=list)

    @property
    def eval_ms(self) -> float:
        return sum(s.ms for s in self.steps if s.kind == "eval")

    @property
    def pred_ms(self) -> float:
        return sum(s.ms for s in self.steps if s.kind == "pred")

    @property
    def pred_tok_per_s(self) -> float:
        # both guards matter: a request that produced 0 predicted tokens has
        # no "pred" steps (duration 0), and a sub-resolution clock can hand
        # back ms == 0.0 for a nonzero token count — neither may divide
        n = sum(s.n_tokens for s in self.steps if s.kind == "pred")
        if n <= 0 or self.pred_ms <= 0.0:
            return 0.0
        return n / (self.pred_ms / 1000.0)

    @property
    def eval_tok_per_s(self) -> float:
        n = sum(s.n_tokens for s in self.steps if s.kind == "eval")
        if n <= 0 or self.eval_ms <= 0.0:
            return 0.0
        return n / (self.eval_ms / 1000.0)


class InferenceEngine:
    """Owns config, params, KV cache, and the jitted step functions."""

    def __init__(self, model_path: str, tokenizer_path: str | None = None, *,
                 tp: int | None = None, sp: int = 1, pp: int = 1, dp: int = 1,
                 max_seq_len: int = 0,
                 weight_mode: str = "auto", sync_type: int = F32,
                 compute_dtype: str = "float32",
                 n_batches: int | None = None,
                 temperature: float = 0.0, topp: float = 0.9, seed: int = 0xB1A5,
                 multihost: bool = False, host_sampling: bool = False,
                 decode_chunk: int = 1, spec_lookup: int = 0,
                 kv_dtype: str = "auto", kv_block_size: int = 0,
                 kv_host_blocks: int = 0,
                 comm_overlap: int | str = "off",
                 profile_split: bool = False,
                 verify_weights: bool = False,
                 numerics_taps: bool = False,
                 numerics_failfast: bool | None = None):
        from ..ops.linear import quant_mode

        # start-up stamps (monotonic seconds per phase of the build; the
        # serving generator adds its own): logged once beside the HBM
        # report (introspection.startup_line), summed by the benchmark's
        # engine_build_s
        self.startup_s: dict[str, float] = {}
        t_phase = time.monotonic()

        # an unknown DLLAMA_TPU_QUANT_MODE fails BEFORE the multi-GB load
        quant_mode()
        self.model_file = ModelFile.open(model_path, max_seq_len=max_seq_len,
                                         sync_type=sync_type)
        self.cfg = ModelConfig.from_header(self.model_file.header,
                                           compute_dtype=compute_dtype)
        if weight_mode == "offload":
            # host-DRAM weight streaming (70B/405B): the forward scan pulls
            # each layer's weights from pinned host memory (ModelConfig.offload)
            from dataclasses import replace as _replace

            self.cfg = _replace(self.cfg, offload=True)
        # prefill chunk buckets (PREFILL_BUCKETS): adaptive when n_batches is
        # None (the default), pinned when the caller passed any explicit
        # value — including 32, so a reference-parity session can force the
        # reference's fixed chunking. packet_slots sizes the multihost
        # control packet to the largest dispatch any path emits.
        self.n_batches = min(n_batches or DEFAULT_N_BATCHES, self.cfg.seq_len)
        if n_batches is None:
            self.prefill_buckets = tuple(
                b for b in PREFILL_BUCKETS if b <= self.cfg.seq_len
            ) or (self.n_batches,)
        else:
            self.prefill_buckets = (self.n_batches,)
        self.packet_slots = max(self.n_batches, *self.prefill_buckets)
        self.tokenizer = Tokenizer.load(tokenizer_path) if tokenizer_path else None
        self.sampler = Sampler(self.cfg.vocab_size, temperature, topp, seed)
        self.host_sampling = host_sampling
        # KV cache dtype: "auto" rides the compute dtype; "f8" stores the
        # cache as float8_e4m3 — half of bf16's footprint and read bandwidth
        # with no scale bookkeeping (both attention paths already upcast
        # reads to f32). Long-context decode is KV-bandwidth-bound, so this
        # is the context-length analogue of Q40 weights. Beyond parity: the
        # reference's cache is always f32 (nn-cpu-ops.cpp shiftForward).
        _kv_dtypes = {"auto": self.cfg.compute_dtype, "f32": jnp.float32,
                      "bf16": jnp.bfloat16, "f8": jnp.float8_e4m3fn}
        if kv_dtype not in _kv_dtypes:
            raise ValueError(f"kv_dtype must be one of {sorted(_kv_dtypes)}, "
                             f"got {kv_dtype!r}")
        self.kv_dtype = jnp.dtype(_kv_dtypes[kv_dtype])
        self.weight_mode = weight_mode
        # multi-step fused decode: K tokens per dispatch (lax.scan feeds the
        # picked token back on device; models.llama.greedy_steps). Output is
        # identical to single-step — EOS overshoot is truncated on host and
        # the sampler RNG rewound to the kept count. Under multihost the
        # chunk also amortizes the control channel: ONE packet per K tokens
        # (coins ride the packet), capped by the packet's coin capacity.
        self.decode_chunk = 1 if host_sampling else max(1, decode_chunk)
        if multihost and self.decode_chunk > max(1, self.packet_slots - 1):
            raise ValueError(
                f"decode_chunk {self.decode_chunk} exceeds the control "
                f"packet's capacity of {self.packet_slots - 1} coins "
                f"(raise --nbatches or lower --decode-chunk)")
        # prompt-lookup speculative decode (greedy only): verify K drafted
        # tokens per dispatch (models.llama.verify_step), drafts from the
        # token history (runtime.speculative.NgramProposer). Output is
        # bit-identical to plain greedy; K+1 tokens must fit a control
        # packet's token slots under multihost.
        self.spec_lookup = max(0, spec_lookup)
        if self.spec_lookup and host_sampling:
            raise ValueError("--spec-lookup requires the fused device path "
                             "(drop --host-sampling)")
        if self.spec_lookup and self.decode_chunk > 1:
            raise ValueError("--spec-lookup and --decode-chunk are exclusive "
                             "(both multiply tokens per dispatch)")
        if multihost and self.spec_lookup + 1 > self.packet_slots:
            raise ValueError(
                f"spec_lookup {self.spec_lookup} exceeds the control packet's "
                f"{self.packet_slots} token slots (raise --nbatches)")

        refusal = family_of(self.cfg).refusal
        if refusal is not None:
            # a slot's context that is more than one list of K/V blocks (a
            # recurrent state, a second pool, a latent pool: the family
            # says which, models/family.py): what this engine does not
            # carry to it is refused HERE, by flag and reason; nothing is
            # silently ignored and no code stands in
            tp = 1 if tp is None else tp
            unsupported = [
                ("no --kv-block-size (the dense slot pool, and the "
                 "single-sequence inference/chat/perplexity path: only the "
                 f"paged generator carries {refusal.carries})",
                 not int(kv_block_size or 0)),
                (f"--spec-lookup ({refusal.spec_lookup})",
                 self.spec_lookup > 0),
                (f"--kv-host-blocks ({refusal.kv_host_blocks})",
                 int(kv_host_blocks or 0) > 0),
                ("--tp > 1", tp > 1), ("--sp > 1", sp > 1),
                ("--pp > 1", pp > 1), ("--dp > 1", dp > 1),
                ("multihost workers", multihost),
                ("--weight-mode offload", weight_mode == "offload"),
                ("--buffer-float-type q80 (Q80 sync emulation)",
                 self.cfg.sync_q80),
                ("--numerics-taps", numerics_taps
                 or os.environ.get("DLLAMA_NUMERICS_TAPS") == "1"),
            ]
            bad = [name for name, hit in unsupported if hit]
            if bad:
                raise ValueError(
                    f"{refusal.what} does not support: {'; '.join(bad)}")
        # paged KV serving (--kv-block-size, runtime/kvblocks.py): validate
        # the block geometry AND the feature combos up front — the paged
        # program family covers plain + tp ragged decode only, and a combo
        # it can't serve must fail at startup with the reason, not as a
        # per-request trace-time error
        self.kv_block_size = max(0, int(kv_block_size or 0))
        if self.kv_block_size and self.cfg.has_window_layers:
            # the sliding part of an admission's column: the window, the
            # widest chunk, and what a commit still writes to blocks
            from dataclasses import replace as _replace

            from .kvblocks import window_column_rows

            self.cfg = _replace(self.cfg, window_column_rows=window_column_rows(
                self.cfg.sliding_window, self.kv_block_size,
                self.prefill_buckets, self.cfg.seq_len))
        if self.kv_block_size:
            from .kvblocks import validate_block_size

            validate_block_size(self.cfg.seq_len, self.kv_block_size)
            from ..models.llama import _OVERLAP_MAX_WIDTH as _DECODE_W

            # speculative decoding is first-class on the paged path
            # (PagedGenerator runs the paged_verify_step program family);
            # the REAL remaining constraints: multihost (no paged worker
            # mirror ops — which also rules out spec×multihost here) and
            # a verify width past the decode regime — the policy width
            # the overlapped merges gate at (_OVERLAP_MAX_WIDTH; the
            # ragged paged-attention kernel itself folds up to MAX_TQ
            # query rows, so it is NOT the binding constraint) and the
            # width band the decode-shaped programs are tuned/tested for
            unsupported = [
                (f"--spec-lookup > {_DECODE_W - 1} (verify width K+1 "
                 f"must stay within the decode regime's "
                 f"{_DECODE_W}-wide dispatches)",
                 self.spec_lookup + 1 > _DECODE_W),
                ("--decode-chunk > 1", self.decode_chunk > 1),
                ("multihost workers", multihost),
                ("--sp > 1", sp > 1),
                ("--pp > 1", pp > 1),
                ("--dp > 1", dp > 1),
                ("attn_impl='flash' (forced)",
                 self.cfg.attn_impl == "flash"),
            ]
            bad = [name for name, hit in unsupported if hit]
            if bad:
                raise ValueError(
                    f"--kv-block-size (paged KV serving) does not support "
                    f"{', '.join(bad)} yet — drop those flags or drop "
                    f"--kv-block-size to use the dense slot pool")
        # tiered KV memory (--kv-host-blocks, runtime/kvblocks.py): a
        # host-DRAM mirror pool under the paged block pool — cold cached
        # blocks spill there under allocation pressure and page back at
        # resume. Pure serving-tier state: sized/validated here, built by
        # PagedGenerator (which also degrades it against the host budget,
        # hbm.fit_host_pool).
        self.kv_host_blocks = max(0, int(kv_host_blocks or 0))
        if self.kv_host_blocks and not self.kv_block_size:
            raise ValueError(
                "--kv-host-blocks is the paged pool's host spill tier — "
                "it needs --kv-block-size (block-granular KV) to have "
                "blocks to spill")

        t_phase = self._stamp_startup("header", t_phase)
        n_dev = len(jax.devices())
        for name, n in (("dp", dp), ("sp", sp), ("pp", pp)):
            if n < 1:
                raise ValueError(f"{name} must be >= 1, got {n}")
        if dp * sp * pp * (tp or 1) > n_dev:
            raise ValueError(
                f"mesh dp={dp} sp={sp} pp={pp} tp={tp or 1} needs "
                f"{dp * sp * pp * (tp or 1)} devices, found {n_dev}")
        if tp is None:
            if pp > 1 and dp == 1 and self.cfg.attn_impl == "flash":
                # pure pp is the ONE pp layout that composes with a forced
                # flash kernel (validate_pp); auto-widening tp here would
                # turn the user's request into an error
                tp = 1
            else:
                # largest power-of-2 device count the model's shapes accept
                # (after reserving the sp and pp axes)
                tp = 1
                while (dp * pp * sp * tp * 2 <= n_dev
                       and _tp_ok(self.cfg, tp * 2)):
                    tp *= 2
        self.tp, self.sp, self.pp, self.dp = tp, sp, pp, dp
        if sp > 1:
            # sp = sequence parallelism: KV cache seq-sharded, ring attention
            # (parallel/ring.py) — long-context capability with no reference
            # analogue (SURVEY.md §5). The cache's PHYSICAL rows pad to a
            # 128-multiple (runtime.kvcache), so any power-of-2 sp divides;
            # only an exotic sp could fail this.
            from .kvcache import padded_cache_len

            if padded_cache_len(self.cfg.seq_len) % sp != 0:
                raise ValueError(
                    f"cache rows {padded_cache_len(self.cfg.seq_len)} not "
                    f"divisible by sp={sp} (adjust --max-seq-len)")
        if pp > 1:
            # pp = pipeline parallelism: layer stages (parallel/pipeline.py);
            # another new capability (SURVEY.md §2.2: reference has none)
            from ..parallel.pipeline import validate_pp

            validate_pp(self.cfg, pp, tp=tp, dp=dp, sp=sp)
            # sp composes with pp: inside the pp-manual region sp stays an
            # AUTO mesh axis, so the per-stage attention runs the XLA
            # oracle over the seq-sharded cache (XLA inserts the
            # collectives; the manual ring schedule stays pp==1-only).
            # The seq-axis memory split — sp's job — holds either way.
        # dp = data parallelism over the BATCH axis: meaningful for batched
        # serving (--batch-slots N with N % dp == 0 shards the slot pool);
        # single-sequence paths run batch 1, which degrades to replicated
        # under dp (sharding_for's divisibility fallback) — allowed but
        # pointless, so nothing breaks when a dp engine serves one sequence.
        axes = {name: n
                for name, n in (("dp", dp), ("pp", pp), ("sp", sp),
                                ("tp", tp)) if n > 1}
        self.plan: MeshPlan | None = make_mesh(axes) if axes else None
        if tp > 1:
            validate_tp(self.cfg, tp)

        # overlapped multichip decode (--comm-overlap {off,auto,N},
        # parallel/qcollectives): resolve the per-merge chunk count against
        # the model dim and refuse unsupported combos up front, the same
        # startup-refusal discipline as --kv-block-size. The resolved count
        # is STATIC trace config (cfg.comm_overlap), so the knob can never
        # retrace mid-serving and multihost fingerprints it.
        from ..parallel.qcollectives import overlap_chunks, wire_q80

        requested = "off" if comm_overlap is None else comm_overlap
        explicit = requested not in ("off", "auto", 0, "0", None, "")
        n_chunks = overlap_chunks(requested, self.cfg.dim)  # raises on bad N
        if n_chunks and tp <= 1:
            if explicit:
                raise ValueError(
                    f"--comm-overlap {requested} needs a tensor-parallel "
                    f"mesh to have a collective to overlap (run with "
                    f"--tp >= 2, or use 'auto' to degrade on one device)")
            n_chunks = 0  # auto on a single device: nothing to overlap
        if n_chunks:
            from ..models.llama import _OVERLAP_MAX_WIDTH

            unsupported = [
                ("--sp > 1", sp > 1),
                ("--pp > 1", pp > 1),
                ("--weight-mode offload", weight_mode == "offload"),
                # a verify dispatch is K+1 columns wide; past the overlap
                # width gate it would trace the monolithic psum while
                # plain greedy traces the ring — their f32 sum orders
                # differ in low ulps, so the engine's "spec output is
                # bit-identical to plain greedy" invariant would silently
                # break on near-tie logits
                (f"--spec-lookup > {_OVERLAP_MAX_WIDTH - 1} (verify "
                 f"width K+1 exceeds the overlapped-merge decode-width "
                 f"gate _OVERLAP_MAX_WIDTH={_OVERLAP_MAX_WIDTH}, "
                 f"models/llama.py — a wider verify would trace the "
                 f"monolithic psum and break spec≡greedy bit-identity; "
                 f"lower --spec-lookup or run --comm-overlap off)",
                 self.spec_lookup + 1 > _OVERLAP_MAX_WIDTH),
            ]
            bad = [name for name, hit in unsupported if hit]
            if bad:
                raise ValueError(
                    f"--comm-overlap (overlapped collectives) does not "
                    f"support {', '.join(bad)} yet — their manual-SPMD "
                    f"regions can't nest the ring shard_map; drop "
                    f"those flags or --comm-overlap")
        if n_chunks:
            from dataclasses import replace as _replace

            self.cfg = _replace(self.cfg, comm_overlap=n_chunks)

        # multi-host SPMD (reference: root + workers co-executing,
        # app.cpp:164-226): non-zero processes mirror dispatches via the
        # control broadcast (parallel.multihost); logits come back replicated
        # so every host can read them.
        self.multihost = multihost
        self._is_root = True
        if multihost:
            from ..parallel.multihost import ControlCodec, validate_cluster_config

            self._is_root = jax.process_index() == 0
            # packet sized for the largest dispatch (adaptive prefill buckets
            # can exceed n_batches); both sides derive this from the same
            # flags, and the cluster fingerprint still pins n_batches itself
            self._ctrl = ControlCodec(self.packet_slots)
            validate_cluster_config(self)  # fail fast before the weight load

        t_phase = self._stamp_startup("mesh_plan", t_phase)
        # pre-staging HBM budget check (runtime.hbm): the reference prints
        # its required-memory estimate before loading (nn-core.cpp:162-176);
        # here a misfit additionally risks wedging the TPU backend for hours,
        # so a clean refusal beats an OOM
        from ..formats.quants import Q40 as _Q40
        from .hbm import check_budget, estimate_device_bytes

        wt = self.model_file.header.weight_type
        if weight_mode in ("f32", "bf16"):
            _repr = weight_mode
        elif weight_mode == "offload" or wt == _Q40:
            _repr = "q40"
        elif wt == Q80:
            _repr = "q80"
        else:
            # dense disk types (F32/F16) load at the COMPUTE dtype
            # (weights.py dense path), not their disk width
            _repr = ("bf16" if self.cfg.compute_dtype == "bfloat16"
                     else "f32")
        self.hbm_weight_repr = _repr
        # analytic per-token collective wire bytes of the col-split merges
        # (qcollectives.wire_traffic_model), priced PER MERGE: a merge
        # whose geometry makes the overlapped path fall back (K not
        # tp-divisible, or a quantized shard whose scale rows can't
        # split) must be priced as the monolithic path it actually
        # traces, or dllama_collective_bytes_total would report
        # collectives that never execute. q80_explicit mirrors whether
        # the sharded Pallas col-split (which routes through wire_psum)
        # would carry the merge when overlap is off.
        from ..formats.quants import QUANT_BLOCK_SIZE as _QBS
        from ..ops.linear import QuantizedWeight as _QW
        from ..ops.linear import fast_numerics_resolved as _fast_res
        from ..ops.quant_matmul import pallas_local_choice
        from ..parallel.qcollectives import wire_traffic_model

        quant_planes = _repr in ("q40", "q80")
        _by_key: dict = {}
        for k_dim in ([self.cfg.q_dim] if self.cfg.is_moe
                      else [self.cfg.q_dim, self.cfg.hidden_dim]):
            chunks = self.cfg.comm_overlap
            if chunks and (k_dim % tp != 0
                           or (quant_planes
                               and (k_dim // tp) % _QBS != 0)):
                chunks = 0  # this merge keeps the monolithic path
            q80_explicit = False
            if not chunks and quant_planes and tp > 1 \
                    and (k_dim // tp) % _QBS == 0:
                k_loc = k_dim // tp
                lw = _QW(  # shapes only — the host-side pricing probe
                    scales=jax.ShapeDtypeStruct((k_loc // _QBS,
                                                 self.cfg.dim),
                                                jnp.float32),
                    codes=jax.ShapeDtypeStruct((k_loc, self.cfg.dim),
                                               jnp.int8))
                q80_explicit = pallas_local_choice(
                    (1, 1, k_loc), lw,
                    _fast_res(self.cfg.compute_dtype)) is not None
            for op, wire_fmt, b in wire_traffic_model(
                    self.cfg.dim, tp, chunks, wire_q80(),
                    q80_explicit=q80_explicit):
                _by_key[(op, wire_fmt)] = (_by_key.get((op, wire_fmt), 0.0)
                                           + b * self.cfg.n_layers)
        self._wire_traffic = [(op, w, b)
                              for (op, w), b in sorted(_by_key.items())]
        # weights shard over tp and pp only — dp replicates them, and
        # batch-1 KV degrades to replicated under dp too
        est = estimate_device_bytes(
            self.cfg, weight_repr=_repr, kv_dtype_bytes=self.kv_dtype.itemsize,
            n_shards=self.tp * self.pp,
            offload=(weight_mode == "offload"))
        self.hbm_estimate = est
        limit = check_budget(est["need_per_device"],
                             f"model {model_path} ({weight_mode})")
        # compile-ledger scope (runtime.introspection): every jitted program
        # below registers under this engine's namespace, so the retrace
        # sentinel's steady-state is per engine — a second engine warming up
        # can never trip the first one's alarm
        self.introspection_scope = f"engine-{next(_ENGINE_SEQ)}"
        # step watchdog (runtime.watchdog): every device dispatch below
        # runs under a deadline guard; the batch scheduler registers its
        # fail-all in watchdog.on_stall. Budget shape comes from env knobs
        # (DLLAMA_WATCHDOG*, README "Failure semantics").
        self.watchdog = StepWatchdog(name=self.introspection_scope)
        # prefill bucket widths this engine has actually dispatched — the
        # HBM admission guard charges an uncompiled bucket's temp estimate
        # on top of the measured programs (runtime.hbm.admission_check)
        self.seen_buckets: set[int] = set()
        # telemetry (runtime.telemetry): cached metric handles — the decode
        # hot path records through attribute reads, no registry lookups
        self._tm = telemetry.registry()
        self._tm.gauge(telemetry.HBM_NEED_BYTES).set(est["need_per_device"])
        self._tm.gauge(telemetry.HBM_LIMIT_BYTES).set(limit or 0)
        self._m_prefill_ms = self._tm.histogram(telemetry.PREFILL_CHUNK_MS)
        self._m_prefill_tok = self._tm.counter(telemetry.PREFILL_TOKENS)
        self._m_step_ms = self._tm.histogram(telemetry.DECODE_STEP_MS)
        self._m_decode_tok = self._tm.counter(telemetry.DECODE_TOKENS)
        self._m_coll_bytes = self._tm.counter(telemetry.COLLECTIVE_BYTES)
        self._m_kv = self._tm.gauge(telemetry.KV_OCCUPANCY)
        # request id stamped onto trace spans by the serving layer (the
        # engine itself has no request concept; -1 = unattributed)
        self.trace_rid = -1
        # flight recorder (runtime/flightrec): the single-sequence path
        # records per-chunk lifecycle events into the same ring the batch
        # scheduler's ticks land in
        self._flight = flightrec.recorder()
        # numerics observatory (runtime/numerics): activation taps are an
        # opt-in engine mode (the tapped program is only jitted when on, so
        # the default engine stays compile-ledger-quiet); the non-finite
        # tripwire is always on via the guarded step programs, and
        # fail-fast decides whether a poisoned dispatch raises
        # NumericsError or just counts and emits garbage
        self.numerics_taps = (numerics_taps
                              or os.environ.get("DLLAMA_NUMERICS_TAPS") == "1")
        if self.numerics_taps and multihost:
            raise ValueError(
                "--numerics-taps is single-host only (the taps pytree is "
                "host-read and would be non-addressable across processes)")
        if self.numerics_taps and pp > 1:
            # fail at STARTUP, not as a per-request trace-time ValueError
            # the HTTP layer would misreport as a client 400
            raise ValueError(
                "--numerics-taps is unsupported under pipeline "
                "parallelism (pp > 1): tap stats cannot thread through "
                "the manual pp shard_map region")
        self.nf_failfast = (numerics_failfast if numerics_failfast is not None
                            else os.environ.get(
                                "DLLAMA_NUMERICS_FAILFAST") == "1")
        # golden canary drift sentinel (numerics.CanarySentinel), wired by
        # the serving layer (run_api_server --canary-interval) or tests
        self.canary = None
        t_phase = self._stamp_startup("hbm_budget", t_phase)

        try:
            if verify_weights:
                # offline-grade full verification BEFORE any device
                # staging (--verify-weights): every tensor crc-checked
                # against the .m.sums manifest, all corrupt tensors named
                from .weights import WeightIntegrityError
                from .weights import verify_weights as _verify_all

                res = _verify_all(self.model_file)
                if res["corrupt"]:
                    raise WeightIntegrityError(
                        f"--verify-weights: {len(res['corrupt'])} of "
                        f"{res['tensors']} tensors corrupt in {model_path}: "
                        + ", ".join(res["corrupt"]))
            self._load_and_build(profile_split, t_phase)
        except BaseException:
            # atomic failure: a load/build that dies partway (corrupt
            # tensor, exhausted read retries, device staging error) must
            # not hand back — or leak — a half-initialized engine: drop
            # any partially placed device buffers, stop the watchdog, and
            # close the mmap before re-raising
            self._teardown_partial()
            raise

    def _load_and_build(self, profile_split: bool, t_phase: float) -> None:
        """Weight load + device staging + jitted-program construction —
        the failable tail of ``__init__``, split out so its caller can
        guarantee atomic teardown on ANY exception. ``t_phase``: where
        the previous start-up stamp ended (an optional --verify-weights
        sweep counts as weight load)."""
        weight_mode, multihost = self.weight_mode, self.multihost
        # streaming loader: shard-direct reads from the mmap, host memory
        # bounded by one tensor shard (VERDICT round-1 missing #4)
        self.params: Params = load_params_from_mfile(
            self.model_file, self.cfg, weight_mode, plan=self.plan)
        # pin the load-time quant-mode resolution: stored scale dtype and the
        # dense-vs-Q40 logits head were decided by
        # DLLAMA_TPU_QUANT_MODE as it read HERE. _dispatch re-checks this
        # resolution so an env flip after load fails loudly instead of
        # silently running one mode's math over the other mode's stored
        # weights.
        self._load_quant_resolution = self._quant_resolution()
        t_phase = self._stamp_startup("weight_load", t_phase)
        # a paged-only decoder is served by the paged generator alone, which
        # owns its pools: no batch-1 cache for a solo path it refuses
        self.kv: KVCache = None if self.cfg.paged_only else self._fresh_kv()
        self.pos = 0
        kinds = telemetry.registry().gauge(telemetry.LAYER_KINDS)
        for kind, n in family_of(self.cfg).layer_kinds(self.cfg).items():
            kinds.set(n, kind=kind)
        # the expert share (models/share.py): held here, of those routed
        telemetry.registry().gauge(telemetry.MOE_EXPERTS_HELD).set(
            self.cfg.n_experts)
        telemetry.registry().gauge(telemetry.MOE_EXPERTS_TOTAL).set(
            self.cfg.moe_router_width or self.cfg.n_experts)
        # Eval/Sync split (reference dllama.cpp:59-67): measured lazily on
        # the first decode of a generation when enabled; see measure_split()
        self.profile_split = profile_split
        self.split = None          # decode program's EvalSyncSplit | None
        self.split_prefill = None  # prefill program's split (measure_split)
        self.traffic = None        # runtime.profiling.TrafficStats | None
        # plan_scoped_jit: the traced programs bake in THIS engine's mesh
        # plan (constrain reads it at trace time), so the trace cache must
        # key on this engine, not the shared module-level function: a
        # second engine with a different plan would otherwise dispatch the
        # first engine's sharding constraints. scope= files every program
        # under this engine in the compile ledger (runtime.introspection).
        _sc = self.introspection_scope
        if multihost:
            from ..parallel.multihost import replicated, replicated_forward
        else:
            replicated = lambda program: program  # noqa: E731

        def jit_decode(program, solo, mirrored, static=(1,)):
            # a decode program as this engine dispatches it: the tripwire
            # rides every dispatch (its poison scalar traced, so arming
            # chaos never recompiles); under multihost its replicated form,
            # which root and worker compile alike. Filed under the name the
            # compile ledger always had for it; the KV cache (arg 4) is
            # donated, so decode updates it in place
            return plan_scoped_jit(
                replicated(program), scope=_sc,
                program=mirrored if multihost else solo,
                static_argnums=static, donate_argnums=(4,))

        self._step = plan_scoped_jit(
            replicated_forward if multihost else forward, scope=_sc,
            static_argnums=1, donate_argnums=(4,))
        # greedy fast path: argmax fused into the step, ONE dispatch per
        # token and a 4-byte host transfer instead of a full logits row;
        # used by next_token() when temperature == 0. The sampled twin
        # fuses temperature/top-p on device the same way (temp/topp/coin
        # are traced scalars, so knob changes never recompile).
        self._greedy_step = jit_decode(
            greedy_step_guarded, "greedy_step", "replicated_greedy")
        self._sampled_step = jit_decode(
            sampled_step_guarded, "sampled_step", "replicated_sampled")
        self._greedy_steps = jit_decode(
            greedy_steps_guarded, "greedy_steps", "replicated_greedy_steps",
            (1, 5))
        self._sampled_steps = jit_decode(
            sampled_steps_guarded, "sampled_steps",
            "replicated_sampled_steps", (1, 8))
        self._verify_step = jit_decode(
            verify_step_guarded, "verify_step", "replicated_verify")
        # the sampled pair again behind packed arguments (runtime/steppack),
        # as the slot-pool generator dispatches them at its pool's batch
        # width (everything broadcasts over rows, so they ARE the ragged
        # step and the ragged chunk): owned here so that every generator
        # serving this engine shares one executable a program. Lazy like
        # the rest: nothing compiles until a generator dispatches.
        self._packed_sampled_step = steppack.jit_packed_step(
            replicated(sampled_step_guarded), scope=_sc,
            name="_replicated_ragged_step" if multihost else "sampled_step")
        self._packed_sampled_steps = steppack.jit_packed_step(
            replicated(sampled_steps_guarded), scope=_sc,
            name="_replicated_ragged_steps" if multihost else "sampled_steps",
            n_static=1)
        # quality observatory (runtime/evalharness): teacher-forced prefill
        # twin whose epilogue is the fused log-softmax-gather NLL reduction,
        # so eval chunks never download full-vocab logits. Registration is
        # trace-lazy: nothing compiles until an eval run dispatches it.
        # Under multihost there is no replicated twin yet: score_nll refuses
        # loudly instead of silently diverging the worker mirrors with an
        # un-broadcast program
        self._nll_step = None if multihost else plan_scoped_jit(
            prefill_nll, scope=_sc, program="prefill_nll", static_argnums=1,
            donate_argnums=(5,))
        # activation taps (numerics observatory): the tapped forward is
        # only jitted when the engine opted in — a taps-off engine never
        # registers the program, keeping the default compile ledger
        # byte-identical to a taps-never-imported baseline
        self._step_tapped = None
        if self.numerics_taps:
            self._step_tapped = plan_scoped_jit(forward_with_taps, scope=_sc,
                                                static_argnums=1,
                                                donate_argnums=(4,))
        self._stamp_startup("kv_and_programs", t_phase)

    def _stamp_startup(self, phase: str, t0: float) -> float:
        """Add the seconds since ``t0`` to ``startup_s[phase]``; returns
        now, the next phase's start."""
        now = time.monotonic()
        self.startup_s[phase] = self.startup_s.get(phase, 0.0) + (now - t0)
        return now

    def _teardown_partial(self) -> None:
        """Explicit teardown after a failed load/build: no half-placed
        params tree stays reachable (device buffers free with the refs),
        the watchdog monitor stops, and the mmap closes. Idempotent."""
        self.params = None  # type: ignore[assignment]
        self.kv = None  # type: ignore[assignment]
        self.watchdog.close()
        try:
            self.model_file.close()
        except Exception:  # noqa: BLE001 — teardown must not mask the original load failure
            pass

    def _quant_resolution(self) -> bool:
        """The env's quant-mode RESOLUTION (not the display label): what the
        loader bakes into the weights. Label spellings that resolve the same
        way (``auto`` on a bf16 config vs explicit ``fast``) are equal here,
        so only a genuine numerics change trips the _dispatch guard."""
        from ..ops.linear import fast_numerics_resolved

        return fast_numerics_resolved(self.cfg.compute_dtype)

    def _require_solo_cache(self) -> None:
        """The single-sequence programs run over ``self.kv``, which a decoder
        with a recurrent state does not have: its context is K/V AND that
        state, and only the paged generator carries both. Nor does a decoder with
        window layers: its context is blocks of two pools."""
        if self.kv is None:
            raise RuntimeError(
                "a decoder with a recurrent state or window layers is "
                "served through BatchScheduler over the paged pool only: the "
                "single-sequence path (inference, chat, perplexity, "
                "score_nll) has no recurrent state and no second pool")

    def _fresh_kv(self) -> KVCache:
        # dtype policy in __init__ (self.kv_dtype): compute dtype for parity,
        # bf16/f8 for serving footprint+bandwidth
        kv = KVCache.create(self.cfg, dtype=self.kv_dtype)
        if self.plan is not None:
            kv = jax.device_put(kv, kv_cache_sharding(self.plan, kv))
        return kv

    def reset(self) -> None:
        if self.multihost and self._is_root:
            from ..parallel.multihost import CTRL_RESET

            self._ctrl.send(self._ctrl.encode(CTRL_RESET))
        if not self.cfg.paged_only:
            self.kv = self._fresh_kv()
        self.pos = 0
        if self.tokenizer is not None:
            self.tokenizer.reset_decoder()

    def close(self) -> None:
        if self.multihost and self._is_root:
            # graceful shutdown: the reference's batchSize=0 stop packet
            # (app.cpp:199-204)
            from ..parallel.multihost import CTRL_STOP

            self._ctrl.send(self._ctrl.encode(CTRL_STOP))
        self.watchdog.close()
        self.model_file.close()

    # -- low-level steps ----------------------------------------------------

    def _dispatch(self, step_fn, tokens_2d, start_pos: int, extras: tuple = ()):
        """Run one jitted step under the active mesh plan; returns
        (primary output, updated kv stored on self). ``extras`` are trailing
        traced f32 scalars (the sampled step's temperature/topp/coin)."""
        self._require_solo_cache()
        live = self._quant_resolution()
        if live != self._load_quant_resolution:
            raise RuntimeError(
                f"DLLAMA_TPU_QUANT_MODE changed after load: weights were "
                f"loaded for fast={self._load_quant_resolution!r} (scale "
                f"dtype and logits head are baked in) but the env now "
                f"resolves fast={live!r} — restart with the desired mode "
                f"instead")
        if self.multihost and self._is_root:
            # the reference's LlmControlPacket broadcast (app.cpp:193-204):
            # ship (program, tokens, position[, sampling scalars]) so workers
            # replay this dispatch
            from ..parallel.multihost import CTRL_GREEDY, CTRL_SAMPLED, CTRL_STEP

            if step_fn is self._greedy_step:
                kind = CTRL_GREEDY
            elif step_fn is self._sampled_step:
                kind = CTRL_SAMPLED
            else:
                kind = CTRL_STEP
            self._ctrl.send(self._ctrl.encode(
                kind, tokens_2d, start_pos,
                scalars=extras if kind == CTRL_SAMPLED else None))
        trailing: tuple = ()
        if step_fn is not self._step and step_fn is not self._step_tapped:
            # guarded decode programs take the tripwire's poison selector
            # as a trailing traced scalar (0.0 = clean; the `logits`
            # failpoint drives it). Multihost pins it to 0 on every
            # process — a root-only injection would desync the replicated
            # outputs — while keeping the scalar in the program so root
            # and workers compile identical executables.
            poison = 0.0 if self.multihost else numerics.poison_code()
            trailing = (jnp.float32(poison),)
        with self.watchdog.guard("dispatch"):
            failpoints.fire("step_hang")
            with (use_plan(self.plan) if self.plan is not None
                    else nullcontext()):
                out, self.kv = step_fn(
                    self.params, self.cfg,
                    jnp.asarray(tokens_2d, dtype=jnp.int32),
                    jnp.int32(start_pos), self.kv,
                    *(jnp.float32(e) for e in extras), *trailing)
        return out

    def _forward(self, tokens_2d: np.ndarray, start_pos: int) -> jax.Array:
        """Run one jitted step; returns logits [1, T, vocab] (device)."""
        return self._dispatch(self._step, tokens_2d, start_pos)

    def _prefill_chunk_size(self, remaining: int) -> int:
        """Largest prefill bucket that ``remaining`` fills, else the smallest
        bucket (the tail rides one padded small-chunk program)."""
        for b in self.prefill_buckets:  # descending
            if remaining >= b:
                return b
        return self.prefill_buckets[-1]

    def prefill(self, token_ids: list[int]) -> tuple[np.ndarray, list[StepMetrics]]:
        """Evaluate the prompt in bucketed chunks (PREFILL_BUCKETS; a pinned
        --nbatches gives the reference's fixed-chunk behavior, app.cpp:28);
        returns logits of the final prompt token and per-chunk metrics.
        Advances ``self.pos``."""
        if self.pos + len(token_ids) > self.cfg.seq_len:
            raise ValueError(
                f"prompt of {len(token_ids)} tokens at position {self.pos} exceeds "
                f"seq_len {self.cfg.seq_len}")
        metrics: list[StepMetrics] = []
        last_logits = None
        i = 0
        n = len(token_ids)
        # unguarded: the span also feeds the always-on /debug/requests ring,
        # which must show the prefill phase without --trace-out
        trace_t0 = telemetry.now_ns()
        while i < n:
            size = self._prefill_chunk_size(n - i)
            chunk = token_ids[i:i + size]
            valid = len(chunk)
            # Never let padding spill past seq_len: dynamic_update_slice would
            # clamp start_pos and overwrite genuine history. At the context
            # tail, pad only up to the remaining room (one extra compile max).
            pad_to = min(size, self.cfg.seq_len - self.pos)
            padded = chunk + [0] * (pad_to - valid)
            t0 = time.perf_counter()
            if self._step_tapped is not None:
                # numerics taps (opt-in): the tapped forward returns the
                # per-layer stats pytree alongside the logits; publish it
                # (gauges + /debug/numerics) per chunk
                logits, taps = self._dispatch(
                    self._step_tapped, np.asarray([padded]), self.pos)
                numerics.record_taps(
                    jax.tree_util.tree_map(np.asarray, taps))
            else:
                logits = self._forward(np.asarray([padded]), self.pos)
            logits_np = np.asarray(logits[0, valid - 1])
            # host-side tripwire on the one row the next token derives
            # from (it is already fetched; the fused in-graph check is
            # decode's — prefill materializes its logits anyway)
            bad = int(logits_np.size
                      - np.count_nonzero(np.isfinite(logits_np)))
            if bad:
                numerics.check_nonfinite(bad, "prefill",
                                         failfast=self.nf_failfast)
            # pad_to, not size: at the context tail the dispatched (and
            # compiled) program is pad_to wide — the admission guard must
            # not see a full-width bucket as compiled when only the
            # tail-width one is
            self.seen_buckets.add(pad_to)
            ms = (time.perf_counter() - t0) * 1000.0
            metrics.append(StepMetrics("eval", ms, valid))
            self._m_prefill_ms.record(ms)
            self._flight.note("prefill_chunk", self.trace_rid,
                              ms=round(ms, 3), n_tokens=valid, pos=self.pos)
            last_logits = logits_np
            self.pos += valid
            i += valid
        self._m_prefill_tok.inc(n)
        self._m_kv.set(self.pos / self.cfg.seq_len)
        telemetry.tracer().emit(self.trace_rid, "prefill", trace_t0,
                                telemetry.now_ns(), n_tokens=n)
        return last_logits, metrics

    def decode_step(self, token: int) -> np.ndarray:
        """One-token decode at the current position; returns logits [vocab]."""
        if self.pos >= self.cfg.seq_len:
            raise ValueError(f"position {self.pos} reached seq_len {self.cfg.seq_len}")
        logits = self._forward(np.asarray([[token]]), self.pos)
        self.pos += 1
        row = np.asarray(logits[0, 0])
        bad = int(row.size - np.count_nonzero(np.isfinite(row)))
        if bad:
            numerics.check_nonfinite(bad, "decode",
                                     failfast=self.nf_failfast)
        return row

    def next_token(self, token: int) -> int:
        """The engine's next-token primitive — always ONE fused dispatch and a
        4-byte device→host transfer: forward+argmax at temperature 0,
        forward+temperature/top-p sample otherwise (ops.sampling; the host
        steps the xorshift* RNG and ships the coin in as a scalar). All decode
        loops (CLI generate, API server) should use this. Set
        ``host_sampling=True`` to fall back to the logits-download + numpy
        oracle path (the parity reference)."""
        if self.pos >= self.cfg.seq_len:
            raise ValueError(f"position {self.pos} reached seq_len {self.cfg.seq_len}")
        t0 = time.perf_counter()
        if self.sampler.temperature == 0.0:
            nxt, nf = self._dispatch(self._greedy_step,
                                     np.asarray([[token]]), self.pos)
            self.pos += 1
            numerics.check_nonfinite(nf, "decode", failfast=self.nf_failfast)
        elif self.host_sampling:
            nxt = (self.sampler.sample(self.decode_step(token)),)
        else:
            coin, self.sampler.rng_state = xorshift_random_f32(self.sampler.rng_state)
            nxt, nf = self._dispatch(
                self._sampled_step, np.asarray([[token]]), self.pos,
                extras=(self.sampler.temperature, self.sampler.topp, coin))
            self.pos += 1
            numerics.check_nonfinite(nf, "decode", failfast=self.nf_failfast)
        self._m_step_ms.record((time.perf_counter() - t0) * 1000.0)
        self._m_decode_tok.inc()
        self.count_collective_bytes()
        self._m_kv.set(self.pos / self.cfg.seq_len)
        return int(nxt[0])

    def decode_chunk_tokens(self, token: int, k: int) -> list[int]:
        """``k`` decode steps in ONE dispatch (multi-step fused decode).

        Returns all ``k`` tokens; the caller decides how many to keep (EOS
        truncation) and then calls :meth:`commit_chunk` with that count —
        until committed, ``self.pos`` and the sampler RNG are NOT advanced.
        Overshoot KV rows beyond the committed count are invisible (causal
        mask) and rewritten by the next tokens at those positions — the same
        safety argument as padded prefill tails (module docstring)."""
        assert not self.host_sampling
        k = min(k, self.cfg.seq_len - self.pos)
        assert k >= 1
        greedy = self.sampler.temperature == 0.0
        coins = None
        if not greedy:
            coins = np.empty(k, dtype=np.float32)
            st = self.sampler.rng_state
            for i in range(k):
                coins[i], st = xorshift_random_f32(st)
        if self.multihost and self._is_root:
            from ..parallel.multihost import CTRL_GREEDY_CHUNK, CTRL_SAMPLED_CHUNK

            self._ctrl.send(self._ctrl.encode_chunk(
                CTRL_GREEDY_CHUNK if greedy else CTRL_SAMPLED_CHUNK,
                token, self.pos, k, coins=coins,
                temp=self.sampler.temperature, topp=self.sampler.topp))
        t0 = time.perf_counter()
        toks = self._run_chunk(token, self.pos, k, greedy,
                               self.sampler.temperature, self.sampler.topp,
                               coins)
        self._m_step_ms.record((time.perf_counter() - t0) * 1000.0)
        return [int(t) for t in toks[0]]

    def _run_chunk(self, token: int, start_pos: int, k: int, greedy: bool,
                   temp: float, topp: float, coins) -> np.ndarray:
        """Dispatch one fused K-step decode (root and worker replay path)."""
        tok0 = jnp.asarray([token], dtype=jnp.int32)
        poison = jnp.float32(0.0 if self.multihost
                             else numerics.poison_code())
        with self.watchdog.guard("chunk"):
            failpoints.fire("step_hang")
            with (use_plan(self.plan) if self.plan is not None
                    else nullcontext()):
                if greedy:
                    (toks, nf), self.kv = self._greedy_steps(
                        self.params, self.cfg, tok0, jnp.int32(start_pos),
                        self.kv, k, poison)
                else:
                    (toks, nf), self.kv = self._sampled_steps(
                        self.params, self.cfg, tok0, jnp.int32(start_pos),
                        self.kv, jnp.float32(temp), jnp.float32(topp),
                        jnp.asarray(coins, dtype=jnp.float32), k, poison)
            toks_np = np.asarray(toks)
        # fail-fast only on the root: this is also the multihost worker
        # replay path, and a NumericsError propagating out of worker_serve
        # would kill the mirror while the root recovers — the next root
        # dispatch would then hang in a collective against dead peers
        numerics.check_nonfinite(nf, "decode",
                                 failfast=self.nf_failfast and self._is_root)
        return toks_np

    @property
    def spec_active(self) -> bool:
        """Whether generation will use speculative verify dispatches — the
        ONE eligibility rule (engine loop, API loop, CLI stats all key off
        this)."""
        return bool(self.spec_lookup) and self.sampler.temperature == 0.0

    def speculative_tokens(self, token: int, drafts: list[int]) -> list[int]:
        """One speculative verify dispatch (greedy only): returns the
        accepted run of 1..K+1 tokens — exactly what that many single greedy
        steps would emit. Uncommitted like :meth:`decode_chunk_tokens`: the
        caller truncates at EOS and calls :meth:`commit_chunk` with the kept
        count (each kept token corresponds to one consumed input position).
        Rejected-draft KV rows sit beyond the committed point: causal-masked,
        then overwritten by the next dispatch's K+1 writes, which start
        exactly where they begin."""
        assert self.sampler.temperature == 0.0 and not self.host_sampling
        toks = np.asarray([[token, *drafts]], dtype=np.int32)
        assert self.pos + toks.shape[1] <= self.cfg.seq_len
        if self.multihost and self._is_root:
            from ..parallel.multihost import CTRL_SPEC_VERIFY

            self._ctrl.send(self._ctrl.encode(CTRL_SPEC_VERIFY, toks, self.pos))
        t0 = time.perf_counter()
        # unguarded (feeds the always-on /debug/requests ring too): one
        # dict + deque append per verify dispatch, µs against a ms dispatch
        trace_t0 = telemetry.now_ns()
        n_acc, preds = self._run_verify(toks, self.pos)
        telemetry.tracer().emit(self.trace_rid, "verify", trace_t0,
                                telemetry.now_ns(), n_tokens=n_acc + 1)
        self._m_step_ms.record((time.perf_counter() - t0) * 1000.0)
        self._tm.counter(telemetry.SPEC_DRAFT_TOKENS).inc(
            len(drafts), generator="engine")
        self._tm.counter(telemetry.SPEC_ACCEPTED_TOKENS).inc(
            n_acc, generator="engine")
        return [int(t) for t in preds[0, : n_acc + 1]]

    def _run_verify(self, tokens_2d, start_pos: int):
        """Dispatch one verify step (root and worker replay path)."""
        poison = jnp.float32(0.0 if self.multihost
                             else numerics.poison_code())
        with self.watchdog.guard("verify"):
            failpoints.fire("step_hang")
            with (use_plan(self.plan) if self.plan is not None
                    else nullcontext()):
                (n_acc, preds, nf), self.kv = self._verify_step(
                    self.params, self.cfg, jnp.asarray(tokens_2d, jnp.int32),
                    jnp.int32(start_pos), self.kv, poison)
            out = int(np.asarray(n_acc)[0]), np.asarray(preds)
        # root-only fail-fast: see _run_chunk (worker replay path)
        numerics.check_nonfinite(nf, "verify",
                                 failfast=self.nf_failfast and self._is_root)
        return out

    def count_collective_bytes(self, n_tokens: int = 1) -> None:
        """Charge ``n_tokens`` emitted decode tokens' analytic wire bytes
        into ``dllama_collective_bytes_total{op,wire}`` (the per-token
        price was fixed at construction — the traced program can't change
        mid-serving). No-op on a single device (no merges cross a wire)."""
        for op, wire, bytes_ in self._wire_traffic:
            self._m_coll_bytes.inc(bytes_ * n_tokens, op=op, wire=wire)

    def commit_chunk(self, n_keep: int) -> None:
        """Advance position and sampler RNG by the kept prefix of a chunk."""
        self.pos += n_keep
        if self.sampler.temperature != 0.0:
            st = self.sampler.rng_state
            for _ in range(n_keep):
                _, st = xorshift_random_f32(st)
            self.sampler.rng_state = st
        self._m_decode_tok.inc(n_keep)
        self.count_collective_bytes(n_keep)
        self._m_kv.set(self.pos / self.cfg.seq_len)

    # -- compile/HBM introspection -------------------------------------------

    def aot_compiled(self, kind: str):
        """AOT-compile one of the engine's programs for introspection
        (``kind``: ``"decode"`` = the fused greedy step, ``"prefill"`` = the
        largest prefill bucket that fits the current tail). Returns
        ``(program label, compiled)`` — the label is the compile ledger's
        program name, so the gauges this feeds line up with
        ``/debug/compiles`` entries. Goes through ``.lower().compile()``,
        which does not share the jit wrapper's executable cache; the
        persistent compile cache absorbs the duplicate (cost note on
        :meth:`measure_split`)."""
        self._require_solo_cache()
        pos = min(self.pos, self.cfg.seq_len - 1)
        with (use_plan(self.plan) if self.plan is not None else nullcontext()):
            if kind == "decode":
                fn = self._greedy_step
                compiled = fn.lower(
                    self.params, self.cfg, jnp.zeros((1, 1), jnp.int32),
                    jnp.int32(pos), self.kv, jnp.float32(0)).compile()
            elif kind == "prefill":
                fn = self._step
                chunk = next((b for b in self.prefill_buckets
                              if b <= self.cfg.seq_len - pos),
                             self.prefill_buckets[-1])
                compiled = fn.lower(
                    self.params, self.cfg, jnp.zeros((1, chunk), jnp.int32),
                    jnp.int32(pos), self.kv).compile()
            else:
                raise ValueError(f"unknown program kind {kind!r} "
                                 f"(decode | prefill)")
        return getattr(fn, "program", kind), compiled

    def collect_traffic(self):
        """Compute (once) and cache the decode program's static collective
        traffic from its compiled HLO (profiling.collective_traffic) —
        shared by :meth:`measure_split` and ``POST /debug/profile``."""
        if self.traffic is None:
            from .profiling import collective_traffic

            _, compiled = self.aot_compiled("decode")
            # per-layer collectives sit inside the layer-scan's while body:
            # once in the HLO text, n_layers executions per step
            self.traffic = collective_traffic(
                compiled.as_text(), len(jax.devices()),
                loop_multiplier=self.cfg.n_layers)
        return self.traffic

    # -- eval/sync split ----------------------------------------------------

    def measure_split(self, n_steps: int = 3):
        """One-off Eval/Sync measurement (reference per-token metrics,
        dllama.cpp:59-67). Two artifacts, both cached on the engine:

        * ``self.traffic`` — collective payload bytes per decode step, read
          off the compiled HLO (exact shapes; runtime.profiling docstring).
        * ``self.split`` — measured compute-vs-collective device time from a
          short profiler capture of scratch greedy dispatches at the current
          position. Scratch steps advance nothing: ``self.pos`` is untouched
          and the KV column they write is rewritten by the next real step
          (the same overwrite argument as decode_chunk_tokens). When the
          compiled program contains no collectives (tp=sp=pp=dp=1 — the
          single-chip case), sync is identically zero and no trace runs.

        Uses the greedy single-step program: every decode-path program shares
        the same forward body, and the sampling epilogue is microseconds.
        Chunked/speculative dispatches repeat that body K times per step, so
        the sync FRACTION transfers while byte counts scale with the step's
        token count (the CLI multiplies by StepMetrics.n_tokens).

        Cost note: reading the compiled HLO goes through the AOT
        ``.lower().compile()`` path, which does NOT share the jit wrapper's
        C++ executable cache — on TPU that's a second multi-second XLA
        compile unless the persistent compile cache (on by default in the
        CLI, ``--compile-cache``) absorbs it. Opt-in diagnostics only.
        """
        from .profiling import EvalSyncSplit, measure_eval_sync

        pos = min(self.pos, self.cfg.seq_len - 1)
        tokens = np.asarray([[0]])
        self.collect_traffic()
        if not self.traffic:
            self.split = EvalSyncSplit(eval_ms=0.0, sync_ms=0.0,
                                       n_steps=0, n_lanes=0)
            self.split_prefill = self.split  # no collectives in any program
            self._publish_split_metrics()
            return self.split

        def _scratch():
            jax.block_until_ready(
                self._dispatch(self._greedy_step, tokens, pos))

        _scratch()  # compile outside the capture window
        # the profiler intermittently delivers an (almost) empty capture —
        # observed on the CPU backend even after measure_eval_sync's warm-up
        # session. This branch only runs when the compiled program provably
        # contains collectives, so a capture with zero sync time IS an empty
        # capture: retry a few times (each costs ~n_steps dispatches).
        for _ in range(4):
            self.split = measure_eval_sync(_scratch, n_steps)
            if self.split.sync_ms > 0.0:
                break

        # the PREFILL program's own split: compute-bound wide chunks have a
        # different sync fraction than HBM-bound decode, and one fraction
        # for every step hid that per-phase variation (VERDICT r4 weak #5).
        # The scratch rides the largest BUCKET width inside the logical
        # seq_len tail — a production prefill shape (no one-off compile for
        # a width generation never runs, positions stay inside the rope
        # tables). Scratch rows [pos, pos+chunk) are unread garbage: every
        # row is rewritten by a real step before anything attends it (the
        # same overwrite argument as decode_chunk_tokens). Skipped (split
        # stays decode-only) when no bucket fits the remaining tail.
        tail = self.cfg.seq_len - pos
        chunk = next((b for b in self.prefill_buckets if b <= tail), None)
        if chunk is not None:
            ptokens = np.zeros((1, chunk), dtype=np.int32)

            def _scratch_p():
                jax.block_until_ready(
                    self._dispatch(self._step, ptokens, pos))

            _scratch_p()
            for _ in range(4):
                self.split_prefill = measure_eval_sync(_scratch_p, n_steps)
                if self.split_prefill.sync_ms > 0.0:
                    break
        self._publish_split_metrics()
        return self.split

    def _publish_split_metrics(self) -> None:
        """Fold the one-off static accounting into the live registry: a
        ``/metrics`` scrape then carries the reference's full per-token
        picture (eval/sync fraction + wire bytes) next to the serving
        metrics the reference never had."""
        if self.traffic is not None:
            self._tm.gauge(telemetry.COLLECTIVE_SENT_KB).set(
                self.traffic.sent_kb)
            self._tm.gauge(telemetry.COLLECTIVE_RECV_KB).set(
                self.traffic.recv_kb)
            self._tm.gauge(telemetry.COLLECTIVE_OPS).set(
                self.traffic.n_collectives)
        if self.split is not None:
            self._tm.gauge(telemetry.SYNC_FRACTION).set(self.split.sync_frac)
            self._tm.gauge(telemetry.COMM_EXPOSED_MS).set(
                self.split.exposed_ms)
        if self.split_prefill is not None:
            self._tm.gauge(telemetry.SYNC_FRACTION_PREFILL).set(
                self.split_prefill.sync_frac)

    # -- generation ---------------------------------------------------------

    def generate(self, prompt: str | list[int], max_tokens: int,
                 on_token=None, stop_on_eos: bool = True) -> GenerationResult:
        """Prefill + sample-decode loop (reference flow: dllama.cpp:13-116).

        ``on_token(token_id, piece)`` streams decoded text; ``max_tokens``
        caps generated tokens (the cache cap also applies).
        """
        if isinstance(prompt, str):
            assert self.tokenizer is not None, "tokenizer required for str prompts"
            ids = self.tokenizer.encode(prompt, is_start=self.pos == 0)
        else:
            ids = list(prompt)
        if not ids:
            raise ValueError("empty prompt")

        steps: list[StepMetrics] = []
        # evaluate all but the last prompt token; the last one seeds decode
        if len(ids) > 1:
            _, m = self.prefill(ids[:-1])
            steps.extend(m)

        out_tokens: list[int] = []
        pieces: list[str] = []
        token = ids[-1]
        limit = min(self.cfg.seq_len - self.pos, max_tokens)

        def emit(tok: int) -> bool:
            """Record/stream one token; True when generation should stop."""
            out_tokens.append(tok)
            piece = self.tokenizer.decode(tok) if self.tokenizer else None
            if piece is not None:
                pieces.append(piece)
            if on_token is not None:
                on_token(tok, piece)
            return (stop_on_eos and self.tokenizer is not None
                    and self.tokenizer.is_eos(tok))

        proposer = None
        if self.spec_active:
            from .speculative import NgramProposer

            proposer = NgramProposer(self.spec_lookup)
            proposer.extend(ids)

        stop = False
        while len(out_tokens) < limit and not stop:
            # Full-size chunks only: n_steps is a static jit argument, so a
            # smaller tail chunk would compile a fresh program mid-generation
            # (a multi-second stall on TPU). Tails run the single-step path.
            if (proposer is not None
                    and self.cfg.seq_len - self.pos >= self.spec_lookup + 1):
                t0 = time.perf_counter()
                run = self.speculative_tokens(token, proposer.draft())
                run = run[: limit - len(out_tokens)]
                n_keep = len(run)
                if stop_on_eos and self.tokenizer is not None:
                    for j, tok in enumerate(run):
                        if self.tokenizer.is_eos(tok):
                            n_keep = j + 1
                            break
                self.commit_chunk(n_keep)  # greedy: positions only
                steps.append(StepMetrics(
                    "pred", (time.perf_counter() - t0) * 1000.0, n_keep,
                    width=self.spec_lookup + 1))
                for tok in run[:n_keep]:
                    stop = emit(tok)
                proposer.extend(run[:n_keep])
                token = run[n_keep - 1]
                continue
            k = self.decode_chunk
            if (limit - len(out_tokens) < k
                    or self.cfg.seq_len - self.pos < k):
                k = 1
            t0 = time.perf_counter()
            if k <= 1:
                token = self.next_token(token)
                steps.append(StepMetrics(
                    "pred", (time.perf_counter() - t0) * 1000.0, 1))
                stop = emit(token)
                continue
            chunk = self.decode_chunk_tokens(token, k)
            n_keep = len(chunk)
            if stop_on_eos and self.tokenizer is not None:
                for j, tok in enumerate(chunk):
                    if self.tokenizer.is_eos(tok):
                        n_keep = j + 1
                        break
            self.commit_chunk(n_keep)
            steps.append(StepMetrics(
                "pred", (time.perf_counter() - t0) * 1000.0, n_keep,
                width=len(chunk)))
            for tok in chunk[:n_keep]:
                stop = emit(tok)
            token = chunk[n_keep - 1]
        if self.profile_split and out_tokens:
            # measured once per engine; each PROGRAM's sync fraction
            # back-fills its own steps' wall times — decode for pred steps,
            # the wide-chunk prefill program for eval steps (their fractions
            # genuinely differ: prefill is MXU-bound, decode HBM-bound).
            # Metrics must never destroy a finished generation: any
            # profiler/proto failure downgrades to "no split" with a warning.
            if self.split is None:
                try:
                    self.measure_split()
                except Exception as exc:  # noqa: BLE001
                    import warnings

                    warnings.warn(f"eval/sync split unavailable: {exc}",
                                  stacklevel=2)
                    # don't re-pay the AOT compile + trace on every
                    # generation once the environment has shown it can't
                    # deliver a split
                    self.profile_split = False
            if self.split is not None:
                frac = self.split.sync_frac
                pfrac = (self.split_prefill.sync_frac
                         if self.split_prefill is not None else None)
                for s in steps:
                    if s.kind == "pred":
                        s.sync_ms = s.ms * frac
                    elif pfrac is not None:
                        s.sync_ms = s.ms * pfrac
        return GenerationResult(tokens=out_tokens, text="".join(pieces),
                                prompt_tokens=len(ids), steps=steps)

    def perplexity(self, token_ids: list[int]) -> float:
        """Perplexity of a token sequence (reference mode: dllama.cpp:132-172):
        mean negative log-likelihood of each next token given its prefix."""
        if len(token_ids) < 2:
            raise ValueError("perplexity needs at least 2 tokens")
        if len(token_ids) > self.cfg.seq_len:
            raise ValueError("sequence longer than seq_len")
        self.reset()
        nll = 0.0
        count = 0
        i = 0
        while i < len(token_ids) - 1:
            size = self._prefill_chunk_size(len(token_ids) - 1 - i)
            chunk = token_ids[i:i + size]
            pad_to = min(size, self.cfg.seq_len - self.pos)
            pad = [0] * (pad_to - len(chunk))
            logits = self._forward(np.asarray([chunk + pad]), self.pos)
            logits_np = np.asarray(logits[0, :len(chunk)], dtype=np.float64)
            for j in range(len(chunk)):
                nxt = i + j + 1
                if nxt >= len(token_ids):
                    break
                row = logits_np[j]
                row = row - row.max()
                logp = row[token_ids[nxt]] - np.log(np.exp(row).sum())
                nll -= logp
                count += 1
            self.pos += len(chunk)
            i += len(chunk)
        return float(np.exp(nll / count))

    def score_nll(self, token_ids: list[int]) -> np.ndarray:
        """Teacher-forced per-token NLL of ``token_ids`` — the quality
        observatory's single-sequence oracle (runtime/evalharness.py).

        Chunks ``token_ids[:-1]`` through the jitted ``prefill_nll``
        program with the same bucket boundaries and zero padding the
        batched serving prefill uses, which is what makes the batched
        path's per-token values bit-identical to this oracle's. Returns
        the ``len(token_ids) - 1`` float32 NLL values in position order.
        Resets the engine's cache and advances ``self.pos`` like
        :meth:`perplexity`.
        """
        self._require_solo_cache()
        if self._nll_step is None:
            raise RuntimeError(
                "eval scoring is unsupported under --multihost (no "
                "replicated prefill_nll twin); score on a single-host "
                "engine")
        if len(token_ids) < 2:
            raise ValueError("scoring needs at least 2 tokens")
        if len(token_ids) > self.cfg.seq_len:
            raise ValueError("sequence longer than seq_len")
        self.reset()
        rest = token_ids[:-1]
        out: list[np.ndarray] = []
        i, n = 0, len(rest)
        while i < n:
            size = self._prefill_chunk_size(n - i)
            chunk = rest[i:i + size]
            valid = len(chunk)
            pad_to = min(size, self.cfg.seq_len - self.pos)
            pad = [0] * (pad_to - valid)
            targets = token_ids[i + 1:i + 1 + valid]
            with self.watchdog.guard("dispatch"):
                failpoints.fire("step_hang")
                with (use_plan(self.plan) if self.plan is not None
                        else nullcontext()):
                    nll, self.kv = self._nll_step(
                        self.params, self.cfg,
                        jnp.asarray(np.asarray([chunk + pad]), jnp.int32),
                        jnp.asarray(np.asarray([targets + pad]), jnp.int32),
                        jnp.int32(self.pos), self.kv)
            vals = np.asarray(nll[0, :valid], dtype=np.float32)
            bad = int(vals.size - np.count_nonzero(np.isfinite(vals)))
            if bad:
                numerics.check_nonfinite(bad, "eval",
                                         failfast=self.nf_failfast)
            out.append(vals)
            self.seen_buckets.add(pad_to)
            self.pos += valid
            i += valid
        return np.concatenate(out)


def _tp_ok(cfg: ModelConfig, tp: int) -> bool:
    try:
        validate_tp(cfg, tp)
        return True
    except ValueError:
        return False

"""Streaming weight loader — bounded host memory, shard-direct device placement.

Replaces the stack-everything-then-device_put loader (round-1
load_params_from_mfile) and the reference's root-to-worker weight streaming
(NnRootWeightLoader, nn-network.cpp:809-854): every parameter becomes a global
array via ``jax.make_array_from_callback``, whose callback reads ONLY the
bytes of the requested device shard straight from the mmap (the .m slice
readers in formats.mfile). Peak host memory is therefore one shard of one
stacked tensor — not the model — and under multi-host each process reads only
its own shards, which is exactly the per-node slice streaming the reference
does over TCP, done by the filesystem instead.

Layout notes:

* stacked per-layer weights ``[L, ...]`` are assembled layer-by-layer inside
  the callback (the scan-stacked axis never exists as a host copy of the
  whole model);
* Q40 planes are K-major (see ops.linear.QuantizedWeight): a shard of the
  ``out`` axis is a contiguous disk row range; a shard of the ``in`` axis is
  a 32-aligned block-column range — both are sliced out of the mmap without
  materializing the full tensor (mfile.tensor_q40_kmajor_sub);
* fully-replicated leaves are read once and ``device_put`` (the callback API
  would re-read per device).

405B-scale note (BASELINE config 5): this bounds *host* memory; weights still
reside in HBM. The host-DRAM offload mode (weights stay host-side, streamed
per-layer through a double buffer during forward) is designed to sit on top
of these same slice readers — see PARITY.md.
"""

from __future__ import annotations

import os
import time
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.mfile import ModelFile
from ..formats.quants import Q40, Q80, QUANT_BLOCK_SIZE
from ..ops.linear import QuantizedWeight
from ..parallel.api import MeshPlan, make_tp_mesh
from . import failpoints, telemetry

if TYPE_CHECKING:
    from ..models.config import ModelConfig
    from ..models.llama import Params


class WeightIntegrityError(RuntimeError):
    """A weight tensor's bytes do not match the checksum manifest. The
    message names the exact tensor — NOT retryable (the bytes are wrong,
    not the read)."""


class WeightLoadError(RuntimeError):
    """A weight read kept failing past the bounded retry budget."""


class ResilientReader:
    """Integrity + transient-retry layer over :class:`ModelFile` reads —
    the read-callback hardening the streaming loader threads every tensor
    access through:

    * **checksum verification** — when the model carries a ``.m.sums``
      manifest, each tensor's full on-disk bytes are crc32-verified ONCE,
      before its first slice is decoded; a mismatch raises
      :class:`WeightIntegrityError` naming the tensor (and counts
      ``dllama_load_corruption_total``). Verification is per tensor, not
      per slice: slices don't have manifest entries, and one sequential
      crc pass over pages the shard reads were about to touch anyway is
      the cheapest point with an exact blame label.
    * **bounded retry** — an ``OSError`` out of a read (NFS flake, EIO on
      a cold page, the armed ``load_read`` failpoint) is retried up to
      ``max_retries`` times with doubling backoff
      (``dllama_weight_io_retries_total``); exhaustion raises
      :class:`WeightLoadError` carrying the original error, which names
      the failing site. Non-OSError failures propagate immediately —
      corrupt bytes and injected hard failures are not transient.

    Either terminal error propagates out of ``load_params`` → the engine
    constructor, whose teardown guarantees the failure is atomic (no
    half-initialized engine)."""

    def __init__(self, mf: ModelFile, *, max_retries: int = 3,
                 backoff_s: float = 0.05):
        self.mf = mf
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self._verified: set[str] = set()

    def _verify(self, key: str) -> None:
        sums = self.mf.checksums
        if sums is None or key in self._verified:
            return
        want = sums.get(key)
        if want is None:
            raise WeightIntegrityError(
                f"weight tensor {key!r} has no entry in the checksum "
                f"manifest ({self.mf.path}.sums) — the manifest does not "
                f"belong to this file; regenerate it or delete it to "
                f"load unverified")
        got = self.mf.tensor_crc32(key)
        if got != want:
            telemetry.registry().counter(telemetry.LOAD_CORRUPTION).inc()
            raise WeightIntegrityError(
                f"weight tensor {key!r} is corrupt: crc32 {got:#010x} != "
                f"manifest {want:#010x} ({self.mf.path}) — the file is "
                f"damaged; re-download or reconvert it")
        self._verified.add(key)

    def _read(self, key: str, fn: Callable, *args):
        delay = self.backoff_s
        attempt = 0
        while True:
            try:
                failpoints.fire("load_read")
                self._verify(key)
                return fn(key, *args)
            except OSError as e:
                if attempt >= self.max_retries:
                    raise WeightLoadError(
                        f"reading weight tensor {key!r} failed after "
                        f"{attempt} retries: {type(e).__name__}: {e}"
                    ) from e
                attempt += 1
                telemetry.registry().counter(
                    telemetry.WEIGHT_IO_RETRIES).inc()
                time.sleep(delay)
                delay *= 2

    # the ModelFile read surface the streaming loader uses, each routed
    # through the verify+retry guard
    def tensor_f32(self, key):
        return self._read(key, self.mf.tensor_f32)

    def tensor_f32_rows(self, key, lo, hi):
        return self._read(key, self.mf.tensor_f32_rows, lo, hi)

    def tensor_q40_kmajor_sub(self, key, out_lo, out_hi, in_lo, in_hi):
        return self._read(key, self.mf.tensor_q40_kmajor_sub,
                          out_lo, out_hi, in_lo, in_hi)

    def tensor_q80_kmajor_sub(self, key, out_lo, out_hi, in_lo, in_hi):
        return self._read(key, self.mf.tensor_q80_kmajor_sub,
                          out_lo, out_hi, in_lo, in_hi)

    def tensor_scales_kmajor_sub(self, key, out_lo, out_hi, in_lo, in_hi):
        return self._read(key, self.mf.tensor_scales_kmajor_sub,
                          out_lo, out_hi, in_lo, in_hi)


def verify_weights(mf: ModelFile, emit=None) -> dict:
    """Offline full-file verification (``python -m dllama_tpu verify``,
    ``--verify-weights``): crc-check every tensor against the manifest.
    Returns ``{"tensors": n, "corrupt": [keys...]}``; raises
    :class:`WeightIntegrityError` when the model has no manifest."""
    if mf.checksums is None:
        raise WeightIntegrityError(
            f"{mf.path} has no checksum manifest ({mf.path}.sums) — "
            f"generate one with: python -m dllama_tpu verify --model "
            f"{mf.path} --write")
    corrupt: list[str] = []
    for key in mf.tensors:
        want = mf.checksums.get(key)
        got = mf.tensor_crc32(key)
        ok = want is not None and got == want
        if not ok:
            corrupt.append(key)
            telemetry.registry().counter(telemetry.LOAD_CORRUPTION).inc()
        if emit is not None:
            emit(f"{'✅' if ok else '❌'} {key}: crc32 {got:#010x}"
                 + ("" if ok else f" != manifest "
                    f"{'-' if want is None else format(want, '#010x')}"))
    return {"tensors": len(mf.tensors), "corrupt": corrupt}


def _bounds(sl: slice, dim: int) -> tuple[int, int]:
    lo, hi, step = sl.indices(dim)
    assert step == 1, sl
    return lo, hi


def _quant_k_bounds(k_sl: slice, in_dim: int,
                    want_scales: bool) -> tuple[int, int, int, int]:
    """K-range of a quantized-plane shard: element bounds ``(k_lo, k_hi)``
    plus the block-aligned superset ``(k_al, k_ah)`` the 32-element block
    reader must fetch (codes shards may not be 32-aligned when a small K
    still divides by tp; the caller trims ``k_lo-k_al : k_hi-k_al``).
    Scale shards are block-granular already, so the superset is exact."""
    if want_scales:
        k_lo, k_hi = _bounds(k_sl, in_dim // QUANT_BLOCK_SIZE)
        k_lo, k_hi = k_lo * QUANT_BLOCK_SIZE, k_hi * QUANT_BLOCK_SIZE
        return k_lo, k_hi, k_lo, k_hi
    k_lo, k_hi = _bounds(k_sl, in_dim)
    k_al = (k_lo // QUANT_BLOCK_SIZE) * QUANT_BLOCK_SIZE
    k_ah = -(-k_hi // QUANT_BLOCK_SIZE) * QUANT_BLOCK_SIZE
    return k_lo, k_hi, k_al, k_ah


def _layer_range(sl: slice, n_layers: int) -> range:
    lo, hi = _bounds(sl, n_layers)
    return range(lo, hi)


def dense_logits_resolved(compute_dtype: str) -> bool:
    """The effective dense-vs-quantized logits head decision for a config —
    the ONE composition of the knob + numerics rule, shared by the loader,
    the HBM estimator, and the multihost fingerprint so they can't drift."""
    from ..ops.linear import fast_numerics_resolved

    return dense_logits_wanted(fast_numerics_resolved(str(compute_dtype)))


def dense_logits_wanted(fast_numerics: bool) -> bool:
    """Whether the logits head loads as a resident dense-bf16 array.

    ``DLLAMA_TPU_DENSE_LOGITS``: ``on`` / ``off`` force it; ``auto``
    (default) follows the fast/exact numerics split — fast configs trade
    ~(vocab*dim) extra HBM bytes for a ~2.5x faster logits GEMV (XLA
    materializes the dequantized head every step otherwise; see
    tools/gemv_sweep.py 2026-07-31). Exact mode keeps the quantized head —
    its goldens are bit-tied to the f32 dequant."""
    knob = os.environ.get("DLLAMA_TPU_DENSE_LOGITS", "auto")
    if knob == "on":
        return True
    if knob == "off":
        return False
    return fast_numerics


def _make(shape: tuple[int, ...], dtype, sharding, cb: Callable) -> jax.Array:
    """Global array from per-shard callback.

    Multi-device fully-replicated leaves are read once and device_put (the
    callback API would re-read per device); everything else — including the
    single-device case — goes through the callback so only the shard bytes
    ever exist on host."""
    if sharding.is_fully_replicated and len(sharding.device_set) > 1:
        full = cb(tuple(slice(None) for _ in shape))
        return jax.device_put(jnp.asarray(full, dtype=dtype), sharding)
    return jax.make_array_from_callback(
        shape, sharding, lambda idx: np.asarray(cb(idx), dtype=dtype))


class StreamingLoader:
    """What a family's ``load_params`` is handed: the file's header ``h``,
    ``quantized`` (the matmul planes stay Q40 / Q80 on the device), and one
    reader a kind of tensor (``matmul``, ``stacked_f32``, ``f32``,
    ``expert_stack``), each of which places what it reads; ``params`` closes
    the tree. ``host_scope`` set: the stacks read meanwhile land in pinned
    host memory under ``--weight-mode offload``."""

    def __init__(self, mf: ModelFile, cfg: "ModelConfig", plan: MeshPlan | None,
                 weight_mode: str):
        self.mf = mf
        # every tensor read goes through the verify+retry guard; tensors
        # are crc-checked against the .m.sums manifest (when present)
        # before their first slice is decoded
        self.rd = ResilientReader(mf)
        self.cfg = cfg
        self.h = mf.header
        # a trivial 1-device mesh gives single-chip loads the same code path
        self.plan = plan if plan is not None else make_tp_mesh(1)
        # "offload" keeps the quantized-on-device semantics of "auto" but
        # places the per-layer stacks in pinned host memory (cfg.offload
        # streams them through the scan; ModelConfig.offload docs)
        self.offload = weight_mode == "offload"
        # Q40 and Q80 share the QuantizedWeight plane layout (codes*scales);
        # only the on-disk block decode differs (mfile.tensor_q*_kmajor_sub)
        self.quantized = (self.h.weight_type in (Q40, Q80)
                          and weight_mode in ("auto", "offload"))
        self.dense_dtype = jnp.bfloat16 if weight_mode == "bf16" else jnp.float32
        self.weight_mode = weight_mode
        self.host_scope = False
        # fast-mode numerics already round dequant to bf16, so storing the
        # scales in bf16 halves their HBM footprint AND removes a per-step
        # f32->bf16 conversion pass over every scale plane (the round-4
        # decode profile showed ~1.2 ms/step of f32 scale slicing+convert on
        # the 1b preset). Exact mode keeps f32 scales — the host-oracle bit
        # goldens depend on them. Resolved ONCE here: flipping
        # DLLAMA_TPU_QUANT_MODE after load leaves the stored dtype behind.
        from ..ops.linear import fast_numerics_resolved

        self.fast_numerics = fast_numerics_resolved(cfg.compute_dtype)
        self.scale_dtype = jnp.bfloat16 if self.fast_numerics else jnp.float32

    def _sharding(self, shape, *axes):
        """Build the target sharding; inside a host-placed scope (the layer
        stacks under offload) the arrays land in pinned host memory."""
        sh = self.plan.sharding_for(shape, *axes)
        if self.offload and self.host_scope:
            sh = sh.with_memory_kind("pinned_host")
        return sh

    # -- matmul weights -----------------------------------------------------

    def matmul(self, name: str, out_dim: int, in_dim: int, *, stacked: bool,
               out_axis: str | None, in_axis: str | None,
               force_dense: object = None, layers: list[int] | None = None):
        """One (possibly layer-stacked) matmul weight, quantized or dense.

        ``force_dense`` (a dtype) loads a quantized disk tensor as a resident
        dense array instead — used for the logits head in fast configs, where
        XLA materializes the huge [dim, vocab] dequant every step anyway
        (166 GB/s effective) while a resident bf16 head streams at
        ~750 GB/s (tools/gemv_sweep.py)."""
        # ``layers``: the model's layers this stack holds, in order (a
        # hybrid decoder's two stacks); absent, every layer
        ids = list(range(self.h.n_layers)) if layers is None else layers
        L = len(ids)
        key = (lambda l: f"{name}.{ids[l]}") if stacked else (lambda _l: name)

        if self.quantized and force_dense is None:
            lead = ("layers",) if stacked else ()  # pipeline axis when present
            cshape = ((L, in_dim, out_dim) if stacked else (in_dim, out_dim))
            sshape = ((L, in_dim // QUANT_BLOCK_SIZE, out_dim) if stacked
                      else (in_dim // QUANT_BLOCK_SIZE, out_dim))
            c_sh = self._sharding(cshape, *lead, in_axis, out_axis)
            s_sh = self._sharding(sshape, *lead, in_axis, out_axis)

            def read(idx, want_scales: bool):
                if stacked:
                    l_sl, k_sl, n_sl = idx
                    layers = _layer_range(l_sl, L)
                else:
                    k_sl, n_sl = idx
                    layers = [None]
                n_lo, n_hi = _bounds(n_sl, out_dim)
                k_lo, k_hi, k_al, k_ah = _quant_k_bounds(
                    k_sl, in_dim, want_scales)
                sub = (self.rd.tensor_q40_kmajor_sub
                       if self.h.weight_type == Q40
                       else self.rd.tensor_q80_kmajor_sub)
                out = None
                for i, l in enumerate(layers):
                    k = key(l) if l is not None else name
                    if want_scales:
                        # scales-only reader: keeps this callback's host
                        # allocation ~the scales slice instead of also
                        # decoding the 16x larger codes plane it discards
                        part = self.rd.tensor_scales_kmajor_sub(
                            k, n_lo, n_hi, k_al, k_ah)
                    else:
                        _, codes = sub(k, n_lo, n_hi, k_al, k_ah)
                        part = codes[k_lo - k_al:k_hi - k_al]
                    if not stacked:
                        return part
                    if out is None:  # fill in place: peak = slice + 1 layer
                        out = np.empty((len(layers),) + part.shape, part.dtype)
                    out[i] = part
                return out

            return QuantizedWeight(
                scales=_make(sshape, self.scale_dtype, s_sh,
                             lambda idx: read(idx, True)),
                codes=_make(cshape, jnp.int8, c_sh,
                            lambda idx: read(idx, False)),
            )

        # dense: reference on-disk orientation [out, in] (row-major)
        lead = ("layers",) if stacked else ()
        shape = (L, out_dim, in_dim) if stacked else (out_dim, in_dim)
        sh = self._sharding(shape, *lead, out_axis, in_axis)

        def read_dense(idx):
            if stacked:
                l_sl, o_sl, i_sl = idx
                layers = _layer_range(l_sl, L)
            else:
                o_sl, i_sl = idx
                layers = [None]
            o_lo, o_hi = _bounds(o_sl, out_dim)
            parts = [self.rd.tensor_f32_rows(key(l) if l is not None else name,
                                             o_lo, o_hi)[:, i_sl]
                     for l in layers]
            return np.stack(parts) if stacked else parts[0]

        return _make(shape, force_dense or self.dense_dtype, sh, read_dense)

    # -- small / dense tensors ---------------------------------------------

    def stacked_f32(self, name: str, *shape_tail: int,
                    layers: list[int] | None = None) -> jax.Array:
        ids = list(range(self.h.n_layers)) if layers is None else layers
        L = len(ids)
        shape = (L, *shape_tail)
        sh = self._sharding(shape, "layers", *([None] * len(shape_tail)))

        def read(idx):
            return np.stack([
                self.rd.tensor_f32(f"{name}.{ids[l]}").reshape(shape_tail)
                for l in _layer_range(idx[0], L)])

        return _make(shape, jnp.float32, sh, read)

    def f32(self, name: str, *shape: int, dtype=jnp.float32) -> jax.Array:
        sh = self.plan.sharding_for(tuple(shape), *([None] * len(shape)))
        return _make(tuple(shape), dtype, sh,
                     lambda idx: self.rd.tensor_f32(name)[idx])

    def params(self, layers) -> "Params":
        """The model's tree around a family's ``layers``: the embedding,
        the final norm and the logits head, which every family reads the
        same way."""
        from ..models.llama import Params

        h = self.h
        # the embedding is only ever read as
        # ``embedding[tokens].astype(compute_dtype)`` (models.llama.forward),
        # so storing it AT compute dtype is bit-identical (same rounding of
        # the same values) and, for bf16 configs, halves its HBM footprint
        # (~1 GB on the 8B shape)
        embedding = self.f32("embedding", h.vocab_size, h.dim,
                             dtype=jnp.dtype(self.cfg.compute_dtype))
        return Params(
            embedding=embedding,
            layers=layers,
            final_norm=self.f32("final_norm", h.dim),
            # a tied head IS the embedding, ``[vocab, dim]`` as a dense
            # weight lies: ONE device buffer, and the file's
            # ``final_matmul_logits`` (the reference format carries it) is
            # not read
            logits=embedding if h.tied_embeddings else self.matmul(
                "final_matmul_logits", h.vocab_size, h.dim, stacked=False,
                out_axis="vocab", in_axis=None,
                force_dense=(jnp.bfloat16
                             if dense_logits_wanted(self.fast_numerics)
                             else None)))

    def expert_stack(self, name: str, out_dim: int, in_dim: int,
                     out_axis: str | None, in_axis: str | None,
                     layers: list[int] | None = None):
        """[L, E, in, out] experts — IN-major, the lax.ragged_dot rhs layout
        (see models.llama.LayerParams). Sharded experts→ep, expert-hidden→tp;
        one (layer, expert) slice read at a time.

        Q40/Q80 files keep the expert planes QUANTIZED on device (stacked
        QuantizedWeight, same K-major plane layout as ``matmul``): experts
        are the bulk of an MoE checkpoint, so dense-loading them paid ~2x
        the HBM the budget estimator charged (VERDICT r4 weak #7). Dense
        files load at compute dtype (bf16 by default: a dense-f32 Mixtral
        would be unloadable — advisor round-1 medium finding)."""
        # ``layers``: the model's layers this stack holds, in order (a
        # model whose leading layers are dense); absent, every layer
        ids = list(range(self.h.n_layers)) if layers is None else layers
        L, E = len(ids), self.h.n_experts
        if self.quantized:
            cshape = (L, E, in_dim, out_dim)
            sshape = (L, E, in_dim // QUANT_BLOCK_SIZE, out_dim)
            c_sh = self._sharding(cshape, "layers", "experts",
                                  in_axis, out_axis)
            s_sh = self._sharding(sshape, "layers", "experts",
                                  in_axis, out_axis)
            sub = (self.rd.tensor_q40_kmajor_sub if self.h.weight_type == Q40
                   else self.rd.tensor_q80_kmajor_sub)

            def read_q(idx, want_scales: bool):
                l_sl, e_sl, k_sl, n_sl = idx
                layers = _layer_range(l_sl, L)
                experts = _layer_range(e_sl, E)
                n_lo, n_hi = _bounds(n_sl, out_dim)
                k_lo, k_hi, k_al, k_ah = _quant_k_bounds(
                    k_sl, in_dim, want_scales)
                out = None
                for li, l in enumerate(layers):
                    for ei, e in enumerate(experts):
                        if want_scales:
                            part = self.rd.tensor_scales_kmajor_sub(
                                f"{name}.{ids[l]}.{e}", n_lo, n_hi, k_al,
                                k_ah)
                        else:
                            _, codes = sub(f"{name}.{ids[l]}.{e}",
                                           n_lo, n_hi, k_al, k_ah)
                            part = codes[k_lo - k_al:k_hi - k_al]
                        if out is None:  # fill in place, one slice at a time
                            out = np.empty(
                                (len(layers), len(experts)) + part.shape,
                                part.dtype)
                        out[li, ei] = part
                return out

            return QuantizedWeight(
                scales=_make(sshape, self.scale_dtype, s_sh,
                             lambda idx: read_q(idx, True)),
                codes=_make(cshape, jnp.int8, c_sh,
                            lambda idx: read_q(idx, False)),
            )

        target = jnp.dtype(self.dense_dtype
                           if self.weight_mode not in ("auto", "offload")
                           else self.cfg.compute_dtype)
        shape = (L, E, in_dim, out_dim)
        sh = self._sharding(shape, "layers", "experts", in_axis, out_axis)

        def read(idx):
            l_sl, e_sl, i_sl, o_sl = idx
            o_lo, o_hi = _bounds(o_sl, out_dim)
            out = None
            for li, l in enumerate(_layer_range(l_sl, L)):
                for ei, e in enumerate(_layer_range(e_sl, E)):
                    part = self.rd.tensor_f32_rows(
                        f"{name}.{ids[l]}.{e}", o_lo, o_hi)[:, i_sl].T  # -> [in, out]
                    if out is None:
                        out = np.empty(
                            (len(_layer_range(l_sl, L)), len(_layer_range(e_sl, E)))
                            + part.shape, dtype=target)
                    out[li, ei] = part
            return out

        return _make(shape, target, sh, read)


def load_params(mf: ModelFile, cfg: "ModelConfig", weight_mode: str = "auto",
                plan: MeshPlan | None = None) -> "Params":
    """Build fully-placed (and, under a plan, fully-sharded) device params.

    Drop-in successor of the round-1 stacking loader: same Params tree, but
    host peak memory is bounded by one tensor shard and no second
    ``device_put``/reshard pass is needed. Which tensors make which tree is
    the decoder family's to say (``models/family.py``: its ``load_params``
    takes the loader).
    """
    from ..models.family import family_of

    if mf.header.n_experts > 0 and not mf.has_moe_router:
        raise ValueError(
            "MoE model file has no router tensors (written by the reference "
            "converter, which never emits block_moe_gate) — reconvert with "
            "python -m dllama_tpu.convert")
    return family_of(cfg).load_params(
        StreamingLoader(mf, cfg, plan, weight_mode), cfg)

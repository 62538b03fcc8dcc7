"""Pipeline parallelism — layer-stage sharding over a ``pp`` mesh axis.

New capability: neither this framework (rounds 1-2) nor the reference has
pipeline parallelism (SURVEY.md §2.2 "Pipeline parallelism: NO — every node
holds a shard of every layer"). The reference's closest concept is
``--gpu-segments``, which pins a segment range to a *local* device
(app.cpp:113-120); here the layer stack itself is sharded across chips.

Why it earns its place next to tp: tensor parallelism costs TWO all-reduces
of a ``[B, T, dim]`` activation per LAYER; a pipeline forward costs
``n_pp - 1`` activation permute rounds plus one activation all-reduce — per
FORWARD, independent of depth. (Under SPMD every stage participates in each
permute round, so total wire bytes are O(n_pp) activation copies per round;
still ~``2·n_layers / n_pp`` times less activation traffic than tp.) That is
the right trade on DCN-connected hosts — the modern form of the reference's
Raspberry-Pis-over-Ethernet deployment — and it divides the weight/KV
footprint by ``n_pp`` without the reference's ``2^n ≤ n_kv_heads`` shape
constraints (any ``n_layers % pp == 0`` works).

Design (TPU-native, single program): ``jax.shard_map`` manual over ``pp``
only — ``tp``/``dp`` stay AUTO inside, so the exact same ``_layer_step``
(with its logical-axis sharding constraints) runs within each stage.
Each device holds ``n_layers / pp`` stacked layers + their KV slices. Two
schedules, chosen statically by batch shape:

* **sequential** (B not divisible by pp, incl. single-sequence decode):
  ``pp`` ticks of [cond(stage == tick): scan local layers] → ``ppermute``
  the activation onward; latency is the sum of stage times (inherent to
  batch-1 pipelining).
* **GPipe microbatch** (B >= pp and divisible): the batch splits into pp
  microbatches flowing through the stages concurrently — stage d computes
  microbatch j-d at tick j, stage 0 injects a fresh microbatch each tick,
  the last stage accumulates outputs; utilization M/(M+pp-1).

A masked ``psum`` replicates the final output either way.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .api import shard_map

if TYPE_CHECKING:
    from ..models.config import ModelConfig
    from .api import MeshPlan

AXIS = "pp"


def _lead_pp_specs(tree):
    """Full-rank specs: leading (layer) axis manual on pp, rest auto."""
    return jax.tree.map(lambda a: P(AXIS, *([None] * (a.ndim - 1))), tree)


def _repl_specs(tree):
    return jax.tree.map(lambda a: P(*([None] * a.ndim)), tree)


def pp_forward(plan: "MeshPlan", cfg: "ModelConfig", params, tokens, start_pos,
               kv):
    """Full forward with the layer stack sharded over ``pp``.

    Same signature contract as models.llama.forward (which dispatches here
    when the active mesh has a pp axis); returns (logits, KVCache)."""
    from ..models.llama import _layer_step
    from ..models.rope import build_rope_cache
    from ..ops.linear import fake_quant_q80, linear
    from ..ops.norms import rms_norm
    from ..parallel.api import constrain
    from ..runtime.kvcache import KVCache

    n_pp = plan.axis_size(AXIS)
    B, T = tokens.shape
    x0 = params.embedding[tokens].astype(cfg.compute_dtype)
    x0 = constrain(x0, "batch", None, None)

    cos, sin = build_rope_cache(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    ragged = start_pos.ndim > 0   # [B] per-slot depths (batched serving)
    positions = ((start_pos[:, None] if ragged else start_pos)
                 + jnp.arange(T, dtype=jnp.int32)[None, :])
    positions = jnp.broadcast_to(positions, (B, T))
    perm = [(i, (i + 1) % n_pp) for i in range(n_pp)]

    # GPipe microbatching: with B divisible by n_pp the batch splits into
    # n_pp microbatches that flow through the stages concurrently — stage d
    # works on microbatch j-d at tick j, so utilization is M/(M+n_pp-1)
    # instead of the sequential schedule's 1/n_pp. Ragged per-row depths
    # ride along: each microbatch carries its own position/start rows.
    microbatched = n_pp > 1 and B % n_pp == 0

    def local(x, layers_l, k_l, v_l, cos, sin, sp0, pos):
        stage = lax.axis_index(AXIS)

        def run_layers(x, k, v, pos_rows, sp0_rows):
            def body(xc, xs):
                lp, k1, v1 = xs
                if cfg.offload:
                    # per-stage host streaming: this stage's layer shard
                    # lives in pinned host memory; each layer transfers on
                    # use, same as models.llama.forward's offload scan
                    lp = jax.device_put(lp, jax.memory.Space.Device)
                xo, k1, v1 = _layer_step(cfg, xc, lp, k1, v1, cos, sin,
                                         sp0_rows, pos_rows)
                return xo, (k1, v1)

            x, (k, v) = lax.scan(body, x, (layers_l, k, v))
            return x, k, v

        if microbatched:
            M = n_pp
            mbs = B // M
            zero = jnp.int32(0)

            def tick(j, carry):
                x_cur, k_l, v_l, out_acc = carry
                m = j - stage                     # this stage's microbatch
                active = (m >= 0) & (m < M)
                row0 = jnp.clip(m, 0, M - 1) * mbs
                # stage 0's input is the injected microbatch j (where m == j,
                # so row0 indexes it); later stages consume what the ring
                # delivered last tick
                inject = lax.dynamic_slice_in_dim(x, row0, mbs, axis=0)
                x_use = jnp.where(stage == 0, inject, x_cur)
                k_mb = lax.dynamic_slice_in_dim(k_l, row0, mbs, axis=1)
                v_mb = lax.dynamic_slice_in_dim(v_l, row0, mbs, axis=1)
                pos_mb = lax.dynamic_slice_in_dim(pos, row0, mbs, axis=0)
                sp0_mb = (lax.dynamic_slice_in_dim(sp0, row0, mbs, axis=0)
                          if ragged else sp0)

                def run(c):
                    x_use, k_mb, v_mb = c
                    return run_layers(x_use, k_mb, v_mb, pos_mb, sp0_mb)

                x_new, k_new, v_new = lax.cond(
                    active, run, lambda c: c, (x_use, k_mb, v_mb))
                # inactive ticks write back the unchanged slices — a no-op,
                # so no extra select is needed around the updates
                k_l = lax.dynamic_update_slice(
                    k_l, k_new, (zero, row0, zero, zero, zero))
                v_l = lax.dynamic_update_slice(
                    v_l, v_new, (zero, row0, zero, zero, zero))
                # the last stage produced microbatch m's final activation
                out_acc = jnp.where(
                    active & (stage == n_pp - 1),
                    lax.dynamic_update_slice(out_acc, x_new, (row0, zero, zero)),
                    out_acc)
                x_cur = lax.ppermute(x_new, AXIS, perm)
                return x_cur, k_l, v_l, out_acc

            x0 = jnp.zeros((mbs, T, x.shape[2]), dtype=x.dtype)
            out0 = jnp.zeros_like(x)
            _, k_l, v_l, out_acc = lax.fori_loop(
                0, M + n_pp - 1, tick, (x0, k_l, v_l, out0))
            x = lax.psum(
                jnp.where(stage == n_pp - 1, out_acc, jnp.zeros_like(out_acc)),
                AXIS)
            return x, k_l, v_l

        def run(carry):
            x, k_l, v_l = carry
            return run_layers(x, k_l, v_l, pos, sp0)

        def tick(s, carry):
            x, k_l, v_l = carry
            x, k_l, v_l = lax.cond(stage == s, run, lambda c: c,
                                   (x, k_l, v_l))
            # hand the activation to the next stage
            x = lax.ppermute(x, AXIS, perm)
            return x, k_l, v_l

        # n_pp - 1 permute rounds; the final stage's output skips the wasted
        # last hop and goes straight into the masked psum, which replicates
        # it so every stage computes identical logits
        x, k_l, v_l = lax.fori_loop(0, n_pp - 1, tick, (x, k_l, v_l))
        x, k_l, v_l = lax.cond(stage == n_pp - 1, run, lambda c: c,
                               (x, k_l, v_l))
        x = lax.psum(jnp.where(stage == n_pp - 1, x, jnp.zeros_like(x)), AXIS)
        return x, k_l, v_l

    fn = shard_map(
        local, mesh=plan.mesh,
        in_specs=(_repl_specs(x0), _lead_pp_specs(params.layers),
                  P(AXIS, None, None, None, None),
                  P(AXIS, None, None, None, None),
                  _repl_specs(cos), _repl_specs(sin),
                  P(None) if ragged else P(), _repl_specs(positions)),
        out_specs=(_repl_specs(x0), P(AXIS, None, None, None, None),
                   P(AXIS, None, None, None, None)),
        axis_names={AXIS}, check_vma=False)
    x, new_k, new_v = fn(x0, params.layers, kv.k, kv.v, cos, sin,
                         start_pos, positions)

    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    if cfg.sync_q80:
        x = fake_quant_q80(x)
    logits = linear(x, params.logits, out_axis="vocab").astype(jnp.float32)
    logits = constrain(logits, "batch", None, "vocab")
    return logits, KVCache(k=new_k, v=new_v)


def validate_pp(cfg: "ModelConfig", pp: int, tp: int = 1, dp: int = 1,
                sp: int = 1) -> None:
    """Pipeline divisibility and composition rules."""
    if cfg.n_layers % pp != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pp={pp}")
    if cfg.attn_impl == "flash" and (tp > 1 or dp > 1 or sp > 1):
        # pure pp is fine: inside the manual pp shard_map every stage's
        # arrays are fully local, so the plain kernel runs per stage
        # (models.llama._use_flash); with tp/dp/sp auto axes inside the
        # manual region a pallas_call can't partition — a forced kernel
        # must fail HERE, not silently run the oracle
        raise ValueError(
            "attn_impl='flash' under pp×(tp|dp|sp) is unsupported (the "
            "Pallas kernel can't nest inside the manual pp shard_map with "
            "auto axes); use 'auto' or 'xla', or pure pp")

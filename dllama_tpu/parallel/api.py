"""Mesh context and logical-axis activation constraints.

Model code names activation axes logically ("batch", "heads", "hidden",
"vocab"); a :class:`MeshPlan` maps those names onto mesh axes. With no active
plan every constraint is a no-op, so the same model code runs single-chip,
under the 8-device CPU test mesh, or on a real TPU slice — the SPMD analogue
of the reference running 1-node without sync steps (nn-executor.cpp:56,79).

Axis conventions:

* ``tp`` — tensor parallelism: attention heads / ffn hidden / vocab, the same
  three shard groups as the reference's row/col matmul split (SURVEY.md §2.2).
* ``dp`` — data parallelism over independent sequences (new capability; the
  reference is single-sequence).
* ``sp`` — sequence parallelism for long context (new capability; see
  :mod:`dllama_tpu.parallel.ring`).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    "batch": "dp",
    "seq": "sp",
    "heads": "tp",
    "kv_heads": "tp",
    "hidden": "tp",
    "vocab": "tp",
    "q_dim": "tp",
    "experts": "ep",
    "layers": "pp",  # pipeline stages: the stacked-layer axis (parallel/pipeline.py)
}


@dataclass(frozen=True)
class MeshPlan:
    """A mesh plus logical-axis→mesh-axis rules."""

    mesh: Mesh
    rules: dict[str, str | tuple[str, ...] | None] = field(
        default_factory=lambda: dict(DEFAULT_RULES))

    def resolve(self, logical: str | None):
        if logical is None:
            return None
        mesh_axis = self.rules.get(logical)
        if mesh_axis is None:
            return None
        # a rule may name a mesh axis that this mesh doesn't have (e.g. "sp"
        # on a pure-TP mesh) — treat as replicated
        axes = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        axes = tuple(a for a in axes if a in self.mesh.axis_names)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    def spec(self, *logical_axes: str | None) -> PartitionSpec:
        return PartitionSpec(*[self.resolve(a) for a in logical_axes])

    def sharding(self, *logical_axes: str | None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical_axes))

    def _axis_size(self, mesh_axis) -> int:
        axes = mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def axis_size(self, name: str) -> int:
        """Size of a mesh axis, 1 if the mesh doesn't have it."""
        return self.mesh.shape.get(name, 1)

    def sharding_for(self, shape: tuple[int, ...], *logical_axes: str | None) -> NamedSharding:
        """Shape-aware sharding: a logical axis whose dimension is not
        divisible by its mesh-axis size falls back to replicated.

        This is how KV-head replication groups work when tp > n_kv_heads (a
        capability the reference lacks — it caps nodes at nKvHeads,
        app.cpp:232-234): the cache's kv-head dim stays replicated while q
        heads remain fully sharded.
        """
        assert len(shape) == len(logical_axes), (shape, logical_axes)
        resolved = []
        for dim, logical in zip(shape, logical_axes):
            m = self.resolve(logical)
            if m is not None and dim % self._axis_size(m) != 0:
                m = None
            resolved.append(m)
        return NamedSharding(self.mesh, PartitionSpec(*resolved))


_state = threading.local()


def current_plan() -> MeshPlan | None:
    return getattr(_state, "plan", None)


@contextlib.contextmanager
def use_plan(plan: MeshPlan | None):
    """Activate a mesh plan for model/engine code in this thread."""
    prev = current_plan()
    _state.plan = plan
    try:
        yield plan
    finally:
        _state.plan = prev


def on_tpu() -> bool:
    """THE rule for "does this process drive a TPU": the default backend's
    platform name. Every kernel gate (``quant_matmul.pallas_mode_gate``,
    ``paged_attention.kernel_choice``, the flash-attention default, the sp
    ring's per-block kernel) asks here, so ``interpret`` can never come out
    differently for two kernels of one program."""
    return jax.default_backend() == "tpu"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True,
              axis_names=None):
    """The single ``jax.shard_map`` entry: all manual-SPMD call sites route
    through here (dlint rule ``shard-map-shim``), so a change of the JAX API
    is one edit, not six."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def plan_scoped_jit(fun, *, program: str | None = None,
                    scope: str | None = None, **jit_kwargs):
    """``jax.jit`` with a function identity unique to THIS call.

    Model functions bake the active :class:`MeshPlan` into their traced
    program (:func:`constrain` reads the thread-local plan at trace
    time), but jax's trace cache is keyed on the function's identity —
    so two engines jitting the SAME module-level function (``forward``,
    ``sampled_step``, ...) under DIFFERENT plans would share cache
    entries, and the second engine would dispatch a program whose
    sharding constraints belong to the first engine's mesh
    ("Received incompatible devices ... sharding_constraint inside
    jit"). Wrapping in a fresh per-call closure makes the cache
    per-engine, which is the true scope of a plan-dependent trace.
    ``functools.wraps`` preserves the signature so ``static_argnums`` /
    ``donate_argnums`` resolve exactly as on the original.

    Every callable built here is ALSO the compile ledger's hook point
    (runtime/introspection): the returned proxy records each trace+compile
    event — program name (default: the function's ``__name__``), ``scope``
    (the owning engine's namespace; retrace steadiness is per scope) — at
    two thread-local writes per call (compiles are detected via
    jax.monitoring events; the pjit cache size is NOT a compile signal).
    It is also where the program store (runtime/program_store) stands: with
    the persistent cache enabled and no mesh plan, the proxy loads each
    specialization's executable from the store instead of tracing it, so
    it is told the function and the jit options the store's key holds."""
    import functools

    from ..runtime.introspection import observe

    @functools.wraps(fun)
    def _plan_scoped(*args, **kwargs):
        return fun(*args, **kwargs)

    return observe(jax.jit(_plan_scoped, **jit_kwargs),
                   scope=scope or "default",
                   program=program or getattr(fun, "__name__", "jit"),
                   fun=fun, options=jit_kwargs)


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Apply a sharding constraint by logical axis names; no-op without a plan.

    Non-divisible axes degrade to replicated (see MeshPlan.sharding_for)."""
    plan = current_plan()
    if plan is None:
        return x
    assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, plan.sharding_for(tuple(x.shape), *logical_axes))


def make_tp_mesh(n_devices: int | None = None, devices=None) -> MeshPlan:
    """A 1-D tensor-parallel mesh over the first ``n_devices`` devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    import numpy as np

    mesh = Mesh(np.asarray(devices), ("tp",))
    return MeshPlan(mesh=mesh)


def make_mesh(axis_sizes: dict[str, int], devices=None) -> MeshPlan:
    """General mesh, e.g. ``{"dp": 2, "tp": 4}``; axis order follows dict order."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    n = 1
    for s in axis_sizes.values():
        n *= s
    arr = np.asarray(devices[:n]).reshape(tuple(axis_sizes.values()))
    return MeshPlan(mesh=Mesh(arr, tuple(axis_sizes.keys())))

"""A step's host arguments as ONE transfer.

A step or verify program takes a handful of small host arrays every tick
(tokens, positions, block tables, the sampling rows, the tripwire's poison
selector). Uploaded one by one each is a Python-level ``device_put`` that
costs about as much as any other whatever its size; packed they are one.
Every field is four bytes wide, so the fields travel as their bit patterns
in one flat int32 vector (:func:`pack`) and the jitted program takes them
apart again by a static layout (:func:`unpack`: a slice, a reshape and, for
a float, a ``bitcast_convert_type``). The model's step function sees
bit-identical arguments, so its tokens are identical, not close.

:func:`packed_program` is the thin wrapper that is jitted in the model
function's place: same name (the XLA module is still
``jit_paged_sampled_step_guarded``), the model function's own signature
underneath.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.api import plan_scoped_jit

# a layout: one (shape, dtype.str) per field, in the order packed
Layout = tuple[tuple[tuple[int, ...], str], ...]


def layout_of(fields) -> Layout:
    """The static half of a packed call: hashable, and constant for as
    long as the fields keep their shapes and dtypes (a program's life)."""
    return tuple((a.shape, a.dtype.str) for a in fields)


def pack(fields) -> np.ndarray:
    """``fields`` (numpy arrays or scalars of 4-byte dtypes) end to end as
    one fresh flat int32 vector of their bit patterns. A field of another
    width is an error, not a cast."""
    words = []
    for a in fields:
        if a.dtype.itemsize != 4:
            raise TypeError(f"a packed step argument is 4 bytes wide, not "
                            f"{a.dtype} {a.shape}")
        words.append(np.asarray(a).reshape(-1).view(np.int32))
    return np.concatenate(words)


def unpack(packed: jax.Array, layout: Layout) -> list[jax.Array]:
    """The fields of :func:`pack` back out of ``packed`` (traced): static
    slices, so nothing here depends on a value."""
    out, at = [], 0
    for shape, dtype in layout:
        n = math.prod(shape)
        x = packed[at:at + n].reshape(shape)
        if np.dtype(dtype) != np.int32:
            x = jax.lax.bitcast_convert_type(x, jnp.dtype(dtype))
        out.append(x)
        at += n
    assert at == packed.shape[0], (at, packed.shape, layout)
    return out


def packed_program(program):
    """``program(params, cfg, tokens, pos, cache, *rest, *static, poison)``
    (the signature every step and verify program has) as
    ``packed(params, cfg, words, cache, layout, *static)``: ``words`` is
    :func:`pack` of (tokens, pos, *rest, poison) and ``layout`` their
    :func:`layout_of`. To jit: ``cfg``, ``layout`` and ``static`` are static
    (arguments 1, 4 and on), the cache to donate is argument 3."""

    @functools.wraps(program)
    def packed(params, cfg, words, cache, layout, *static):
        *host, poison = unpack(words, layout)
        return program(params, cfg, host[0], host[1], cache, *host[2:],
                       *static, poison)

    # the signature is the wrapper's own: jit resolves its argnums against it
    del packed.__wrapped__
    return packed


def jit_packed_step(program, *, scope: str, name: str, n_static: int = 0):
    """A step or verify program jitted behind its packed arguments
    (:func:`packed_program`, what ``serving._StepIO.call`` dispatches):
    ``(params, cfg, words, cache, layout, *static)`` with ``cfg``, the
    layout and the ``n_static`` trailing arguments static and the cache
    donated. The wrapper bears the program's name, so the XLA module and the
    compile ledger's entry (``name``, under the engine's ``scope``) are the
    ones they were."""
    return plan_scoped_jit(
        packed_program(program), scope=scope, program=name,
        static_argnums=(1, 4) + tuple(range(5, 5 + n_static)),
        donate_argnums=(3,))

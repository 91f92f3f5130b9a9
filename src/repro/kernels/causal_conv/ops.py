"""Causal conv and its SiLU on the Pallas kernel pair, and when to take
it.

:func:`causal_conv_silu` is ``silu(causal_conv(x))`` of the channels
``[offset, offset + C)`` of an array, as one differentiable op:
``jax.custom_vjp`` over ``causal_conv_fwd`` and ``causal_conv_bwd``,
whose residuals are its inputs.  ``models.ssm.mamba2_forward`` takes it on
a TPU where :func:`takes_kernel` holds and the SSD takes its own pair
(``models.ssm._conv_kernel_applies``), with ``in_proj``'s output as the
array, so that the conv's channels are never sliced out of it.

Under a mesh the op runs in ``shard_map``: each chip takes its own rows
(the ``batch`` axes of ``ACT_RULES``) and every channel, and the
gradients of the taps and the bias, which every chip holds whole, are
summed over the rows' axes.  A ``model`` axis, which would split the
channels, takes the jnp path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...distributed.sharding import (ACT_RULES, get_abstract_mesh_or_none,
                                     logical_to_spec, model_axis_size)
from . import kernel


def _should_interpret() -> bool:
    """Interpret the kernel on the CPU only; any other backend compiles it
    (and raises what its compiler refuses)."""
    return jax.default_backend() == "cpu"


def takes_kernel(shape, offset: int, w_shape, dtype) -> bool:
    """Whether ``mamba2_forward`` on a TPU takes the pair for the channels
    ``[offset, offset + C)`` of an array of ``shape`` [B, S, W] with taps
    of ``w_shape`` [K, C] under the current mesh: no ``model`` axis splits
    the channels, and the kernels tile them (``kernel.tiles``)."""
    k, width = w_shape
    return (model_axis_size(get_abstract_mesh_or_none()) == 1
            and kernel.tiles(shape[1], offset, width, k,
                             jnp.dtype(dtype).itemsize))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv(xs, w, b, offset):
    return kernel.causal_conv_fwd(xs, w, b, offset,
                                  interpret=_should_interpret())


def _conv_fwd(xs, w, b, offset):
    return _conv(xs, w, b, offset), (xs, w, b)


def _conv_bwd(offset, res, dy):
    xs, w, b = res
    k, width = w.shape
    dx, dwb = kernel.causal_conv_bwd(xs, w, b, dy, offset,
                                     interpret=_should_interpret())
    dwb = jnp.sum(dwb, axis=0)
    # the cotangent of the whole array: zeros outside the conv's channels
    dxs = jnp.pad(dx, ((0, 0), (0, 0),
                       (offset, xs.shape[2] - offset - width)))
    return dxs, dwb[:k], dwb[k:]


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(xs, w, b, offset: int):
    """silu(causal conv) of the channels ``[offset, offset + C)`` of xs [B,
    S, W], C = w's width, w [K, C] and b [C]: ``models.ssm._causal_conv``
    and its SiLU, in the same precision.  Returns [B, S, C] in xs's
    dtype; gradients reach xs (zero outside the channels), w and b."""
    k, width = w.shape
    if not kernel.tiles(xs.shape[1], offset, width, k, xs.dtype.itemsize):
        raise ValueError(f"the causal conv kernel cannot tile {k} taps of "
                         f"channels [{offset}, {offset + width}) of "
                         f"{xs.shape}")
    args = (xs, w.astype(jnp.float32), b.astype(jnp.float32)[None])
    op = functools.partial(_conv, offset=offset)
    mesh = get_abstract_mesh_or_none()
    if mesh is not None:
        rows = logical_to_spec(("batch",), ACT_RULES, mesh, (xs.shape[0],))
        act = P(rows[0] if len(rows) else None)
        op = jax.shard_map(op, mesh=mesh, in_specs=(act, P(), P()),
                           out_specs=act, check_vma=False)
    return op(*args)

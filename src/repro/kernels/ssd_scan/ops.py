"""SSD intra-chunk op on the Pallas kernel pair, and when to take it.

:func:`ssd_intra` is the intra-chunk dual form (C·Bᵀ, the decay mask, the
product with dt·x and the inbound-state term) as one differentiable op:
``jax.custom_vjp`` over ``ssd_chunk_fwd`` and ``ssd_chunk_bwd``, so that
its L×L tiles live in VMEM in both passes.  ``models.ssm.ssd_chunked``
takes it on a TPU where :func:`takes_kernel` holds; everything around it
(dt·a, the within-chunk cumsum, the chunk states, the inter-chunk scan)
stays jnp and is differentiated by JAX.

Under a mesh the op runs in ``shard_map``: each chip takes its own rows
(the ``batch`` axes of ``ACT_RULES``) and its own heads (``model``, as
``ssm_heads`` maps), so the kernels add no collective.  A chip's heads
must be whole groups or lie in one group (:func:`chip_heads`); in the
second case B and C go to the kernels in one copy per chip of their group,
and the copies' gradients are summed outside, as the jnp path sums its
heads'.  Where neither holds the jnp path is taken.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...distributed.sharding import (ACT_RULES, get_abstract_mesh_or_none,
                                     logical_to_spec, model_axis_size)
from . import kernel

#: group counts G at which the pair was measured to pay in a training step
#: (TPU v5e, one chip, against the jnp path on the same chip).  G = 1:
#: mamba2-370m's step (N 128, 32 heads, 16 rows of 2048) 1.9 to 2.4% faster
#: in four pairs of runs; a 4-layer step at zamba2-7b's widths (d 3584, 112
#: heads, N 64, 2 rows of 4096) 4.5% faster.  G = 2, that 4-layer step: 3.6%
#: slower at N 64 and 3.1% slower at N 128, although one layer's SSD alone
#: is 18% faster on the pair at N 64.  Its profile puts the loss in the
#: gated norm that follows, which reduces per group and took 17 ms more a
#: step on the pair's output.  So G > 1 keeps the jnp path.
MEASURED_GROUPS = (1,)


def _should_interpret() -> bool:
    """Interpret the kernel on the CPU only; any other backend compiles it
    (and raises what its compiler refuses)."""
    return jax.default_backend() == "cpu"


def chip_heads(mesh, heads: int, groups: int):
    """How the heads split over ``mesh``'s ``model`` axis, M: (heads a
    chip, groups a chip, copies of each group's B and C).  A chip holds
    whole groups where M divides G, else the heads of one group where G
    divides M and M / G divides the group's heads (B and C then in M / G
    copies, one a chip); None where neither holds."""
    m = model_axis_size(mesh)
    if groups % m == 0:
        return heads // m, groups // m, 1
    if m % groups == 0 and (heads // groups) % (m // groups) == 0:
        return heads // m, 1, m // groups
    return None


def on_chip_tiling(x_shape, n: int, groups: int, chunk: int, dtype) -> bool:
    """Whether the chip's tiling takes these shapes (x [B, S, H, P] of one
    chip in chunks of ``chunk``, ``groups`` groups of state width ``n``):
    the L×L tiles and each step's lane tiles are whole 128-lane tiles, and
    a head block's backward step fits the VMEM budget."""
    _, s, h, p = x_shape
    blk = kernel.block_heads(h, groups, p, n, chunk,
                             jnp.dtype(dtype).itemsize)
    return (s % chunk == 0 and chunk % kernel.LANES == 0 and blk is not None
            and kernel.tile_heads(h, groups, p) * p == kernel.LANES)


def takes_kernel(x_shape, n: int, groups: int, chunk: int, dtype) -> bool:
    """Whether ``ssd_chunked`` on a TPU takes the pair for x [B, S, H, P]
    (the whole array) under the current mesh: a chip's heads split
    (:func:`chip_heads`), its shapes tile (:func:`on_chip_tiling`) and the
    group count is one the pair was measured to pay at
    (:data:`MEASURED_GROUPS`)."""
    b, s, h, p = x_shape
    split = chip_heads(get_abstract_mesh_or_none(), h, groups)
    return (split is not None and groups in MEASURED_GROUPS
            and on_chip_tiling((b, s, split[0], p), n, split[1], chunk,
                               dtype))


@jax.custom_vjp
def _intra(x, x32, dt, b, c, cs, states):
    y, _ = _intra_fwd(x, x32, dt, b, c, cs, states)
    return y


def _intra_fwd(x, x32, dt, b, c, cs, states):
    res = (x, dt, b.astype(x.dtype), c.astype(x.dtype), cs, states)
    return kernel.ssd_chunk_fwd(*res, interpret=_should_interpret()), res


def _intra_bwd(res, dy):
    dx, *rest = kernel.ssd_chunk_bwd(*res, dy, interpret=_should_interpret())
    return (None, dx, *rest)


_intra.defvjp(_intra_fwd, _intra_bwd)


def ssd_intra(x, dt, b, c, cs, states):
    """Intra-chunk output plus the inbound-state term.

    x: [B, S, H, P] (its dtype is the MXU operands' and y's); dt: [B, S,
    H] f32; b, c: [B, G, S, N] f32; cs: [B, S, H] f32 (within-chunk
    cumulative dt·a); states: [C, B, H, P, N] f32 (the state each chunk
    inherits, as the inter-chunk scan emits it).  Returns y [B, S, H, P];
    gradients reach every operand, in f32."""
    bsz, s, h, p = x.shape
    nc = states.shape[0]
    l = s // nc
    g, n = b.shape[1], b.shape[3]
    mesh = get_abstract_mesh_or_none()
    split = chip_heads(mesh, h, g)
    blk = None if split is None else kernel.block_heads(
        split[0], split[1], p, n, l, x.dtype.itemsize)
    if blk is None:
        raise ValueError(f"the SSD kernel cannot tile x {x.shape} with "
                         f"{g} groups of state {n} in chunks of {l}")
    t = kernel.tile_heads(split[0], split[1], p)

    def by_tile(v):
        """[B, S, H] -> [B, C, H / blk, blk / T, T, L]: a step's heads by
        lane tile, head-major."""
        v = jnp.moveaxis(v.reshape(bsz, nc, l, h), 2, 3)
        return v.reshape(bsz, nc, h // blk, blk // t, t, l)

    if split[2] > 1:
        # one copy of its group's B and C a chip; autodiff sums the copies
        b, c = (jnp.repeat(v, split[2], axis=1) for v in (b, c))
    # the kernel reads x in its own dtype; its f32 copy, which the kernel
    # never reads, takes x's gradient, so that the gradient adds up in f32
    xs = x.reshape(bsz, s, h * p)
    args = (xs, xs.astype(jnp.float32), by_tile(dt), b, c, by_tile(cs),
            states)
    op = _intra
    if mesh is not None:
        rows = logical_to_spec(("batch",), ACT_RULES, mesh, (bsz,))
        rows = rows[0] if len(rows) else None
        heads = "model" if model_axis_size(mesh) > 1 else None
        act = P(rows, None, heads)
        grp = P(rows, heads)
        op = jax.shard_map(op, mesh=mesh,
                           in_specs=(act, act, act, grp, grp, act,
                                     P(None, rows, heads)),
                           out_specs=act, check_vma=False)
    return op(*args).reshape(bsz, s, h, p)

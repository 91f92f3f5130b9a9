"""Mamba-2's depthwise causal conv and its SiLU as a Pallas TPU kernel
pair (forward and backward), the channels read in place from the array
that holds them.

For channel c, taps w[0..K-1] and bias b, the forward pass computes

    pre[t] = round(Σ_i x[t-(K-1)+i] · w[i] + b)      (x = 0 before t = 0)
    y[t]   = silu(pre[t])

with the sum in f32 and ``round`` to the activations' dtype, the order of
the jnp path (``models.ssm._causal_conv`` and its SiLU).  The backward
pass recomputes ``pre`` from x and, from dy, forms

    dpre[t] = dy[t] · silu'(pre[t])
    dx[t]   = Σ_i w[i] · dpre[t+(K-1)-i]                 (dpre = 0 past S)
    dw[i]   = Σ_t x[t-(K-1)+i] · dpre[t],   db = Σ_t dpre[t]

dw and db come out as f32 partial sums of each grid step, which the
caller sums over rows.

Grid: (row, channel tile), both parallel.  A block is one row's whole
sequence for ``CHANNELS`` channels, so no tap reaches across blocks.  x
is read from ``[B, S, W]`` at a lane-block offset: the channels
``[offset, offset + C)`` of the array that holds them (``in_proj``'s
output), which is never sliced.  y, dy and dx are ``[B, S, C]``.

Inside a step the rows go in chunks of ``ROWS``, in f32.  A chunk's taps
are sublane rotations (``pltpu.roll``) of the chunk after ``HALO`` rows
carried from the previous chunk (zeros before the first), so every load
and store is tile-aligned.  The backward pass keeps the row's dpre in a
VMEM scratch, with zero rows past S, and takes dx's taps from rotations
of each chunk's dpre and the next ``HALO`` rows.  On v5e the rotations
beat static unaligned loads from a padded scratch (the forward 1.45
against 1.52 ms, forward and backward 3.58 against 3.93 ms at
mamba2-370m's widths), and chunks of 64 rows beat 32, 128 and 256.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: channels a grid step takes (two 128-lane tiles)
CHANNELS = 256
#: rows a loop step takes (a multiple of 8)
ROWS = 64
#: rows carried before a chunk for its taps: one f32 sublane tile, so
#: K <= 9
HALO = 8
#: scoped VMEM a kernel may use; v5e has 128 MiB a core
VMEM_LIMIT = 64 * 2**20
#: what the double-buffered blocks and scratch of one step may take
VMEM_BUDGET = 32 * 2**20


def _rows(c):
    return pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)


def _taps(x_ref, c, halo, k):
    """Chunk ``c`` of x after ``halo``, the previous chunk's last ``HALO``
    rows, as f32: the K shifted views [ROWS, CHANNELS], tap i reading
    x[t-(K-1)+i], and the chunk's last rows, the next chunk's halo."""
    chunk = x_ref[_rows(c)].astype(jnp.float32)
    cat = jnp.concatenate([halo, chunk], axis=0)
    return [_shift(cat, k - 1 - i)[HALO:] for i in range(k)], chunk[-HALO:]


def _shift(v, s):
    """Rows of ``v`` moved ``s`` down (``s`` >= 0), wrapping round."""
    return pltpu.roll(v, s, 0) if s % v.shape[0] else v


def _pre(taps, w, b, dtype):
    """The pre-activation, summed in the jnp path's order and rounded to
    the activations' dtype, as f32."""
    acc = jnp.zeros_like(taps[0])
    for i, tap in enumerate(taps):
        acc = acc + tap * w[i:i + 1]
    return (acc + b).astype(dtype).astype(jnp.float32)


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref):
    k = w_ref.shape[0]
    w, b = w_ref[...], b_ref[...]

    def step(c, halo):
        taps, halo = _taps(x_ref, c, halo, k)
        pre = _pre(taps, w, b, x_ref.dtype)
        y_ref[_rows(c)] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return halo

    jax.lax.fori_loop(0, x_ref.shape[0] // ROWS, step,
                      jnp.zeros((HALO, x_ref.shape[1]), jnp.float32))


def _fold(v):
    """[ROWS, N] -> [8, N]: the rows summed by sublane, with VPU adds only
    (one cross-sublane sum per grid step, at the end)."""
    return v.reshape(ROWS // 8, 8, v.shape[1]).sum(axis=0)


def _bwd_kernel(x_ref, w_ref, b_ref, dy_ref, dx_ref, dwb_ref, dp_ref):
    k = w_ref.shape[0]
    s, ct = x_ref.shape
    w, b = w_ref[...], b_ref[...]
    dp_ref[s:] = jnp.zeros((HALO, ct), jnp.float32)

    def grads(c, carry):
        halo, acc = carry
        taps, halo = _taps(x_ref, c, halo, k)
        pre = _pre(taps, w, b, x_ref.dtype)
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[_rows(c)].astype(jnp.float32) * (
            sig * (1.0 + pre * (1.0 - sig)))
        dp_ref[_rows(c)] = dpre
        return halo, tuple(a + _fold(t * dpre) for a, t in zip(acc, taps)) + (
            acc[k] + _fold(dpre),)

    halo = jnp.zeros((HALO, ct), jnp.float32)
    acc = (jnp.zeros((8, ct), jnp.float32),) * (k + 1)
    _, acc = jax.lax.fori_loop(0, s // ROWS, grads, (halo, acc))
    for i, a in enumerate(acc):
        dwb_ref[i:i + 1] = a.sum(axis=0, keepdims=True)

    def inputs(c, carry):
        # dpre of the chunk and the next HALO rows; tap i reads
        # dpre[t+(K-1)-i]: the rows moved up by K-1-i
        win = dp_ref[pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS + HALO)]
        dx = jnp.zeros((ROWS, ct), jnp.float32)
        for i in range(k):
            up = _shift(win, ROWS + HALO - (k - 1 - i))[:ROWS]
            dx = dx + up * w[i:i + 1]
        dx_ref[_rows(c)] = dx.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, s // ROWS, inputs, 0)


def tiles(s: int, offset: int, width: int, taps: int, itemsize: int) -> bool:
    """Whether the pair takes a sequence of ``s`` rows, the channels
    ``[offset, offset + width)`` and ``taps`` taps: whole channel tiles at
    a whole tile's offset, whole row chunks, taps that reach back no
    further than :data:`HALO` rows, and a backward step within
    :data:`VMEM_BUDGET`."""
    return (offset % CHANNELS == 0 and width % CHANNELS == 0 and width > 0
            and s % ROWS == 0 and 1 <= taps <= HALO + 1
            and _bwd_vmem(s, itemsize) <= VMEM_BUDGET)


def _bwd_vmem(s: int, itemsize: int) -> int:
    """Bytes of the backward step's blocks, double-buffered, and scratch."""
    blocks = s * CHANNELS * 3 * itemsize                   # x, dy -> dx
    scratch = (s + HALO) * CHANNELS * 4                    # dpre
    return 2 * blocks + scratch


def _call(kernel, xs, w, offset, *, with_dy, outs, scratch_rows, name,
          interpret):
    """``pallas_call`` of ``kernel`` over the (row, channel tile) grid:
    x from ``xs`` [B, S, W] at channel ``offset``, w and b [K, C] and [1,
    C], and dy [B, S, C] ``with_dy``; ``outs`` name the outputs (``"act"``:
    [B, S, C] in x's dtype; ``"dwb"``: [B, K + 1, C] f32)."""
    bsz, s, _ = xs.shape
    k, width = w.shape
    base = offset // CHANNELS
    act = pl.BlockSpec((None, s, CHANNELS), lambda r, j: (r, 0, j))
    spec = {"act": act,
            "dwb": pl.BlockSpec((None, k + 1, CHANNELS),
                                lambda r, j: (r, 0, j))}
    shape = {"act": jax.ShapeDtypeStruct((bsz, s, width), xs.dtype),
             "dwb": jax.ShapeDtypeStruct((bsz, k + 1, width), jnp.float32)}
    ins = [pl.BlockSpec((None, s, CHANNELS), lambda r, j: (r, 0, base + j)),
           pl.BlockSpec((k, CHANNELS), lambda r, j: (0, j)),
           pl.BlockSpec((1, CHANNELS), lambda r, j: (0, j))]
    return pl.pallas_call(
        kernel,
        grid=(bsz, width // CHANNELS),
        in_specs=ins + [act] * with_dy,
        out_specs=[spec[o] for o in outs],
        out_shape=[shape[o] for o in outs],
        scratch_shapes=[pltpu.VMEM((r, CHANNELS), jnp.float32)
                        for r in scratch_rows],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name)


def causal_conv_fwd(xs, w, b, offset: int, *, interpret: bool = False):
    """silu(causal conv) of the channels ``[offset, offset + C)`` of xs
    [B, S, W]; w [K, C] and b [1, C] f32.  Returns y [B, S, C] in xs's
    dtype."""
    y, = _call(_fwd_kernel, xs, w, offset, with_dy=False, outs=("act",),
               scratch_rows=(), name="causal_conv_fwd",
               interpret=interpret)(xs, w, b)
    return y


def causal_conv_bwd(xs, w, b, dy, offset: int, *, interpret: bool = False):
    """Gradients of :func:`causal_conv_fwd` from dy [B, S, C]: (dx [B, S,
    C] in xs's dtype, [B, K + 1, C] f32 whose rows are each batch row's
    dw and, last, db)."""
    s = xs.shape[1]
    return _call(_bwd_kernel, xs, w, offset, with_dy=True,
                 outs=("act", "dwb"),
                 scratch_rows=(s + HALO,),
                 name="causal_conv_bwd", interpret=interpret)(xs, w, b, dy)

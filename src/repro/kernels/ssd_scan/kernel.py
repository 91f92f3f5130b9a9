"""SSD intra-chunk dual form as a Pallas TPU kernel.

The Mamba2 SSD insight: within a chunk of length L, the SSM output is an
attention-like product  Y = (L ∘ (C Bᵀ)) · (dt·X)  plus a contribution from
the inbound state; both are dense matmuls — MXU work — while only the
O(S/L) inter-chunk state recurrence is sequential (left in jnp/lax.scan).

Grid: (batch, heads, chunks), all independent.  Operands are head-major
(``[B, H, C, L, ...]``), so every block's two minor dims are whole array
dims — (L, P), (L, N), (P, N), (L, 1), (1, L) — which the TPU tiling
accepts at any width.  VMEM blocks per step: dt-weighted x (L×P), B/C
(L×N), the chunk's cumulative decays as a column and as a row, inbound
state (P×N) → output y (L×P).  L=256, P=64, N=128: ~0.5 MB resident
with the L×L score tile; MXU shapes 256×128×64.

The host wrapper (ops.py) precomputes the cumulative decays (cheap
elementwise) and runs the inter-chunk scan; the kernel fuses the three
matmul-heavy contractions of the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(dtx_ref, b_ref, c_ref, dacs_col_ref, dacs_row_ref, state_ref,
            y_ref):
    dtx = dtx_ref[...].astype(jnp.float32)                 # [L,P]
    b = b_ref[...].astype(jnp.float32)                     # [L,N]
    c = c_ref[...].astype(jnp.float32)                     # [L,N]
    dacs_col = dacs_col_ref[...]                           # [L,1]
    dacs_row = dacs_row_ref[...]                           # [1,L]
    state = state_ref[...]                                 # [P,N]

    # intra-chunk: scores = (C Bᵀ) ∘ L  where L[i,j] = exp(dacs_i - dacs_j)
    scores = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [L,L]
    l = scores.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.exp(dacs_col - dacs_row)
    scores = jnp.where(ii >= jj, scores * decay, 0.0)
    y = jax.lax.dot_general(
        scores, dtx, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [L,P]

    # inbound-state contribution: (C · stateᵀ) scaled by decay-from-start
    y_off = jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [L,P]
    y_ref[...] = (y + y_off * jnp.exp(dacs_col)).astype(y_ref.dtype)


def ssd_chunk_pallas(dtx, b_in, c_in, dacs, states, *,
                     interpret: bool = False):
    """Chunk outputs over a (B, H, C) grid, head-major operands.

    dtx: [B,H,C,L,P]; b_in, c_in: [B,H,C,L,N]; dacs: [B,H,C,L] f32
    (within-chunk cumulative decay); states: [B,H,C,P,N] f32 (inbound
    state per chunk).  Returns y [B,H,C,L,P] in ``dtx.dtype``."""
    bsz, h, nc, l, p = dtx.shape
    n = b_in.shape[-1]

    def block(*minor):
        return pl.BlockSpec((None, None, None, *minor),
                            lambda bb, hh, cc: (bb, hh, cc, 0, 0))

    return pl.pallas_call(
        _kernel,
        grid=(bsz, h, nc),
        in_specs=[block(l, p), block(l, n), block(l, n), block(l, 1),
                  block(1, l), block(p, n)],
        out_specs=block(l, p),
        out_shape=jax.ShapeDtypeStruct((bsz, h, nc, l, p), dtx.dtype),
        interpret=interpret,
    )(dtx, b_in, c_in, dacs[..., None], dacs[..., None, :], states)

"""Public SSD entry point: chunk the sequence, run the Pallas kernel for
the matmul-heavy intra-chunk work, lax.scan for the inter-chunk state."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import ssd_chunk_pallas


def _should_interpret() -> bool:
    """Interpret the kernel on the CPU only; any other backend compiles it
    (and raises what its compiler refuses)."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
             c_in: jax.Array, *, chunk: int = 256,
             initial_state: jax.Array | None = None):
    """x: [B,S,H,P], dt: [B,S,H], a: [H], b_in/c_in: [B,S,G,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N]).  Matches
    repro.kernels.ssd_scan.ref.ssd_ref."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    hpg = h // g
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)
    f32 = jnp.float32

    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h).astype(f32)
    bh = jnp.repeat(b_in, hpg, axis=2).reshape(bsz, nc, chunk, h, n)
    ch = jnp.repeat(c_in, hpg, axis=2).reshape(bsz, nc, chunk, h, n)
    da = dtc * a.astype(f32)[None, None, None, :]          # [B,C,L,H]
    dacs = jnp.cumsum(da, axis=2)
    datot = dacs[:, :, -1]                                 # [B,C,H]
    dtx = (xc.astype(f32) * dtc[..., None]).astype(x.dtype)

    # inter-chunk state recurrence (sequential, O(S/L) steps)
    # S_c^in = exp(datot_{c-1}) S_{c-1}^in + S_{c-1}^local
    # we need local chunk states first; compute them with the same kernel by
    # passing zero inbound states, then scan, then re-run for outputs with
    # the true inbound states.  To avoid running the kernel twice, compute
    # local states analytically here (cheap einsum) and give the kernel the
    # resolved inbound states for the fused output pass.
    w = jnp.exp(datot[:, :, None, :] - dacs)               # [B,C,L,H]
    local_states = jnp.einsum(
        "bclhn,bclhp->bchpn", bh.astype(f32),
        dtx.astype(f32) * w[..., None])                    # [B,C,H,P,N]

    def scan_step(carry, inp):
        s_local, da_tot = inp
        new = carry * jnp.exp(da_tot)[:, :, None, None] + s_local
        return new, carry                                  # emit inbound

    init = (jnp.zeros((bsz, h, p, n), f32) if initial_state is None
            else initial_state.astype(f32))
    final, inbound = jax.lax.scan(
        scan_step, init,
        (local_states.transpose(1, 0, 2, 3, 4), datot.transpose(1, 0, 2)))
    inbound = inbound.transpose(1, 0, 2, 3, 4)             # [B,C,H,P,N]

    def head_major(t):                                     # [B,C,L,H,..]
        return jnp.moveaxis(t, 3, 1)                       # -> [B,H,C,L,..]

    y = ssd_chunk_pallas(head_major(dtx), head_major(bh), head_major(ch),
                         head_major(dacs), inbound.transpose(0, 2, 1, 3, 4),
                         interpret=_should_interpret())
    y = jnp.moveaxis(y, 1, 3)                              # [B,C,L,H,P]
    return y.reshape(bsz, s, h, p).astype(x.dtype), final

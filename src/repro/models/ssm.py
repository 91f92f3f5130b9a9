"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

Training/prefill uses the chunked dual form: intra-chunk attention-like
matmuls (on a TPU the Pallas pair of ``kernels/ssd_scan``, see
:func:`ssd_chunked`) plus an inter-chunk state recurrence.  Decode uses
the O(1) recurrent update.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, SSMConfig
from .common import dense, rms_norm
from .params import ParamSpec


def ssm_specs(cfg: ModelConfig, stacked: int = 0) -> dict:
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    g, n = s.n_groups, s.d_state
    conv_ch = di + 2 * g * n
    dt = cfg.dtype

    def p(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             dtype=dt, **kw)
        return ParamSpec(shape, axes, dtype=dt, **kw)

    return {
        # projects to [z, x, B, C, dt]
        "in_proj": p((d, 2 * di + 2 * g * n + nh), ("embed", "ssm_inner"),
                     init="scaled"),
        "conv_w": p((s.d_conv, conv_ch), ("conv", "ssm_inner"),
                    init="scaled"),
        "conv_b": p((conv_ch,), ("ssm_inner",), init="zeros"),
        "a_log": ParamSpec((stacked, nh) if stacked else (nh,),
                           ("layers", "ssm_heads") if stacked
                           else ("ssm_heads",), init="ssm_a", dtype="float32"),
        "dt_bias": p((nh,), ("ssm_heads",), init="zeros"),
        "d_skip": p((nh,), ("ssm_heads",), init="ones"),
        "out_norm": p((di,), ("norm",), init="ones"),
        "out_proj": p((di, d), ("ssm_inner", "embed"), init="scaled"),
    }


def _segsum(cs: jax.Array) -> jax.Array:
    """Segment sums from a cumulative sum ``cs`` of x along the last axis:
    out[..., i, j] = sum_{k=j+1..i} x[..., k] = cs[..., i] - cs[..., j]
    for j <= i, and -inf above the diagonal."""
    l = cs.shape[-1]
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


#: SSD calls traced so far, by the path each took ("kernel" or "jnp")
SSD_PATHS: collections.Counter = collections.Counter()


def _kernel_applies(x: jax.Array, b_in: jax.Array, chunk: int) -> bool:
    """The Pallas pair runs on a TPU, where ``ops.takes_kernel`` holds."""
    from ..kernels.ssd_scan import ops
    return jax.default_backend() == "tpu" and ops.takes_kernel(
        x.shape, b_in.shape[3], b_in.shape[2], chunk, x.dtype)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
                c_in: jax.Array, chunk: int,
                initial_state: jax.Array | None = None,
                kernel: bool | None = None):
    """SSD dual form.

    x:  [B, S, H, P]  (P = head dim)
    dt: [B, S, H]     (positive step sizes)
    a:  [H]           (negative decay rates)
    b_in, c_in: [B, S, G, N]  (head h reads group h // (H / G))
    Returns (y [B, S, H, P], final_state [B, H, P, N]).

    B and C are contracted once per group: the heads of a group share the
    intra-chunk scores C·Bᵀ, the chunk states' B and the inter-chunk
    output's C. Only the decay mask exp(segsum(dt·a)) is per head.

    The intra-chunk part (scores, decay mask, their product with dt·x and
    the inbound-state term) runs on the Pallas pair of
    ``kernels/ssd_scan`` (``ssd_chunk_fwd``/``ssd_chunk_bwd``, its own
    VJP, the L×L tiles kept in VMEM) where the backend is a TPU and
    ``ops.takes_kernel`` holds: a chip's heads under the mesh are whole
    groups or lie in one group, its shapes fit the kernels' tiling, and
    the group count is one the pair was measured to pay at.  Otherwise,
    and on the CPU, it runs on the jnp path below, the kernel's reference.
    ``kernel`` forces either path (the tests'); each trace counts its path
    in :data:`SSD_PATHS`.  Decode steps take :func:`ssd_decode_step`.
    """
    if kernel is None:
        kernel = _kernel_applies(x, b_in, chunk)
    SSD_PATHS["kernel" if kernel else "jnp"] += 1
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    nc = s // chunk
    assert nc * chunk == s, f"seq {s} not divisible by chunk {chunk}"
    hpg = h // g
    f32 = jnp.float32

    # [B, C, L, ...] chunked views, heads split as [G, E] (E = H / G)
    xc = x.reshape(bsz, nc, chunk, g, hpg, p).astype(f32)
    dtc = dt.reshape(bsz, nc, chunk, g, hpg).astype(f32)
    bc = b_in.reshape(bsz, nc, chunk, g, n).astype(f32)
    cc = c_in.reshape(bsz, nc, chunk, g, n).astype(f32)
    da = dtc * a.astype(f32).reshape(g, hpg)              # [B,C,L,G,E]
    da_cs = jnp.cumsum(da, axis=2)                        # within-chunk cumsum
    da_total = da_cs[:, :, -1]                            # [B,C,G,E]
    xdt = xc * dtc[..., None]                             # dt-weighted input

    if not kernel:
        # ---- intra-chunk (dual / attention-like) ----
        # [B,C,G,E,L,L], from the cumsum the chunk states use
        lmat = jnp.exp(_segsum(da_cs.transpose(0, 1, 3, 4, 2)))
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc)     # [B,C,G,L,S]
        scores = cb[:, :, :, None] * lmat                 # [B,C,G,E,L,S]
        y_diag = jnp.einsum("bcgels,bcsgep->bclgep", scores, xdt)

    # ---- chunk states ----
    decay_to_end = jnp.exp(da_total[:, :, None] - da_cs)  # [B,C,L,G,E]
    states = jnp.einsum("bclgn,bclgep->bcgepn", bc,
                        xdt * decay_to_end[..., None])

    # ---- inter-chunk recurrence ----
    def step(carry, inp):
        st, = (carry,)
        s_c, da_tot = inp
        new = st * jnp.exp(da_tot)[:, :, None, None] + s_c
        return new, st                                   # emit state BEFORE chunk

    init = (jnp.zeros((bsz, h, p, n), f32) if initial_state is None
            else initial_state.astype(f32))
    final, prev_states = jax.lax.scan(
        step, init,
        (states.reshape(bsz, nc, h, p, n).transpose(1, 0, 2, 3, 4),
         da_total.reshape(bsz, nc, h).transpose(1, 0, 2)))

    if kernel:
        from ..kernels.ssd_scan.ops import ssd_intra
        group_major = lambda t: t.reshape(bsz, s, g, n).transpose(0, 2, 1, 3)
        y = ssd_intra(x, dt.astype(f32), group_major(bc), group_major(cc),
                      da_cs.reshape(bsz, s, h), prev_states)
        return y, final

    prev_states = prev_states.transpose(1, 0, 2, 3, 4).reshape(
        bsz, nc, g, hpg, p, n)                            # [B,C,G,E,P,N]

    # ---- inter-chunk contribution ----
    y_off = (jnp.einsum("bclgn,bcgepn->bclgep", cc, prev_states)
             * jnp.exp(da_cs)[..., None])

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final


def ssd_decode_step(x, dt, a, b_in, c_in, state):
    """Recurrent update for one token.

    x: [B, 1, H, P], dt: [B, 1, H], b_in/c_in: [B, 1, G, N],
    state: [B, H, P, N] -> (y [B,1,H,P], new_state)."""
    bsz, _, h, p = x.shape
    g = b_in.shape[2]
    hpg = h // g
    f32 = jnp.float32
    da = (dt[:, 0].astype(f32) * a.astype(f32)[None, :])  # [B,H]
    bh = jnp.repeat(b_in[:, 0], hpg, axis=1).astype(f32)  # [B,H,N]
    chh = jnp.repeat(c_in[:, 0], hpg, axis=1).astype(f32)
    xdt = (x[:, 0].astype(f32) * dt[:, 0, :, None].astype(f32))  # [B,H,P]
    new_state = (state.astype(f32) * jnp.exp(da)[:, :, None, None]
                 + jnp.einsum("bhn,bhp->bhpn", bh, xdt))
    y = jnp.einsum("bhpn,bhn->bhp", new_state, chh)
    return y[:, None].astype(x.dtype), new_state


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: [B, S, C]; w: [K, C]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1]].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
                   eps: float) -> jax.Array:
    """Mamba-2's gated norm: ``y * silu(z)`` RMS-normalised over each of
    ``groups`` groups of channels on its own (mamba_ssm's and Zamba2's
    ``group_size = d_inner / n_groups``), times ``w``; y, z: [..., d_inner].
    One group is the norm over all channels."""
    *lead, di = y.shape
    split = lambda t: t.reshape(*lead, groups, di // groups)
    out = rms_norm(split(y * jax.nn.silu(z)),
                   w.reshape(groups, di // groups), eps)
    return out.reshape(*lead, di)


def mamba2_forward(cfg: ModelConfig, p: dict, hidden: jax.Array,
                   ssm_state: jax.Array | None = None,
                   conv_state: jax.Array | None = None,
                   decode: bool = False):
    """Full Mamba2 block. hidden: [B, S, d].

    Train/prefill: decode=False, states None -> returns (y, final_states).
    Decode: decode=True with states -> one-token update.
    """
    s_cfg: SSMConfig = cfg.ssm
    d = cfg.d_model
    di = s_cfg.d_inner(d)
    nh = s_cfg.n_heads(d)
    g, n = s_cfg.n_groups, s_cfg.d_state
    bsz, s, _ = hidden.shape

    with jax.named_scope("proj"):
        zxbcdt = dense(hidden, p["in_proj"])
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, di + di + 2 * g * n], axis=-1)
    with jax.named_scope("conv"):
        if decode:
            # rolling conv state: [B, K-1, conv_ch]
            conv_in = jnp.concatenate([conv_state, xbc], axis=1)
            new_conv_state = conv_in[:, 1:]
            k = p["conv_w"].shape[0]
            xbc_conv = jnp.einsum("bkc,kc->bc", conv_in[:, -k:],
                                  p["conv_w"].astype(jnp.float32)) \
                + p["conv_b"].astype(jnp.float32)
            xbc_conv = jax.nn.silu(xbc_conv)[:, None].astype(hidden.dtype)
        else:
            xbc_conv = jax.nn.silu(
                _causal_conv(xbc, p["conv_w"], p["conv_b"]))
            new_conv_state = xbc[:, -(p["conv_w"].shape[0] - 1):]

    x_in, b_in, c_in = jnp.split(xbc_conv, [di, di + g * n], axis=-1)
    x_in = x_in.reshape(bsz, s, nh, s_cfg.head_dim)
    b_in = b_in.reshape(bsz, s, g, n)
    c_in = c_in.reshape(bsz, s, g, n)
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))

        if decode:
            y, new_state = ssd_decode_step(x_in, dt, a, b_in, c_in,
                                           ssm_state)
        else:
            y, new_state = ssd_chunked(
                x_in, dt, a, b_in, c_in, chunk=min(s_cfg.chunk_size, s),
                initial_state=ssm_state)
        y = y + x_in * p["d_skip"].astype(hidden.dtype)[None, None, :, None]
    y = gated_rms_norm(y.reshape(bsz, s, di), z, p["out_norm"], g,
                       cfg.rms_eps)
    with jax.named_scope("proj"):
        out = dense(y, p["out_proj"])
    return out, new_state, new_conv_state

"""SSD intra-chunk dual form as a Pallas TPU kernel pair (forward and
backward), so that no per-head L×L tile leaves VMEM.

Within a chunk of length L, head e of group g computes

    y_e = A_e · X_e + exp(cs_e) ∘ (C · S_eᵀ)
    A_e[l, s] = [s <= l] · (C·Bᵀ)[l, s] · exp(cs_e[l] - cs_e[s])

with X_e = dt·x of the head ([L, P]), B and C the group's [L, N] inputs,
cs_e the within-chunk cumulative sum of dt·a (non-increasing: dt > 0, a <
0) and S_e the state the chunk inherits ([P, N]).  C·Bᵀ is per group;
only the decay is per head.

The L×L tile is taken in (Q, Q) blocks, Q = 128.  A diagonal block
forms each head's decay elementwise.  A block (i, j < i) factors it
through r, the cumsum at block j's last row:

    exp(cs[l] - cs[s]) = exp(cs[l] - r) · exp(r - cs[s]),  both <= 1,

so its product (C·Bᵀ)_ij · (exp(r - cs) ∘ X)_j serves every head at once
and is scaled by exp(cs - r) after.  Only the diagonal blocks pay the
per-head elementwise work (half of it at L = 256).

Backward, from dy_e ([L, P]), with dA_e = [s <= l] dy_e · X_eᵀ:

    dX_e  = A_eᵀ · dy_e
    dC·Bᵀ = Σ_e dA_e ∘ exp(cs_e[l] - cs_e[s])       (masked)
    dC    = dC·Bᵀ · B + Σ_e (exp(cs_e) ∘ dy_e) · S_e
    dB    = (dC·Bᵀ)ᵀ · C
    dcs_e = rowsum(dA_e ∘ A_e) - colsum(dA_e ∘ A_e)
            + Σ_p dy_e ∘ exp(cs_e) ∘ (C · S_eᵀ)
    dS_e  = (exp(cs_e) ∘ dy_e)ᵀ · C

taken by the same blocks: in a factored block, dcs gets +Σ_p dy ∘ y_ij
at the rows l, -Σ_p d(vX) ∘ vX at the rows s, and their difference at
r's row.  The kernel takes dt and x apart (dt·x is formed in VMEM) and
returns dx = dX ∘ dt and ddt = Σ_p dX ∘ x.

Grid: (batch, group, chunk, head block), all but the last parallel.  A
head block is ``block_heads`` of the group's heads (as many as the VMEM
budget lets the backward step hold: all of them at both benchmark
cells' widths).  C·Bᵀ is computed at a group's first head block and kept
in VMEM for the others; in the backward pass dC·Bᵀ gathers there too,
and dB and dC are written after the last.

VMEM blocks of a step, each operand in the layout it has outside:

- x, y, dy and dx: ``[B, S, H·P]`` (a free view of ``[B, S, H, P]``),
  blocks of (L, block_heads·P);
- B, C, dB, dC: ``[B, G, S, N]`` (a free view at G = 1, a small
  transpose otherwise), blocks of (L, N);
- dt, the cumulative decays and their gradients: head-major ``[B, C, H
  / block_heads, block_heads / T, T, L]`` f32, small (the only
  head-major operands; whole (T, L) tiles, so that a lane tile's rows
  are one leading index);
- the inbound states and their gradient: ``[C, B, H, P, N]`` f32, as the
  inter-chunk scan emits them, blocks of (block_heads, P, N);
- scratch: C·Bᵀ and dC·Bᵀ, (L, L) f32.

Inside a step the heads go by lane tiles: T = 128 / P heads a tile (all
the group's heads where they are fewer).  Every product runs on the
whole tile and a head's lanes are selected from it, so no operand is
narrower than the MXU's 128 lanes and no lane slice is unaligned (each
head's product on its own transposed (P, Q) slice streams half the rows
but was slower on v5e: its transposes cost more than the rows saved).  MXU
operands are in the activations' dtype (bf16 on the chip's training
path, as XLA's default precision feeds the jnp path's einsums), every
accumulation is f32, and the exponents, mask and cumulative decays stay
f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: scoped VMEM a kernel may use; v5e has 128 MiB a core
VMEM_LIMIT = 64 * 2**20
#: what the double-buffered blocks and scratch of one step may take
VMEM_BUDGET = 32 * 2**20


def _lanes(j, w):
    return pl.ds(pl.multiple_of(j * w, w), w)


def _decay(cols, rows, e, causal):
    """exp(cs[l] - cs[s]) for s <= l, else 0, of the tile's head ``e``."""
    return jnp.where(causal, jnp.exp(cols[:, e:e + 1] - rows[e:e + 1, :]),
                     0.0)


def _per_lane(cols, head):
    """[L, T] per-head columns -> [L, W]: each lane takes its head's."""
    out = jnp.zeros((cols.shape[0], head.shape[1]), cols.dtype)
    for e in range(cols.shape[1]):
        out = jnp.where(head == e, cols[:, e:e + 1], out)
    return out


def _head_sums(v, head, t):
    """[L, W] -> [T, L]: row e sums the lanes of head e."""
    cols = jnp.zeros((v.shape[0], t), jnp.float32)
    col_of = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    for e in range(t):
        cols = jnp.where(col_of == e, jnp.sum(jnp.where(head == e, v, 0.0),
                                              axis=1, keepdims=True), cols)
    return cols.T


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _setup(x_ref, cs_ref, cb_ref, p):
    """The step's sizes and masks: (L, Q, T, W, MXU dtype, the causal mask
    of a (Q, Q) diagonal block, the head of each lane [1, W])."""
    l = cb_ref.shape[0]
    q = LANES if l % LANES == 0 else l
    t = cs_ref.shape[1]
    w = t * p
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    head = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    return l, q, t, w, x_ref.dtype, causal, head


def _tile_inputs(j, x_ref, dt_ref, cs_ref, w, head):
    """Lane tile ``j``: x and dt·x as f32, dt by lane, the cumulative
    decays as rows [T, L] and columns [L, T], and by lane [L, W]."""
    xf = x_ref[:, _lanes(j, w)].astype(jnp.float32)        # [L,W]
    dt = _per_lane(dt_ref[j].T, head)                      # [L,W]
    rows = cs_ref[j]                                       # [T,L]
    cols = rows.T                                          # [L,T]
    return xf, dt, xf * dt, rows, cols, _per_lane(cols, head)


def _blocks(l, q):
    """The (Q, Q) blocks of the causal L×L tile: (i, slice) rows, and for
    each the earlier blocks (jb, slice, index of jb's last row)."""
    out = []
    for i in range(l // q):
        out.append((i, slice(i * q, (i + 1) * q),
                    [(jb, slice(jb * q, (jb + 1) * q), (jb + 1) * q - 1)
                     for jb in range(i)]))
    return out


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, cs_ref, s_ref, y_ref, cb_ref,
                *, p):
    l, q, t, w, cdt, causal, head = _setup(x_ref, cs_ref, cb_ref, p)

    @pl.when(pl.program_id(3) == 0)
    def _():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], ((1,), (1,)))

    def tile(j, carry):
        _, _, xdt32, rows, cols, cs = _tile_inputs(j, x_ref, dt_ref, cs_ref,
                                                   w, head)
        xdt = xdt32.astype(cdt)
        st = s_ref[pl.ds(j * t, t)].reshape(w, -1).astype(cdt)  # [W,N]
        for i, li, earlier in _blocks(l, q):
            # diagonal block: each head's decay, elementwise
            cb = cb_ref[li, li]
            y = jnp.zeros((q, w), jnp.float32)
            for e in range(t):
                decay = _decay(cols[li], rows[:, li], e, causal)
                y = jnp.where(head == e, _dot((cb * decay).astype(cdt),
                                              xdt[li], ((1,), (0,))), y)
            # earlier blocks: exp(cs_l - cs_s) = u_l · v_s through the
            # cumsum r at the earlier block's end, both factors <= 1, so
            # one product serves every head of the tile
            for jb, lj, end in earlier:
                r = cs[end:end + 1]                        # [1,W]
                vx = (jnp.exp(r - cs[lj]) * xdt32[lj]).astype(cdt)
                z = _dot(cb_ref[li, lj].astype(cdt), vx, ((1,), (0,)))
                y = y + jnp.exp(cs[li] - r) * z
            y_off = _dot(c_ref[li, :], st, ((1,), (1,))) * jnp.exp(cs[li])
            y_ref[li, _lanes(j, w)] = (y + y_off).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // w, tile, 0)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, cs_ref, s_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, dcs_ref, ds_ref,
                cb_ref, dcb_ref, *, p):
    l, q, t, w, cdt, causal, head = _setup(x_ref, cs_ref, cb_ref, p)

    @pl.when(pl.program_id(3) == 0)
    def _():
        cb_ref[...] = _dot(c_ref[...], b_ref[...], ((1,), (1,)))
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    row_of = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    col_of = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1

    def tile(j, carry):
        xf, dt, xdt32, rows, cols, cs = _tile_inputs(j, x_ref, dt_ref,
                                                     cs_ref, w, head)
        xdt = xdt32.astype(cdt)
        dyf = dy_ref[:, _lanes(j, w)].astype(jnp.float32)
        dy = dyf.astype(cdt)
        blocks = _blocks(l, q)
        dxdt = [jnp.zeros((q, w), jnp.float32) for _ in blocks]
        dcs = [jnp.zeros((t, q), jnp.float32) for _ in blocks]
        for i, li, earlier in blocks:
            cb = cb_ref[li, li]
            dcb = jnp.zeros((q, q), jnp.float32)
            row_sums = jnp.zeros((q, t), jnp.float32)      # d cs[l], as l
            col_sums = jnp.zeros((t, q), jnp.float32)      # d cs[s], as s
            for e in range(t):
                mine = head == e
                decay = _decay(cols[li], rows[:, li], e, causal)
                a = cb * decay
                dxdt[i] = dxdt[i] + jnp.where(
                    mine, _dot(a.astype(cdt), dy[li], ((0,), (0,))), 0.0)
                da = _dot(jnp.where(mine, dy[li], 0).astype(cdt), xdt[li],
                          ((1,), (1,)))
                dcb = dcb + da * decay
                g = da * a
                row_sums = jnp.where(col_of == e, jnp.sum(
                    g, axis=1, keepdims=True), row_sums)
                col_sums = jnp.where(row_of == e, jnp.sum(
                    g, axis=0, keepdims=True), col_sums)
            dcb_ref[li, li] += dcb
            dcs[i] = dcs[i] + row_sums.T - col_sums
            for jb, lj, end in earlier:
                r = cs[end:end + 1]                        # [1,W]
                v = jnp.exp(r - cs[lj])
                u = jnp.exp(cs[li] - r)
                vx32 = v * xdt32[lj]
                vx = vx32.astype(cdt)
                cbij = cb_ref[li, lj].astype(cdt)
                z = _dot(cbij, vx, ((1,), (0,)))           # [Q,W]
                dz = (u * dyf[li]).astype(cdt)
                dcb_ref[li, lj] += _dot(dz, vx, ((1,), (1,)))
                dvx = _dot(cbij, dz, ((0,), (0,)))         # [Q,W]
                dxdt[jb] = dxdt[jb] + v * dvx
                gu = _head_sums(dyf[li] * z * u, head, t)  # via u, at l
                gv = _head_sums(dvx * vx32, head, t)       # via v, at s
                dr = (jnp.sum(gv, axis=1, keepdims=True)
                      - jnp.sum(gu, axis=1, keepdims=True))
                dcs[i] = dcs[i] + gu
                dcs[jb] = dcs[jb] - gv + jnp.where(last, dr, 0.0)
        scale = jnp.exp(cs)                                # exp(cs) by lane
        st = s_ref[pl.ds(j * t, t)].reshape(w, -1)         # [W,N] f32
        c = c_ref[...]
        y_off = _dot(c, st.astype(cdt), ((1,), (1,))) * scale
        dsc = (dyf * scale).astype(cdt)                    # d(C·Sᵀ)
        dc_ref[...] += _dot(dsc, st.astype(cdt), ((1,), (0,)))
        ds = _dot(dsc, c, ((0,), (0,)))                    # [W,N]
        ds_ref[pl.ds(j * t, t)] = ds.reshape(t, p, -1)
        off = _head_sums(dyf * y_off, head, t)             # [T,L]
        for i, li, _ in blocks:
            dcs_ref[j, :, li] = dcs[i] + off[:, li]
            dx_ref[li, _lanes(j, w)] = dxdt[i] * dt[li]
            ddt_ref[j, :, li] = _head_sums(dxdt[i] * xf[li], head, t)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // w, tile, 0)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _():
        dcb = dcb_ref[...].astype(cdt)
        dc_ref[...] += _dot(dcb, b_ref[...], ((1,), (0,)))
        db_ref[...] = _dot(dcb, c_ref[...], ((0,), (0,)))


def tile_heads(heads: int, groups: int, p: int) -> int | None:
    """Heads a lane tile holds, T: 128 / P, or all the group's heads where
    they are fewer; None where P does not divide 128."""
    if LANES % p or heads % groups:
        return None
    return min(LANES // p, heads // groups)


def block_heads(heads: int, groups: int, p: int, n: int, chunk: int,
                itemsize: int) -> int | None:
    """The most heads of a group one grid step can take: whole lane tiles
    (:func:`tile_heads`) whose backward step fits :data:`VMEM_BUDGET`;
    None where no block does."""
    t = tile_heads(heads, groups, p)
    if t is None:
        return None
    e = heads // groups
    for blk in range(e, 0, -1):
        if e % blk == 0 and blk % t == 0 and \
                _bwd_vmem(blk, t, p, n, chunk, itemsize) <= VMEM_BUDGET:
            return blk
    return None


def _bwd_vmem(blk, t, p, n, chunk, itemsize) -> int:
    """Bytes of the backward step's blocks, double-buffered, and scratch."""
    pad = lambda v, m: -(-v // m) * m
    act = chunk * blk * p * (2 * itemsize + 4)             # x, dy -> dx
    bc = chunk * n * 2 * (itemsize + 4)                    # b, c -> db, dc
    cs = blk // t * 8 * pad(chunk, LANES) * 4 * 4          # dt, cs -> ddt, dcs
    st = blk * p * n * 4 * 2                               # s -> ds
    return 2 * (act + bc + cs + st) + 2 * chunk * chunk * 4


def _call(kernel, x, b, cs, *, ins, outs, out_dtypes, scratch, name,
          interpret):
    """``pallas_call`` of ``kernel`` over the (batch, group, chunk, head
    block) grid, the operands in the layouts :func:`ssd_chunk_fwd` names;
    ``ins`` and ``outs`` name each operand's kind ("act", "grp", "cs",
    "st"), ``out_dtypes`` each output's dtype."""
    bsz, nc, hb, nt, t, l = cs.shape
    g, n = b.shape[1], b.shape[3]
    blk = nt * t
    p = x.shape[2] // (hb * blk)
    nb = hb // g
    spec = {
        "act": pl.BlockSpec((None, l, blk * p),
                            lambda b_, g_, c_, h_: (b_, c_, g_ * nb + h_)),
        "grp": pl.BlockSpec((None, None, l, n),
                            lambda b_, g_, c_, h_: (b_, g_, c_, 0)),
        "cs": pl.BlockSpec((None, None, None, nt, t, l),
                           lambda b_, g_, c_, h_: (b_, c_, g_ * nb + h_, 0,
                                                   0, 0)),
        "st": pl.BlockSpec((None, None, blk, p, n),
                           lambda b_, g_, c_, h_: (c_, b_, g_ * nb + h_, 0,
                                                   0)),
    }
    shape = {"act": x.shape, "grp": b.shape, "cs": cs.shape,
             "st": (nc, bsz, hb * blk, p, n)}
    return pl.pallas_call(
        lambda *refs: kernel(*refs, p=p),
        grid=(bsz, g, nc, nb),
        in_specs=[spec[k] for k in ins],
        out_specs=[spec[k] for k in outs],
        out_shape=[jax.ShapeDtypeStruct(shape[k], d)
                   for k, d in zip(outs, out_dtypes)],
        scratch_shapes=[pltpu.VMEM((l, l), jnp.float32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name)


def ssd_chunk_fwd(x, dt, b, c, cs, states, *, interpret: bool = False):
    """Chunk outputs of the dual form.

    x: [B, S, H·P] (its dtype is the MXU dtype and y's); b, c: [B, G, S,
    N] (MXU dtype); dt and cs: [B, C, H / blk, blk / T, T, L] f32 (the step
    sizes and the within-chunk cumulative dt·a; ``blk`` heads a grid step,
    :func:`block_heads`, T a lane tile, :func:`tile_heads`); states: [C,
    B, H, P, N] f32 (the state each chunk inherits).  Returns y [B, S,
    H·P]."""
    y, = _call(_fwd_kernel, x, b, cs,
               ins=("act", "cs", "grp", "grp", "cs", "st"), outs=("act",),
               out_dtypes=(x.dtype,), scratch=1, name="ssd_chunk_fwd",
               interpret=interpret)(x, dt, b, c, cs, states)
    return y


def ssd_chunk_bwd(x, dt, b, c, cs, states, dy, *, interpret: bool = False):
    """Gradients of :func:`ssd_chunk_fwd` from dy [B, S, H·P]: returns
    (dx, ddt, db, dc, dcs, dstates), all f32, in their operands'
    layouts."""
    kinds = ("act", "cs", "grp", "grp", "cs", "st")
    return _call(_bwd_kernel, x, b, cs, ins=kinds + ("act",), outs=kinds,
                 out_dtypes=(jnp.float32,) * 6, scratch=2,
                 name="ssd_chunk_bwd", interpret=interpret)(
                     x, dt, b, c, cs, states, dy)

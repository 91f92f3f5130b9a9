"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation (not a CUDA port): the online-softmax recurrence is
blocked for VMEM — one (block_q × head_dim) query tile stays resident in
VMEM while (block_k × head_dim) key/value tiles stream HBM→VMEM; the two
matmuls per tile hit the MXU with 128-aligned shapes; running max / sum /
accumulator live in VMEM scratch across the K-grid iterations (TPU grids
execute sequentially over the innermost dimension, which is what makes the
scratch-carry pattern sound).

Grid: (batch·heads, Sq/block_q, Skv/block_k); the K dimension is innermost.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window: int, logit_cap: float, q_offset: int,
                  kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, d]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, bk]
    if logit_cap > 0:
        scores = logit_cap * jnp.tanh(scores / logit_cap)

    q_idx = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    k_idx = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    diff = q_idx - k_idx
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= diff >= 0
    if window > 0:
        mask &= diff < window
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]                               # [bq]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[:, None])              # [bq, bk]
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           logit_cap: float = 0.0, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D]; k, v: [BH, Skv, D] -> [BH, Sq, D]."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"flash attention: sequence lengths ({sq}, {skv}) must be "
            f"multiples of the blocks ({block_q}, {block_k})")
    q_steps = sq // block_q
    kv_steps = skv // block_k
    grid = (bh, q_steps, kv_steps)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, logit_cap=logit_cap,
        q_offset=q_offset, kv_steps=kv_steps)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # accumulator
        ],
        interpret=interpret,
    )(q, k, v)

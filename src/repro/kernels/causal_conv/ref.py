"""Pure-jnp oracle for the causal conv kernel pair: a depthwise
convolution (one feature group a channel) with K - 1 zero rows before the
sequence, in f32, rounded to the activations' dtype, then SiLU.

    y[t, c] = silu(round(Σ_i x[t-(K-1)+i, c] · w[i, c] + b[c]))"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv_ref(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: [B, S, C], w: [K, C], b: [C] -> [B, S, C] in x's dtype."""
    k, c = w.shape
    f32 = jnp.float32
    pre = jax.lax.conv_general_dilated(
        x.astype(f32), w.astype(f32)[:, None, :], window_strides=(1,),
        padding=[(k - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=c, precision=jax.lax.Precision.HIGHEST)
    pre = (pre + b.astype(f32)).astype(x.dtype).astype(f32)
    return jax.nn.silu(pre).astype(x.dtype)

"""jit'd public wrapper: GQA-aware flash attention on [B, H, S, D]."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_kernel


def _should_interpret() -> bool:
    """Interpret the kernel on the CPU only; any other backend compiles it
    (and raises what its compiler refuses)."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "logit_cap",
                                   "q_offset", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    logit_cap: float = 0.0, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] (GQA: Hq % Hkv == 0)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    out = flash_attention_kernel(
        q.reshape(b * hq, sq, d), k.reshape(b * hq, skv, d),
        v.reshape(b * hq, skv, d), causal=causal,
        window=window,
        logit_cap=logit_cap, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
        interpret=_should_interpret())
    return out.reshape(b, hq, sq, d)

"""Shared building blocks: norms, activations, embeddings, positional enc."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from .params import ParamSpec


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5,
             offset: float = 0.0) -> jax.Array:
    with jax.named_scope("norm"):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * (offset + weight.astype(jnp.float32))).astype(dtype)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    if cap <= 0:
        return logits
    return cap * jnp.tanh(logits / cap)


def dense(x: jax.Array, w: jax.Array, b: jax.Array | None = None,
          accum_f32: bool = True) -> jax.Array:
    """x:[..., in] @ w:[in, out]; accumulates in f32 on the MXU."""
    pet = jnp.float32 if accum_f32 else None
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=pet)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y.astype(x.dtype)


def embed_lookup(tokens: jax.Array, table: jax.Array) -> jax.Array:
    """Token embedding via one-hot matmul (TPU-friendly gather)."""
    return jnp.take(table, tokens, axis=0)


def embedding_spec(vocab: int, d_model: int, dtype: str) -> ParamSpec:
    return ParamSpec((vocab, d_model), ("vocab", "embed"),
                     init="normal", dtype=dtype)


def norm_spec(d: int, dtype: str) -> ParamSpec:
    return ParamSpec((d,), ("norm",), init="ones", dtype=dtype)


def shard_act(x, axes):
    return constrain(x, axes)

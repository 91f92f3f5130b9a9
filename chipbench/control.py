"""The control of a train cell's check: the plain reference computed one
precision below the configuration's bfloat16, as float8 training does it.

Every matrix product of the reference takes its operands rounded to
float8 e4m3 with one scale per tensor, and its backward pass takes the
incoming gradient rounded to float8 e5m2 the same way; the products of
the rounded values are exact in float32.  The check must find this
reference as not correct against the float32 one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


def fp8_dot(a, b, spec: str):
    """``einsum(spec, a, b)`` with float8 operands and gradients."""
    def ein(x, y):
        return jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGHEST)

    @jax.custom_vjp
    def f(x, y):
        return ein(_round(x, jnp.float8_e4m3fn), _round(y, jnp.float8_e4m3fn))

    def fwd(x, y):
        qx = _round(x, jnp.float8_e4m3fn)
        qy = _round(y, jnp.float8_e4m3fn)
        return ein(qx, qy), (qx, qy)

    def bwd(res, g):
        _, vjp = jax.vjp(ein, *res)
        return vjp(_round(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f(a.astype(F32), b.astype(F32))

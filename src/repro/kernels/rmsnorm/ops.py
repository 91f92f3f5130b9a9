"""jit'd fused-RMSNorm wrapper over arbitrary leading dims."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import rmsnorm_kernel


def _should_interpret() -> bool:
    """Interpret the kernel on the CPU only; any other backend compiles it
    (and raises what its compiler refuses)."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    rows = flat.shape[0]
    block = 128
    while rows % block and block > 1:
        block //= 2
    out = rmsnorm_kernel(flat, w, eps=eps, block_rows=block,
                         interpret=_should_interpret())
    return out.reshape(*lead, d)

"""Record the small device trace with two named layers that
``test_scopes.py`` reduces.

    python3 chipbench/tests/record_scoped_trace.py OUT.xplane.pb [SIZE]

Run once on a chip: a jitted step of two of the program's layers, a
bfloat16 matrix product of SIZE-square operands (default 8192) under
``jax.named_scope("proj")`` and an RMS norm of its result under
``jax.named_scope("norm")``, three times inside a ``chipbench:window``
host span.  Operands this large stay out of the core's fast memory, so
that no copy the compiler adds (which carries no name) takes time of its
own.  An optimization barrier keeps the two layers in separate fusions.
Prints the reduction's device seconds per layer beside ``busy_s``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str, size: int = 8192) -> int:
    from chipbench import harness
    harness.setup_jax()
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from chipbench import scopes

    harness.tpu_devices(1)

    @jax.jit
    def step(x, w):
        with jax.named_scope("proj"):
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        y = jax.lax.optimization_barrier(y)
        with jax.named_scope("norm"):
            var = jnp.mean(y * y, axis=-1, keepdims=True)
            return (y * jax.lax.rsqrt(var + 1e-5)).astype(x.dtype)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (size, size), jnp.bfloat16)
    w = jax.random.normal(k2, (size, size), jnp.bfloat16) / size ** 0.5
    x = step(x, w).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with TraceAnnotation("chipbench:window"):
            for _ in range(3):
                with TraceAnnotation("chipbench:step"):
                    x = step(x, w).block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        shutil.copy(path, out)
    s, by_path = scopes.seconds(scopes.load(out))
    print("busy_s", s.busy_s, "window_s", s.window_s)
    print("layers", scopes.shares(by_path))
    print("paths", by_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], *map(int, sys.argv[2:3])))

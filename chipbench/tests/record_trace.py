"""Record the small device trace that ``test_trace.py`` reduces.

    python3 chipbench/tests/record_trace.py OUT.xplane.pb

Run once on a chip: a few bfloat16 matrix products inside a
``chipbench:window`` host span, separated by ``chipbench:feed`` host
spans that sleep, so the device idles in known gaps.  Also prints the
planes and lines the trace holds.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> int:
    from chipbench import harness
    harness.setup_jax()
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    harness.tpu_devices(1)
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((4096, 4096), jnp.bfloat16)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with TraceAnnotation("chipbench:window"):
            for _ in range(3):
                with TraceAnnotation("chipbench:step"):
                    x = f(x).block_until_ready()
                with TraceAnnotation("chipbench:feed"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        shutil.copy(path, out)
    for plane in jax.profiler.ProfileData.from_file(out).planes:
        lines = {ln.name: sum(1 for _ in ln.events) for ln in plane.lines}
        print(plane.name, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

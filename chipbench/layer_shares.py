"""Device time of a train cell's window by program layer.

    python3 chipbench/layer_shares.py --workload <name> --seed <n> \\
        --seconds <s>

Runs the cell's set-up as ``run.py`` does (weights from ``--seed``, the
compiled step, the check's steps), then its window under the JAX
profiler, and prints one JSON line: each layer's device self time as a
percentage of the window (``device_pct.<layer>``, :mod:`chipbench.scopes`),
the device's idle share, ``busy_s``, ``window_s``, the trace file's
bytes, the seconds that reading it takes with and without the operation
paths, and the twenty paths with the most device time.  No reference
runs and nothing is checked.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    harness.setup_jax()
    import jax

    from chipbench import scopes, trace

    cell = harness.cell(args.workload)
    gen = cell.kind
    prog = gen.build(cell.config, cell.traffic, args.seed,
                     harness.tpu_devices(cell.chips))
    gen.check_steps(prog, cell.traffic)
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
        jax.profiler.start_trace(d)
        gen.window(prog, args.seconds)
        jax.profiler.stop_trace()
        (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        t0 = time.perf_counter()
        trace.reduce(trace.load(path))
        t1 = time.perf_counter()
        scoped = scopes.load(path)
        s, by_path = scopes.seconds(scoped)
        t2 = time.perf_counter()
        line = dict(scopes.percent(scoped), busy_s=s.busy_s,
                    window_s=s.window_s, trace_bytes=os.path.getsize(path),
                    read_s=t1 - t0, read_with_paths_s=t2 - t1,
                    paths=sorted(by_path.items(), key=lambda kv: -kv[1])[:20])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once and print its result as one JSON line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's traffic names the generator that runs it
(``chipbench/kinds/<kind>.py``, see :mod:`chipbench.harness`): its set-up
makes the inputs from ``--seed`` and compiles (from the compilation cache
in ``.chipbench_cache/`` after a checkout's first run), its window
measures for ``--seconds``, and after the window the plain reference
decides ``correct``.  With ``--trace 1`` the window runs under the JAX
profiler and the line carries the per-layer metrics, ``busy_s``,
``window_s`` and a ``breakdown``; with ``--trace 0`` it carries the
end-to-end metrics.  Each number compared is printed beside its limit as
the last lines of standard error and under the line's last key,
``checks``.

A host where JAX finds no TPU, or fewer chips than the cell asks for,
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def result(cell, out: dict, trace: bool, setup_s: float) -> dict:
    """The result line: end-to-end metrics, or per-layer ones when
    traced, and the checks last."""
    correct = out["failed"] == 0 and harness.passed(out["checks"])
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"]}
    metrics = {}
    if trace:
        s = out["summary"]
        ctx = dict(out, cell=cell, peak=harness.peak)
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["device"].update(busy_s=s.busy_s, window_s=s.window_s)
    else:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = out["device"]
    if trace:
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in out["summary"].device_ops],
            "idle_gaps": [[k, v] for k, v in out["summary"].idle_gaps]}
    line["checks"] = harness.checks_line(out["checks"])
    return line


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t0: float) -> tuple[dict, dict]:
    """One run of ``cell`` by the generator its traffic names: the result
    line, and the generator's own output."""
    out = cell.kind.run(cell, seed, seconds, trace, devices)
    return result(cell, out, trace, out["t_window"] - t0), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.setup_jax()
    try:
        cell = harness.cell(args.workload)
        devices = harness.tpu_devices(cell.chips)
    except (harness.BenchError, OSError, KeyError) as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    line, out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T0)
    for note in out.get("notes", []):
        print(note, file=sys.stderr)
    for name, (v, lim) in out["checks"].items():
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    # the checks stay the last lines of standard error: the runtime's
    # messages at exit go nowhere
    os.dup2(os.open(os.devnull, os.O_WRONLY), 2)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

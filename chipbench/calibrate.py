"""Readings that a train cell's limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --what program,control,half [--out FILE]

For each seed and each reading, the three numbers the check compares
(the train generator's ``CHECKS``) against the float32 reference:

- ``program``: the system under test's first steps, as a run's set-up
  drives them (the lower readings);
- ``control``: the reference in float8 (:mod:`chipbench.control`) in the
  program's place (an upper reading);
- ``half``: the reference in the program's place with half of each batch
  left out and the mean taken over the rest (a fault).

A run's own path is not used here: no window is measured.  Each reading is
printed as one JSON line and, with ``--out``, appended to that file.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control,half")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    harness.setup_jax()
    cell = harness.cell(args.workload)
    devices = harness.tpu_devices(cell.chips)
    from chipbench import control

    generator = cell.kind

    b = cell.traffic["global_batch"]
    for seed in (int(s) for s in args.seeds.split(",")):
        ref = generator.reference_steps(cell.config, cell.traffic, seed,
                                        devices)
        for what in args.what.split(","):
            if what == "program":
                prog = generator.build(cell.config, cell.traffic, seed,
                                       devices)
                got = generator.check_steps(prog, cell.traffic)
                prog.params = prog.opt_state = None
                del prog
                gc.collect()
            elif what == "control":
                got = generator.reference_steps(
                    cell.config, cell.traffic, seed, devices,
                    dot=control.fp8_dot)
            elif what == "half":
                if b < 2:
                    raise SystemExit(f"{b} row: no half of the batch")
                got = generator.reference_steps(
                    cell.config, cell.traffic, seed, devices,
                    rows=slice(0, b // 2))
            else:
                raise SystemExit(f"unknown reading {what!r}")
            line = {"workload": args.workload, "seed": seed, "what": what,
                    **generator.compare(got, ref),
                    "still": generator.still_leaves(ref),
                    "loss": got["loss"], "ref_loss": ref["loss"]}
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A train cell on a (4, 1) data x model mesh, on four virtual CPU
devices at the smoke size: the program's sharded step against the sharded
reference is correct, and a step that leaves out the exchange of the
gradient between chips, each chip updating its own part of the state
from its own rows, is not.  Each case runs in a process of its own: the
device count is fixed when JAX starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import smoke

SCRIPT = """
import json, sys
sys.path.insert(0, {tests!r})
import smoke
import jax
from jax.sharding import PartitionSpec as P


class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def no_exchange(step):
    from repro.distributed import sharding

    def broken(params, opt_state, batch):
        mesh = sharding.get_abstract_mesh_or_none()
        find = sharding.get_abstract_mesh_or_none
        # each chip runs the whole step on its own rows; the state it
        # hands back keeps each chip's own part of its own update
        local = jax.shard_map(step, mesh=mesh,
                              in_specs=(P(), P(), P("data")),
                              out_specs=(P(), P(), P()), check_vma=False)
        sharding.get_abstract_mesh_or_none = lambda: None
        try:
            return local(params, opt_state, batch)
        finally:
            sharding.get_abstract_mesh_or_none = find
    return broken


from chipbench import harness
config = smoke.program(Patch())
if {fault!r} == "no_exchange":
    from repro.train import loop
    real = loop.make_train_step
    loop.make_train_step = lambda *a, **k: no_exchange(real(*a, **k))
cell = smoke.cell(config)
cell.traffic = dict(cell.traffic, mesh=[4, 1], reference_rows=4)
devices = jax.devices("cpu")
assert len(devices) == 4, devices
out = cell.kind.run(cell, 7, 0.5, False, devices)
print(json.dumps({{"passed": harness.passed(out["checks"]),
                   "checks": out["checks"], "failed": out["failed"]}}))
"""


@pytest.mark.parametrize("fault,passes", [("none", True),
                                          ("no_exchange", False)])
def test_sharded_step_against_the_sharded_reference(fault, passes):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    tests = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(tests=tests, fault=fault)],
        cwd=smoke.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["failed"] == 0 and got["passed"] == passes, got

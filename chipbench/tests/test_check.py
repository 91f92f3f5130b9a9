"""The check that decides ``correct`` in a train cell, on the CPU at the
smoke size of ``smoke.py``: a sound run passes, the float8 control does
not, and a run with its timed step broken underneath does not, once for
each fault a one-chip train cell can have (``test_mesh.py`` adds the
fault only a cell on several chips can have).

The look for a chip is skipped: the run is driven from the train
generator's ``run`` with the CPU device."""
from __future__ import annotations

import pytest

from smoke import cell, program  # noqa: I001

from chipbench import control, harness  # noqa: E402

SEED = 2**31 + 12345
train = harness.kind("train")


def _cpu():
    import jax
    return jax.devices("cpu")[:1]


def test_sound_run_is_correct(monkeypatch):
    c = cell(program(monkeypatch))
    out = train.run(c, SEED, 0.5, False, _cpu())
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert harness.passed(out["checks"]), out["checks"]


def test_float8_control_is_not_correct(monkeypatch):
    config = program(monkeypatch)
    c = cell(config)
    ref = train.reference_steps(config, c.traffic, SEED, _cpu())
    ctl = train.reference_steps(config, c.traffic, SEED, _cpu(),
                                dot=control.fp8_dot)
    got = train.compare(ctl, ref)
    assert not harness.passed({k: (got[k], c.limits[k])
                               for k in train.CHECKS}), got


def _unchanged(step):
    def broken(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics
    return broken


def _half_batch(step):
    def broken(params, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt_state, half)
    return broken


def _altered(step):
    """The new value of one leaf moved twice as far as the step moved it."""
    def broken(params, opt_state, batch):
        new, opt, metrics = step(params, opt_state, batch)
        old = params["layers"]["ssm"]["out_proj"]
        moved = new["layers"]["ssm"]["out_proj"]
        new["layers"]["ssm"]["out_proj"] = (2 * moved.astype("float32")
                                            - old).astype(old.dtype)
        return new, opt, metrics
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    from repro.train import loop

    c = cell(program(monkeypatch))
    real = loop.make_train_step
    monkeypatch.setattr(loop, "make_train_step",
                        lambda *a, **k: fault(real(*a, **k)))
    out = train.run(c, SEED, 0.5, False, _cpu())
    assert not harness.passed(out["checks"]), out["checks"]


def test_still_leaves_are_named_and_left_out():
    ref = {"grad": {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-4},
           "change": {"a": 1.0, "b": 1.0, "c": 1.0, "tiny": 1e-3},
           "loss": [1.0]}
    prog = dict(ref, change=dict(ref["change"], tiny=1.0))
    # the median of the four leaves is 1.5
    assert train.still_leaves(ref) == {"tiny": pytest.approx(1e-4 / 1.5)}
    assert train.compare(prog, ref)["change_gap"] == 0.0

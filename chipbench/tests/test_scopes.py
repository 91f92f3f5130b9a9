"""The program's layers read from operation paths (:mod:`chipbench.scopes`),
their device time by the unchanged trace reduction, and a trace with two named
layers recorded on a TPU v5e by ``record_scoped_trace.py``."""
from __future__ import annotations

import os

import pytest

import smoke  # noqa: F401

from chipbench import scopes  # noqa: E402
from chipbench import trace as tr  # noqa: E402

TRACES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traces")


@pytest.mark.parametrize("path,layer", [
    # the backward pass: the scope inside JAX's transform wrappers
    ("jit(train_step)/transpose(jvp(vocab))/add_any", "vocab"),
    ("jit(train_step)/vmap(transpose(jvp(proj)))", "proj"),
    # the forward rematerialised in the backward pass, under JAX's own
    # components and a function JAX jits
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ssd/jit(cumsum)", "ssd"),
    # nested scopes: the innermost wins
    ("jit(train_step)/jvp(vocab)/norm", "norm"),
    # a JAX function that shares a layer's name is not the layer
    ("jit(train_step)/optimizer/jit(norm)", "optimizer"),
    # the layer stack's own writes are under no layer
    ("jit(train_step)/transpose(jvp())/while/body/dynamic_update_slice",
     "unscoped"),
    # merged instructions: the first name is read
    ("jit(step)/conv/reshape;jit(step)/ssd/reshape", "conv"),
    ("", "unscoped"),
])
def test_layer_of_a_path(path, layer):
    assert scopes.layer(path) == layer


def test_path_of_a_tf_op():
    assert scopes.path_of("jit(step)/jvp(vocab)/dot_general:Dot") == \
        "jit(step)/jvp(vocab)"
    assert scopes.path_of("jit(step)/conv/reshape;jit(step)/ssd/reshape") \
        == "jit(step)/conv/reshape;jit(step)/ssd"
    assert scopes.path_of("jit(my step)/proj/dot_general") == \
        "jit(my_step)/proj"
    assert scopes.path_of("") == ""


def ev(path, start, end):
    return tr.Event(path, start, end - start)


def test_seconds_put_device_time_under_paths():
    """Device operations named by their paths, as :func:`scopes.load`
    names them: the unchanged reduction sums self time per path."""
    t = tr.Trace(
        devices={
            0: [ev("jit(step)/jvp()", 0, 60),
                ev("jit(step)/jvp()/while/body/closed_call/ssd", 5, 25),
                ev("jit(step)/transpose(jvp())/while/body/closed_call/"
                   "checkpoint/proj", 30, 50),
                ev("jit(step)/optimizer", 70, 90)],
            1: [ev("jit(step)/jvp()/while/body/closed_call/ssd", 0, 100)],
        },
        host=[ev("chipbench:window", 10, 90)])
    s, by_path = scopes.seconds(t)
    # in the window: device 0's while self 50 - 15 - 20, ssd 15, proj 20,
    # optimizer 20; device 1's ssd 80; each path's mean over two devices
    assert by_path == {
        "jit(step)/jvp()": pytest.approx(7.5e-9),
        "jit(step)/jvp()/while/body/closed_call/ssd": pytest.approx(47.5e-9),
        "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "proj": pytest.approx(10e-9),
        "jit(step)/optimizer": pytest.approx(10e-9),
    }
    layers = scopes.shares(by_path)
    assert layers == {"ssd": pytest.approx(47.5e-9),
                      "proj": pytest.approx(10e-9),
                      "unscoped": pytest.approx(7.5e-9),
                      "optimizer": pytest.approx(10e-9)}
    # no two operations of a device overlap here but nested ones, so the
    # layers' shares add up to the busy share, and with idle to 100
    assert sum(layers.values()) == pytest.approx(s.busy_s)
    pct = scopes.percent(t)
    assert set(pct) == {"device_pct.ssd", "device_pct.proj",
                        "device_pct.unscoped", "device_pct.optimizer",
                        "device_idle_pct"}
    assert sum(pct.values()) == pytest.approx(100.0)


def test_a_program_without_the_scopes_is_all_unscoped():
    t = tr.Trace(devices={0: [ev("jit(step)/while", 0, 5),
                              ev("", 5, 6)]},
                 host=[ev("chipbench:window", 0, 10)])
    assert scopes.percent(t) == {"device_pct.unscoped": pytest.approx(60.0),
                                 "device_idle_pct": pytest.approx(40.0)}


def test_load_reads_each_operation_path():
    """The name stack of each device operation, from the event metadata
    of the trace that ``test_trace.py`` reduces; the rest as
    :func:`chipbench.trace.load` reads it."""
    path = os.path.join(TRACES, "small.xplane.pb")
    plain, scoped = tr.load(path), scopes.load(path)
    assert scoped.host == plain.host
    names = [tr.op_label(e.name).split(" ")[0] for e in plain.devices[0]]
    paths = dict(zip(names, (e.name for e in scoped.devices[0])))
    assert paths == {"copy-start": "", "copy-done": "",
                     "convolution_tanh_fusion": "jit(<lambda>)"}
    assert [(e.start_ns, e.dur_ns) for e in scoped.devices[0]] == \
        [(e.start_ns, e.dur_ns) for e in plain.devices[0]]


def test_recorded_scoped_trace():
    """A matrix product under ``proj`` and an RMS norm under ``norm``,
    three times: both layers are found, and together they hold the
    device's busy time."""
    s, by_path = scopes.seconds(scopes.load(
        os.path.join(TRACES, "scoped.xplane.pb")))
    layers = scopes.shares(by_path)
    assert layers["proj"] > 0 and layers["norm"] > 0
    assert layers["proj"] + layers["norm"] == pytest.approx(s.busy_s,
                                                            rel=0.01)

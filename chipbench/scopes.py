"""The program's layers as the device trace names them.

The program opens a ``jax.named_scope`` at each layer boundary of its
train step (``src/repro``): ``vocab`` (embedding lookup, head and loss),
``norm`` (``rms_norm``), ``proj`` (the mixer's in and out projections),
``conv`` (causal convolution and its SiLU), ``ssd`` (the SSD core from
``dt`` to the ``D`` skip) and ``optimizer`` (clip and update).  JAX
writes the name stack into each HLO instruction's ``op_name``; on a TPU
the trace carries it as the ``tf_op`` stat of each operation's event
metadata, which ``jax.profiler.ProfileData`` does not expose and
:mod:`chipbench.xplane` reads.

:func:`load` reads a trace file as :func:`chipbench.trace.load` does and
names each device operation by its path (its ``tf_op`` less the final
primitive), so that :func:`chipbench.trace.reduce`, unchanged, sums
device self time per path inside the window, averaged over devices
(:func:`seconds`).  The paths therefore add up to ``busy_s``, less the
overlap of operations on one device.

:func:`layer` maps a path to the innermost of :data:`NAMES` it holds.  It
looks inside JAX's transform wrappers (``transpose(jvp(vocab))`` is
``vocab``), so the backward pass and rematerialised forward count with
the forward.  JAX's own components (``while``, ``body``, ``closed_call``,
``checkpoint``, ``rematted_computation``, and functions JAX jits such as
``jit(cumsum)``) are not layers.  A path with none of the names is
``unscoped``.

A fusion counts under its own ``tf_op``: the ``op_name`` that XLA gives
the fusion instruction.  On the TPU that is the name of the fusion's main
operation, which need not be its root: the fusion that computes the
in_proj weight gradient and writes it into the layer scan's stacked
gradient has a ``dynamic_update_slice`` root but the product's name, so
it counts as ``proj``.  A fusion that holds operations of two layers
counts wholly under one.  A copy or slice that XLA adds between the
layers' operations takes the name of the loop it sits in and counts as
``unscoped``.  Where XLA merged instructions, their names are joined by
``;`` and the first is read.

The benchmark's harness reads no metric from here yet: its traced run
hands its readers the reduced ``Summary`` alone, after the trace file is
gone.  ``chipbench/layer_shares.py`` runs a cell's window under the
profiler and prints the layers' shares.
"""
from __future__ import annotations

import re

from chipbench import trace, xplane

#: the layers the program names, as its ``jax.named_scope`` calls spell them
NAMES = ("vocab", "norm", "proj", "conv", "ssd", "optimizer")
UNSCOPED = "unscoped"

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_TYPE = re.compile(r":[^/:]*$")
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


def _bare(component: str) -> str:
    """A path component with JAX's transform wrappers peeled off."""
    while True:
        m = _TRANSFORM.match(component)
        if not m:
            return component
        component = m.group(1)


def layer(path: str) -> str:
    """The innermost listed name in a ``tf_op`` path, else ``unscoped``."""
    for component in reversed(path.split(";", 1)[0].split("/")):
        bare = _bare(component)
        if bare in NAMES:
            return bare
    return UNSCOPED


def shares(by_path: dict[str, float]) -> dict[str, float]:
    """Device seconds per layer from seconds per path."""
    out: dict[str, float] = {}
    for path, s in by_path.items():
        name = layer(path)
        out[name] = out.get(name, 0.0) + s
    return out


def path_of(tf_op: str) -> str:
    """An operation's name-stack path: its ``tf_op`` less the ``:<type>``
    suffix and the final primitive, with no space (which
    :func:`chipbench.trace.op_label` would cut at)."""
    return _TYPE.sub("", tf_op).rpartition("/")[0].replace(" ", "_")


def load(path: str) -> trace.Trace:
    """The trace in ``path`` as :func:`chipbench.trace.load` reads it,
    each device operation named by its :func:`path_of`."""
    out = trace.load(path)
    planes = xplane.event_stats(path, _DEVICE.pattern, trace.OPS_LINE,
                                "tf_op")
    for plane, rows in planes.items():
        n = int(_DEVICE.match(plane).group(1))
        events = out.devices.get(n, [])
        if [e.name for e in events] != [name for name, _ in rows]:
            raise ValueError(f"{plane}: the event metadata and ProfileData "
                             f"list different operations")
        out.devices[n] = [trace.Event(path_of(tf_op), e.start_ns, e.dur_ns)
                          for e, (_, tf_op) in zip(events, rows)]
    return out


def seconds(t: trace.Trace) -> tuple[trace.Summary, dict[str, float]]:
    """The reduction of a trace whose device operations are named by
    their paths, and the device self time of each path in the window."""
    s = trace.reduce(t, top=sum(map(len, t.devices.values())))
    return s, dict(s.device_ops)


def percent(t: trace.Trace) -> dict[str, float]:
    """Each layer's device self time, and the device's idle time, as a
    percentage of the window: ``device_pct.<layer>`` and
    ``device_idle_pct``, which add up to 100 less the overlap of
    operations on one device."""
    s, by_path = seconds(t)
    out = {f"device_pct.{name}": 100.0 * v / s.window_s
           for name, v in sorted(shares(by_path).items())}
    out["device_idle_pct"] = 100.0 * (1.0 - s.busy_s / s.window_s)
    return out

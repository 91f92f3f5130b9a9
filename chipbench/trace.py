"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is read once into plain tuples (:func:`load`), so that the
reduction (:func:`reduce`) is a function of data a test can write by hand.

- busy: the union of the intervals in which an operation runs on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  window that the host annotation ``chipbench:window`` spans;
- collective exposed: the part of the union of collective operations that
  no other operation of that device covers;
- top operations: device self time (less nested operations) per
  operation, averaged over devices;
- idle gaps: the longest gaps between busy intervals of the first device,
  each labelled by the innermost ``chipbench:`` host annotation running at
  the gap's midpoint.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

WINDOW = "chipbench:window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                         r"collective-permute|send|recv")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    devices: dict[int, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)   # chipbench: spans


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over devices
    collective_exposed_s: float         # mean over devices
    device_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]
    n_devices: int


def load(path: str) -> Trace:
    """The device operations and the benchmark's host annotations of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                out.devices.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                out.host.extend(Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith("chipbench:"))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce(trace: Trace, top: int = 10) -> Summary:
    wins = [e for e in trace.host if e.name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span, found "
                         f"{len(wins)}")
    if not trace.devices:
        raise ValueError("the trace holds no TPU device operations")
    lo, hi = wins[0].start_ns, wins[0].end_ns
    busy, exposed = [], []
    per_op: dict[str, float] = {}
    for events in trace.devices.values():
        ivs = [(e.start_ns, e.end_ns) for e in events]
        busy.append(_length(union(_clip(ivs, lo, hi))))
        coll = union(_clip([(e.start_ns, e.end_ns) for e in events
                            if _COLLECTIVE.search(e.name)], lo, hi))
        comp = union(_clip([(e.start_ns, e.end_ns) for e in events
                            if not _COLLECTIVE.search(e.name)], lo, hi))
        exposed.append(_minus(coll, comp))
        for name, d in self_times(events, lo, hi):
            per_op[name] = per_op.get(name, 0.0) + d
    n = len(trace.devices)
    ops = sorted(((k, v / n / 1e9) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    first = union(_clip([(e.start_ns, e.end_ns)
                         for e in trace.devices[min(trace.devices)]], lo, hi))
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / n / 1e9,
        collective_exposed_s=sum(exposed) / n / 1e9,
        device_ops=ops,
        idle_gaps=[(host_label(trace.host, (s + e) / 2), (e - s) / 1e9)
                   for s, e in gaps],
        n_devices=n)


_OP = re.compile(r"^%?([^ ]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def op_label(name: str) -> str:
    """``fusion.12 f32[16,2048]`` from the HLO text a TPU trace gives as an
    operation's name: its instruction name and first result shape."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.split(" ")[0]


def self_times(events, lo, hi):
    """``(label, time)`` of each operation inside the window, less the time
    of the operations nested in it (a ``while`` holds its body's)."""
    out = []
    stack: list[list] = []                  # [end, label, self time]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        while stack and stack[-1][0] <= e.start_ns:
            out.append(tuple(stack.pop()[1:]))
        if t <= s:
            continue
        if stack:
            stack[-1][2] -= min(t, stack[-1][0]) - s
        stack.append([e.end_ns, op_label(e.name), t - s])
    out.extend(tuple(x[1:]) for x in stack)
    return out


def host_label(host: list[Event], t: float) -> str:
    """The innermost benchmark annotation running at ``t``."""
    live = [e for e in host if e.start_ns <= t < e.end_ns]
    if not live:
        return "none"
    return min(live, key=lambda e: e.dur_ns).name.split(":", 1)[1]


class Tracer:
    """The JAX profiler over a window, written under ``TMPDIR`` and read
    back by :func:`load`."""

    def start(self):
        import tempfile

        import jax

        self._dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        jax.profiler.start_trace(self._dir.name)

    def stop(self) -> Trace:
        import glob

        import jax

        jax.profiler.stop_trace()
        try:
            files = glob.glob(f"{self._dir.name}/plugins/profile/*/"
                              "*.xplane.pb")
            if len(files) != 1:
                raise RuntimeError(f"expected one trace file, found {files}")
            return load(files[0])
        finally:
            self._dir.cleanup()

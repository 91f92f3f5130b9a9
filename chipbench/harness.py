"""What a run of one cell needs, found by name from ``BENCHMARK.json``.

- the cell: an entry of ``workloads``;
- its configuration: ``configs/<config>.json``, whose ``reference`` names
  the plain reference module ``reference/<reference>.py``;
- its traffic: ``traffic/<traffic>.json``, whose ``kind`` names the
  generator ``kinds/<kind>.py`` that runs it; the rest of the file is the
  generator's parameters;
- its limits: ``limits/<cell>.json``, one limit for each number its
  check compares (the generator's ``CHECKS`` names those it can);
- each per-layer metric: ``metrics/<metric>.py``, whose ``read(ctx)``
  returns the number or ``None`` where it finds nothing to read;
- the device's peaks: ``peaks.json``, keyed by ``device_kind``.

A generator module has ``CHECKS``, ``END_TO_END`` (the end-to-end metrics
it measures, ``setup_s`` aside) and ``run(cell, seed, seconds, trace,
devices)``, whose result holds ``end_to_end`` (a value for each of those
metrics), ``checks`` (``name -> (value, limit)``), ``attempted``,
``failed``, ``t_window`` (when set-up ended), ``device`` and, traced,
``summary`` (:class:`trace.Summary`); the per-layer readers see all of it.

Adding a cell, a configuration, a traffic mix, a generator or a metric
adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import re
import sys
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """A cell that cannot be run as the benchmark describes it."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    kind: object = None
    root: str = ROOT


def _module(path: str, tag: str):
    """The module in the file at ``path``, loaded once per process."""
    name = "chipbench_" + re.sub(r"\W", "_", tag)
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _file(root: str, where: str, name: str, ext: str) -> str:
    if not _NAME.match(name):
        raise BenchError(f"bad name {name!r}")
    return os.path.join(root, "chipbench", where, name + ext)


def kind(name: str, root: str = ROOT):
    """The generator that runs a traffic file's ``kind``."""
    return _module(_file(root, "kinds", name, ".py"), f"kind_{name}")


def cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(_file(root, "traffic", w["traffic"], ".json"))
    limits = load_json(_file(root, "limits", name, ".json"))
    gen = kind(traffic["kind"], root)
    unknown = set(limits) - set(gen.CHECKS)
    if unknown:
        raise BenchError(f"limits of {name} name numbers no check computes: "
                         f"{sorted(unknown)}")

    def mine(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    missing = {m["name"] for m in end_to_end} - {"setup_s"} \
        - set(gen.END_TO_END)
    if missing:
        raise BenchError(f"traffic kind {traffic['kind']!r} measures none "
                         f"of {sorted(missing)}")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits, end_to_end=end_to_end,
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                kind=gen, root=root)


def reference(config: dict):
    """The plain reference module a configuration file names."""
    mod = config["reference"]
    if not mod.replace("_", "").isalnum():
        raise BenchError(f"bad reference module name {mod!r}")
    return importlib.import_module(f"chipbench.reference.{mod}")


def metric_reader(name: str, root: str = ROOT):
    return _module(_file(root, "metrics", name, ".py"), f"metric_{name}").read


def peak(device_kind: str, what: str, root: str = ROOT) -> float:
    """A published peak of one chip; an unknown kind is an error."""
    table = load_json(os.path.join(root, "chipbench", "peaks.json"))
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"(known: {sorted(kinds)})")
    return float(kinds[device_kind][what])


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it."""
    import jax

    if seed < 0:
        raise BenchError(f"--seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def cache_dir(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: a fixed path in the checkout."""
    return os.path.join(root, ".chipbench_cache", "jax")


def setup_jax(root: str = ROOT) -> None:
    """Point JAX's compilation cache at :func:`cache_dir` and keep every
    program there, so that only a checkout's first run compiles.  Call
    before JAX is imported."""
    path = cache_dir(root)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if root not in sys.path:
        sys.path.insert(0, root)


def tpu_devices(chips: int):
    """The cell's chips, or :class:`BenchError` where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (default device {devs[0]})")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def peak_bytes(stats: dict):
    """A chip's peak memory: the allocator's peak in use, plus the peak it
    reserved, where the runtime keeps a compiled program's temporaries
    outside the bytes in use (a TPU does)."""
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def device_info(devices) -> dict:
    peaks = [peak_bytes(d.memory_stats() or {}) for d in devices]
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}
    if all(p is not None for p in peaks):
        info["memory_peak_bytes"] = max(peaks)
    return info


def checks_line(checks: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` with each number as measured."""
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def passed(checks: dict) -> bool:
    return all(math.isfinite(v) and v <= lim for v, lim in checks.values())

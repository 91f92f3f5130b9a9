"""Profiling estimator (paper §IV-C2).

Each compute region is re-emitted as a standalone StableHLO module,
compiled by XLA for the process's default device (the TPU on a chip host,
the CPU elsewhere) and executed with synthetic inputs; the measured
median runtime is the region latency.  This mirrors
``hlo_runner_main``-based profiling, including its characteristic bias:
compilation scope is truncated at region boundaries, so cross-region
fusion/global optimization is lost — the profiling path is
systematically pessimistic (paper §V-A).

The profiled system is the device's catalog record, found through
:data:`repro.core.catalog.DEVICE_KINDS` (a device kind missing there is an
error).  When it differs from the target system, latencies are rescaled
by the roofline ratio of the two systems for the region's dominant
resource (a pragmatic cross-platform projection).

A region that cannot be re-emitted as a module (:class:`RegionEmitError`)
is costed by roofline and counted in ``emit_failures``.  A compile or
execute failure on the device raises: it is never turned into a roofline
answer.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from ..ir.graph import Program
from ..registry import register_estimator
from ..slicing.emit import RegionEmitError, region_to_module
from ..slicing.regions import ComputeRegion
from ..systems import System
from .analytical import RooflineEstimator
from .base import ComputeEstimator

_F_DTYPES = {"f16": np.float16, "f32": np.float32, "f64": np.float64}
_I_DTYPES = {"s8": np.int8, "s16": np.int16, "s32": np.int32,
             "s64": np.int64, "u8": np.uint8, "u16": np.uint16,
             "u32": np.uint32, "u64": np.uint64, "i1": np.bool_,
             "pred": np.bool_}


def _synthetic_inputs(types) -> list[np.ndarray]:
    """Standard-normal floats, zero integers and predicates.

    Floats of one dtype are views of one random pool, so a region with
    gigabytes of parameters costs one draw of its largest input."""
    import ml_dtypes

    floats = dict(_F_DTYPES, bf16=ml_dtypes.bfloat16)
    rng = np.random.default_rng(0)
    sizes: dict[str, int] = {}
    for t in types:
        if t.dtype in floats:
            sizes[t.dtype] = max(sizes.get(t.dtype, 0), t.num_elements)
    pools = {d: rng.standard_normal(n, dtype=np.float32).astype(floats[d])
             for d, n in sizes.items()}
    out = []
    for t in types:
        if t.dtype in floats:
            out.append(pools[t.dtype][:t.num_elements].reshape(t.shape))
        else:
            out.append(np.zeros(t.shape, _I_DTYPES.get(t.dtype, np.float32)))
    return out


def profiled_system(device) -> System:
    """The catalog record of ``device`` (``host`` for a CPU device)."""
    from ..catalog import default_registry, system_id_for_device
    return default_registry().get(system_id_for_device(device))


@register_estimator("profiling")
class ProfilingEstimator(ComputeEstimator):
    toolchain = "xla"
    #: runs regions on the process's device, which one process owns: the
    #: process-pool executor and the serve fleet refuse this estimator
    holds_device = True

    def __init__(self, program: Program, runs: int = 5,
                 target_system: System | None = None):
        """``program``: the source program the regions are re-emitted from.
        ``target_system``: if set and not the profiled system, results are
        roofline-projected onto it."""
        import jax
        self.device = jax.devices()[0]
        super().__init__(profiled_system(self.device))
        self.program = program
        self.runs = runs
        if target_system is not None and target_system == self.system:
            target_system = None
        self.target_system = target_system
        self.fallback = RooflineEstimator(self.system, mode="per-op",
                                          include_overheads=True)
        self.emit_failures = 0
        #: seconds spent compiling regions for the device, summed
        self.compile_seconds = 0.0

    @classmethod
    def from_spec(cls, options: dict, system: System,
                  context) -> "ProfilingEstimator":
        """Spec form: profile on the process's device, roofline-projecting
        onto the grid system unless the grid system is the profiled one
        (``host`` on a CPU host, ``tpu-v5e`` on a v5e: no projection)."""
        return cls(program=context.program,
                   runs=int(options.get("runs", 3)),
                   target_system=system)

    # Compute API
    def get_compile_args(self) -> dict:
        return {"backend": self.device.platform, "num_partitions": 1}

    def get_exec_args(self) -> dict:
        return {"runs": self.runs, "reduction": "median"}

    def _compile(self, module_text: str):
        from jax._src import compiler
        from jax._src.interpreters import mlir as jmlir
        from jax._src.lib.mlir import ir
        from jaxlib._jax import DeviceList
        with jmlir.make_ir_context():
            module = ir.Module.parse(module_text)
        opts = compiler.get_compile_options(num_replicas=1, num_partitions=1)
        return compiler.backend_compile_and_load(
            self.device.client, module, DeviceList((self.device,)), opts, [])

    def _measure(self, exe, in_types, aliases: dict[int, int]) -> float:
        """Median seconds of ``runs`` executions after one warm-up; each
        run's aliased results are the next run's (donated) arguments."""
        client = self.device.client
        bufs = [client.buffer_from_pyval(x, self.device)
                for x in _synthetic_inputs(in_types)]
        times = []
        for i in range(self.runs + 1):
            t0 = time.perf_counter()
            out = exe.execute(bufs)
            for o in out:
                o.block_until_ready()
            if i:
                times.append(time.perf_counter() - t0)
            for arg, res in aliases.items():
                bufs[arg] = out[res]
        return statistics.median(times)

    def get_run_time_estimate(self, region: ComputeRegion) -> float:
        try:
            module_text, in_types, aliases = region_to_module(
                region.ops, self.program, name="profiled_region")
        except RegionEmitError:
            self.emit_failures += 1
            return self.fallback.get_run_time_estimate(region)
        t0 = time.perf_counter()
        exe = self._compile(module_text)
        self.compile_seconds += time.perf_counter() - t0
        return self._project(region, self._measure(exe, in_types, aliases))

    def _project(self, region: ComputeRegion, measured_s: float) -> float:
        """Project a latency measured on the profiled device onto the
        target system."""
        if self.target_system is None:
            return measured_s
        src, dst = self.system, self.target_system
        dtype = "f32"
        for op in region.ops:
            if op.result_types:
                dtype = op.result_types[0].dtype
                break
        compute_ratio = src.flops_for(dtype) / dst.flops_for(dtype)
        memory_ratio = src.mem_bw / dst.mem_bw
        # dominant resource on the *target* decides the scaling
        c_t = region.cost.flops / dst.flops_for(dtype)
        m_t = (region.boundary_in_bytes + region.boundary_out_bytes) / dst.mem_bw
        ratio = compute_ratio if c_t >= m_t else memory_ratio
        return measured_s * ratio

    @property
    def cache_hw_key(self) -> str:
        """Names the profiled device, so latencies measured on one kind of
        device never answer for another."""
        tgt = self.target_system.name if self.target_system else "native"
        return f"{self.device.platform}:{self.device.device_kind}->{tgt}"

    @property
    def cache_config_key(self) -> str:
        return f"runs{self.runs}"

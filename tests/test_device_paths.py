"""What decides where work runs: the device-kind table, the profiling
tier's failures, kernel errors, the compile cache, CPU-only exports, and
the one-process-per-chip rule of the executors and the serve fleet."""
import hashlib
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.campaign.runner import run_campaign
from repro.campaign.spec import CampaignSpec
from repro.core.catalog import DEVICE_KINDS, default_registry, \
    system_id_for_device
from repro.core.estimators import ProfilingEstimator, profiling
from repro.core.ir import parse
from repro.core.pipeline import export_workload
from repro.core.slicing import linear_split
from repro.core.slicing.emit import RegionEmitError
from repro.core.systems import TPU_V5E, host_system
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _device(kind, platform):
    return SimpleNamespace(device_kind=kind, platform=platform)


class TestDeviceKinds:
    def test_v5e_maps_to_its_catalog_record(self):
        v5e = _device("TPU v5 lite", "tpu")
        assert system_id_for_device(v5e) == "tpu-v5e"
        assert profiling.profiled_system(v5e) == \
            default_registry().get("tpu-v5e")

    def test_this_host_is_in_the_table(self):
        assert system_id_for_device(jax.devices()[0]) == "host"

    def test_unknown_kind_raises(self):
        with pytest.raises(KeyError, match="DEVICE_KINDS"):
            system_id_for_device(_device("TPU v9 imaginary", "tpu"))
        assert "TPU v9 imaginary" not in DEVICE_KINDS


@pytest.fixture(scope="module")
def gemm():
    txt = jax.jit(lambda a, b: jnp.tanh(a @ b)).lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).as_text()
    prog = parse(txt)
    return prog, linear_split(prog)[0].region


class TestProfilingTier:
    def test_compile_failure_raises(self, gemm, monkeypatch):
        prog, region = gemm
        refused = ("module @m {\n  func.func public @main(%a: tensor<4xf32>)"
                   " -> tensor<4xf32> {\n    %0 = stablehlo.custom_call "
                   "@no_such_target(%a) : (tensor<4xf32>) -> tensor<4xf32>\n"
                   "    return %0 : tensor<4xf32>\n  }\n}")
        monkeypatch.setattr(profiling, "region_to_module",
                            lambda *a, **k: (refused, [], {}))
        est = ProfilingEstimator(program=prog, runs=1)
        with pytest.raises(jax.errors.JaxRuntimeError):
            est.get_run_time_estimate(region)
        assert est.emit_failures == 0

    def test_emit_failure_is_counted_and_costed(self, gemm, monkeypatch):
        prog, region = gemm

        def refuse(*a, **k):
            raise RegionEmitError("not a standalone module")
        monkeypatch.setattr(profiling, "region_to_module", refuse)
        est = ProfilingEstimator(program=prog, runs=1)
        assert est.get_run_time_estimate(region) == \
            est.fallback.get_run_time_estimate(region)
        assert est.emit_failures == 1

    def test_profiled_system_is_not_projected(self, gemm):
        prog, region = gemm
        native = ProfilingEstimator(program=prog, runs=1,
                                    target_system=host_system())
        assert native.target_system is None
        assert native.cache_hw_key == "cpu:cpu->native"
        projected = ProfilingEstimator(program=prog, runs=1,
                                       target_system=TPU_V5E)
        assert projected.target_system == TPU_V5E
        assert projected.cache_hw_key != native.cache_hw_key
        assert native.get_run_time_estimate(region) > 0
        assert native.compile_seconds > 0


def test_bad_flash_attention_shape_raises():
    from repro.models.attention import AttnArgs, multihead_attention
    q = jnp.zeros((1, 2, 200, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiples of the blocks"):
        multihead_attention(q, q, q, AttnArgs(), impl="pallas")


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore(self, monkeypatch):
        # record the variable so that the test's own writes are undone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_environment_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_the_repo_cache(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
        assert jax.config.jax_compilation_cache_dir == path


class TestCpuExports:
    def test_export_mesh_is_built_on_cpu_devices(self):
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = make_mesh((1, 1), ("data", "model"))
        assert {d.platform for d in mesh.devices.flat} == {"cpu"}
        spec = jax.ShapeDtypeStruct(
            (8, 8), jnp.float32,
            sharding=NamedSharding(mesh, PartitionSpec("data", None)))
        with mesh:
            w = export_workload(jax.jit(lambda x: x @ x), spec, name="m")
        assert "dot" in w.hlo_text

    def test_accelerator_placed_specs_are_refused(self):
        tpu_spec = SimpleNamespace(sharding=SimpleNamespace(
            device_set=[_device("TPU v5 lite", "tpu")]))
        with pytest.raises(ValueError, match="CPU devices"):
            export_workload(jax.jit(lambda x: x), tpu_spec, name="t")

    def test_texts_do_not_depend_on_the_caller(self):
        spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)

        def digest():
            w = export_workload(jax.jit(lambda x: jnp.tanh(x @ x)), spec)
            return [hashlib.sha256(t.encode()).hexdigest()
                    for t in (w.stablehlo_text, w.hlo_text)]

        def from_elsewhere():
            return digest()
        assert digest() == from_elsewhere()


class TestOneProcessPerChip:
    SPEC = {"name": "p",
            "workloads": [{"name": "g", "gemm": {"m": 64, "n": 64,
                                                 "k": 64}}],
            "systems": ["a100"],
            "estimators": [{"kind": "profiling", "fidelity": "raw"}]}

    def test_process_executor_refuses_device_holders(self):
        with pytest.raises(ValueError, match="executor='thread'"):
            run_campaign(CampaignSpec.from_dict(self.SPEC),
                         executor="process")

    def test_fleet_finds_device_holders(self, tmp_path):
        from repro.serve.fleet import device_holding_kinds
        assert device_holding_kinds({"estimator": "profiling"}) == \
            ["profiling"]
        assert device_holding_kinds({"estimator": {"kind": "roofline"}}) \
            == []
        assert device_holding_kinds({"spec": self.SPEC}) == ["profiling"]
        path = tmp_path / "s.json"
        path.write_text(json.dumps(self.SPEC))
        assert device_holding_kinds({"spec_path": str(path)}) == \
            ["profiling"]
        assert device_holding_kinds(
            {"spec": {"ladder": [{"kind": "roofline"},
                                 {"kind": "profiling"}]}}) == ["profiling"]


def test_train_on_a_mesh_steps_under_the_mesh(monkeypatch):
    """The step keeps its state placement, and traces under the mesh so
    that the models' activation sharding constraints apply."""
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.distributed import sharding
    from repro.models import get_smoke_config
    from repro.train import train
    seen = []
    real = sharding.get_abstract_mesh_or_none

    def spy():
        seen.append(real())
        return seen[-1]
    monkeypatch.setattr(sharding, "get_abstract_mesh_or_none", spy)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices())
    run = RunConfig(model=get_smoke_config("llama3-100m"),
                    shape=ShapeConfig("m", 32, 2, "train"))
    res = train(run, mesh=mesh, num_steps=2, log_every=0)
    assert len(res.losses) == 2
    assert all(jnp.isfinite(jnp.asarray(res.losses)))
    assert seen and all(m is not None for m in seen)

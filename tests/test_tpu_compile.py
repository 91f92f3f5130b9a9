"""The Pallas kernels compile for a TPU v5e at the widths the models run.

Each test lowers a public kernel entry point for one chip of a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU compiler
installed here, so a block shape or a VMEM budget the chip's compiler
refuses fails in CI, not on the chip.  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.rmsnorm import ops as rmsnorm_ops
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models import ssm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels' own compile path: the wrappers interpret on the CPU
    backend, which is what this process has."""
    for mod in (flash_ops, rmsnorm_ops, ssd_ops):
        monkeypatch.setattr(mod, "_should_interpret", lambda: False)


def _compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.usefixtures("compiled_kernels")
class TestKernelsCompileForV5e:
    def test_flash_attention_llama3_1b(self, one_chip):
        # llama3-1b: 32 query heads over 8 kv heads of 64, seq 2048
        b, hq, hkv, s, d = 8, 32, 8, 2048, 64
        text = _compile_for(
            one_chip, lambda q, k, v: flash_ops.flash_attention(q, k, v),
            ((b, hq, s, d), BF16), ((b, hkv, s, d), BF16),
            ((b, hkv, s, d), BF16))
        assert "tpu_custom_call" in text

    def test_rmsnorm_llama3_1b(self, one_chip):
        text = _compile_for(one_chip, rmsnorm_ops.rmsnorm,
                            ((16384, 2048), BF16), ((2048,), BF16))
        assert "tpu_custom_call" in text

    def test_ssd_scan_mamba2_370m(self, one_chip):
        # mamba2-370m: d_inner 2048 = 32 heads of 64, d_state 128, one
        # group, chunk 256, seq 2048 — the shape the old blocking refused
        b, s, h, p, g, n = 1, 2048, 32, 64, 1, 128
        text = _compile_for(
            one_chip,
            lambda x, dt, a, bi, ci: ssm.ssd_chunked(x, dt, a, bi, ci, 256,
                                                     kernel=True),
            ((b, s, h, p), BF16), ((b, s, h), F32), ((h,), F32),
            ((b, s, g, n), BF16), ((b, s, g, n), BF16))
        assert "tpu_custom_call" in text


def _ssd_shapes(b, s, h, p, g, n):
    return (((b, s, h, p), BF16), ((b, s, h), F32), ((h,), F32),
            ((b, s, g, n), BF16), ((b, s, g, n), BF16))


def _ssd_grad(x, dt, a, bi, ci):
    """Gradients of every input through the SSD on the kernel pair."""
    def loss(*args):
        y, fin = ssm.ssd_chunked(*args, 256, kernel=True)
        return jnp.sum(y.astype(F32) ** 2) + jnp.sum(fin)
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, a, bi, ci)


def _compile_sharded(topo, mesh_shape, shapes, specs):
    """The kernel path's gradients compiled for ``topo`` under a ``(data,
    model)`` mesh, each input laid out as ``PartitionSpec(*spec)``."""
    mesh = Mesh(np.array(topo.devices).reshape(mesh_shape),
                ("data", "model"))
    args = [jax.ShapeDtypeStruct(
        sh, d, sharding=NamedSharding(mesh, PartitionSpec(
            *(sp if isinstance(sp, tuple) else (sp,)))))
        for (sh, d), sp in zip(shapes, specs)]
    with mesh:
        return jax.jit(_ssd_grad).lower(*args).compile().as_text()


def _collectives(text):
    """{kind: set of (dtype, elements)} of the program's collectives' results
    (a tuple result gives each of its arrays)."""
    out = {}
    for shape, kind in re.findall(
            r"^\s*(?:ROOT )?%\S+ = (.*?) (all-reduce|all-gather|"
            r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(",
            text, re.M):
        out.setdefault(kind, set()).update(
            (dtype, int(np.prod([int(d) for d in dims.split(",") if d])))
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", shape))
    return out


def _assert_kernel_pair(text):
    assert "tpu_custom_call" in text
    assert "ssd_chunk_fwd" in text and "ssd_chunk_bwd" in text
    assert "all-gather" not in _collectives(text)


@pytest.mark.usefixtures("compiled_kernels")
class TestSSDVJPCompilesForV5e:
    """The SSD kernel pair under ``jax.grad``, forward and backward
    kernels both in the program, at the benchmark cells' widths."""

    @pytest.mark.parametrize("b,s,h,p,g,n", [
        (16, 2048, 32, 64, 1, 128),    # mamba2-370m.train
        (2, 4096, 112, 64, 2, 64),     # zamba2-7b.train4, rows a chip
    ])
    def test_grad_one_chip(self, one_chip, b, s, h, p, g, n):
        _assert_kernel_pair(_compile_for(one_chip, _ssd_grad,
                                         *_ssd_shapes(b, s, h, p, g, n)))

    def test_grad_sharded_over_data(self, topo):
        """zamba2-7b's (4, 1) mesh: each chip runs the pair on its own 2
        rows in ``shard_map``, with no collective around the kernels; the
        gradient of ``a``, which every chip holds whole, is summed."""
        text = _compile_sharded(
            topo, (4, 1), _ssd_shapes(8, 4096, 112, 64, 2, 64),
            ("data", "data", None, "data", "data"))
        _assert_kernel_pair(text)
        # the only exchange is the gradient of the replicated decay rates
        assert _collectives(text) == {"all-reduce": {("f32", 112)}}

    @pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)])
    def test_grad_sharded_over_model(self, topo, mesh_shape):
        """mamba2-370m's heads (one group of 32) over ``model``, as
        ``ssm_heads`` maps them: each chip runs the pair on its own heads
        with its own copy of the group's B and C, so x, dt and their
        gradients are never gathered; the only exchange sums the copies'
        gradients of B and C."""
        b, s, h, p, g, n = 4, 2048, 32, 64, 1, 128
        text = _compile_sharded(
            topo, mesh_shape, _ssd_shapes(b, s, h, p, g, n),
            (("data", None, "model"), ("data", None, "model"), "model",
             "data", "data"))
        _assert_kernel_pair(text)
        rows, heads = b // mesh_shape[0], h // mesh_shape[1]
        # B's and C's gradients, and a's where rows are split too
        want = {("bf16", rows * s * g * n)} | (
            {("f32", heads)} if mesh_shape[0] > 1 else set())
        assert _collectives(text) == {"all-reduce": want}

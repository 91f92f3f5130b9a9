"""The Pallas kernels compile for a TPU v5e at the widths the models run.

Each test lowers a public kernel entry point for one chip of a described
(not attached) ``v5e:2x2`` topology and compiles it with the TPU compiler
installed here, so a block shape or a VMEM budget the chip's compiler
refuses fails in CI, not on the chip.  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.rmsnorm import ops as rmsnorm_ops
from repro.kernels.ssd_scan import ops as ssd_ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


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
            lambda x, dt, a, bi, ci: ssd_ops.ssd_scan(x, dt, a, bi, ci,
                                                      chunk=256),
            ((b, s, h, p), BF16), ((b, s, h), F32), ((h,), F32),
            ((b, s, g, n), BF16), ((b, s, g, n), BF16))
        assert "tpu_custom_call" in text

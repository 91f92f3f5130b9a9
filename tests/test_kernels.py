"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.causal_conv import kernel as conv_kernel
from repro.kernels.causal_conv import ops as conv_ops
from repro.kernels.causal_conv.ref import causal_conv_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan import kernel as ssd_kernel
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.models import ssm


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,sq,skv,d", [
        (2, 4, 2, 256, 256, 64),
        (1, 4, 4, 128, 256, 64),
        (2, 2, 2, 256, 256, 32),
        (1, 8, 2, 128, 128, 128),
    ])
    def test_matches_ref_causal(self, b, hq, hkv, sq, skv, d):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, hq, sq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
        out = flash_attention(q, k, v, causal=True)
        kr = jnp.repeat(k, hq // hkv, axis=1)
        vr = jnp.repeat(v, hq // hkv, axis=1)
        ref = attention_ref(q, kr, vr, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("window,cap,causal", [
        (128, 0.0, True), (0, 50.0, True), (64, 30.0, True), (0, 0.0, False),
    ])
    def test_masking_variants(self, window, cap, causal):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              logit_cap=cap)
        ref = attention_ref(q, k, v, causal=causal, window=window,
                            logit_cap=cap)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.bfloat16)
        out = flash_attention(q, k, v)
        ref = attention_ref(q, k, v)
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32), atol=3e-2)

    def test_block_shape_independence(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 2, 512, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
        a = flash_attention(q, k, v, block_q=128, block_k=128)
        b = flash_attention(q, k, v, block_q=256, block_k=64)
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
        (2, 128, 4, 32, 1, 16, 32),
        (1, 256, 8, 64, 2, 32, 64),
        (1, 64, 2, 16, 1, 8, 16),
    ])
    def test_matches_sequential_ref(self, b, s, h, p, g, n, chunk):
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bi = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
        ci = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
        y, st = ssm.ssd_chunked(x, dt, a, bi, ci, chunk, kernel=True)
        yr, sr = ssd_ref(x, dt, a, bi, ci)
        np.testing.assert_allclose(y, yr, atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(st, sr, atol=2e-3, rtol=2e-3)

    def test_initial_state_continuation(self):
        """Scanning two halves with state carry == scanning the whole."""
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        b, s, h, p, g, n = 1, 128, 2, 16, 1, 8
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bi = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
        ci = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
        y_full, st_full = ssm.ssd_chunked(x, dt, a, bi, ci, 32, kernel=True)
        half = s // 2
        y1, st1 = ssm.ssd_chunked(x[:, :half], dt[:, :half], a,
                                  bi[:, :half], ci[:, :half], 32,
                                  kernel=True)
        y2, st2 = ssm.ssd_chunked(x[:, half:], dt[:, half:], a,
                                  bi[:, half:], ci[:, half:], 32,
                                  initial_state=st1, kernel=True)
        np.testing.assert_allclose(
            jnp.concatenate([y1, y2], axis=1), y_full, atol=2e-3)
        np.testing.assert_allclose(st2, st_full, atol=2e-3)


def _ssd_inputs(b, s, h, p, g, n, dtype, with_state, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bi = jax.random.normal(ks[3], (b, s, g, n), jnp.float32).astype(dtype)
    ci = jax.random.normal(ks[4], (b, s, g, n), jnp.float32).astype(dtype)
    st = (jax.random.normal(ks[5], (b, h, p, n)) if with_state else None)
    return x, dt, a, bi, ci, st


class TestSSDKernelVJP:
    """The kernel path of ``ssd_chunked`` (Pallas pair, its own VJP)
    against the jnp path: outputs, final state and the gradients of x, dt,
    a, B, C and the initial state.  With f32 activations the kernel's MXU
    operands are f32 and the two paths differ by summation order; with
    bf16 ones by bf16's rounding of the operands (2**-8)."""

    @staticmethod
    def _value_and_grads(kernel, x, dt, a, bi, ci, st, chunk):
        wy = jax.random.normal(jax.random.PRNGKey(9), x.shape)

        def loss(x, dt, a, bi, ci, st):
            y, fin = ssm.ssd_chunked(x, dt, a, bi, ci, chunk,
                                     initial_state=st, kernel=kernel)
            return (jnp.sum(y.astype(jnp.float32) * wy)
                    + jnp.sum(fin * jnp.cos(fin)), (y, fin))

        argnums = (0, 1, 2, 3, 4) + ((5,) if st is not None else ())
        return jax.jit(jax.value_and_grad(loss, argnums, has_aux=True))(
            x, dt, a, bi, ci, st)

    @pytest.mark.parametrize("h,g,with_state,dtype,chunk,tol", [
        (4, 1, False, jnp.float32, 128, 1e-4),
        (4, 1, True, jnp.float32, 256, 1e-4),
        (8, 2, False, jnp.float32, 256, 1e-4),
        (8, 2, True, jnp.float32, 128, 1e-4),
        (8, 2, True, jnp.bfloat16, 256, 2e-2),
    ])
    def test_matches_jnp_path(self, h, g, with_state, dtype, chunk, tol):
        """Chunks of 256 take the kernel's split into 128-row blocks (the
        earlier block through the cumsum at its end); 128, one block."""
        args = _ssd_inputs(1, 512, h, 64, g, 16, dtype, with_state)
        (_, (yk, fk)), gk = self._value_and_grads(True, *args, chunk=chunk)
        (_, (yj, fj)), gj = self._value_and_grads(False, *args, chunk=chunk)

        def close(got, want):
            got, want = (np.asarray(t, np.float32) for t in (got, want))
            scale = np.max(np.abs(want))
            assert np.max(np.abs(got - want)) <= tol * scale

        close(yk, yj)
        close(fk, fj)
        assert len(gk) == (6 if with_state else 5)
        for got, want in zip(gk, gj):
            close(got, want)

    def test_head_blocks_share_the_group_scores(self, monkeypatch):
        """A VMEM budget that takes one lane tile of heads a step splits
        each group into head blocks (C·Bᵀ and dC·Bᵀ kept across them);
        the result is the one-block result."""
        args = _ssd_inputs(1, 256, 8, 64, 2, 16, jnp.float32, True)
        (_, (y1, f1)), g1 = self._value_and_grads(True, *args, chunk=128)
        full = ssd_kernel.block_heads(8, 2, 64, 16, 128, 4)
        monkeypatch.setattr(ssd_kernel, "VMEM_BUDGET",
                            ssd_kernel._bwd_vmem(2, 2, 64, 16, 128, 4))
        assert ssd_kernel.block_heads(8, 2, 64, 16, 128, 4) == 2 < full
        (_, (y2, f2)), g2 = self._value_and_grads(True, *args, chunk=128)
        for got, want in zip((y2, f2, *g2), (y1, f1, *g1)):
            np.testing.assert_allclose(got, want, rtol=1e-5,
                                       atol=1e-5 * float(jnp.max(jnp.abs(
                                           want))))


    @pytest.mark.parametrize("mesh_shape,h,g", [
        ((1, 4), 8, 1),     # one group's heads over model: B, C copied
        ((2, 2), 8, 2),     # whole groups a chip, rows over data
        ((1, 4), 16, 2),    # half a group a chip: two copies a group
    ])
    def test_matches_jnp_path_on_a_mesh(self, tmp_path, mesh_shape, h, g):
        """Under a (data, model) mesh of 4 CPU devices (a subprocess: the
        device count is fixed at start-up) the kernel path runs in
        ``shard_map`` with rows and heads split; its loss and gradients
        match the jnp path's."""
        script = tmp_path / "mesh_check.py"
        script.write_text(_MESH_CHECK % (os.path.join(
            os.path.dirname(__file__), "..", "src"), mesh_shape, h, g))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert proc.stdout.strip().endswith("OK"), proc.stdout


_MESH_CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models import ssm

mesh_shape, h, g = %r, %d, %d
ks = jax.random.split(jax.random.PRNGKey(0), 6)
b, s, p, n = 2, 256, 64, 16
args = (jax.random.normal(ks[0], (b, s, h, p)),
        jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0),
        -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5),
        jax.random.normal(ks[3], (b, s, g, n)),
        jax.random.normal(ks[4], (b, s, g, n)))
wy = jax.random.normal(ks[5], (b, s, h, p))
specs = (P("data", None, "model"), P("data", None, "model"), P("model"),
         P("data"), P("data"))
mesh = Mesh(np.array(jax.devices()).reshape(mesh_shape), ("data", "model"))

def run(kernel):
    def loss(*a):
        y, fin = ssm.ssd_chunked(*a, 128, kernel=kernel)
        return jnp.sum(y * wy) + jnp.sum(fin * jnp.cos(fin))
    with mesh:
        sharded = [jax.device_put(v, NamedSharding(mesh, sp))
                   for v, sp in zip(args, specs)]
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4)))(*sharded)

(lk, gk), (lj, gj) = run(True), run(False)
assert abs(float(lk) - float(lj)) <= 1e-5 * abs(float(lj)), (lk, lj)
for name, got, want in zip("x dt a B C".split(), gk, gj):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err <= 1e-4, (name, err)
print("OK")
"""


class TestSSDPathSelection:
    """``ssd_chunked`` counts the path each trace took in
    ``ssm.SSD_PATHS``: the kernel on a TPU at shapes its tiling takes and
    a group count it was measured to pay at, with a chip's heads whole
    groups or within one group under the mesh; the jnp path otherwise."""

    @staticmethod
    def _trace(chunk, s=512, h=4, p=64, g=1, n=128):
        shapes = [((1, s, h, p), jnp.bfloat16), ((1, s, h), jnp.float32),
                  ((h,), jnp.float32), ((1, s, g, n), jnp.bfloat16),
                  ((1, s, g, n), jnp.bfloat16)]
        before = dict(ssm.SSD_PATHS)
        jax.eval_shape(lambda *a: ssm.ssd_chunked(*a, chunk),
                       *(jax.ShapeDtypeStruct(sh, d) for sh, d in shapes))
        return {k: v - before.get(k, 0) for k, v in ssm.SSD_PATHS.items()
                if v != before.get(k, 0)}

    def test_cpu_takes_the_jnp_path(self):
        assert self._trace(128) == {"jnp": 1}

    @pytest.mark.parametrize("chunk,s,p,g,n,path", [
        (128, 512, 64, 1, 128, "kernel"),    # whole lane tiles
        (256, 512, 128, 1, 128, "kernel"),
        (256, 512, 64, 1, 64, "kernel"),     # N = 64 tiles too
        (64, 512, 64, 1, 128, "jnp"),        # L×L tile under 128 lanes
        (128, 512, 256, 1, 128, "jnp"),      # P wider than a lane tile
        (256, 512, 64, 2, 64, "jnp"),        # G = 2 tiles, not measured
    ])
    def test_tpu_takes_the_kernel_where_it_tiles(self, monkeypatch, chunk,
                                                 s, p, g, n, path):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert self._trace(chunk, s=s, p=p, g=g, n=n) == {path: 1}

    @pytest.mark.parametrize("model,h,g,takes", [
        (4, 32, 1, True),      # 8 heads of the group a chip, B and C copied
        (16, 32, 1, True),     # 2 heads a chip: one lane tile
        (32, 32, 1, False),    # 1 head a chip: half a lane tile
        (3, 32, 1, False),     # 3 chips do not split 32 heads
        (4, 8, 2, True),       # half a group a chip
        (2, 8, 4, True),       # two whole groups a chip
        (2, 6, 3, False),      # 3 heads a chip straddle groups of 2
        (4, 12, 2, False),     # 3 heads a chip: no whole lane tile
    ])
    def test_mesh_splits_heads_or_takes_the_jnp_path(self, monkeypatch,
                                                      model, h, g, takes):
        """Under a mesh the kernel is taken only where a chip's heads are
        whole groups or lie in one group, and the chip's heads tile (the
        split rule alone: every group count here counts as measured)."""
        from repro.kernels.ssd_scan import ops
        mesh = types.SimpleNamespace(axis_names=("data", "model"),
                                     devices=np.empty((1, model)))
        monkeypatch.setattr(ops, "get_abstract_mesh_or_none", lambda: mesh)
        monkeypatch.setattr(ops, "MEASURED_GROUPS", (1, 2, 3, 4))
        assert ops.takes_kernel((1, 512, h, 64), 128, g, 128,
                                jnp.bfloat16) is takes


def _conv_inputs(b, s, total, width, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    xs = jax.random.normal(ks[0], (b, s, total), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (4, width)) * 0.5).astype(dtype)
    bias = (jax.random.normal(ks[2], (width,)) * 0.5).astype(dtype)
    wy = jax.random.normal(ks[3], (b, s, width))
    return xs, w, bias, wy


def _conv_value_and_grads(kernel, xs, w, bias, wy, offset):
    """silu(causal conv) of xs's channels [offset, offset + C) and the
    gradients of a loss of it w.r.t. xs, w and b, on the kernel pair or on
    the jnp path (``_causal_conv`` and its SiLU)."""
    def conv(xs, w, bias):
        if kernel:
            return conv_ops.causal_conv_silu(xs, w, bias, offset)
        xbc = xs[..., offset:offset + w.shape[1]]
        return jax.nn.silu(ssm._causal_conv(xbc, w, bias))

    def loss(xs, w, bias):
        y = conv(xs, w, bias)
        return jnp.sum(y.astype(jnp.float32) * wy), y

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(
        xs, w, bias)


class TestCausalConvKernelVJP:
    """The conv pair (interpreted on the CPU) against the jnp path:
    forward and the gradients of xs (zero outside the conv's channels), w
    and b.  With f32 activations they differ by summation order; with
    bf16 ones by where the SiLU and its derivative round (the jnp path
    rounds each to bf16, the kernel keeps f32 until its outputs), a few
    units of bf16's last place (2**-8) of the largest value."""

    @pytest.mark.parametrize("b,s,total,offset,width,dtype,tol", [
        (2, 128, 1024, 256, 512, jnp.float32, 1e-5),   # two row chunks
        (1, 64, 256, 0, 256, jnp.float32, 1e-5),       # every channel
        (2, 192, 768, 512, 256, jnp.bfloat16, 2e-2),   # the last channels
        (1, 128, 4384, 2048, 2304, jnp.bfloat16, 2e-2),    # mamba2-370m
        (1, 64, 14704, 7168, 7424, jnp.bfloat16, 2e-2),    # zamba2-7b
    ])
    def test_matches_jnp_path(self, b, s, total, offset, width, dtype, tol):
        args = _conv_inputs(b, s, total, width, dtype)
        (_, yk), gk = _conv_value_and_grads(True, *args, offset)
        (_, yj), gj = _conv_value_and_grads(False, *args, offset)

        def close(got, want):
            got, want = (np.asarray(t, np.float32) for t in (got, want))
            assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))

        close(yk, yj)
        # the first K - 1 rows, where the taps read the zeros before t = 0
        close(yk[:, :3], yj[:, :3])
        for got, want in zip(gk, gj):
            close(got, want)
        dxs = np.asarray(gk[0], np.float32)
        assert not dxs[..., :offset].any()
        assert not dxs[..., offset + width:].any()

    def test_taps_reach_across_row_chunks(self, monkeypatch):
        """Chunks of 8 rows: each chunk's taps read the previous chunk's
        last rows, and dx's the next chunk's first; the result is the
        one-chunk result, up to the order of dw's and db's sums."""
        args = _conv_inputs(1, 64, 512, 256, jnp.float32)
        (_, y1), g1 = _conv_value_and_grads(True, *args, 256)
        monkeypatch.setattr(conv_kernel, "ROWS", 8)
        (_, y2), g2 = _conv_value_and_grads(True, *args, 256)
        for got, want in zip((y2, *g2), (y1, *g1)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 *
                                       float(jnp.max(jnp.abs(want))))

    def test_jnp_path_matches_the_reference(self):
        """The jnp path, the kernel's reference, against ``ref.py``'s
        grouped convolution."""
        xs, w, bias, _ = _conv_inputs(2, 64, 256, 256, jnp.float32)
        got = jax.nn.silu(ssm._causal_conv(xs, w, bias))
        np.testing.assert_allclose(got, causal_conv_ref(xs, w, bias),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_jnp_path_on_a_mesh(self, tmp_path):
        """Under a (4, 1) mesh of 4 CPU devices (a subprocess: the device
        count is fixed at start-up) the pair runs in ``shard_map`` on each
        chip's rows; loss and gradients match the jnp path's."""
        script = tmp_path / "conv_mesh_check.py"
        script.write_text(_CONV_MESH_CHECK % os.path.join(
            os.path.dirname(__file__), "..", "src"))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run([sys.executable, str(script)], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert proc.stdout.strip().endswith("OK"), proc.stdout


_CONV_MESH_CHECK = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels.causal_conv import ops
from repro.models import ssm

ks = jax.random.split(jax.random.PRNGKey(0), 4)
b, s, total, offset, width = 8, 64, 1024, 256, 512
args = (jax.random.normal(ks[0], (b, s, total)),
        jax.random.normal(ks[1], (4, width)) * 0.5,
        jax.random.normal(ks[2], (width,)) * 0.5)
wy = jax.random.normal(ks[3], (b, s, width))
specs = (P("data"), P(), P())
mesh = Mesh(np.array(jax.devices()).reshape(4, 1), ("data", "model"))

def run(kernel):
    def loss(xs, w, bias):
        if kernel:
            y = ops.causal_conv_silu(xs, w, bias, offset)
        else:
            y = jax.nn.silu(ssm._causal_conv(
                xs[..., offset:offset + width], w, bias))
        return jnp.sum(y * wy)
    with mesh:
        sharded = [jax.device_put(v, NamedSharding(mesh, sp))
                   for v, sp in zip(args, specs)]
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(*sharded)

(lk, gk), (lj, gj) = run(True), run(False)
assert abs(float(lk) - float(lj)) <= 1e-5 * abs(float(lj)), (lk, lj)
for name, got, want in zip(("xs", "w", "b"), gk, gj):
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err <= 1e-5, (name, err)
print("OK")
"""


class TestCausalConvPathSelection:
    """``mamba2_forward`` counts the conv's path in ``ssm.CONV_PATHS``:
    the kernel pair on a TPU where the SSD takes its pair too, the conv's
    channels and their offset in ``in_proj``'s output are whole channel
    tiles, the rows whole chunks and no ``model`` axis splits the
    channels; the jnp path otherwise, and on the CPU.  d_model 128 and
    heads of 64 in chunks of 128: the SSD's pair tiles at G = 1."""

    @staticmethod
    def _trace(d_state, seq=128, decode=False, d_conv=4, groups=1):
        from dataclasses import replace

        from repro.models import get_smoke_config
        cfg = get_smoke_config("mamba2-370m")
        cfg = cfg.scaled(d_model=128, ssm=replace(
            cfg.ssm, d_state=d_state, d_conv=d_conv, n_groups=groups,
            head_dim=64, chunk_size=128))
        specs = ssm.ssm_specs(cfg)
        params = {k: jax.ShapeDtypeStruct(v.shape, jnp.dtype(v.dtype))
                  for k, v in specs.items()}
        rows = 1 if decode else seq
        hidden = jax.ShapeDtypeStruct((2, rows, cfg.d_model), jnp.bfloat16)
        states = {}
        if decode:
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            states = dict(
                ssm_state=jax.ShapeDtypeStruct(
                    (2, s.n_heads(cfg.d_model), s.head_dim, d_state),
                    jnp.float32),
                conv_state=jax.ShapeDtypeStruct(
                    (2, s.d_conv - 1, di + 2 * groups * d_state),
                    jnp.bfloat16))
        before = dict(ssm.CONV_PATHS)
        jax.eval_shape(lambda p, h, st: ssm.mamba2_forward(
            cfg, p, h, decode=decode, **st), params, hidden, states)
        return {k: v - before.get(k, 0) for k, v in ssm.CONV_PATHS.items()
                if v != before.get(k, 0)}

    def test_cpu_takes_the_jnp_path(self):
        # d_inner 256 and 2·N = 256: the channels tile
        assert self._trace(128) == {"jnp": 1}

    @pytest.mark.parametrize("d_state,seq,d_conv,groups,path", [
        (128, 128, 4, 1, "kernel"),    # channels [256, 768) of 772
        (64, 128, 4, 1, "jnp"),        # 384 channels: one and a half tiles
        (128, 96, 4, 1, "jnp"),        # 96 rows: one and a half chunks
        (128, 128, 9, 1, "kernel"),    # taps reach back 8 rows, the halo
        (128, 128, 10, 1, "jnp"),      # 9 rows: past the halo
        (64, 128, 4, 2, "jnp"),        # 512 channels tile; the SSD at G = 2
                                       # takes the jnp path, and so the conv
    ])
    def test_tpu_takes_the_kernel_where_it_tiles(self, monkeypatch, d_state,
                                                 seq, d_conv, groups, path):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert self._trace(d_state, seq, d_conv=d_conv,
                           groups=groups) == {path: 1}

    def test_decode_keeps_the_rolling_state(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert self._trace(128, decode=True) == {}

    @pytest.mark.parametrize("mesh_shape,takes", [
        ((4, 1), True),      # rows over data, every channel a chip
        ((2, 2), False),     # a model axis splits the channels
    ])
    def test_mesh_keeps_the_channels_whole(self, monkeypatch, mesh_shape,
                                           takes):
        mesh = types.SimpleNamespace(axis_names=("data", "model"),
                                     devices=np.empty(mesh_shape))
        monkeypatch.setattr(conv_ops, "get_abstract_mesh_or_none",
                            lambda: mesh)
        assert conv_ops.takes_kernel((8, 4096, 14704), 7168, (4, 7424),
                                     jnp.bfloat16) is takes


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(4, 128, 512), (2, 64, 1024),
                                       (128, 768), (1, 1, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, shape, dtype)
        w = jax.random.normal(key, shape[-1:], dtype)
        out = rmsnorm(x, w)
        ref = rmsnorm_ref(x, w)
        atol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32), atol=atol)

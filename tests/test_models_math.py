"""Numerical properties of model components beyond smoke coverage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based tests need the hypothesis dev dependency "
           "(pip install -e .[dev])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.models.attention import (AttnArgs, _chunked_attention,
                                    _dense_attention)
from repro.models.common import rms_norm, softcap
from repro.models.mlp import moe_forward
from repro.models.rope import apply_mrope, apply_rope
from repro.models.ssm import _causal_conv, ssd_chunked
from repro.kernels.ssd_scan.ref import ssd_ref


class TestAttentionImpls:
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                               (False, 0)])
    def test_chunked_equals_dense(self, causal, window):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (2, 4, 128, 32), jnp.float32)
        k = jax.random.normal(ks[1], (2, 2, 128, 32), jnp.float32)
        v = jax.random.normal(ks[2], (2, 2, 128, 32), jnp.float32)
        args = AttnArgs(causal=causal, window=window)
        dense = _dense_attention(q, k, v, args)
        chunked = _chunked_attention(q, k, v, args, chunk=32)
        np.testing.assert_allclose(dense, chunked, atol=2e-5, rtol=2e-5)

    def test_chunked_handles_padding(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 2, 100, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 100, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 100, 32), jnp.float32)
        args = AttnArgs(causal=True)
        dense = _dense_attention(q, k, v, args)
        chunked = _chunked_attention(q, k, v, args, chunk=64)  # pad to 128
        np.testing.assert_allclose(dense, chunked, atol=2e-5, rtol=2e-5)

    def test_dynamic_window_matches_static(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 64, 16), jnp.float32)
        k, v = q, q
        stat = _dense_attention(q, k, v, AttnArgs(causal=True, window=16))
        dyn = _dense_attention(q, k, v,
                               AttnArgs(causal=True, window=jnp.int32(16)))
        np.testing.assert_allclose(stat, dyn, atol=1e-6)


class TestRope:
    def test_rope_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
        y = apply_rope(x, pos)
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
            rtol=1e-5)

    def test_rope_relative_property(self):
        """<rope(q,i), rope(k,j)> depends only on i-j."""
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 64))

        def score(i, j):
            qr = apply_rope(q, jnp.full((1, 1), i))
            kr = apply_rope(k, jnp.full((1, 1), j))
            return float(jnp.sum(qr * kr))
        assert score(5, 3) == pytest.approx(score(10, 8), rel=1e-4)

    def test_mrope_matches_rope_for_equal_streams(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
        pos3 = jnp.stack([pos, pos, pos])
        a = apply_rope(x, pos)
        b = apply_mrope(x, pos3, (8, 4, 4))
        np.testing.assert_allclose(a, b, atol=1e-5)


class TestMoE:
    def _cfg(self, **kw):
        from repro.models import get_smoke_config
        return get_smoke_config("mixtral-8x22b").scaled(**kw)

    def test_output_finite_and_shaped(self):
        from repro.models.mlp import moe_specs
        from repro.models.params import init_params
        cfg = self._cfg()
        p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                              jnp.bfloat16)
        y = moe_forward(cfg, p, x)
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))

    def test_capacity_drop_is_graceful(self):
        """With capacity factor << 1 most tokens drop, output stays finite
        and small."""
        from dataclasses import replace
        from repro.models.mlp import moe_specs
        from repro.models.params import init_params
        cfg = self._cfg()
        cfg = cfg.scaled(moe=replace(cfg.moe, capacity_factor=0.01))
        p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model),
                              jnp.bfloat16)
        y = moe_forward(cfg, p, x)
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))

    def test_flops_scale_with_topk_not_experts(self):
        """Sort-based dispatch: HLO flops track k·tokens, not E·tokens."""
        from repro.core.ir import parse, program_cost
        from repro.models.mlp import moe_specs
        from repro.models.params import abstract_params

        def flops_for(n_experts):
            from dataclasses import replace
            cfg = self._cfg()
            cfg = cfg.scaled(moe=replace(cfg.moe, num_experts=n_experts,
                                         capacity_factor=1.0))
            specs = moe_specs(cfg)
            pa = abstract_params(specs)
            xa = jax.ShapeDtypeStruct((2, 128, cfg.d_model), jnp.bfloat16)
            txt = jax.jit(lambda p, x: moe_forward(cfg, p, x)).lower(
                pa, xa).as_text()
            return program_cost(parse(txt)).flops

        f4, f8 = flops_for(4), flops_for(8)
        # doubling experts must NOT double compute (one-hot dispatch would)
        assert f8 < 1.5 * f4


class TestSSM:
    def test_causal_conv_is_causal(self):
        x = jnp.zeros((1, 16, 4)).at[0, 8, :].set(1.0)
        w = jnp.ones((4, 4))
        b = jnp.zeros((4,))
        y = _causal_conv(x, w, b)
        assert float(jnp.abs(y[0, :5]).sum()) == 0.0  # nothing before t=8-3

    @staticmethod
    def _ssd_inputs(seed, h, g):
        b, s, p, n = 1, 64, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        bi = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
        ci = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
        return x, dt, a, bi, ci

    @pytest.mark.parametrize("h,g", [(2, 1), (4, 1), (4, 2), (4, 4)])
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_ssd_chunked_matches_sequential(self, seed, h, g):
        x, dt, a, bi, ci = self._ssd_inputs(seed, h, g)
        y, st_ = jax.jit(ssd_chunked, static_argnames="chunk")(
            x, dt, a, bi, ci, chunk=16)
        yr, sr = jax.jit(ssd_ref)(x, dt, a, bi, ci)
        np.testing.assert_allclose(y, yr, atol=3e-3, rtol=3e-3)
        np.testing.assert_allclose(st_, sr, atol=3e-3, rtol=3e-3)

    @pytest.mark.parametrize("h,g", [(2, 1), (4, 1), (4, 2), (4, 4)])
    def test_ssd_chunked_grads_match_sequential(self, h, g):
        """Gradients w.r.t. x, dt, a, B and C match the recurrence's."""
        args = self._ssd_inputs(7, h, g)
        ky, ks = jax.random.split(jax.random.PRNGKey(11))
        wy = jax.random.normal(ky, args[0].shape)
        ws = jax.random.normal(ks, (1, h, 8, 4))          # [B,H,P,N]

        def loss(fn):
            def f(*a):
                y, st_ = fn(*a)
                return jnp.sum(y * wy) + jnp.sum(st_ * ws)
            return jax.jit(jax.grad(f, argnums=range(5)))(*args)

        got = loss(lambda *a: ssd_chunked(*a, chunk=16))
        want = loss(ssd_ref)
        for gc, gr in zip(got, want):
            scale = float(jnp.max(jnp.abs(gr)))
            np.testing.assert_allclose(gc, gr, atol=3e-3 * scale, rtol=3e-3)

    def test_score_flops_do_not_scale_with_heads(self):
        """C·Bᵀ is contracted once per group: with one group, the lowered
        program does far fewer FLOPs than with a group per head."""
        from repro.core.ir import parse, program_cost
        h, p, n, s, chunk = 8, 4, 64, 128, 64

        def flops_for(g):
            f32 = jnp.float32
            shapes = [(1, s, h, p), (1, s, h), (h,), (1, s, g, n),
                      (1, s, g, n)]
            txt = jax.jit(lambda *a: ssd_chunked(*a, chunk=chunk)).lower(
                *(jax.ShapeDtypeStruct(sh, f32) for sh in shapes)).as_text()
            return program_cost(parse(txt)).flops

        # head-repeated B and C would cost the same at g = 1 as at g = h
        assert flops_for(1) < 0.5 * flops_for(h)


class TestNumerics:
    def test_softcap_bounded(self):
        x = jnp.array([-1e9, -1.0, 0.0, 1.0, 1e9])
        y = softcap(x, 30.0)
        assert bool(jnp.all(jnp.abs(y) <= 30.0))
        np.testing.assert_allclose(softcap(x, 0.0), x)

    def test_rms_norm_unit_scale(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 100
        y = rms_norm(x, jnp.ones(64))
        rms = jnp.sqrt(jnp.mean(y.astype(jnp.float32) ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-2)

"""Correctness of the §Perf optimizations: they must be exact (or bf16-
rounding-equivalent) rewrites of the baseline math."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import forward, get_smoke_config, model_specs
from repro.models.params import init_params


class TestChunkedLoss:
    def test_matches_full_loss(self):
        cfg0 = get_smoke_config("stablelm-12b")
        cfg1 = cfg0.scaled(loss_vocab_chunk=100)   # 256 vocab -> 3 chunks
        params = init_params(model_specs(cfg0), jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                         cfg0.vocab_size),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                          cfg0.vocab_size),
        }
        l0, _ = forward(cfg0, params, batch)
        l1, logits1 = forward(cfg1, params, batch)
        assert logits1 is None
        assert float(abs(l0 - l1)) < 1e-3

    def test_grads_match(self):
        cfg0 = get_smoke_config("stablelm-12b")
        cfg1 = cfg0.scaled(loss_vocab_chunk=64)
        params = init_params(model_specs(cfg0), jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                         cfg0.vocab_size),
            "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                          cfg0.vocab_size),
        }
        g0 = jax.grad(lambda p: forward(cfg0, p, batch)[0])(params)
        g1 = jax.grad(lambda p: forward(cfg1, p, batch)[0])(params)
        flat0 = jax.tree.leaves(g0)
        flat1 = jax.tree.leaves(g1)
        for a, b in zip(flat0, flat1):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            # bf16 params: chunked-scan vs single-GEMM accumulation order
            # differs; compare in relative-Frobenius terms
            denom = np.linalg.norm(a) + 1e-9
            assert np.linalg.norm(a - b) / denom < 0.02, \
                np.linalg.norm(a - b) / denom


class TestMoEShardMap:
    """moe_forward_ep ≡ moe_forward on multi-device meshes (subprocess —
    the device count is locked in the main test process)."""

    @pytest.mark.slow
    def test_both_schemes_multi_device(self, tmp_path):
        import subprocess
        import sys
        script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from dataclasses import replace
from repro.models import get_smoke_config
from repro.models.mlp import moe_forward, moe_forward_ep, moe_specs
from repro.models.params import init_params
from repro.launch.mesh import make_mesh

def check(cfg, mesh_shape):
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    with mesh:
        y0 = jax.jit(lambda p, x: moe_forward(cfg, p, x))(p, x)
        y1 = jax.jit(lambda p, x: moe_forward_ep(cfg, p, x))(p, x)
    import numpy as np
    a = np.asarray(y0, np.float32); b = np.asarray(y1, np.float32)
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-6)
    assert rel < 0.02, rel

cfg = get_smoke_config("mixtral-8x22b")            # 4 experts
check(cfg, (1, 4))                                  # expert scheme (4e/4)
check(cfg, (2, 2))                                  # expert scheme (4e/2)
cfg2 = cfg.scaled(moe=replace(cfg.moe, num_experts=2, d_ff_expert=64))
check(cfg2, (1, 4))                                 # ffn scheme (2e on 4)
print("OK")
""" % os.path.join(os.path.dirname(__file__), "..", "src")
        path = tmp_path / "ep_check.py"
        path.write_text(script)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run([sys.executable, str(path)], env=env,
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout

    def test_single_device_fallback(self):
        """No mesh -> falls back to the reference path."""
        from repro.models.mlp import moe_forward, moe_forward_ep, moe_specs
        cfg = get_smoke_config("mixtral-8x22b")
        p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                              jnp.bfloat16)
        y0 = moe_forward(cfg, p, x)
        y1 = moe_forward_ep(cfg, p, x)
        np.testing.assert_allclose(np.asarray(y0, np.float32),
                                   np.asarray(y1, np.float32), atol=1e-5)

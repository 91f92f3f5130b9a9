"""Per-architecture smoke tests (deliverable f): a REDUCED config of the
same family runs one forward/train step on CPU, asserting output shapes
and no NaNs; decode runs one autoregressive step."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import (ARCH_IDS, decode_step, forward, get_config,
                          get_smoke_config, init_cache_specs, model_specs,
                          shape_cells, skip_reason)
from repro.models.params import init_params, param_count

KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=64):
    batch = {}
    if cfg.frontend == "stub":
        batch["embeds"] = jax.random.normal(KEY, (b, s, cfg.d_model),
                                            jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    batch["targets"] = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        batch["mrope_positions"] = jnp.stack([pos, pos, pos])
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestSmoke:
    def test_forward_and_grad(self, arch):
        cfg = get_smoke_config(arch)
        params = init_params(model_specs(cfg), KEY)
        batch = _batch(cfg)
        b, s = 2, 64
        loss, logits = jax.jit(
            lambda p, bt: forward(cfg, p, bt))(params, batch)
        assert logits.shape == (b, s, cfg.vocab_size)
        assert jnp.isfinite(loss), f"{arch}: NaN loss"
        assert bool(jnp.all(jnp.isfinite(logits))), f"{arch}: NaN logits"
        grads = jax.jit(jax.grad(
            lambda p, bt: forward(cfg, p, bt)[0]))(params, batch)
        gsum = jax.tree.reduce(
            lambda a, x: a + jnp.sum(jnp.abs(x.astype(jnp.float32))),
            grads, 0.0)
        assert jnp.isfinite(gsum), f"{arch}: NaN grads"
        assert float(gsum) > 0, f"{arch}: zero grads"

    def test_train_step(self, arch):
        from repro.train.loop import make_train_step
        from repro.train.optimizer import OptimizerConfig, make_optimizer
        cfg = get_smoke_config(arch)
        opt_cfg = OptimizerConfig(warmup_steps=1)
        init_fn, _ = make_optimizer(opt_cfg)
        params = init_params(model_specs(cfg), KEY)
        opt = init_fn(params, opt_cfg)
        step = jax.jit(make_train_step(cfg, opt_cfg))
        batch = _batch(cfg)
        p1, o1, m1 = step(params, opt, batch)
        p2, o2, m2 = step(p1, o1, batch)
        assert jnp.isfinite(m1["loss"]) and jnp.isfinite(m2["loss"])
        assert int(o2["step"]) == 2
        # params actually moved
        delta = jax.tree.reduce(
            lambda a, t: a + float(jnp.sum(jnp.abs(
                t[0].astype(jnp.float32) - t[1].astype(jnp.float32)))),
            jax.tree.map(lambda a, b_: (a, b_), params, p1), 0.0)
        assert delta > 0

    def test_decode_step(self, arch):
        cfg = get_smoke_config(arch)
        if cfg.is_encoder_only:
            pytest.skip("encoder-only: no decode step")
        params = init_params(model_specs(cfg), KEY)
        cache = init_params(init_cache_specs(cfg, 2, 32), KEY)
        tok = ({"tokens": jnp.zeros((2, 1), jnp.int32)}
               if cfg.frontend != "stub"
               else {"embeds": jnp.zeros((2, 1, cfg.d_model), jnp.bfloat16)})
        logits, cache = jax.jit(
            lambda p, c, b: decode_step(cfg, p, c, b))(params, cache, tok)
        assert logits.shape == (2, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert int(cache["index"]) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "mamba2-370m": dict(num_layers=48, d_model=1024, vocab_size=50280),
        "deepseek-67b": dict(num_layers=95, d_model=8192, num_heads=64,
                             num_kv_heads=8, d_ff=22016, vocab_size=102400),
        "stablelm-12b": dict(num_layers=40, d_model=5120, num_heads=32,
                             num_kv_heads=8, d_ff=13824, vocab_size=100352),
        "qwen2.5-32b": dict(num_layers=64, d_model=5120, num_heads=40,
                            num_kv_heads=8, d_ff=27648, vocab_size=152064,
                            qkv_bias=True),
        "gemma2-27b": dict(num_layers=46, d_model=4608, num_heads=32,
                           num_kv_heads=16, d_ff=36864, vocab_size=256000),
        "zamba2-2.7b": dict(num_layers=54, d_model=2560, num_heads=32,
                            num_kv_heads=32, d_ff=10240, vocab_size=32000),
        "deepseek-v3-671b": dict(num_layers=61, d_model=7168, num_heads=128,
                                 vocab_size=129280),
        "mixtral-8x22b": dict(num_layers=56, d_model=6144, num_heads=48,
                              num_kv_heads=8, vocab_size=32768),
        "hubert-xlarge": dict(num_layers=48, d_model=1280, num_heads=16,
                              d_ff=5120, vocab_size=504, causal=False),
        "qwen2-vl-7b": dict(num_layers=28, d_model=3584, num_heads=28,
                            num_kv_heads=4, d_ff=18944, vocab_size=152064),
        "zamba2-7b": dict(num_layers=81, d_model=3584, num_heads=32,
                          num_kv_heads=32, head_dim=224, d_ff=14336,
                          vocab_size=32000, num_mem_blocks=2,
                          adapter_rank=128,
                          hybrid_layer_ids=[6, 11, 17, 23, 29, 35, 41, 47,
                                            53, 59, 65, 71, 77]),
    }[arch]
    for k, v in expected.items():
        assert getattr(cfg, k) == v, f"{arch}.{k}: {getattr(cfg, k)} != {v}"


def test_param_counts_plausible():
    """Param counts land near the advertised sizes."""
    for arch, lo, hi in [("mamba2-370m", 0.3e9, 0.45e9),
                         ("deepseek-67b", 60e9, 72e9),
                         ("stablelm-12b", 10e9, 14e9),
                         ("qwen2.5-32b", 28e9, 36e9),
                         ("gemma2-27b", 24e9, 31e9),
                         ("mixtral-8x22b", 130e9, 150e9),
                         ("deepseek-v3-671b", 600e9, 720e9),
                         ("zamba2-2.7b", 2.2e9, 3.2e9),
                         ("zamba2-7b", 7.0e9, 7.8e9),
                         ("hubert-xlarge", 0.8e9, 1.4e9),
                         ("qwen2-vl-7b", 6.5e9, 9e9)]:
        total, active = get_config(arch).param_count()
        assert lo <= total <= hi, f"{arch}: {total/1e9:.1f}B not in " \
                                  f"[{lo/1e9:.0f}, {hi/1e9:.0f}]"
        assert active <= total


def test_shape_cell_assignment_rules():
    assert "long_500k" in shape_cells(get_config("mamba2-370m"))
    assert "long_500k" in shape_cells(get_config("zamba2-2.7b"))
    assert "long_500k" in shape_cells(get_config("mixtral-8x22b"))
    assert "long_500k" not in shape_cells(get_config("qwen2.5-32b"))
    assert "long_500k" not in shape_cells(get_config("gemma2-27b"))
    assert "decode_32k" not in shape_cells(get_config("hubert-xlarge"))
    assert skip_reason(get_config("hubert-xlarge"), "decode_32k")
    assert skip_reason(get_config("deepseek-67b"), "long_500k")
    assert skip_reason(get_config("mamba2-370m"), "train_4k") is None


def test_hybrid_param_count_is_the_tree_count():
    """The analytic count of Zamba2's structure (81 Mamba2 layers, two
    shared blocks, 13 invocations' linears and adapters, tied embedding)
    is the parameter tree's, at full size and at the benchmark's cut."""
    from repro.models.params import param_count
    for cfg in (get_config("zamba2-7b"), get_config("zamba2-2.7b"),
                get_config("zamba2-7b").scaled(num_layers=12,
                                               hybrid_layer_ids=[6, 11])):
        assert cfg.param_count()[0] == param_count(model_specs(cfg))


def test_smoke_param_trees_match_full_structure():
    """Smoke and full configs produce the same tree structure per arch."""
    from repro.models.params import tree_paths
    for arch in ARCH_IDS:
        smoke = set(tree_paths(model_specs(get_smoke_config(arch))))
        full = set(tree_paths(model_specs(get_config(arch))))
        assert smoke == full, f"{arch}: smoke/full param trees differ"

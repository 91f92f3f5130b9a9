"""Each configuration's model operations per token against the compiled
step's own count, at a width the CPU compiles in seconds.

The step is compiled with no rematerialisation and no loop over layers
(XLA's cost analysis counts a loop's body once), so its count is the
forward and backward work plus the elementwise work the model count leaves
out: norms, activations, the softmax, the loss and the optimizer's update,
some tens of operations per parameter or activation.  The model count may
therefore fall short of the compiled one, by under 15% at these widths
(0.89 when this test was written), and may never exceed it."""
from __future__ import annotations

import smoke

from chipbench.reference import mamba2  # noqa: E402


def test_model_flops_match_the_compiled_step():
    import jax
    import jax.numpy as jnp
    from repro.configs.base import SSMConfig
    from repro.models import get_config
    from repro.models.params import abstract_params
    from repro.models.transformer import model_specs
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig, opt_state_abstract

    m = dict(smoke.MODEL, d_model=256)
    seq, batch = 512, 2
    cfg = get_config(smoke.REGISTRY).scaled(
        ssm=SSMConfig(**smoke.SSM), remat="none", scan_layers=False,
        **{k: m[k] for k in ("num_layers", "d_model", "vocab_size")})
    specs = model_specs(cfg)
    rows = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    compiled = jax.jit(make_train_step(cfg, OptimizerConfig())).lower(
        abstract_params(specs), opt_state_abstract(specs, "adamw"),
        {"tokens": rows, "targets": rows}).compile()
    cost = compiled.cost_analysis()
    cost = cost if isinstance(cost, dict) else cost[0]
    model = mamba2.model_flops_per_token(m, seq) * batch * seq
    assert 0.85 <= model / cost["flops"] <= 1.0, (model, cost["flops"])

"""The train step's named layers (``jax.named_scope`` in ``models/`` and
``train/optimizer.py``), which the benchmark reads from the device trace
by name (``chipbench/scopes.py``): each must reach the compiled program's
``op_name`` metadata, in the forward and the backward pass, so that a
rename fails here instead of silencing a metric."""
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench import scopes
from repro.models import get_smoke_config
from repro.models.params import is_spec
from repro.models.transformer import model_specs
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig, opt_state_abstract

MODEL_LAYERS = ("vocab", "norm", "proj", "conv", "ssd")


def _step_op_names(arch: str) -> set:
    """Every ``op_name`` of the compiled step of ``arch``'s smoke model,
    with the benchmark cells' remat (full) and optimizer (AdamW)."""
    cfg = get_smoke_config(arch).scaled(remat="full")
    specs = model_specs(cfg)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), specs,
        is_leaf=is_spec)
    opt = opt_state_abstract(specs, "adamw")
    rows = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    step = make_train_step(cfg, OptimizerConfig(warmup_steps=1))
    text = jax.jit(step).lower(
        params, opt, {"tokens": rows, "targets": rows}).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def op_names():
    return _step_op_names("mamba2-370m")


@pytest.fixture(scope="module")
def hybrid_op_names():
    return _step_op_names("zamba2-7b")


def test_every_layer_the_benchmark_reads_is_named():
    assert set(MODEL_LAYERS) | {"optimizer"} == set(scopes.NAMES)


@pytest.mark.parametrize("name", MODEL_LAYERS)
def test_model_layer_is_named_forward_and_backward(op_names, name):
    mine = [p for p in op_names if scopes.layer(p) == name]
    forward = [p for p in mine if "transpose(" not in p]
    # the backward pass proper, not the forward it rematerialises
    backward = [p for p in mine if "transpose(" in p
                and "rematted_computation" not in p]
    assert forward and backward, (name, mine)


def test_optimizer_covers_the_update(op_names):
    mine = [p for p in op_names if scopes.layer(p) == "optimizer"]
    assert mine and not [p for p in mine if "transpose(" in p]
    # the global norm of the clip and AdamW's denominator are the step's
    # only square roots
    roots = [p for p in op_names if p.endswith("/sqrt")]
    assert roots and all(scopes.layer(p) == "optimizer" for p in roots), \
        roots


def _in_scope(path: str, name: str) -> bool:
    """Whether a path component is ``name``, JAX's transform wrappers
    (``transpose(jvp(name))``) peeled off."""
    return any(re.fullmatch(rf"(?:\w+\()*{name}\)*", c)
               for c in path.split("/"))


@pytest.mark.parametrize("name", ["attn", "mlp"])
def test_shared_block_is_named_forward_and_backward(hybrid_op_names, name):
    """Zamba2's shared attention and MLP (adapter and linear included)
    reach the hybrid step, forward and backward; the benchmark reads them
    as unscoped until ``chipbench/scopes.NAMES`` lists them."""
    mine = [p for p in hybrid_op_names if _in_scope(p, name)]
    forward = [p for p in mine if "transpose(" not in p]
    backward = [p for p in mine if "transpose(" in p
                and "rematted_computation" not in p]
    assert forward and backward, (name, mine)
    # the shared block's norms keep their own name
    assert not [p for p in mine if _in_scope(p, "norm")], mine


def test_hybrid_step_keeps_the_mamba_layers_names(hybrid_op_names):
    for name in ("proj", "conv", "ssd", "norm", "vocab"):
        assert [p for p in hybrid_op_names if scopes.layer(p) == name], name

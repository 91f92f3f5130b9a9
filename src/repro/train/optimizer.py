"""Optimizers in pure JAX: AdamW and Adafactor (factored second moments).

Optimizer state is fully sharded: each moment inherits its parameter's
sharding (which is itself FSDP-sharded over the "data" axis), so per-device
optimizer bytes scale as 1/|mesh| — required for the 671B MoE config to fit
a v5e pod (see EXPERIMENTS.md §Dry-run memory table)."""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial, wraps

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    learning_rate: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: str = "float32"
    warmup_steps: int = 100
    # adafactor
    min_dim_size_to_factor: int = 128


def lr_schedule(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(step.astype(jnp.float32) / max(cfg.warmup_steps, 1),
                       1.0)
    return cfg.learning_rate * warm


def _global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = _global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), norm


def opt_state_abstract(specs, opt_name: str, mesh=None, rules=None):
    """ShapeDtypeStructs (sharded) for the optimizer state, from ParamSpecs.

    The zero-allocation twin of ``adamw_init``/``adafactor_init`` used to
    *lower* a train step without materializing state (dry-runs, workload
    export).  Moments inherit the parameter sharding (fully sharded
    optimizer); adafactor's factored moments drop the corresponding axes.
    """
    from ..distributed.sharding import param_sharding
    from ..models.params import ParamSpec, is_spec

    def like(spec: ParamSpec, dtype="float32"):
        if mesh is None:
            return jax.ShapeDtypeStruct(spec.shape, jnp.dtype(dtype))
        return jax.ShapeDtypeStruct(
            spec.shape, jnp.dtype(dtype),
            sharding=param_sharding(spec.axes, mesh, rules, spec.shape))

    step = jax.ShapeDtypeStruct((), jnp.int32)
    if opt_name == "adamw":
        return {
            "step": step,
            "m": jax.tree.map(like, specs, is_leaf=is_spec),
            "v": jax.tree.map(like, specs, is_leaf=is_spec),
        }
    # adafactor
    def fac(spec: ParamSpec):
        if len(spec.shape) >= 2 and spec.shape[-1] >= 128 \
                and spec.shape[-2] >= 128:
            vr = ParamSpec(spec.shape[:-1], spec.axes[:-1], dtype="float32")
            vc = ParamSpec((*spec.shape[:-2], spec.shape[-1]),
                           (*spec.axes[:-2], spec.axes[-1]),
                           dtype="float32")
            return {"vr": like(vr), "vc": like(vc)}
        return {"v": like(spec)}

    return {"step": step,
            "v": jax.tree.map(fac, specs, is_leaf=is_spec)}


def _scoped(update):
    """``update`` under the ``optimizer`` name scope: the clip and the
    moments' update are named so in the compiled step and its trace."""
    @wraps(update)
    def run(*args, **kwargs):
        with jax.named_scope("optimizer"):
            return update(*args, **kwargs)
    return run


# --------------------------------------------------------------------------
# AdamW
# --------------------------------------------------------------------------

def adamw_init(params, cfg: OptimizerConfig):
    """Zero moments placed like their parameters (sharded parameters get
    sharded moments, not copies on one device)."""
    dt = jnp.dtype(cfg.state_dtype)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(lambda p: jnp.zeros_like(p, dtype=dt), params),
        "v": jax.tree.map(lambda p: jnp.zeros_like(p, dtype=dt), params),
    }


@_scoped
def adamw_update(params, grads, state, cfg: OptimizerConfig):
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v_new = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
        mh = m_new / c1
        vh = v_new / c2
        delta = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * \
            p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, {"step": step, "m": new_m, "v": new_v}, \
        {"grad_norm": gnorm, "lr": lr}


# --------------------------------------------------------------------------
# Adafactor (factored second moment — O(n+m) state for an n×m matrix)
# --------------------------------------------------------------------------

def _factored(shape, min_size) -> bool:
    return len(shape) >= 2 and shape[-1] >= min_size and shape[-2] >= min_size


def adafactor_init(params, cfg: OptimizerConfig):
    dt = jnp.dtype(cfg.state_dtype)

    def one(p):
        if _factored(p.shape, cfg.min_dim_size_to_factor):
            return {"vr": jnp.zeros(p.shape[:-1], dt),
                    "vc": jnp.zeros((*p.shape[:-2], p.shape[-1]), dt)}
        return {"v": jnp.zeros_like(p, dtype=dt)}

    return {"step": jnp.zeros((), jnp.int32),
            "v": jax.tree.map(one, params,
                              is_leaf=lambda x: hasattr(x, "shape"))}


@_scoped
def adafactor_update(params, grads, state, cfg: OptimizerConfig):
    step = state["step"] + 1
    lr = lr_schedule(cfg, step)
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    decay = 1.0 - step.astype(jnp.float32) ** -0.8

    def upd(p, g, v):
        gf = g.astype(jnp.float32)
        g2 = gf * gf + 1e-30
        if "vr" in v:
            vr = decay * v["vr"].astype(jnp.float32) + \
                (1 - decay) * g2.mean(axis=-1)
            vc = decay * v["vc"].astype(jnp.float32) + \
                (1 - decay) * g2.mean(axis=-2)
            denom = (vr[..., None] * vc[..., None, :]
                     / jnp.maximum(vr.mean(axis=-1, keepdims=True)
                                   [..., None], 1e-30))
            update = gf / jnp.sqrt(denom + 1e-30)
            new_v = {"vr": vr.astype(v["vr"].dtype),
                     "vc": vc.astype(v["vc"].dtype)}
        else:
            vv = decay * v["v"].astype(jnp.float32) + (1 - decay) * g2
            update = gf / jnp.sqrt(vv + 1e-30)
            new_v = {"v": vv.astype(v["v"].dtype)}
        # update clipping (RMS <= 1) as in the Adafactor paper
        rms = jnp.sqrt(jnp.mean(update * update) + 1e-30)
        update = update / jnp.maximum(1.0, rms)
        p_new = (p.astype(jnp.float32)
                 - lr * update - lr * cfg.weight_decay * p.astype(jnp.float32))
        return p_new.astype(p.dtype), new_v

    is_v = lambda x: isinstance(x, dict) and ("v" in x or "vr" in x)
    out = jax.tree.map(upd, params, grads, state["v"], is_leaf=None)
    # jax.tree.map with mixed output: separate
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, {"step": step, "v": new_v}, \
        {"grad_norm": gnorm, "lr": lr}


def make_optimizer(cfg: OptimizerConfig):
    if cfg.name == "adamw":
        return adamw_init, adamw_update
    if cfg.name == "adafactor":
        return adafactor_init, adafactor_update
    raise ValueError(cfg.name)

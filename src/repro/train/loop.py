"""Training loop: jitted pjit train_step + fault-tolerant outer loop."""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..distributed.sharding import ShardingRules, act_sharding, param_sharding
from ..models.params import abstract_params, init_params
from ..models.transformer import forward, model_specs
from .checkpoint import CheckpointManager
from .data import DataConfig, ShardedLoader, SyntheticSource
from .fault_tolerance import StragglerDetector
from .optimizer import OptimizerConfig, make_optimizer


def quantize_int8(g: jax.Array):
    """Symmetric per-tensor int8 quantization (gradient compression)."""
    scale = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    *, microbatch: int = 0,
                    gradient_compression: bool = False):
    """Builds the pure train_step(params, opt_state, batch) function."""
    _, update_fn = make_optimizer(opt_cfg)

    def loss_fn(params, batch):
        loss, _ = forward(cfg, params, batch)
        return loss

    def compute_grads(params, batch):
        if microbatch and microbatch > 1:
            # gradient accumulation over microbatches via scan
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatch, b // microbatch, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                loss_sum, g_sum = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                g_sum = jax.tree.map(
                    lambda a, b_: a + b_.astype(a.dtype), g_sum, g)
                return (loss_sum + l, g_sum), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc, (jnp.zeros((), jnp.float32), g0), micro)
            inv = 1.0 / microbatch
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)
        return jax.value_and_grad(loss_fn)(params, batch)

    def train_step(params, opt_state, batch):
        loss, grads = compute_grads(params, batch)
        if gradient_compression:
            # int8 round-trip: models quantized gradient exchange (the
            # network simulator scales the all-reduce payload to match)
            def rt(g):
                q, s = quantize_int8(g)
                return dequantize_int8(q, s, g.dtype)
            grads = jax.tree.map(rt, grads)
        new_params, new_opt, metrics = update_fn(
            params, grads, opt_state, opt_cfg)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def train_step_exports(cfg: ModelConfig, seq: int, batch: int, mesh=None,
                       *, rules: ShardingRules | None = None,
                       opt_cfg: OptimizerConfig | None = None,
                       name: str = "bench"):
    """Jitted full train step + abstract (sharded) args for workload export.

    The export-side twin of :func:`train`: builds
    ``train_step(params, opt_state, batch)`` — loss + grad + optimizer
    update — and the zero-allocation ShapeDtypeStruct stand-ins for every
    argument (parameters, optimizer state via
    :func:`~repro.train.optimizer.opt_state_abstract`, and the token
    batch), all carrying mesh shardings when ``mesh`` is given.  This is
    the single source the fig6/fig9/fig11 benchmarks and the campaign
    engine's ``mode="train"`` spec export share, so a campaign prediction
    is bit-identical to a hand-rolled sweep over the same step.

    Returns ``(jitted_step, (params_abs, opt_abs, batch_abs))`` ready for
    :func:`repro.core.pipeline.export_workload`.
    """
    from ..configs.base import ShapeConfig
    from ..models.registry import input_specs
    from .optimizer import opt_state_abstract

    rules = rules or ShardingRules()
    opt_cfg = opt_cfg or OptimizerConfig()
    specs = model_specs(cfg)
    shape = ShapeConfig(name, seq, batch, "train")
    params_abs = abstract_params(specs, mesh, rules)
    batch_abs = input_specs(cfg, shape, mesh, rules)
    opt_abs = opt_state_abstract(specs, opt_cfg.name, mesh, rules)
    step = make_train_step(cfg, opt_cfg)
    jitted = jax.jit(step, donate_argnums=(0, 1))
    return jitted, (params_abs, opt_abs, batch_abs)


def optimizer_config(run: RunConfig) -> OptimizerConfig:
    """The optimizer :func:`train` builds for ``run``."""
    return OptimizerConfig(
        name=run.optimizer, learning_rate=run.learning_rate,
        warmup_steps=run.warmup_steps,
        weight_decay=run.weight_decay, grad_clip=run.grad_clip)


@dataclass
class TrainResult:
    steps: int
    final_loss: float
    losses: list
    step_times: list
    restarts: int = 0


def train(run: RunConfig, *, mesh=None, num_steps: int = 20,
          checkpoint_dir: str | None = None, checkpoint_every: int = 0,
          resume: bool = False, log_every: int = 10,
          rules: ShardingRules | None = None,
          inject_failure_at: int | None = None) -> TrainResult:
    """End-to-end training with checkpoint/restart and straggler tracking.

    ``inject_failure_at``: raise a simulated node failure at that step —
    the loop restores from the last committed checkpoint and continues
    (tested in tests/test_fault_tolerance.py)."""
    cfg = run.model
    opt_cfg = optimizer_config(run)
    init_fn, _ = make_optimizer(opt_cfg)
    rules = rules or ShardingRules()

    specs = model_specs(cfg)
    key = jax.random.PRNGKey(run.seed)
    params = init_params(specs, key)
    if mesh is not None:
        def place(subtree, spec):
            return jax.device_put(
                subtree, param_sharding(spec.axes, mesh, rules))
        params = jax.tree.map(place, params, specs,
                              is_leaf=lambda x: hasattr(x, "shape")
                              and not isinstance(x, dict))
    opt_state = init_fn(params, opt_cfg)
    if mesh is not None:
        # state not placed like a parameter (the step counter) is
        # replicated over the mesh, not left on the first device
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        opt_state = jax.tree.map(
            lambda x: x if isinstance(x.sharding, jax.sharding.NamedSharding)
            else jax.device_put(x, replicated), opt_state)

    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=run.shape.seq_len,
        global_batch=run.shape.global_batch, seed=run.seed,
        frontend=cfg.frontend, d_model=cfg.d_model)
    source = SyntheticSource(data_cfg)
    loader = ShardedLoader(source, mesh, rules) if mesh is not None \
        else source

    ckpt = CheckpointManager(checkpoint_dir) if checkpoint_dir else None
    start_step = 0
    restarts = 0
    if ckpt and resume:
        state, data_state, step = ckpt.restore_latest()
        if step >= 0:
            params, opt_state = state["params"], state["opt"]
            if data_state:
                source.restore(data_state)
            start_step = step + 1

    # on a mesh the step returns its state placed as it came in, so the
    # second step reuses the first step's executable
    state_shardings = None if mesh is None else (
        jax.tree.map(lambda x: x.sharding, (params, opt_state)) + (None,))
    step_fn = jax.jit(make_train_step(
        cfg, opt_cfg, microbatch=run.microbatch,
        gradient_compression=run.gradient_compression),
        donate_argnums=(0, 1), out_shardings=state_shardings)
    # the step traces under the mesh, so the models' activation sharding
    # constraints apply; without them the attention scan's buffers hold
    # the global batch on every device
    in_mesh = mesh if mesh is not None else contextlib.nullcontext()

    detector = StragglerDetector()
    losses: list[float] = []
    times: list[float] = []
    step = start_step
    failure_armed = inject_failure_at is not None
    while step < num_steps:
        try:
            batch = next(loader)
            t0 = time.perf_counter()
            if failure_armed and step == inject_failure_at:
                failure_armed = False
                raise RuntimeError("injected node failure")
            with in_mesh:
                params, opt_state, metrics = jax.block_until_ready(
                    step_fn(params, opt_state, batch))
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            detector.observe(step, dt)
            losses.append(loss)
            times.append(dt)
            if log_every and step % log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f} ms")
            if ckpt and checkpoint_every and step % checkpoint_every == 0 \
                    and step > 0:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          source.state())
            step += 1
        except RuntimeError as e:
            if "injected node failure" not in str(e) or ckpt is None:
                raise
            restarts += 1
            ckpt.wait()
            state, data_state, last = ckpt.restore_latest()
            if last < 0:
                raise RuntimeError("failure before first checkpoint") from e
            params, opt_state = state["params"], state["opt"]
            if data_state:
                source.restore(data_state)
            step = last + 1
            print(f"[fault-tolerance] restored step {last}, resuming")
    if ckpt:
        ckpt.wait()
    return TrainResult(steps=step - start_step,
                       final_loss=losses[-1] if losses else float("nan"),
                       losses=losses, step_times=times, restarts=restarts)

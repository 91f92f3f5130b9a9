"""Architecture registry: --arch <id> -> config + input specs.

``input_specs(cfg, shape, mesh)`` builds ShapeDtypeStruct stand-ins for
every model input (weak-type-correct, shardable, zero allocation) — the
dry-run contract."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig

ARCH_IDS = [
    "mamba2-370m", "deepseek-67b", "stablelm-12b", "qwen2.5-32b",
    "gemma2-27b", "zamba2-2.7b", "deepseek-v3-671b", "mixtral-8x22b",
    "hubert-xlarge", "qwen2-vl-7b", "zamba2-7b",
]
# paper-reproduction workload families (not part of the 40-cell matrix)
EXTRA_IDS = ["llama3-100m", "llama3-500m", "llama3-1b", "llama3-3b",
             "llama2-7b"]

_MODULE_FOR = {a: a.replace("-", "_").replace(".", "_") for a in
               ARCH_IDS + EXTRA_IDS}


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch]}")
    return mod.SMOKE


def shape_cells(cfg: ModelConfig) -> list[str]:
    """Which shape cells apply to this architecture (assignment rules)."""
    cells = ["train_4k", "prefill_32k"]
    if not cfg.is_encoder_only:
        cells.append("decode_32k")
        subquadratic = (cfg.family in ("ssm", "hybrid")
                        or (cfg.sliding_window > 0
                            and cfg.local_global_pattern == 0))
        if subquadratic:
            cells.append("long_500k")
    return cells


def skip_reason(cfg: ModelConfig, shape_name: str) -> str | None:
    if shape_name in shape_cells(cfg):
        return None
    if cfg.is_encoder_only and shape_name in ("decode_32k", "long_500k"):
        return "encoder-only: no autoregressive decode step"
    if shape_name == "long_500k":
        return "full-quadratic attention at 524288 tokens (see DESIGN.md)"
    return "not applicable"


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                rules=None, seq_sharded: bool = False) -> dict:
    """ShapeDtypeStruct stand-ins for one step's inputs."""
    from ..distributed.sharding import ShardingRules, act_sharding

    b, s = shape.global_batch, shape.seq_len
    r = rules or ShardingRules()
    if seq_sharded:
        from ..distributed.sharding import ACT_RULES_SEQ_SHARDED
        r = ShardingRules(r.param_rules, dict(ACT_RULES_SEQ_SHARDED))

    def sds(shp, dtype, axes):
        if mesh is None:
            return jax.ShapeDtypeStruct(shp, jnp.dtype(dtype))
        return jax.ShapeDtypeStruct(
            shp, jnp.dtype(dtype), sharding=act_sharding(axes, mesh, r, shp))

    batch: dict = {}
    if shape.kind == "decode":
        lead = (b, 1)
    else:
        lead = (b, s)
    if cfg.frontend == "stub":
        batch["embeds"] = sds((*lead, cfg.d_model), cfg.dtype,
                              ("batch", "seq", "embed"))
    else:
        batch["tokens"] = sds(lead, "int32", ("batch", "seq"))
    if shape.kind == "train":
        batch["targets"] = sds(lead, "int32", ("batch", "seq"))
    if cfg.mrope_sections and shape.kind != "decode":
        batch["mrope_positions"] = sds((3, *lead), "int32",
                                       ("norm", "batch", "seq"))
    return batch


def cache_specs_abstract(cfg: ModelConfig, shape: ShapeConfig, mesh=None,
                         rules=None, seq_sharded: bool = False) -> dict:
    from ..distributed.sharding import ShardingRules, act_sharding
    from .transformer import cache_shapes

    r = rules or ShardingRules()
    if seq_sharded:
        from ..distributed.sharding import ACT_RULES_SEQ_SHARDED
        r = ShardingRules(r.param_rules, dict(ACT_RULES_SEQ_SHARDED))
    out = {}
    for name, (shp, dtype, axes) in cache_shapes(
            cfg, shape.global_batch, shape.seq_len).items():
        if mesh is None:
            out[name] = jax.ShapeDtypeStruct(shp, jnp.dtype(dtype))
        else:
            out[name] = jax.ShapeDtypeStruct(
                shp, jnp.dtype(dtype),
                sharding=act_sharding(axes, mesh, r, shp))
    return out

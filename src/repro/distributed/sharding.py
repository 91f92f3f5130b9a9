"""Logical-axis sharding rules (MaxText-style) -> PartitionSpec/NamedSharding.

Every parameter and activation carries a tuple of *logical* axis names;
rule tables map logical names to mesh axes.  Swapping rule tables re-shards
the whole model without touching model code.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# default logical-axis -> mesh-axis rules. None = replicated.
# "data" shards FSDP/batch; "model" shards TP/EP dims; "pod" is the
# multi-pod data-parallel outer axis.
PARAM_RULES: dict[str, object] = {
    "layers": None,          # scan dimension, never sharded
    "embed": "data",         # ZeRO-3: params sharded over the data axis
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "qk_dim": None,
    "v_dim": None,
    "mlp": "model",
    "experts": "model",      # expert parallelism
    "mlp_expert": None,
    "conv": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "lora": None,
    "norm": None,
}

ACT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "qk_dim": None,
    "v_dim": None,
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "cache_seq": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
}

# long-context decode with batch=1: batch cannot use the data axis, so the
# KV-cache length / SSD chunk dimension takes it (sequence parallelism).
ACT_RULES_SEQ_SHARDED = dict(ACT_RULES, **{
    "batch": "pod",
    "cache_seq": "data",
    "seq": "data",
})


@dataclass
class ShardingRules:
    param_rules: dict = field(default_factory=lambda: dict(PARAM_RULES))
    act_rules: dict = field(default_factory=lambda: dict(ACT_RULES))

    def with_overrides(self, *, params: dict | None = None,
                       acts: dict | None = None) -> "ShardingRules":
        pr = dict(self.param_rules)
        ar = dict(self.act_rules)
        pr.update(params or {})
        ar.update(acts or {})
        return ShardingRules(pr, ar)


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def model_axis_size(mesh) -> int:
    """Chips along ``mesh``'s ``model`` axis: 1 without a mesh or axis."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def _resolve(axes: tuple[str, ...], rules: dict, mesh: Mesh,
             shape: tuple[int, ...] | None = None) -> P:
    """Map logical axes to mesh axes, dropping any mapping whose mesh-axis
    product does not evenly divide the dimension (jit argument shardings
    must tile exactly; non-dividing dims — 40 heads or kv=8 on a 16-way
    model axis, vocab 50280, batch 1 — stay replicated on that axis, and
    the waste shows up in the roofline's useful-flops ratio)."""
    parts = []
    used: set[str] = set()
    for i, name in enumerate(axes):
        rule = rules.get(name)
        if rule is None:
            parts.append(None)
            continue
        entries = rule if isinstance(rule, tuple) else (rule,)
        picked = [e for e in entries
                  if e in mesh.axis_names and e not in used]
        if shape is not None:
            dim = shape[i]
            while picked:
                prod = 1
                for e in picked:
                    prod *= _axis_size(mesh, e)
                if dim % prod == 0:
                    break
                picked.pop()          # drop trailing mesh axes until it fits
        used.update(picked)
        if not picked:
            parts.append(None)
        elif len(picked) == 1:
            parts.append(picked[0])
        else:
            parts.append(tuple(picked))
    # trim trailing Nones for cleanliness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def logical_to_spec(axes: tuple[str, ...], rules: dict, mesh: Mesh,
                    shape: tuple[int, ...] | None = None) -> P:
    return _resolve(axes, rules, mesh, shape)


def param_sharding(axes: tuple[str, ...], mesh: Mesh,
                   rules: ShardingRules | None = None,
                   shape: tuple[int, ...] | None = None) -> NamedSharding:
    r = (rules or ShardingRules()).param_rules
    return NamedSharding(mesh, _resolve(axes, r, mesh, shape))


def act_sharding(axes: tuple[str, ...], mesh: Mesh,
                 rules: ShardingRules | None = None,
                 shape: tuple[int, ...] | None = None) -> NamedSharding:
    r = (rules or ShardingRules()).act_rules
    return NamedSharding(mesh, _resolve(axes, r, mesh, shape))


def constrain(x, axes: tuple[str, ...], rules: ShardingRules | None = None):
    """with_sharding_constraint by logical axes; no-op outside a mesh."""
    mesh = get_abstract_mesh_or_none()
    if mesh is None:
        return x
    r = (rules or ShardingRules()).act_rules
    spec = _resolve(axes, r, mesh, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def get_abstract_mesh_or_none():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.get_concrete_mesh()
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        if m.empty:
            return None
        return m
    except Exception:
        return None

"""MLPs: gated (SwiGLU/GeGLU) dense blocks and sort-based MoE.

The MoE dispatch is sort-based rather than one-hot-einsum based: tokens are
ordered by expert id with argsort and moved with zero-FLOP gather/scatter,
then each expert runs a capacity-padded grouped GEMM.  This keeps the
compiled HLO's FLOP count ≈ the model's active FLOPs (one-hot dispatch
einsums would add a tokens × E·C × d_model matmul *per layer* that
dominates the real expert compute at E=256 — visible garbage in the
roofline's MODEL_FLOPS/HLO_FLOPS ratio).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import activation, dense
from .params import ParamSpec


def mlp_specs(cfg: ModelConfig, stacked: int = 0, d_ff: int | None = None,
              suffix: str = "") -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.dtype

    def p(shape, axes):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             init="scaled", dtype=dt)
        return ParamSpec(shape, axes, init="scaled", dtype=dt)

    return {
        f"w_gate{suffix}": p((d, f), ("embed", "mlp")),
        f"w_up{suffix}": p((d, f), ("embed", "mlp")),
        f"w_down{suffix}": p((f, d), ("mlp", "embed")),
    }


def adapter_specs(cfg: ModelConfig, stacked: int) -> dict:
    """Zamba2's per-invocation LoRA on the shared MLP's gate_up: ``lora_a``
    [d, r] then ``lora_b`` [r, 2·d_ff], gate half first."""
    d, r, f, dt = cfg.d_model, cfg.adapter_rank, cfg.d_ff, cfg.dtype
    return {
        "lora_a": ParamSpec((stacked, d, r), ("layers", "embed", "lora"),
                            init="scaled", dtype=dt),
        "lora_b": ParamSpec((stacked, r, 2 * f), ("layers", "lora", "mlp"),
                            init="scaled", dtype=dt),
    }


def mlp_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                suffix: str = "", adapter: dict | None = None) -> jax.Array:
    """Gated MLP; ``adapter`` (one invocation's ``lora_a``/``lora_b``, see
    :func:`adapter_specs`) adds its low-rank term to the gate and up
    projections before the activation."""
    act = activation(cfg.act)
    g = dense(x, p[f"w_gate{suffix}"])
    u = dense(x, p[f"w_up{suffix}"])
    if adapter is not None:
        ga, ua = jnp.split(dense(dense(x, adapter["lora_a"]),
                                 adapter["lora_b"]), 2, axis=-1)
        g, u = g + ga, u + ua
    return dense(act(g) * u, p[f"w_down{suffix}"])


# --------------------------------------------------------------------------
# Mixture of Experts
# --------------------------------------------------------------------------

def moe_specs(cfg: ModelConfig, stacked: int = 0) -> dict:
    mo = cfg.moe
    d, dt = cfg.d_model, cfg.dtype
    e, f = mo.num_experts, mo.d_ff_expert

    def p(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             dtype=dt, **kw)
        return ParamSpec(shape, axes, dtype=dt, **kw)

    specs = {
        "router": p((d, e), ("embed", "experts"), init="scaled"),
        "we_gate": p((e, d, f), ("experts", "embed", "mlp_expert"),
                     init="scaled"),
        "we_up": p((e, d, f), ("experts", "embed", "mlp_expert"),
                   init="scaled"),
        "we_down": p((e, f, d), ("experts", "mlp_expert", "embed"),
                     init="scaled"),
    }
    if mo.num_shared_experts:
        fs = mo.d_ff_shared or f
        specs.update(mlp_specs(
            cfg, stacked=stacked, d_ff=fs * mo.num_shared_experts,
            suffix="_shared"))
    return specs


def _capacity(tokens: int, mo) -> int:
    c = int(tokens * mo.top_k * mo.capacity_factor / mo.num_experts)
    return max(8, -(-c // 8) * 8)   # multiple of 8, >= 8


def _route(cfg: ModelConfig, p: dict, xf: jax.Array):
    """Top-k routing of tokens ``xf`` [T, d]: ``(top_w, top_e)``, each
    [T, k].  ``scoring_func`` "softmax" takes the experts' softmax;
    "sigmoid" (DeepSeek-V3) takes sigmoid scores and renormalises the top
    k to sum to one."""
    mo = cfg.moe
    if mo.scoring_func not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown MoE scoring_func {mo.scoring_func!r}")
    router_dt = jnp.float32 if mo.router_dtype == "float32" else xf.dtype
    logits = dense(xf.astype(router_dt), p["router"].astype(router_dt),
                   accum_f32=False)                       # [T, E]
    if mo.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(scores, mo.top_k)         # [T, k]
    if mo.scoring_func == "sigmoid":
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    return top_w, top_e


def moe_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Sort-based top-k MoE. x: [B, S, d] -> [B, S, d].

    1. route: logits -> top-k experts/weights per token;
    2. sort the (token, k) assignment list by expert id (argsort);
    3. scatter tokens into an [E, C, d] capacity buffer (zero-FLOP);
    4. grouped GEMMs over the expert axis (sharded: expert parallelism);
    5. gather back and combine with routing weights.

    Tokens beyond an expert's capacity are dropped (standard capacity-based
    MoE; the residual path carries them).
    """
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = mo.top_k
    e = mo.num_experts
    cap = _capacity(t, mo)
    xf = x.reshape(t, d)
    top_w, top_e = _route(cfg, p, xf)                      # [T, k]

    # ---- assignment list, sorted by expert ----
    flat_e = top_e.reshape(t * k)                          # expert of slot i
    flat_w = top_w.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)                            # stable
    se, sw, stok = flat_e[order], flat_w[order], flat_tok[order]
    # position within expert segment
    counts = jnp.bincount(se, length=e)                    # [E]
    starts = jnp.cumsum(counts) - counts                   # segment starts
    pos_in_e = jnp.arange(t * k) - starts[se]              # [T*k]
    keep = pos_in_e < cap
    slot = se * cap + jnp.where(keep, pos_in_e, 0)

    # ---- scatter tokens into [E*C, d] (zero-FLOP data movement) ----
    gathered = jnp.take(xf, stok, axis=0)                  # [T*k, d]
    gathered = jnp.where(keep[:, None], gathered, 0)
    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].add(gathered)                       # scatter-add
    buf = buf.reshape(e, cap, d)

    # ---- grouped expert GEMMs (expert axis sharded = EP) ----
    act = activation(cfg.act)
    g = jnp.einsum("ecd,edf->ecf", buf, p["we_gate"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.einsum("ecd,edf->ecf", buf, p["we_up"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", act(g) * u, p["we_down"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    y = y.reshape(e * cap, d)

    # ---- gather back, weighted combine ----
    out_tokens = jnp.take(y, slot, axis=0)                 # [T*k, d]
    out_tokens = out_tokens * (sw * keep)[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[stok].add(out_tokens)

    if mo.num_shared_experts:
        out = out + mlp_forward(cfg, p, xf, suffix="_shared")
    return out.reshape(b, s, d)


# --------------------------------------------------------------------------
# explicit expert parallelism (beyond-paper §Perf optimization)
# --------------------------------------------------------------------------

def moe_forward_ep(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Expert-parallel MoE via shard_map: the partitioner-free dispatch.

    Auto-partitioning the sort-based dispatch lets GSPMD bounce the
    token-major buffers between the data and model axes (measured: ~49 TB
    of all-reduce per chip per step on mixtral-8x22b x train_4k).  Here the
    data flow is explicit and communication-minimal:

      * routing (small GEMM + top-k) runs auto-sharded outside;
      * scheme A (E % |model| == 0, e.g. deepseek-v3 256e/16): each model
        shard builds capacity buffers ONLY for its own E/|model| experts
        from ONLY its own token shard — zero dispatch communication;
      * scheme B (E < |model|, |model| % ... via d_ff % |model| == 0, e.g.
        mixtral 8e/16): every shard processes all experts on its d_ff
        slice (expert-FFN tensor parallelism) — zero dispatch
        communication as well;
      * in both schemes one psum over "model" combines partial token
        outputs — the information-theoretic minimum for the combine.
    """
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from ..distributed.sharding import get_abstract_mesh_or_none

    mesh = get_abstract_mesh_or_none()
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = mo.top_k
    e = mo.num_experts
    f = mo.d_ff_expert
    if mesh is None or "model" not in mesh.axis_names:
        return moe_forward(cfg, p, x)
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if e % n_model == 0:
        scheme = "expert"
    elif f % n_model == 0:
        scheme = "ffn"
    else:
        return moe_forward(cfg, p, x)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in data_axes:
        n_data *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    if t % max(n_data, 1) != 0:
        return moe_forward(cfg, p, x)

    xf = x.reshape(t, d)
    top_w, top_e = _route(cfg, p, xf)
    top_w = top_w.astype(x.dtype)

    t_local = t // max(n_data, 1)
    e_local = e // n_model if scheme == "expert" else e
    cap = max(8, -(-int(t_local * k * mo.capacity_factor / e) // 8) * 8)
    dspec = data_axes if len(data_axes) > 1 else (
        data_axes[0] if data_axes else None)

    def local(xf_l, tw_l, te_l, wg_l, wu_l, wd_l):
        tl = xf_l.shape[0]
        flat_e = te_l.reshape(tl * k)
        flat_w = tw_l.reshape(tl * k)
        flat_tok = jnp.repeat(jnp.arange(tl), k)
        if scheme == "expert":
            midx = jax.lax.axis_index("model")
            lo = midx * e_local
            mine = (flat_e >= lo) & (flat_e < lo + e_local)
            le = jnp.where(mine, flat_e - lo, e_local)   # e_local = discard
        else:
            mine = jnp.ones_like(flat_e, dtype=bool)
            le = flat_e
        order = jnp.argsort(le)
        se, sw, stok = le[order], flat_w[order], flat_tok[order]
        counts = jnp.bincount(se, length=e_local + 1)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(tl * k) - starts[se]
        keep = (pos < cap) & (se < e_local)
        slot = jnp.where(se < e_local, se, 0) * cap + \
            jnp.where(keep, pos, 0)
        gathered = jnp.take(xf_l, stok, axis=0)
        gathered = jnp.where(keep[:, None], gathered, 0)
        buf = jnp.zeros((e_local * cap, d), x.dtype)
        buf = buf.at[slot].add(gathered).reshape(e_local, cap, d)
        act = activation(cfg.act)
        g = jnp.einsum("ecd,edf->ecf", buf, wg_l,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.einsum("ecd,edf->ecf", buf, wu_l,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        yy = jnp.einsum("ecf,efd->ecd", act(g) * u, wd_l,
                        preferred_element_type=jnp.float32).astype(x.dtype)
        yy = yy.reshape(e_local * cap, d)
        out_tok = jnp.take(yy, slot, axis=0)
        out_tok = out_tok * (sw * keep).astype(x.dtype)[:, None]
        partial = jnp.zeros((tl, d), x.dtype).at[stok].add(out_tok)
        return jax.lax.psum(partial, "model")

    if scheme == "expert":
        wg_spec = wu_spec = wd_spec = P("model", None, None)
    else:  # ffn: shard d_ff over the model axis
        wg_spec = wu_spec = P(None, None, "model")
        wd_spec = P(None, "model", None)

    out = shard_map(
        local, mesh=mesh,
        in_specs=(P(dspec, None), P(dspec, None), P(dspec, None),
                  wg_spec, wu_spec, wd_spec),
        out_specs=P(dspec, None),
        check_rep=False,
    )(xf, top_w, top_e, p["we_gate"], p["we_up"], p["we_down"])

    if mo.num_shared_experts:
        out = out + mlp_forward(cfg, p, xf, suffix="_shared")
    return out.reshape(b, s, d)


def ffn(cfg: ModelConfig, lp: dict, x: jax.Array) -> jax.Array:
    """A layer's feed-forward part on ``x`` [B, S, d]: the MoE layer
    ``lp["moe"]`` (through :func:`moe_forward_ep` where
    ``cfg.moe_ep_shardmap``) or the dense MLP ``lp["mlp"]``."""
    if cfg.moe is None:
        return mlp_forward(cfg, lp["mlp"], x)
    fwd = moe_forward_ep if cfg.moe_ep_shardmap else moe_forward
    return fwd(cfg, lp["moe"], x)

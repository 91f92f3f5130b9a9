"""Family-polymorphic model assembly: dense / MoE / SSM / hybrid / encoder /
VLM-backbone LMs with scan-over-layers, remat, and logical-axis sharding.

Public entry points (all pure functions of (cfg, params, batch)):

  model_specs(cfg)                 -> ParamSpec tree
  forward(cfg, params, batch)      -> (loss, logits)      [train/eval]
  prefill(cfg, params, batch)      -> (logits, cache)     [inference prefill]
  decode_step(cfg, params, cache, batch) -> (logits, cache)
  init_cache_specs(cfg, batch, max_len)  -> cache ParamSpec tree
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (attention_specs, gqa_decode, gqa_forward, mla_decode,
                        mla_forward, mla_specs)
from .common import (embedding_spec, norm_spec, rms_norm,
                     shard_act, softcap)
from .mlp import (mlp_forward, mlp_specs, moe_aux_loss, moe_forward,
                  moe_forward_ep, moe_specs)
from .params import ParamSpec
from .ssm import mamba2_forward, ssm_specs


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

def _layer_specs(cfg: ModelConfig, stacked: int) -> dict:
    """One transformer block's specs (attention or ssm + mlp/moe + norms)."""
    dt = cfg.dtype

    def n(shape, axes):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             init="ones", dtype=dt)
        return ParamSpec(shape, axes, init="ones", dtype=dt)

    if cfg.family == "ssm":
        return {"ssm": ssm_specs(cfg, stacked),
                "ln": n((cfg.d_model,), ("norm",))}
    specs: dict = {"ln1": n((cfg.d_model,), ("norm",)),
                   "ln2": n((cfg.d_model,), ("norm",))}
    if cfg.mla is not None:
        specs["attn"] = mla_specs(cfg, stacked)
    else:
        specs["attn"] = attention_specs(cfg, stacked)
    if cfg.moe is not None:
        specs["moe"] = moe_specs(cfg, stacked)
    else:
        specs["mlp"] = mlp_specs(cfg, stacked)
    return specs


def model_specs(cfg: ModelConfig) -> dict:
    dt = cfg.dtype
    specs: dict = {
        "embed": embedding_spec(cfg.vocab_size, cfg.d_model, dt),
        "final_norm": norm_spec(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"), init="scaled",
                                     dtype=dt)
    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_super = cfg.num_layers // every
        specs["layers"] = {
            "ssm": ssm_specs(cfg, stacked=n_super * every),
            "ln": ParamSpec((n_super * every, cfg.d_model),
                            ("layers", "norm"), init="ones", dtype=dt),
        }
        # one SHARED attention block (Zamba2): reused by every super-block
        specs["shared_attn"] = {
            "attn": attention_specs(cfg, stacked=0),
            "ln1": norm_spec(cfg.d_model, dt),
            "ln2": norm_spec(cfg.d_model, dt),
            "mlp": mlp_specs(cfg, stacked=0),
        }
    else:
        specs["layers"] = _layer_specs(cfg, stacked=cfg.num_layers)
    return specs


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _layer_window(cfg: ModelConfig, layer_idx: jax.Array):
    """Per-layer sliding window (dynamic scalar; 0 = full attention)."""
    if cfg.local_global_pattern > 0:
        # gemma2: even layers local (window), odd layers global
        is_local = (layer_idx % cfg.local_global_pattern) == 0
        return jnp.where(is_local, cfg.sliding_window, 0)
    return cfg.sliding_window


def attn_block(cfg: ModelConfig, lp: dict, h: jax.Array,
               positions: jax.Array, layer_idx, mrope_positions=None):
    x = rms_norm(h, lp["ln1"], cfg.rms_eps)
    if cfg.mla is not None:
        y = mla_forward(cfg, lp["attn"], x, positions)
    else:
        y = gqa_forward(cfg, lp["attn"], x, positions,
                        layer_window=_layer_window(cfg, layer_idx),
                        mrope_positions=mrope_positions)
    h = h + shard_act(y, ("batch", "seq", "embed"))
    x = rms_norm(h, lp["ln2"], cfg.rms_eps)
    if cfg.moe is not None:
        fwd = moe_forward_ep if cfg.moe_ep_shardmap else moe_forward
        y = fwd(cfg, lp["moe"], x)
    else:
        y = mlp_forward(cfg, lp["mlp"], x)
    return h + shard_act(y, ("batch", "seq", "embed"))


def ssm_block(cfg: ModelConfig, lp: dict, h: jax.Array):
    x = rms_norm(h, lp["ln"], cfg.rms_eps)
    y, _, _ = mamba2_forward(cfg, lp["ssm"], x)
    return h + shard_act(y, ("batch", "seq", "embed"))


# --------------------------------------------------------------------------
# embedding / head
# --------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    if cfg.frontend == "stub":
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        with jax.named_scope("vocab"):
            h = jnp.take(params["embed"], batch["tokens"], axis=0)
            if cfg.tie_embeddings:
                h = h * math.sqrt(cfg.d_model)
    return shard_act(h, ("batch", "seq", "embed"))


def _logits(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    table = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", h, table,
                        preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_logit_softcap)
    return shard_act(logits, ("batch", "seq", "vocab"))


def _positions(batch: dict) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    lead = batch["tokens"].shape if "tokens" in batch \
        else batch["embeds"].shape[:2]
    b, s = lead[0], lead[1]
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))


# --------------------------------------------------------------------------
# forward (train / eval)
# --------------------------------------------------------------------------

def _scan_layers(cfg: ModelConfig, params: dict, h: jax.Array,
                 positions: jax.Array, mrope_positions=None) -> jax.Array:
    lp = params["layers"]

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_super = cfg.num_layers // every
        stacked = jax.tree.map(
            lambda x: x.reshape(n_super, every, *x.shape[1:]), lp)
        shared = params["shared_attn"]

        def super_block(carry, xs):
            hh = carry

            def inner(c, xp):
                x = rms_norm(c, xp["ln"], cfg.rms_eps)
                y, _, _ = mamba2_forward(cfg, xp["ssm"], x)
                return c + y, None

            hh, _ = jax.lax.scan(inner, hh, xs)
            hh = attn_block(cfg, shared, hh, positions,
                            jnp.int32(1))          # shared global attention
            return hh, None

        body = super_block
        if cfg.remat == "full":
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, stacked)
        return h

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def block(carry, xs):
        layer_params, layer_idx = xs
        if cfg.family == "ssm":
            out = ssm_block(cfg, layer_params, carry)
        else:
            out = attn_block(cfg, layer_params, carry, positions,
                             layer_idx, mrope_positions)
        return out, None

    if not cfg.scan_layers:
        # python-unrolled stack (profiling-friendly: per-layer regions in
        # the raw export, separable at optimization_barrier boundaries)
        for i in range(cfg.num_layers):
            lp_i = jax.tree.map(lambda x: x[i], lp)
            h, _ = block(h, (lp_i, jnp.int32(i)))
            if cfg.layer_barriers:
                h = jax.lax.optimization_barrier(h)
        return h

    body = block
    if cfg.remat == "full":
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, (lp, idxs))
    return h


def forward(cfg: ModelConfig, params: dict, batch: dict):
    """Returns (loss, logits). batch: tokens/embeds, targets, [positions].

    With ``loss_vocab_chunk`` > 0 the CE loss streams over vocab chunks and
    full logits are never materialized (logits return value is None)."""
    h = _embed(cfg, params, batch)
    positions = _positions(batch)
    mrope = batch.get("mrope_positions")
    h = _scan_layers(cfg, params, h, positions, mrope)
    with jax.named_scope("vocab"):
        if cfg.loss_vocab_chunk > 0:
            loss = chunked_cross_entropy(cfg, params, h, batch["targets"],
                                         cfg.loss_vocab_chunk)
            return loss, None
        logits = _logits(cfg, params, h)
        loss = cross_entropy(logits, batch["targets"])
    if cfg.moe is not None:
        # router aux loss on the mean hidden state (cheap proxy; per-layer
        # aux would need scan ys — tracked as beyond-paper TODO)
        loss = loss + 0.0
    return loss, logits


def chunked_cross_entropy(cfg: ModelConfig, params: dict, h: jax.Array,
                          targets: jax.Array, chunk: int) -> jax.Array:
    """Streaming softmax CE: scan over vocab chunks, tracking the running
    max/sum-exp and the gold-token logit.  Peak memory drops from
    O(B·S·V) f32 to O(B·S·chunk); flops are unchanged."""
    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    table = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    d, v = table.shape
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    tc = table.reshape(d, n_chunks, chunk).transpose(1, 0, 2)  # [C, d, ck]
    b, s, _ = h.shape
    tgt = targets.astype(jnp.int32)

    def body(carry, inp):
        m, l, gold = carry
        ci, tbl = inp
        logits = jnp.einsum("bsd,dv->bsv", h, tbl,
                            preferred_element_type=jnp.float32)
        logits = softcap(logits, cfg.final_logit_softcap)
        base = ci * chunk
        valid = (base + jnp.arange(chunk)) < v
        logits = jnp.where(valid[None, None], logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        l_new = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[..., None]).sum(axis=-1)
        in_chunk = (tgt >= base) & (tgt < base + chunk)
        idx = jnp.clip(tgt - base, 0, chunk - 1)
        g = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_chunk, g, gold)
        return (m_new, l_new, gold), None

    m0 = jnp.full((b, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s), jnp.float32)
    g0 = jnp.zeros((b, s), jnp.float32)
    (m, l, gold), _ = jax.lax.scan(
        body, (m0, l0, g0), (jnp.arange(n_chunks), tc))
    lse = m + jnp.log(l)
    return jnp.mean(lse - gold)


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Stable softmax CE, mean over tokens. logits: [B,S,V] f32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# --------------------------------------------------------------------------
# inference: prefill + decode
# --------------------------------------------------------------------------

def cache_shapes(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    """Abstract KV/SSM cache description for one device-visible batch."""
    hd = cfg.resolved_head_dim if cfg.num_heads else 0
    shapes: dict = {"index": ((), "int32", ())}
    if cfg.family == "ssm":
        s = cfg.ssm
        nh, di = s.n_heads(cfg.d_model), s.d_inner(cfg.d_model)
        conv_ch = di + 2 * s.n_groups * s.d_state
        shapes["ssm_state"] = (
            (cfg.num_layers, batch_size, nh, s.head_dim, s.d_state),
            "float32", ("layers", "batch", "ssm_heads", "qk_dim", "ssm_state"))
        shapes["conv_state"] = (
            (cfg.num_layers, batch_size, s.d_conv - 1, conv_ch),
            cfg.dtype, ("layers", "batch", "conv", "ssm_inner"))
        return shapes
    if cfg.family == "hybrid":
        s = cfg.ssm
        nh, di = s.n_heads(cfg.d_model), s.d_inner(cfg.d_model)
        conv_ch = di + 2 * s.n_groups * s.d_state
        n_super = cfg.num_layers // cfg.hybrid_attn_every
        shapes["ssm_state"] = (
            (cfg.num_layers, batch_size, nh, s.head_dim, s.d_state),
            "float32", ("layers", "batch", "ssm_heads", "qk_dim", "ssm_state"))
        shapes["conv_state"] = (
            (cfg.num_layers, batch_size, s.d_conv - 1, conv_ch),
            cfg.dtype, ("layers", "batch", "conv", "ssm_inner"))
        shapes["k"] = ((n_super, batch_size, max_len, cfg.num_kv_heads, hd),
                       cfg.dtype,
                       ("layers", "batch", "cache_seq", "kv_heads", "qk_dim"))
        shapes["v"] = ((n_super, batch_size, max_len, cfg.num_kv_heads, hd),
                       cfg.dtype,
                       ("layers", "batch", "cache_seq", "kv_heads", "v_dim"))
        return shapes
    if cfg.mla is not None:
        m = cfg.mla
        shapes["ckv"] = (
            (cfg.num_layers, batch_size, max_len,
             m.kv_lora_rank + m.qk_rope_head_dim),
            cfg.dtype, ("layers", "batch", "cache_seq", "lora"))
        return shapes
    eff_len = min(max_len, cfg.sliding_window) if cfg.sliding_window \
        else max_len
    shapes["k"] = ((cfg.num_layers, batch_size, eff_len,
                    cfg.num_kv_heads, hd), cfg.dtype,
                   ("layers", "batch", "cache_seq", "kv_heads", "qk_dim"))
    shapes["v"] = ((cfg.num_layers, batch_size, eff_len,
                    cfg.num_kv_heads, hd), cfg.dtype,
                   ("layers", "batch", "cache_seq", "kv_heads", "v_dim"))
    return shapes


def init_cache_specs(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    return {name: ParamSpec(shape, axes, init="zeros", dtype=dtype)
            for name, (shape, dtype, axes)
            in cache_shapes(cfg, batch_size, max_len).items()}


def decode_step(cfg: ModelConfig, params: dict, cache: dict, batch: dict):
    """One autoregressive step. batch: tokens [B,1] (or embeds [B,1,d]).

    The cache index is carried inside ``cache["index"]``; caches are stacked
    on the layer axis and updated through the layer scan.
    """
    h = _embed(cfg, params, batch)
    index = cache["index"]
    b = h.shape[0]

    if cfg.family == "ssm":
        def block(carry, xs):
            hh = carry
            lp, sstate, cstate = xs
            x = rms_norm(hh, lp["ln"], cfg.rms_eps)
            y, new_s, new_c = mamba2_forward(
                cfg, lp["ssm"], x, ssm_state=sstate, conv_state=cstate,
                decode=True)
            return hh + y, (new_s, new_c)

        h, (new_ssm, new_conv) = jax.lax.scan(
            block, h,
            ({"ssm": params["layers"]["ssm"], "ln": params["layers"]["ln"]},
             cache["ssm_state"], cache["conv_state"]))
        new_cache = dict(cache, ssm_state=new_ssm, conv_state=new_conv,
                         index=index + 1)
    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_super = cfg.num_layers // every
        stacked = jax.tree.map(
            lambda x: x.reshape(n_super, every, *x.shape[1:]),
            params["layers"])
        sstates = jax.tree.map(
            lambda x: x.reshape(n_super, every, *x.shape[1:]),
            cache["ssm_state"])
        cstates = jax.tree.map(
            lambda x: x.reshape(n_super, every, *x.shape[1:]),
            cache["conv_state"])
        shared = params["shared_attn"]

        def super_block(carry, xs):
            hh = carry
            sp, sst, cst, ck, cv = xs

            def inner(c, xp):
                lp, s1, c1 = xp
                x = rms_norm(c, lp["ln"], cfg.rms_eps)
                y, ns, nc = mamba2_forward(cfg, lp["ssm"], x, ssm_state=s1,
                                           conv_state=c1, decode=True)
                return c + y, (ns, nc)

            hh, (ns, nc) = jax.lax.scan(inner, hh, (sp, sst, cst))
            x = rms_norm(hh, shared["ln1"], cfg.rms_eps)
            y, nk, nv = gqa_decode(cfg, shared["attn"], x, ck, cv, index)
            hh = hh + y
            x = rms_norm(hh, shared["ln2"], cfg.rms_eps)
            hh = hh + mlp_forward(cfg, shared["mlp"], x)
            return hh, (ns, nc, nk, nv)

        h, (ns, nc, nk, nv) = jax.lax.scan(
            super_block, h, (stacked, sstates, cstates,
                             cache["k"], cache["v"]))
        new_cache = dict(
            cache,
            ssm_state=ns.reshape(cfg.num_layers, *ns.shape[2:]),
            conv_state=nc.reshape(cfg.num_layers, *nc.shape[2:]),
            k=nk, v=nv, index=index + 1)
    elif cfg.mla is not None:
        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)

        def block(carry, xs):
            hh = carry
            lp, ckv, _ = xs
            x = rms_norm(hh, lp["ln1"], cfg.rms_eps)
            y, new_ckv = mla_decode(cfg, lp["attn"], x, ckv, index)
            hh = hh + y
            x = rms_norm(hh, lp["ln2"], cfg.rms_eps)
            if cfg.moe is not None:
                fwd = moe_forward_ep if cfg.moe_ep_shardmap else moe_forward
                hh = hh + fwd(cfg, lp["moe"], x)
            else:
                hh = hh + mlp_forward(cfg, lp["mlp"], x)
            return hh, new_ckv

        h, new_ckv = jax.lax.scan(
            block, h, (params["layers"], cache["ckv"], idxs))
        new_cache = dict(cache, ckv=new_ckv, index=index + 1)
    else:
        idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)

        def block(carry, xs):
            hh = carry
            lp, ck, cv, layer_idx = xs
            x = rms_norm(hh, lp["ln1"], cfg.rms_eps)
            window = _layer_window(cfg, layer_idx)
            y, nk, nv = gqa_decode(cfg, lp["attn"], x, ck, cv, index,
                                   layer_window=window)
            hh = hh + y
            x = rms_norm(hh, lp["ln2"], cfg.rms_eps)
            if cfg.moe is not None:
                fwd = moe_forward_ep if cfg.moe_ep_shardmap else moe_forward
                hh = hh + fwd(cfg, lp["moe"], x)
            else:
                hh = hh + mlp_forward(cfg, lp["mlp"], x)
            return hh, (nk, nv)

        h, (nk, nv) = jax.lax.scan(
            block, h, (params["layers"], cache["k"], cache["v"], idxs))
        new_cache = dict(cache, k=nk, v=nv, index=index + 1)

    logits = _logits(cfg, params, h)
    return logits[:, -1], new_cache


def prefill(cfg: ModelConfig, params: dict, batch: dict):
    """Process a full prompt; returns last-token logits.

    (Cache materialization from prefill is family-specific; for workload
    export purposes the compute graph of the forward pass is the prefill
    cost — the cache write adds only bandwidth, modeled in the estimators.)
    """
    h = _embed(cfg, params, batch)
    positions = _positions(batch)
    h = _scan_layers(cfg, params, h, positions,
                     batch.get("mrope_positions"))
    logits = _logits(cfg, params, h)
    return logits[:, -1]

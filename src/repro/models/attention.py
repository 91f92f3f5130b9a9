"""Attention: GQA (with SWA / local-global / softcap / bias) and MLA.

Four implementations behind one interface:
  * dense   — materialized [Sq, Skv] scores (small shapes, oracle)
  * chunked — online-softmax scan over KV blocks (pure JAX flash attention;
              memory O(Sq · block) — required for 32k prefill)
  * blocked — causal self-attention in query blocks, each over the keys up
              to its end and recomputed in the backward pass (training at
              long rows: one block's scores live at a time)
  * pallas  — TPU kernel (repro.kernels.flash_attention), same math

Decode (Sq == 1) always uses the dense path over the KV cache; with a
sequence-sharded cache, XLA turns the softmax reductions into the
all-reduce pair of flash-decoding.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import dense, rms_norm, softcap
from .params import ParamSpec
from .rope import apply_mrope, apply_rope

NEG_INF = -2.0e38


# --------------------------------------------------------------------------
# parameter specs
# --------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, stacked: int = 0,
                    d_in: int = 0) -> dict:
    """GQA projection specs; ``stacked``>0 prepends a layer axis (for scan).
    ``d_in`` is the input width where it is not ``d_model`` (Zamba2's
    shared attention reads 2·d_model and writes d_model)."""
    d, h, kv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    d_in = d_in or d
    dt = cfg.dtype

    def p(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             dtype=dt, **kw)
        return ParamSpec(shape, axes, dtype=dt, **kw)

    specs = {
        "wq": p((d_in, h, hd), ("embed", "heads", "qk_dim"), init="scaled"),
        "wk": p((d_in, kv, hd), ("embed", "kv_heads", "qk_dim"),
                init="scaled"),
        "wv": p((d_in, kv, hd), ("embed", "kv_heads", "v_dim"),
                init="scaled"),
        "wo": p((h, hd, d), ("heads", "v_dim", "embed"), init="scaled"),
    }
    if cfg.qkv_bias:
        specs["bq"] = p((h, hd), ("heads", "qk_dim"), init="zeros")
        specs["bk"] = p((kv, hd), ("kv_heads", "qk_dim"), init="zeros")
        specs["bv"] = p((kv, hd), ("kv_heads", "v_dim"), init="zeros")
    return specs


def mla_specs(cfg: ModelConfig, stacked: int = 0) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = cfg.dtype
    qk = m.qk_nope_head_dim

    def p(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked, *shape), ("layers", *axes),
                             dtype=dt, **kw)
        return ParamSpec(shape, axes, dtype=dt, **kw)

    return {
        "wdq": p((d, m.q_lora_rank), ("embed", "lora"), init="scaled"),
        "q_norm": p((m.q_lora_rank,), ("norm",), init="ones"),
        "wuq": p((m.q_lora_rank, h, qk + m.qk_rope_head_dim),
                 ("lora", "heads", "qk_dim"), init="scaled"),
        "wdkv": p((d, m.kv_lora_rank + m.qk_rope_head_dim),
                  ("embed", "lora"), init="scaled"),
        "kv_norm": p((m.kv_lora_rank,), ("norm",), init="ones"),
        "wuk": p((m.kv_lora_rank, h, qk), ("lora", "heads", "qk_dim"),
                 init="scaled"),
        "wuv": p((m.kv_lora_rank, h, m.v_head_dim),
                 ("lora", "heads", "v_dim"), init="scaled"),
        "wo": p((h, m.v_head_dim, d), ("heads", "v_dim", "embed"),
                init="scaled"),
    }


# --------------------------------------------------------------------------
# masking
# --------------------------------------------------------------------------

def _apply_window(mask: jax.Array, diff: jax.Array, window) -> jax.Array:
    """Sliding-window constraint; ``window`` may be a static int or a traced
    scalar (gemma2 alternates local/global inside a layer scan — the window
    is data there, 0 meaning full attention)."""
    if isinstance(window, int):
        if window <= 0:
            return mask
        return mask & (diff < window)
    w = jnp.asarray(window)
    return mask & ((diff < w) | (w <= 0))


def _block_mask(q_idx: jax.Array, k_idx: jax.Array, *, causal: bool,
                window) -> jax.Array:
    """[Sq, Skv] boolean mask from absolute indices."""
    diff = q_idx[:, None] - k_idx[None, :]
    mask = jnp.ones(diff.shape, dtype=bool)
    if causal:
        mask &= diff >= 0
    return _apply_window(mask, diff, window)


# --------------------------------------------------------------------------
# core attention (dense / chunked)
# --------------------------------------------------------------------------

class AttnArgs(NamedTuple):
    causal: bool = True
    window: int = 0              # >0: sliding window
    logit_cap: float = 0.0
    q_offset: int = 0            # absolute position of q[0] (decode/prefill)
    score_dim: float = 0         # >0: scores over sqrt(score_dim), not
    #                              sqrt(head_dim)


def score_dim(cfg: ModelConfig) -> float:
    """Zamba2 scales its shared attention's scores by (head_dim / 2)^-0.5
    (its heads read the concatenation of two streams); 0 elsewhere, for
    the usual head_dim^-0.5."""
    return cfg.resolved_head_dim / 2 if cfg.family == "hybrid" else 0


def _dense_attention(q, k, v, args: AttnArgs) -> jax.Array:
    """q: [B, Hq, Sq, D], k/v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D]."""
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, dh)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(
                            args.score_dim or dh)
    scores = softcap(scores, args.logit_cap)
    q_idx = jnp.arange(sq) + args.q_offset
    k_idx = jnp.arange(skv)
    mask = _block_mask(q_idx, k_idx, causal=args.causal, window=args.window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def _chunked_attention(q, k, v, args: AttnArgs, chunk: int) -> jax.Array:
    """Online-softmax scan over KV chunks — the flash-attention recurrence."""
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = hq // hkv
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, hkv, n_chunks, chunk, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, n_chunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    qg = (q.reshape(b, hkv, group, sq, dh).astype(jnp.float32)
          / math.sqrt(args.score_dim or dh))
    q_idx = jnp.arange(sq) + args.q_offset

    def body(carry, inputs):
        m, l, acc = carry
        ci, (kb, vb) = inputs
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kb.astype(jnp.float32))
        scores = softcap(scores, args.logit_cap)
        k_idx = ci * chunk + jnp.arange(chunk)
        valid = k_idx < skv
        diff = q_idx[:, None] - k_idx[None, :]
        mask = jnp.broadcast_to(valid[None, :], diff.shape)
        if args.causal:
            mask = mask & (diff >= 0)
        mask = _apply_window(mask, diff, args.window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, group, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(n_chunks), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, hq, sq, dv).astype(q.dtype)


def _blocked_attention(q, k, v, args: AttnArgs, block: int) -> jax.Array:
    """Causal self-attention in query blocks of ``block``: block i attends
    the keys up to its own end (no work above the diagonal's blocks) and is
    recomputed in the backward pass, so one block's scores are live."""
    sq = q.shape[2]
    if not args.causal or args.q_offset or k.shape[2] != sq:
        raise ValueError("blocked attention is causal self-attention")
    outs = []
    for lo in range(0, sq, block):
        hi = min(lo + block, sq)
        one = jax.checkpoint(partial(_dense_attention,
                                     args=args._replace(q_offset=lo)))
        outs.append(one(q[:, :, lo:hi], k[:, :, :hi], v[:, :, :hi]))
    return jnp.concatenate(outs, axis=2)


def multihead_attention(q, k, v, args: AttnArgs, impl: str = "chunked",
                        chunk: int = 1024) -> jax.Array:
    if impl == "pallas":
        from ..kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=args.causal,
                               window=args.window, logit_cap=args.logit_cap,
                               q_offset=args.q_offset)
    if impl == "dense" or q.shape[2] == 1:
        return _dense_attention(q, k, v, args)
    if q.shape[2] <= chunk and k.shape[2] <= chunk:
        return _dense_attention(q, k, v, args)
    if impl == "blocked":
        return _blocked_attention(q, k, v, args, chunk)
    return _chunked_attention(q, k, v, args, chunk)


# --------------------------------------------------------------------------
# GQA layer (projections + rope + attention)
# --------------------------------------------------------------------------

def gqa_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array, *, layer_window: int = 0,
                mrope_positions: jax.Array | None = None,
                return_kv: bool = False):
    """Full-sequence GQA for train/prefill. x: [B, S, d].  With
    ``return_kv`` also the keys (after rope) and values, [B, S, kv, hd],
    as a decode cache holds them."""
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.mrope_sections and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.causal or cfg.family == "audio":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    args = AttnArgs(causal=cfg.causal, window=layer_window,
                    logit_cap=cfg.attn_logit_softcap,
                    score_dim=score_dim(cfg))
    out = multihead_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), args, impl=cfg.attn_impl,
        chunk=cfg.attn_chunk)
    out = out.transpose(0, 2, 1, 3)                      # [B, S, H, hd]
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return (y, k, v) if return_kv else y


def gqa_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache_k: jax.Array,
               cache_v: jax.Array, cache_index: jax.Array, *,
               layer_window: int = 0) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: [B, 1, d]; cache_k/v: [B, S_max, kv, hd].

    Returns (attn_out [B,1,d], new_cache_k, new_cache_v).  With SWA the
    cache is a rolling buffer of size ``window``; absolute positions are
    recovered from ``cache_index``.
    """
    b = x.shape[0]
    s_max = cache_k.shape[1]
    pos = jnp.full((b, 1), cache_index, dtype=jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(pos, (3, b, 1))
        q = apply_mrope(q, pos3, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, pos3, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    # ring buffer iff the window is a static int and the cache was sized to
    # it (pure-SWA archs, e.g. Mixtral).  Dynamic (traced) windows — gemma2's
    # local/global alternation — use a full-length cache with masking.
    ring = isinstance(layer_window, int) and 0 < layer_window >= s_max
    slot = jnp.mod(cache_index, s_max) if ring else cache_index
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    # scores over the cache; mask invalid (future / unwritten) slots
    kt = cache_k.transpose(0, 2, 1, 3)                   # [B, kv, S, hd]
    vt = cache_v.transpose(0, 2, 1, 3)
    qt = q.transpose(0, 2, 1, 3)                         # [B, H, 1, hd]
    hq, hkv = qt.shape[1], kt.shape[1]
    group = hq // hkv
    qg = qt.reshape(b, hkv, group, 1, -1).astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt.astype(jnp.float32))
    scores = scores / math.sqrt(score_dim(cfg) or qt.shape[-1])
    scores = softcap(scores, cfg.attn_logit_softcap)
    slot_idx = jnp.arange(s_max)
    if ring:
        valid = slot_idx < jnp.minimum(cache_index + 1, s_max)
    else:
        valid = slot_idx <= cache_index
        if not (isinstance(layer_window, int) and layer_window == 0):
            w = jnp.asarray(layer_window)
            in_window = (cache_index - slot_idx < w) | (w <= 0)
            valid = valid & in_window
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vt.astype(jnp.float32))
    out = out.reshape(b, hq, 1, -1).transpose(0, 2, 1, 3).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, cache_k, cache_v


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# --------------------------------------------------------------------------

def mla_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                positions: jax.Array) -> jax.Array:
    """Materialized MLA for train/prefill."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    cq = rms_norm(dense(x, p["wdq"]), p["q_norm"], cfg.rms_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = dense(x, p["wdkv"])                       # [B,S,rank+rope]
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.rms_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wuk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wuv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_head_dim))],
        axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    args = AttnArgs(causal=True, logit_cap=0.0)
    out = multihead_attention(
        qf.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), args, impl=cfg.attn_impl,
        chunk=cfg.attn_chunk)
    out = out.transpose(0, 2, 1, 3)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache_ckv: jax.Array,
               cache_index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Absorbed-form MLA decode against the *compressed* KV cache.

    cache_ckv: [B, S_max, kv_lora_rank + qk_rope_head_dim] — the DeepSeek
    inference trick: W_uk is absorbed into the query, W_uv into the output,
    so per-step compute and cache stay in the compressed space.
    """
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    s_max = cache_ckv.shape[1]
    pos = jnp.full((b, 1), cache_index, dtype=jnp.int32)

    cq = rms_norm(dense(x, p["wdq"]), p["q_norm"], cfg.rms_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    # absorb W_uk: q_c[b,1,h,rank] = q_nope . W_uk^T
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])

    ckv_full = dense(x, p["wdkv"])
    ckv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.rms_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], pos,
                        cfg.rope_theta)[:, :, 0, :]
    entry = jnp.concatenate([ckv, k_rope], axis=-1)
    cache_ckv = jax.lax.dynamic_update_slice(
        cache_ckv, entry, (0, cache_index, 0))

    c_k = cache_ckv[:, :, :m.kv_lora_rank].astype(jnp.float32)
    r_k = cache_ckv[:, :, m.kv_lora_rank:].astype(jnp.float32)
    scores = (jnp.einsum("bshr,btr->bhst", q_c.astype(jnp.float32), c_k)
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32), r_k))
    scores = scores / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    valid = jnp.arange(s_max) <= cache_index
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", probs, c_k)        # compressed ctx
    out = jnp.einsum("bshr,rhk->bshk", ctx.astype(x.dtype), p["wuv"])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, cache_ckv

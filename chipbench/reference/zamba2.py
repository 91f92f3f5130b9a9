"""Plain float32 reference of the Zamba2 language model (arXiv:2411.15242),
as the configuration files under ``configs/`` state it and as transformers'
``modeling_zamba2`` writes its layers.

Every layer is a Mamba-2 layer, ``h <- h + Mamba_i(norm(h + T))``, where
``T`` is zero except at the hybrid layers.  At the k-th hybrid layer,
shared block ``b = k mod num_mem_blocks`` reads ``concat(h, e)``, with
``e`` the unscaled embedding, and gives
``T = linear_k(MLP_b(norm(Attn_b(norm(concat(h, e)))), adapter_k))``:
attention of width 2·d_model with rope over the whole head and scores
scaled by ``(head_dim / 2)^-0.5``, and a gated exact-GELU MLP whose
gate_up gets the invocation's low-rank adapter (gate half first).  There
is no residual inside the shared block.  The Mamba-2 layer's gated norm
normalises each group of ``d_inner / n_groups`` channels on its own.

Straightforward ``jax.numpy``, one layer after another; the state-space
map is :func:`chipbench.reference.mamba2.ssd`, the paper's own listing.
Attention is computed exactly over all keys for one block of queries at a
time, so that a long row's scores fit.  Every matrix product runs through
``dot`` at the ``highest`` precision.  Nothing here imports the system
under test.  Parameters are laid out as the program holds them
(:func:`layout`); weights come from :func:`init` and a seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .mamba2 import highest_dot, nest, rms_norm, ssd

F32 = jnp.float32
#: positions per block of the MLP and the loss (attention: half as many)
BLOCK = 1024


# ------------------------------------------------------------------ layout

def _dims(m: dict):
    s = m["ssm"]
    d = m["d_model"]
    di = s["expand"] * d
    nh = di // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    return d, di, nh, gn, di + 2 * gn


def layout(m: dict) -> dict:
    """``path -> (shape, dtype, init)`` for every parameter leaf.

    ``m`` is the ``model`` object of a configuration file.  Every leaf has
    a leading stack axis but the embedding and the final norm: the layers
    (``num_layers``), the shared blocks (``num_mem_blocks``) and the
    invocations (one per hybrid layer).  ``init`` is normal (std 0.02),
    fan_in or fan_in2 (std 1/sqrt(fan-in), the first one or two dimensions
    after the stack axis), zeros, ones, a_log and dt_bias (the Mamba-2
    initialisations of A and of the step size)."""
    if m["family"] != "hybrid":
        raise ValueError(f"no reference of a {m['family']!r} model here")
    d, di, nh, gn, conv_ch = _dims(m)
    s = m["ssm"]
    dt = m["dtype"]
    L = (m["num_layers"],)
    M = (m["num_mem_blocks"],)
    K = (len(m["hybrid_layer_ids"]),)
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f, r = m["d_ff"], m["adapter_rank"]
    out = {
        "embed": ((m["vocab_size"], d), dt, "normal"),
        "final_norm": ((d,), dt, "ones"),
        "layers/ln": (L + (d,), dt, "ones"),
        "layers/ssm/in_proj": (L + (d, 2 * di + 2 * gn + nh), dt, "fan_in"),
        "layers/ssm/conv_w": (L + (s["d_conv"], conv_ch), dt, "fan_in"),
        "layers/ssm/conv_b": (L + (conv_ch,), dt, "zeros"),
        "layers/ssm/a_log": (L + (nh,), "float32", "a_log"),
        "layers/ssm/dt_bias": (L + (nh,), dt, "dt_bias"),
        "layers/ssm/d_skip": (L + (nh,), dt, "ones"),
        "layers/ssm/out_norm": (L + (di,), dt, "ones"),
        "layers/ssm/out_proj": (L + (di, d), dt, "fan_in"),
        "shared/ln_attn": (M + (2 * d,), dt, "ones"),
        "shared/attn/wq": (M + (2 * d, h, hd), dt, "fan_in"),
        "shared/attn/wk": (M + (2 * d, kv, hd), dt, "fan_in"),
        "shared/attn/wv": (M + (2 * d, kv, hd), dt, "fan_in"),
        "shared/attn/wo": (M + (h, hd, d), dt, "fan_in2"),
        "shared/ln_mlp": (M + (d,), dt, "ones"),
        "shared/mlp/w_gate": (M + (d, f), dt, "fan_in"),
        "shared/mlp/w_up": (M + (d, f), dt, "fan_in"),
        "shared/mlp/w_down": (M + (f, d), dt, "fan_in"),
        "hybrid/linear": (K + (d, d), dt, "fan_in"),
        "hybrid/lora_a": (K + (d, r), dt, "fan_in"),
        "hybrid/lora_b": (K + (r, 2 * f), dt, "fan_in"),
    }
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, m["vocab_size"]), dt, "fan_in")
    return out


def _leaf(key, shape, dtype, init, stacked: bool):
    lead = 1 if stacked else 0
    if init == "zeros":
        v = jnp.zeros(shape, F32)
    elif init == "ones":
        v = jnp.ones(shape, F32)
    elif init == "normal":
        v = 0.02 * jax.random.normal(key, shape, F32)
    elif init in ("fan_in", "fan_in2"):
        fan_in = math.prod(shape[lead:lead + (2 if init == "fan_in2" else 1)])
        v = jax.random.normal(key, shape, F32) / math.sqrt(fan_in)
    elif init == "a_log":      # A ~ U[1, 16]
        v = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif init == "dt_bias":    # softplus(dt_bias) ~ log-uniform [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {init!r}")
    return v.astype(jnp.dtype(dtype))


def init(key, m: dict) -> dict:
    """The weights of seed ``key``: one leaf per :func:`layout` entry, in
    the dtype the configuration states."""
    lay = layout(m)
    keys = jax.random.split(key, len(lay))
    return nest({path: _leaf(k, *spec, stacked="/" in path)
                 for k, (path, spec) in zip(keys, sorted(lay.items()))})


# ----------------------------------------------------------------- forward

def _at(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def gated_norm(y, z, w, groups: int, eps):
    """``y * silu(z)``, RMS-normalised over each of ``groups`` groups of
    channels, times ``w``."""
    bsz, s, di = y.shape
    x = (y * jax.nn.silu(z)).reshape(bsz, s, groups, di // groups)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(bsz, s, di) * w.astype(F32)


def mamba2_layer(m, lp, hidden, t, dot):
    """One Mamba-2 layer with its pre-norm and residual; ``t`` joins the
    norm's input."""
    d, di, nh, gn, conv_ch = _dims(m)
    s_cfg = m["ssm"]
    g = s_cfg["n_groups"]
    bsz, s, _ = hidden.shape
    x = rms_norm(hidden + t, lp["ln"], m["rms_eps"])
    p = lp["ssm"]
    zxbcdt = dot(x, p["in_proj"], "bsd,de->bse")
    z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * gn], axis=-1)
    k = s_cfg["d_conv"]
    w = p["conv_w"].astype(F32)
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, i:i + s] * w[i] for i in range(k)) \
        + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(conv)
    xs, b, c = jnp.split(xbc, [di, di + gn], axis=-1)
    xs = xs.reshape(bsz, s, nh, s_cfg["head_dim"])
    b = b.reshape(bsz, s, g, s_cfg["d_state"])
    c = c.reshape(bsz, s, g, s_cfg["d_state"])
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    y = jax.checkpoint(ssd)(xs, dt, a, b, c) \
        + xs * p["d_skip"].astype(F32)[:, None]
    y = gated_norm(y.reshape(bsz, s, di), z, p["out_norm"], g, m["rms_eps"])
    return hidden + dot(y, p["out_proj"], "bse,ed->bsd")


def rope(x, theta):
    """Rotary embedding over the whole head, x [B,S,H,D], position = index
    (rotate-half form)."""
    s, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv
    emb = jnp.concatenate([ang, ang], -1)[None, :, None]
    half = dim // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def attention(m, p, x, dot):
    """Causal multi-head attention of the shared block, x [B,S,2d]."""
    bsz, s, _ = x.shape
    hd = m["head_dim"]
    q = rope(dot(x, p["wq"], "bse,ehk->bshk"), m["rope_theta"])
    k = rope(dot(x, p["wk"], "bse,ehk->bshk"), m["rope_theta"])
    v = dot(x, p["wv"], "bse,ehk->bshk")
    rep = m["num_heads"] // m["num_kv_heads"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    blk = math.gcd(s, BLOCK // 2)
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(xs):
        qb, lo = xs
        scores = dot(qb, k, "bqhk,bshk->bhqs") * (hd / 2) ** -0.5
        mask = keys[None, :] <= lo + jnp.arange(blk)[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return dot(probs, v, "bhqs,bshk->bqhk")

    out = _merge(jax.lax.map(block, (_split(q, blk), jnp.arange(0, s, blk))))
    return dot(out, p["wo"], "bshk,hkd->bsd")


def shared_block(m, sp, inv, h, e, dot):
    """One invocation of a shared block: ``T`` for the next Mamba layer."""
    x = rms_norm(jnp.concatenate([h, e], -1), sp["ln_attn"], m["rms_eps"])
    x = rms_norm(attention(m, sp["attn"], x, dot), sp["ln_mlp"],
                 m["rms_eps"])
    mp = sp["mlp"]

    @jax.checkpoint
    def mlp(xb):
        gate_up = (jnp.concatenate([dot(xb, mp["w_gate"], "bsd,df->bsf"),
                                    dot(xb, mp["w_up"], "bsd,df->bsf")], -1)
                   + dot(dot(xb, inv["lora_a"], "bsd,dr->bsr"),
                         inv["lora_b"], "bsr,rf->bsf"))
        gate, up = jnp.split(gate_up, 2, axis=-1)
        y = dot(jax.nn.gelu(gate, approximate=False) * up, mp["w_down"],
                "bsf,fd->bsd")
        return dot(y, inv["linear"], "bsd,de->bse")

    return _merge(jax.lax.map(mlp, _split(x)))


def _split(t, block: int = BLOCK):
    """[B,S,...] as [S/blk, B, blk, ...] for a map over blocks of
    positions."""
    bsz, s = t.shape[:2]
    blk = math.gcd(s, block)
    return jnp.moveaxis(t.reshape(bsz, s // blk, blk, *t.shape[2:]), 1, 0)


def _merge(t):
    """The inverse of :func:`_split`."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape(t.shape[0], -1, *t.shape[3:])


def hidden_states(m, params, tokens, dot=highest_dot):
    """Final hidden states [B,S,d] before the last norm: one scan over the
    layers, each a checkpoint; at a hybrid layer ``T`` is the shared
    block's output, elsewhere zero."""
    e = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    ids = list(m["hybrid_layer_ids"])
    layer_ids = range(m["num_layers"])
    hybrid = jnp.array([i in ids for i in layer_ids])
    invocation = jnp.array([ids.index(i) if i in ids else 0
                            for i in layer_ids])

    @jax.checkpoint
    def layer(h, xs):
        lp, is_hybrid, k = xs

        def shared(h):
            return shared_block(
                m, _at(params["shared"], k % m["num_mem_blocks"]),
                _at(params["hybrid"], k), h, e, dot)

        t = jax.lax.cond(is_hybrid, shared, jnp.zeros_like, h)
        return mamba2_layer(m, lp, h, t, dot), None

    h, _ = jax.lax.scan(layer, e, (params["layers"], hybrid, invocation))
    return h


def loss(m, params, tokens, targets, dot=highest_dot, row_block: int = 1):
    """Mean next-token cross-entropy over every position of every row."""
    h = hidden_states(m, params, tokens, dot)
    table_spec = "bsd,vd->bsv" if m["tie_embeddings"] else "bsd,dv->bsv"
    table = params["embed"] if m["tie_embeddings"] else params["lm_head"]
    bsz = h.shape[0]
    rb = math.gcd(bsz, row_block)

    @jax.checkpoint
    def positions(ht):
        hr, tr = ht
        logits = dot(rms_norm(hr, params["final_norm"], m["rms_eps"]),
                     table, table_spec)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[..., None], -1)[..., 0]
        return jnp.sum(lse - gold, axis=1)

    def rows(hr, tr):
        return jnp.sum(jax.lax.map(positions, (_split(hr), _split(tr))))

    split = lambda t: t.reshape(bsz // rb, rb, *t.shape[1:])
    total = jax.lax.map(lambda t: rows(*t), (split(h), split(targets)))
    return jnp.sum(total) / targets.size


# ------------------------------------------------------------ model work

def model_flops_per_token(m: dict, seq: int) -> float:
    """Operations of one training step per token: three times the forward
    pass's matrix products, with no recomputation.  Attention counts the
    causal half (a token's scores over itself and the keys before it); the
    state-space layer counts its chunked dual form at the configuration's
    chunk (arXiv:2405.21060, section 6) with C·Bᵀ once per group."""
    d, di, nh, gn, conv_ch = _dims(m)
    s = m["ssm"]
    chunk = min(s["chunk_size"], seq)
    n, p, g = s["d_state"], s["head_dim"], s["n_groups"]
    ssm = (2 * d * (2 * di + 2 * gn + nh) + 2 * s["d_conv"] * conv_ch
           + 2 * chunk * n * g + 2 * chunk * p * nh + 2 * 2 * n * p * nh
           + 2 * di * d)
    hq, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f, r = m["d_ff"], m["adapter_rank"]
    shared = (2 * 2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d   # q k v o
              + 2 * hq * hd * (seq + 1)                          # scores, PV
              + 2 * 3 * d * f + 2 * (d * r + r * 2 * f)          # mlp, adapter
              + 2 * d * d)                                       # linear
    fwd = (m["num_layers"] * ssm + len(m["hybrid_layer_ids"]) * shared
           + 2 * d * m["vocab_size"])
    return 3.0 * fwd

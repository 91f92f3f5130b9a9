"""Plain float32 reference of the Mamba-2 language model (arXiv:2405.21060),
as the configuration files under ``configs/`` state it.

Straightforward ``jax.numpy``: the state-space layer is the paper's own
minimal listing of the SSD map (a test holds it to the recurrence step by
step), and the loss runs over blocks of batch rows.  Every matrix product
runs through ``dot`` at the ``highest`` precision, so a float32 product is
a float32 product on a TPU.
Nothing here imports the system under test.

Parameters are a nested dict whose leaves are laid out as the program
holds them (:func:`layout`); weights come from :func:`init` and a seed,
never from the program.  Where the program departs from the published
model, the reference follows the program and the configuration file lists
the departure under ``assumed``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def highest_dot(a, b, spec: str):
    """The reference's matrix product: float32 operands at full precision."""
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------------------ layout

def _ssm_dims(m: dict):
    s = m["ssm"]
    d = m["d_model"]
    di = s["expand"] * d
    nh = di // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    return d, di, nh, gn, di + 2 * gn


def layout(m: dict) -> dict:
    """``path -> (shape, dtype, init)`` for every parameter leaf.

    ``m`` is the ``model`` object of a configuration file.  ``init`` is
    one of normal (std 0.02), fan_in (std 1/sqrt(fan-in), fan-in the
    leading non-layer dimensions), zeros, ones, a_log and dt_bias (the
    Mamba-2 initialisations of A and of the step size)."""
    if m["family"] != "ssm":
        raise ValueError(f"no reference of a {m['family']!r} model here")
    d, di, nh, gn, conv_ch = _ssm_dims(m)
    s = m["ssm"]
    dt = m["dtype"]
    n_ssm = m["num_layers"]
    L = (n_ssm,)
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
    }
    if not m["tie_embeddings"]:
        out["lm_head"] = ((d, m["vocab_size"]), dt, "fan_in")
    return out


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def _leaf(key, shape, dtype, init, layered: bool):
    lead = 1 if layered else 0
    if init == "zeros":
        v = jnp.zeros(shape, F32)
    elif init == "ones":
        v = jnp.ones(shape, F32)
    elif init == "normal":
        v = 0.02 * jax.random.normal(key, shape, F32)
    elif init == "fan_in":
        fan_in = math.prod(shape[lead:-1]) if len(shape) - lead > 2 \
            else shape[lead]
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
    the dtype the configuration states.  Jit it with the program's
    shardings as ``out_shardings`` to make the weights on the device in one
    call."""
    lay = layout(m)
    keys = jax.random.split(key, len(lay))
    return nest({path: _leaf(k, *spec, layered=path.startswith("layers/"))
                 for k, (path, spec) in zip(keys, sorted(lay.items()))})


# ----------------------------------------------------------------- forward

def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def segsum(x):
    """out[..., i, j] = x[..., j+1] + ... + x[..., i] for j <= i, else -inf."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, dt, a, b, c, block: int = 64):
    """The selective state-space map of Mamba-2 from a zero state.

    x [B,S,H,P], dt [B,S,H], a [H], b and c [B,S,G,N] -> y [B,S,H,P],
    what the recurrence state_t = exp(dt_t a) state_{t-1} + dt_t x_t b_t^T,
    y_t = state_t c_t gives.  Computed as the paper's minimal listing
    (arXiv:2405.21060, listing 1, ``ssd_minimal_discrete``): blocks of
    ``block`` steps, each the masked quadratic form, and the states
    between blocks passed by one more quadratic form over the blocks'
    total decays; every exponent is a difference within a segment."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    hi = jax.lax.Precision.HIGHEST
    b = jnp.repeat(b, h // g, axis=2)
    c = jnp.repeat(c, h // g, axis=2)
    blk = math.gcd(s, block)
    split = lambda t: t.reshape(bsz, s // blk, blk, *t.shape[2:])
    xs, bs, cs = split(x * dt[..., None]), split(b), split(c)
    da = jnp.moveaxis(split(dt * a), 3, 1)           # [B,H,C,L]
    cum = jnp.cumsum(da, axis=-1)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", cs, bs,
                        jnp.exp(segsum(da)), xs, precision=hi)
    decay = jnp.exp(cum[..., -1:] - cum)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", bs, decay, xs,
                        precision=hi)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    totals = jnp.pad(cum[..., -1], ((0, 0), (0, 0), (1, 0)))
    states = jnp.einsum("bhzc,bchpn->bzhpn", jnp.exp(segsum(totals)),
                        states, precision=hi)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", cs, states, jnp.exp(cum),
                       precision=hi)
    return (y_diag + y_off).reshape(bsz, s, h, p)


def mamba2_layer(m, lp, hidden, dot):
    """One Mamba-2 block with its pre-norm and residual."""
    d, di, nh, gn, conv_ch = _ssm_dims(m)
    s_cfg = m["ssm"]
    bsz, s, _ = hidden.shape
    x = rms_norm(hidden, lp["ln"], m["rms_eps"])
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
    hd = s_cfg["head_dim"]
    xs = xs.reshape(bsz, s, nh, hd)
    g = s_cfg["n_groups"]
    b = b.reshape(bsz, s, g, s_cfg["d_state"])
    c = c.reshape(bsz, s, g, s_cfg["d_state"])
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["a_log"].astype(F32))
    y = ssd(xs, dt, a, b, c)
    y = y + xs * p["d_skip"].astype(F32)[:, None]
    y = y.reshape(bsz, s, di)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], m["rms_eps"])
    return hidden + dot(y, p["out_proj"], "bse,ed->bsd")


def hidden_states(m, params, tokens, dot=highest_dot):
    """Final hidden states [B,S,d] before the last norm."""
    h = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if m["tie_embeddings"]:
        h = h * math.sqrt(m["d_model"])
    layers = params["layers"]
    layer = jax.checkpoint(lambda hh, lp: (mamba2_layer(m, lp, hh, dot),
                                           None))
    h, _ = jax.lax.scan(layer, h, layers)
    return h


def loss(m, params, tokens, targets, dot=highest_dot, row_block: int = 1):
    """Mean next-token cross-entropy over every position of every row."""
    h = hidden_states(m, params, tokens, dot)
    table_spec = "bsd,vd->bsv" if m["tie_embeddings"] else "bsd,dv->bsv"
    table = params["embed"] if m["tie_embeddings"] else params["lm_head"]
    bsz = h.shape[0]
    rb = math.gcd(bsz, row_block)

    @jax.checkpoint
    def rows(hr, tr):
        logits = dot(rms_norm(hr, params["final_norm"], m["rms_eps"]),
                     table, table_spec)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tr[..., None], -1)[..., 0]
        return jnp.sum(lse - gold)

    split = lambda t: t.reshape(bsz // rb, rb, *t.shape[1:])
    total = jax.lax.map(lambda t: rows(*t), (split(h), split(targets)))
    return jnp.sum(total) / targets.size


# ------------------------------------------------------------ model work

def model_flops_per_token(m: dict, seq: int) -> float:
    """Operations of one training step per token: three times the forward
    pass's matrix products (the backward pass does two for each), with no
    recomputation.  The state-space layer counts its chunked dual form at
    the configuration's chunk (arXiv:2405.21060, section 6)."""
    d, di, nh, gn, conv_ch = _ssm_dims(m)
    s = m["ssm"]
    chunk = min(s["chunk_size"], seq)
    n, p = s["d_state"], s["head_dim"]
    ssm = (2 * d * (2 * di + 2 * gn + nh) + 2 * s["d_conv"] * conv_ch
           + 2 * chunk * n * nh + 2 * chunk * p * nh + 2 * 2 * n * p * nh
           + 2 * di * d)
    fwd = m["num_layers"] * ssm + 2 * d * m["vocab_size"]
    return 3.0 * fwd

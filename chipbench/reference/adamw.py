"""Plain reference of the training steps a train cell checks: the loss,
its gradient, clipping by the global norm, and AdamW (Loshchilov & Hutter,
arXiv:1711.05101) with the learning rate warmed up linearly, as the
traffic file's ``optimizer`` states them.

Parameters are stored in the dtype the configuration states and updated in
float32; the moments are float32.  Nothing here imports the system under
test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def leaf_norms(tree) -> dict:
    """``path -> float32 L2 norm`` of every leaf of a nested dict."""
    from .mamba2 import flatten
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in flatten(tree).items()}


def diff_norms(a, b) -> dict:
    from .mamba2 import flatten
    fb = flatten(b)
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)
                                           - fb[k].astype(F32))))
            for k, v in flatten(a).items()}


def init_state(params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, F32)
    return {"m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params)}


def mean_and_grad(loss_fn, params, tokens, targets, row_block: int):
    """The loss over all rows and its gradient, taken over blocks of
    ``row_block`` rows so that one block's activations are live at a
    time; ``loss_fn`` gives the mean over the rows it is given.  Block
    ``j`` holds every ``rows / row_block``-th row from row ``j``, so that
    where the rows are split over chips each block is split alike."""
    rows = tokens.shape[0]
    rb = min(row_block, rows)
    if rows % rb:
        raise ValueError(f"{rows} rows do not split into blocks of {rb}")
    split = lambda t: jnp.swapaxes(t.reshape(rb, rows // rb, *t.shape[1:]),
                                   0, 1)
    vg = jax.value_and_grad(loss_fn)

    def block(acc, xs):
        loss, grads = vg(params, *xs)
        return jax.tree.map(lambda a, g: a + g * (rb / rows), acc,
                            (loss, grads)), None

    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, params))
    out, _ = jax.lax.scan(block, zero, (split(tokens), split(targets)))
    return out


def step(loss_fn, opt: dict, params, state, t, tokens, targets,
         row_block: int):
    """One AdamW step at step number ``t`` (1 for the first).

    Returns ``(loss, clipped gradient norms per leaf, params, state)``."""
    pf = jax.tree.map(lambda p: p.astype(F32), params)
    loss, grads = mean_and_grad(loss_fn, pf, tokens, targets, row_block)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    tf = jnp.asarray(t, F32)
    lr = opt["learning_rate"] * jnp.minimum(
        tf / max(opt["warmup_steps"], 1), 1.0)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                     state["v"], grads)

    def new(p, pf_, m_, v_):
        mh = m_ / (1 - b1 ** tf)
        vh = v_ / (1 - b2 ** tf)
        upd = mh / (jnp.sqrt(vh) + opt["eps"]) + opt["weight_decay"] * pf_
        return (pf_ - lr * upd).astype(p.dtype)

    params = jax.tree.map(new, params, pf, m, v)
    return loss, leaf_norms(grads), params, {"m": m, "v": v}

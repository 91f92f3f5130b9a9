"""ResNet v1.5 (18/34/50/101/152/200) in pure JAX — the paper's Fig 7
workload family (data-parallel ResNet training on 4×A100).

BatchNorm uses batch statistics (training mode); running averages are not
tracked (irrelevant for the exported workload graph — only the compute
matters for the performance model)."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .params import ParamSpec

_STAGES = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
    200: ("bottleneck", (3, 24, 36, 3)),
}


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    dtype: str = "float16"       # paper Table III: FP16
    block_barriers: bool = False  # optimization_barrier between blocks
    #                               (profiling-slicing region boundaries)

    @property
    def block(self) -> str:
        return _STAGES[self.depth][0]

    @property
    def stage_sizes(self) -> tuple[int, ...]:
        return _STAGES[self.depth][1]


def _conv_spec(k, cin, cout, dt):
    return ParamSpec((k, k, cin, cout), ("conv", "conv", "embed", "mlp"),
                     init="scaled", dtype=dt)


def _bn_specs(c, dt):
    return {"scale": ParamSpec((c,), ("norm",), init="ones", dtype=dt),
            "bias": ParamSpec((c,), ("norm",), init="zeros", dtype=dt)}


def resnet_specs(cfg: ResNetConfig) -> dict:
    dt = cfg.dtype
    specs: dict = {"stem": {"conv": _conv_spec(7, 3, cfg.width, dt),
                            "bn": _bn_specs(cfg.width, dt)}}
    cin = cfg.width
    expansion = 4 if cfg.block == "bottleneck" else 1
    for si, n_blocks in enumerate(cfg.stage_sizes):
        cmid = cfg.width * (2 ** si)
        cout = cmid * expansion
        stage: dict = {}
        for bi in range(n_blocks):
            blk: dict = {}
            if cfg.block == "bottleneck":
                blk["conv1"] = _conv_spec(1, cin, cmid, dt)
                blk["bn1"] = _bn_specs(cmid, dt)
                blk["conv2"] = _conv_spec(3, cmid, cmid, dt)
                blk["bn2"] = _bn_specs(cmid, dt)
                blk["conv3"] = _conv_spec(1, cmid, cout, dt)
                blk["bn3"] = _bn_specs(cout, dt)
            else:
                blk["conv1"] = _conv_spec(3, cin, cmid, dt)
                blk["bn1"] = _bn_specs(cmid, dt)
                blk["conv2"] = _conv_spec(3, cmid, cout, dt)
                blk["bn2"] = _bn_specs(cout, dt)
            if cin != cout or bi == 0:
                blk["proj"] = _conv_spec(1, cin, cout, dt)
                blk["proj_bn"] = _bn_specs(cout, dt)
            stage[f"block{bi}"] = blk
            cin = cout
        specs[f"stage{si}"] = stage
    specs["head"] = ParamSpec((cin, cfg.num_classes), ("embed", "vocab"),
                              init="scaled", dtype=dt)
    return specs


def _conv(x, w, stride=1):
    # no preferred_element_type: its conv transpose rule rejects mixed
    # f16/f32 operands on the CPU backend (cotangents stay in input dtype)
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(axis=(0, 1, 2), keepdims=True)
    var = xf.var(axis=(0, 1, 2), keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def resnet_forward(cfg: ResNetConfig, params: dict, images: jax.Array,
                   labels: jax.Array):
    """images: [B, H, W, 3]; labels: [B] -> (loss, logits)."""
    x = images.astype(jnp.dtype(cfg.dtype))
    x = _conv(x, params["stem"]["conv"], stride=2)
    x = jax.nn.relu(_bn(x, params["stem"]["bn"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si in range(len(cfg.stage_sizes)):
        stage = params[f"stage{si}"]
        for bi in range(cfg.stage_sizes[si]):
            blk = stage[f"block{bi}"]
            stride = 2 if (bi == 0 and si > 0) else 1
            identity = x
            if cfg.block == "bottleneck":
                y = jax.nn.relu(_bn(_conv(x, blk["conv1"]), blk["bn1"]))
                y = jax.nn.relu(_bn(_conv(y, blk["conv2"], stride),
                                    blk["bn2"]))
                y = _bn(_conv(y, blk["conv3"]), blk["bn3"])
            else:
                y = jax.nn.relu(_bn(_conv(x, blk["conv1"], stride),
                                    blk["bn1"]))
                y = _bn(_conv(y, blk["conv2"]), blk["bn2"])
            if "proj" in blk:
                identity = _bn(_conv(x, blk["proj"], stride),
                               blk["proj_bn"])
            x = jax.nn.relu(y + identity)
            if cfg.block_barriers:
                x = jax.lax.optimization_barrier(x)
    x = x.mean(axis=(1, 2))
    logits = (x.astype(jnp.float32)
              @ params["head"].astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold), logits


def resnet_arch_config(arch: str) -> ResNetConfig:
    """``"resnet50"`` -> :class:`ResNetConfig` (campaign ``arch`` ids)."""
    if not arch.startswith("resnet"):
        raise ValueError(f"not a resnet arch id: {arch!r}")
    suffix = arch[len("resnet"):]
    if not suffix.isdigit() or int(suffix) not in _STAGES:
        raise ValueError(
            f"unknown resnet depth in {arch!r}; have {sorted(_STAGES)}")
    return ResNetConfig(depth=int(suffix))


def resnet_train_exports(cfg: ResNetConfig, batch: int, img: int, mesh=None,
                         opt_cfg=None):
    """Jitted ResNet train step + abstract args for workload export.

    Data-parallel fig-7 configuration: loss + grad + optimizer update
    (AdamW by default; any :class:`OptimizerConfig`), FP16 images sharded
    over the mesh "data" axis.  Shared by the fig7 benchmark loop and
    the campaign engine's ``mode="train"`` resnet export, so both
    produce the identical StableHLO/HLO pair.

    Returns ``(jitted_step, (params_abs, opt_abs, images_abs, labels_abs))``.
    """
    from ..distributed.sharding import act_sharding
    from ..models.params import abstract_params
    from ..train.optimizer import (OptimizerConfig, make_optimizer,
                                   opt_state_abstract)

    specs = resnet_specs(cfg)
    opt_cfg = opt_cfg or OptimizerConfig(name="adamw")
    _, update_fn = make_optimizer(opt_cfg)

    def step(params, opt, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: resnet_forward(cfg, p, images, labels)[0])(params)
        params, opt, _ = update_fn(params, grads, opt, opt_cfg)
        return params, opt, loss

    jitted = jax.jit(step, donate_argnums=(0, 1))
    params_abs = abstract_params(specs, mesh)
    if mesh is None:
        imgs = jax.ShapeDtypeStruct((batch, img, img, 3), jnp.float16)
        lbls = jax.ShapeDtypeStruct((batch,), jnp.int32)
    else:
        img_sh = act_sharding(("batch", "seq", "seq", "embed"), mesh, None,
                              (batch, img, img, 3))
        lbl_sh = act_sharding(("batch",), mesh, None, (batch,))
        imgs = jax.ShapeDtypeStruct((batch, img, img, 3), jnp.float16,
                                    sharding=img_sh)
        lbls = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=lbl_sh)
    opt_abs = opt_state_abstract(specs, opt_cfg.name, mesh, None)
    return jitted, (params_abs, opt_abs, imgs, lbls)

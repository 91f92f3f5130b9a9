"""Model FLOP/s utilisation of the training window: the configuration's
model operations per token (its reference's ``model_flops_per_token``, no
recomputation) times the window's tokens per second, over the chips'
bf16 peak from ``peaks.json``."""


def read(ctx):
    from chipbench import harness

    cell = ctx["cell"]
    ref = harness.reference(cell.config)
    per_token = ref.model_flops_per_token(cell.config["model"],
                                          cell.traffic["seq_len"])
    peak = ctx["peak"](ctx["device"]["kind"], "bf16_flops")
    return 100.0 * per_token * ctx["rate"] / (ctx["device"]["count"] * peak)

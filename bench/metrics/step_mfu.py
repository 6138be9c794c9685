"""The whole round's share of the chip's bf16 peak while the device was
busy: model FLOPs of the traced rounds (valid samples only) over busy
device seconds times the peak."""


def read(ctx):
    red = ctx["reduction"]
    if red.busy_s <= 0 or not ctx["peaks"]:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["model_flops"] / (red.busy_s * peak)

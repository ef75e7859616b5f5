"""Model FLOP utilization of the whole step: the model FLOPs of the traced
steps (the configuration's FLOP function; recomputation not counted) over
the traced window's length, the chips and the chip's bf16 peak. A kernel
taken off the path leaves its roofline silent; this still bounds it."""


def read(ctx):
    window_s = (ctx.hi - ctx.lo) / 1e9
    return 100.0 * ctx.flops_per_step * ctx.steps / (window_s * ctx.chips * ctx.peaks["bf16_flops"])

"""Model FLOPs of the traced window (local rounds at three forward passes
per sample and step, the Eq. 1 accuracy term and the global evaluation at
one forward pass per test sample) per second of the window, over the
chip's bf16 peak (%)."""


def read(ctx):
    if ctx.peaks is None or ctx.driver != "closed_loop":
        return None
    work = ctx.flops.event_flops(ctx.cfg, ctx.traffic) * ctx.events
    return 100.0 * work / (ctx.window_s * ctx.peaks["bf16_flops"])

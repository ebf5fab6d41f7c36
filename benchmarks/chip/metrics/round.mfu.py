"""FLOPs the traced rounds require (work/<model>.py) over the traced wall
time x chips x the chip's bf16 peak."""


def read(ctx):
    work = ctx.work()
    flops = sum(work.round_flops(ctx.stats, ctx.schedule, t) for t in ctx.rounds)
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops / (ctx.trace.window_s * peak)

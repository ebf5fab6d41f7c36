"""Device ms per round of the local-training program (jitted
``FGLTrainer._local_rounds``), averaged over the chips."""


def read(ctx):
    s = ctx.trace.mean_module_s(lambda name: "_local_rounds" in name)
    return ctx.per_round_ms(s) if s > 0 else None

"""Device ms per round of the evaluation program (jitted
``FGLTrainer._evaluate``), averaged over the chips."""


def read(ctx):
    s = ctx.trace.mean_module_s(lambda name: "_evaluate" in name)
    return ctx.per_round_ms(s) if s > 0 else None

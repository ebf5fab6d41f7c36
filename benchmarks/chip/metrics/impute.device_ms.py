"""Device ms per imputation round of the imputation program (jitted
``FGLTrainer._impute``, module ``jit__impute``: the whole of the
``generator``, ``sim_topk`` and ``patch`` scopes), averaged over the chips.
A traced window with no imputation round, or a program without that
module, reads nothing."""

MODULE = "jit__impute"


def read(ctx):
    work = ctx.work()
    rounds = [t for t in ctx.rounds if work.is_impute_round(t, ctx.schedule)]
    s = ctx.trace.mean_module_s(lambda name: name.split("(", 1)[0] == MODULE)
    return s / len(rounds) * 1e3 if rounds and s > 0 else None

"""Host seconds of ``fgl_train.build``: graph, partition, config, trainer."""


def read(ctx):
    return ctx.build_s

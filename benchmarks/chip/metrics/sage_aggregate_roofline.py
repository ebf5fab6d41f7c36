"""Least time of the aggregations the traced rounds require (roofline at the
chip's bf16 peak and HBM bandwidth, work/<model>.py) over the device time of
the ``sage_aggregate`` kernel's ops."""


def is_kernel(label):
    """The Pallas kernel's own ops: under ``jit(sage_aggregate)`` and a
    ``pallas_call`` (or named after the kernel), not the wrapper's padding
    or the XLA backward that share the jit's name."""
    low = label.lower()
    return "sage" in low and ("pallas_call" in low or "_sage_kernel" in low
                              or "tpu_custom_call" in low)


def read(ctx):
    kernel_s = ctx.trace.mean_op_s(is_kernel) * ctx.chips
    if kernel_s <= 0:
        return None
    least = ctx.work().aggregation_least_time(ctx.stats, ctx.schedule,
                                              ctx.rounds, ctx.peaks)
    return 100.0 * least / kernel_s

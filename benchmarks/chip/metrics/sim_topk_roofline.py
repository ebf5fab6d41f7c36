"""Least time of the similarity searches the traced rounds require (roofline
at the chip's bf16 peak and HBM bandwidth, ``work/<model>.py: similarity``)
over the device time of the ``sim_topk`` kernel's ops."""


def is_kernel(label):
    """The fused masked top-k kernel's own ops: its custom call (on a TPU
    ``%sim_topk.N = ... custom_call_target="tpu_custom_call"``), not the
    padding and fusion around it."""
    low = label.lower()
    return "sim_topk" in low and ("pallas_call" in low or "_sim_topk_kernel" in low
                                  or "tpu_custom_call" in low)


def read(ctx):
    kernel_s = ctx.trace.mean_op_s(is_kernel) * ctx.chips
    work = ctx.work()
    rounds = [t for t in ctx.rounds if work.is_impute_round(t, ctx.schedule)]
    if kernel_s <= 0 or not rounds:
        return None
    least = sum(work.least_time(f, b, ctx.peaks) for f, b in work.similarity(ctx.stats))
    return 100.0 * least * len(rounds) / kernel_s

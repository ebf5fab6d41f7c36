"""Device ms per round of the ops under the program's ``aggregate`` scope,
averaged over the chips. The scope is the whole body of the aggregation
program (``FGLTrainer._aggregate``, module ``jit__aggregate``), and a TPU op
event names its HLO instruction and no scope, so the ops read are those that
run inside that program's runs: the union of their intervals. A program
without ``FGLTrainer._aggregate`` has no such module, and nothing is read."""
from chipbench import devtrace

MODULE = "jit__aggregate"


def device_s(trace, dev):
    """Seconds chip ``dev`` ran ops inside the runs of ``MODULE``."""
    runs = devtrace.union(devtrace.clip(
        [(s, e) for n, s, e in trace.devices[dev]["modules"]
         if n.split("(", 1)[0] == MODULE], trace.start, trace.end))
    ops = [(s, e) for _, s, e in trace.ops(dev)]
    return sum(e - s for lo, hi in runs
               for s, e in devtrace.union(devtrace.clip(ops, lo, hi))) * 1e-9


def read(ctx):
    devices = ctx.trace.devices
    s = sum(device_s(ctx.trace, d) for d in devices) / len(devices)
    return ctx.per_round_ms(s) if s > 0 else None

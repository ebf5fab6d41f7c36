"""One run of one cell: set-up, the measured window, the trace, the check.

Set-up is ``fgl_train``'s own: ``parse_args`` + ``build`` with the cell's
flags and ``--impl pallas`` give ``(trainer, batch)``; ``trainer.init``
takes the run's key. The same trainer and state then run the first rounds
through ``FGLTrainer.step``, one ``block_until_ready`` on each round's
metrics, as the window does: they compile or load every program the
schedule uses (local, impute, each aggregation phase, evaluate) and give the
readings the check compares (each round's loss, the optimizers' first
moments after round 0, the weights after the compared rounds). The window
continues from that state in whole schedule periods until ``--seconds``
have passed. Afterwards the program's state is freed and the plain
reference runs the compared rounds from the same key on the same batch.
"""
from __future__ import annotations

import dataclasses
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
BATCH_FIELDS = ("x", "adj", "y", "node_mask", "train_mask", "test_mask")
MIN_TRACE_S = 0.5
MAX_TRACE_ROUNDS = 64


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts and times JAX's tracing, lowering, compiling and cache loads."""

    def __init__(self):
        import jax.monitoring
        self.count: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.count[event] = self.count.get(event, 0) + 1
            self.seconds[event] = self.seconds.get(event, 0.0) + duration

    def total(self):
        return sum(self.count.values()), sum(self.seconds.values())


def key_from_seed(seed: int):
    """A threefry key holding all 64 bits of ``seed``."""
    import jax
    s = int(seed) % 2 ** 64
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], dtype=np.uint32))


def host_batch(batch) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(batch, f)) for f in BATCH_FIELDS}


def weights(state) -> Dict[str, Any]:
    return {"cls": state.params, "ae": state.ae_params, "as": state.as_params}


def moments(state) -> Dict[str, Any]:
    return {"cls": state.opt_state.mu, "ae": state.ae_opt.mu, "as": state.as_opt.mu}


@dataclasses.dataclass
class Program:
    """A built cell: the trainer and what set it up."""
    trainer: Any
    batch: Any
    method: str
    build_s: float


def build(cell, impl: str) -> Program:
    from repro.launch import fgl_train
    t0 = time.perf_counter()
    args = fgl_train.parse_args(cell.fgl_train_argv(impl))
    trainer, batch = fgl_train.build(args)
    build_s = time.perf_counter() - t0
    check_shapes(cell, trainer, batch)
    return Program(trainer, batch, args.method, build_s)


def check_shapes(cell, trainer, batch) -> None:
    """The built program must be the configuration the cell states."""
    conf = cell.config
    got = {"feature_dim": batch.x.shape[-1], "num_classes": batch.num_classes,
           "hidden_dim": trainer.cfg.hidden_dim, "num_layers": trainer.cfg.num_layers,
           "model": trainer.cfg.gnn_kind}
    want = {k: conf[k] for k in got}
    if got != want or batch.aug_max != conf["assumed"]["aug_max"]:
        raise RuntimeError(f"built {got} (aug_max {batch.aug_max}), but the "
                           f"configuration states {want}")


def first_rounds(state, step: Callable, n_compare: int, n_rounds: int):
    """``n_rounds`` rounds from a fresh state through the window's own call.

    Returns the state after them and the readings of the first
    ``n_compare`` rounds, as host arrays.
    """
    import jax
    read = {"init": jax.device_get(weights(state)), "loss": []}
    for t in range(n_rounds):
        state, m = step(state)
        jax.block_until_ready(m)
        if t == 0:
            read["moment"] = jax.device_get(moments(state))
        if t < n_compare:
            read["loss"].append(float(m["loss"]))
        if t == n_compare - 1:
            read["final"] = jax.device_get(weights(state))
    return state, read


def make_reference(cell, program: Program, precision: str = "stated"):
    """The plain reference of the cell, at the stated precision or the
    control's one step below."""
    sched = cell.schedule
    flags = cell.flags
    return cell.reference().Reference(
        method=program.method, servers=program.trainer.n_servers,
        local_rounds=sched["local_rounds"],
        imputation_interval=sched["imputation_interval"],
        top_k=sched["top_k"], gossip_every=sched["gossip_every"],
        participation=float(flags.get("participation", 1.0)),
        aug_max=program.batch.aug_max, num_classes=program.batch.num_classes,
        hidden=cell.config["hidden_dim"], precision=precision)


def reference_readings(reference, program: Program, seed: int, n_compare: int):
    return reference.run(key_from_seed(seed), host_batch(program.batch), n_compare)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def graph_stats(cell, trainer, state) -> Dict[str, Any]:
    """Per-client real nodes, directed edges and link targets of the batch
    the traced rounds start from, for the work functions."""
    import jax
    import jax.numpy as jnp
    n_local = trainer.n_local

    @jax.jit
    def count(b):
        mask2d = b.node_mask[:, :, None] * b.node_mask[:, None, :]
        return (jnp.sum(b.node_mask, 1), jnp.sum((b.adj * mask2d) > 0, (1, 2)),
                jnp.sum(b.node_mask[:, :n_local], 1))
    nodes, edges, targets = jax.device_get(count(state.batch))
    return {"nodes": [int(v) for v in nodes], "edges": [int(v) for v in edges],
            "targets": [int(v) for v in targets],
            "d": int(state.batch.x.shape[-1]), "hidden": cell.config["hidden_dim"],
            "c": int(state.batch.num_classes), "servers": trainer.n_servers,
            "top_k": cell.schedule["top_k"]}


def traced_rounds(trainer, state, period: int, trace_dir: str, step=None):
    """Whole periods of rounds under the profiler, each round in the
    harness's spans, until MIN_TRACE_S have passed."""
    import jax
    from chipbench import devtrace
    step = step or trainer.step
    rounds = []
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    try:
        while (time.perf_counter() - t0 < MIN_TRACE_S or len(rounds) < 2 * period) \
                and len(rounds) < MAX_TRACE_ROUNDS:
            for _ in range(period):
                t = int(state.round)
                with jax.profiler.TraceAnnotation(devtrace.STEP, round=t):
                    state, m = step(state)
                with jax.profiler.TraceAnnotation(devtrace.SYNC, round=t):
                    jax.block_until_ready(m)
                rounds.append(t)
    finally:
        jax.profiler.stop_trace()
    return state, rounds


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             impl: str = "pallas", peaks: Optional[Dict] = None,
             devices=None, step_patch: Optional[Callable] = None) -> Dict[str, Any]:
    """One run; returns the result line's fields (and prints the rest)."""
    import jax
    from chipbench import compare, devtrace
    devices = devices or jax.devices()[:cell.chips]
    watch = CompileWatch()
    program = build(cell, impl)
    trainer = program.trainer
    step = step_patch(trainer) if step_patch else trainer.step
    n_compare = int(cell.limits["compare_rounds"])
    imputes = bool(trainer.imputation.active)
    period = cell.period(imputes)
    n_warm = max(n_compare, period)
    key = key_from_seed(seed)
    t_init = time.perf_counter()
    state = trainer.init(key, program.batch)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t_init
    c0, s0 = watch.total()
    t_warm = time.perf_counter()
    state, read = first_rounds(state, step, n_compare, n_warm)
    warm_s = time.perf_counter() - t_warm
    c1, s1 = watch.total()
    log(f"[setup] build {program.build_s:.3f} s, init and transfer to the "
        f"device {init_s:.3f} s, compile or cache load {s1 - s0:.3f} s "
        f"({c1 - c0} events) within {n_warm} warm-up rounds of {warm_s:.3f} s")

    result: Dict[str, Any] = {"metrics": {}}
    window_losses = []
    if not trace:
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        rounds = 0
        while True:
            for _ in range(period):
                state, m = step(state)
                jax.block_until_ready(m)
                window_losses.append(m["loss"])
                rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        c2, _ = watch.total()
        log(f"[window] {rounds} rounds in {elapsed:.6f} s; compiles in window: {c2 - c1}")
        result["metrics"]["round_ms"] = {"value": elapsed / rounds * 1e3, "unit": "ms"}
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
        result["attempted"] = rounds
    else:
        stats = graph_stats(cell, trainer, state)
        c1, _ = watch.total()
        tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
        try:
            state, rounds = traced_rounds(trainer, state, period, tmp, step)
            c2, _ = watch.total()
            log(f"[trace] rounds {rounds[0]}..{rounds[-1]}; compiles in window: {c2 - c1}")
            extraction = devtrace.extract(devtrace.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        tr = devtrace.Trace(extraction)
        ctx = ReadContext(cell=cell, trace=tr, stats=stats, peaks=peaks,
                          build_s=program.build_s, chips=len(devices),
                          schedule={**cell.schedule, "imputes": imputes})
        for metric in cell.per_layer:
            value = cell.reader(metric["name"])(ctx)
            if value is None:
                log(f"[trace] {metric['name']}: its reader found nothing to read")
            else:
                result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
        result["busy_s"] = tr.mean_busy_s()
        result["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
        result["attempted"] = len(rounds)
    result["memory_peak_bytes"] = memory_peak(devices)
    losses = [float(v) for v in jax.device_get(window_losses)]
    result["failed"] = sum(not math.isfinite(v) for v in losses)
    del state, window_losses
    t_ref = time.perf_counter()
    ref = reference_readings(make_reference(cell, program), program, seed, n_compare)
    checks = compare.checks(read, ref, cell.limits)
    log(f"[reference] {n_compare} rounds in {time.perf_counter() - t_ref:.3f} s; "
        f"loss {read['loss']} vs reference {ref['loss']}")
    result["checks"] = checks
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    return result


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader gets."""
    cell: Any
    trace: Any
    stats: Dict[str, Any]
    peaks: Optional[Dict[str, Any]]
    build_s: float
    chips: int
    schedule: Dict[str, Any]

    @property
    def rounds(self):
        return self.trace.rounds

    def per_round_ms(self, seconds: float) -> float:
        return seconds / len(self.rounds) * 1e3

    def work(self):
        return self.cell.work()


"""The cell ``cora.impute-k1``: its check driven through whole runs, and the
readers of its imputation program.

The runs skip the harness's look for a chip and drive the cell (SpreadFGL:
generator, assessor, similarity top-k, patcher, Eq. 16 ring) on the CPU at a
quarter of Cora (677 nodes, the published 1,433 features) with the kernels'
jnp path.

The cell's limits come from chip readings, where the generator's adversarial
Adam steps grow the bf16 rounding of the embeddings it trains on into gaps as
large as those of the control, the ``altered`` fault and a stale generator
(PERF.md): against them a sound run is correct and the gross faults are not.
On the CPU the program and the reference both compute in f32, so a sound run
reads within f32 round-off of the reference (1e-5 or less on each number),
and the control, every fault of ``chipbench.faults`` and a stale generator
read far above it: those are held to ``F32_LIMITS``.
"""
import copy
import pathlib
import time
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import cellrun, compare, devtrace, faults, spec
from repro.core import assessor as assessor_lib
from repro.core import imputation

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
CELL = "cora.impute-k1"
SEED = 2 ** 33 + 12345
# f32 round-off over the 3 compared rounds, with room: a sound CPU run reads
# at most 3.9e-7 at SEED and 9.8e-6 at seed 3100000000; the control, the
# faults and the stale generator read 0.02 or more on at least one.
F32_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-4, "change_gap": 1e-4}
GROSS_FAULTS = ("half_batch", "unchanged")


@pytest.fixture(scope="module")
def cell():
    c = spec.load_cell(ROOT, CELL)
    c.config = copy.deepcopy(c.config)
    c.config["fgl_train"]["scale"] = 0.25
    return c


def run(cell, step_patch=None):
    return cellrun.run_cell(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                            impl="reference", devices=jax.devices()[:1],
                            step_patch=step_patch)


def fails_f32(checks):
    return any(c["value"] > F32_LIMITS[name] for name, c in checks.items())


def _stale_train_generator(self, key, ae, ae_opt, asr, as_opt, h_real, flat_mask):
    """The generator round as it stood before it alternated: the scan bodies
    close over names rebound between ``lax.scan`` calls, and the trace of
    each body is reused, so every outer pass trains against the first pass's
    counterpart."""
    cfg = self.cfg
    theta = cfg.theta(self.num_classes)
    n = h_real.shape[0]
    e = (assessor_lib.negative_mask(h_real, theta) if self.use_ns
         else jnp.ones_like(h_real))
    _, ks = jax.random.split(key)
    s_noise = imputation.sample_noise(ks, n, self.num_classes)

    def ae_step(carry, _):
        ae, ae_opt = carry
        grads = jax.grad(lambda p: assessor_lib.autoencoder_loss(
            p, asr_current[0], s_noise, h_real, e, flat_mask))(ae)
        ae, ae_opt = self.gen_opt.update(grads, ae_opt, ae)
        return (ae, ae_opt), ()

    def as_step(carry, _):
        asr, as_opt = carry
        _, h_fake = imputation.reconstruct(ae_current[0], s_noise)
        grads = jax.grad(lambda p: assessor_lib.assessor_loss(
            p, h_real, h_fake, e, flat_mask))(asr)
        asr, as_opt = self.gen_opt.update(grads, as_opt, asr)
        return (asr, as_opt), ()

    for _ in range(cfg.ae_outer_iters):
        asr_current = (asr, as_opt)
        (ae, ae_opt), _ = jax.lax.scan(ae_step, (ae, ae_opt), None, length=cfg.ae_iters)
        ae_current = (ae, ae_opt)
        (asr, as_opt), _ = jax.lax.scan(as_step, (asr, as_opt), None,
                                        length=cfg.assessor_iters)
    return ae, ae_opt, asr, as_opt, s_noise


def stale_generator(trainer):
    """The stale-generator fault: the trainer's imputation program rebuilt on
    ``_stale_train_generator``."""
    assert trainer.use_assessor and trainer.use_ns
    trainer._train_generator = types.MethodType(_stale_train_generator, trainer)
    trainer._impute_fn = trainer._impute_step_fn = jax.jit(trainer._impute)
    return trainer.step


def test_a_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert not fails_f32(res["checks"]), res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"round_ms", "setup_s"}


def test_the_control_reads_above_f32_round_off(cell):
    program = cellrun.build(cell, "reference")
    n = cell.limits["compare_rounds"]
    ref = cellrun.reference_readings(cellrun.make_reference(cell, program), program, SEED, n)
    ctl = cellrun.reference_readings(cellrun.make_reference(cell, program, "control"),
                                     program, SEED, n)
    assert fails_f32(compare.checks(ctl, ref, cell.limits))


@pytest.mark.parametrize("fault", sorted(faults.FAULTS) + ["stale_generator"])
def test_a_fault_under_the_timed_path_is_caught(cell, fault):
    """A gross fault fails the cell's own limits; every fault, the stale
    generator included, reads above f32 round-off."""
    res = run(cell, faults.FAULTS.get(fault, stale_generator))
    assert fails_f32(res["checks"]), res["checks"]
    if fault in GROSS_FAULTS:
        assert not res["correct"], res["checks"]


# -- the readers, on a synthetic trace -------------------------------------

MS = 1_000_000  # ns
NEW = ("impute.device_ms", "sim_topk_roofline")
SIM_TOPK = ('%sim_topk.3 = (f32[3,1024,4], s32[3,1024,4]) custom-call(), '
            'custom_call_target="tpu_custom_call"')


def two_rounds(impute="jit__impute", chips=1):
    """Two imputation rounds as a TPU records them: step spans [0, 5) and
    [10, 15) ms, syncs after. Each round runs the local program [0.5, 1.4),
    the imputation program [1.5, 3.5) (the generator's loop, the top-k
    kernel [2.9, 3.1), the patch), the aggregation and the evaluation; round
    1 is round 0 shifted by 10 ms. A chip past the first runs each op
    ``0.01 * chip`` ms longer."""
    host = [["chipbench.step", 0, 5 * MS, 0], ["chipbench.sync", 5 * MS, 10 * MS, 0],
            ["chipbench.step", 10 * MS, 15 * MS, 1], ["chipbench.sync", 15 * MS, 20 * MS, 1]]
    devices = {}
    for c in range(chips):
        longer = 0.01 * c
        ops, modules = [], []
        for t0 in (0, 10):
            def op(name, s, e):
                return [name, (t0 + s) * MS, (t0 + e + longer) * MS]
            ops += [op("%jvp_vmap_jit_sage_aggregate___.6 = custom-call(), "
                       'custom_call_target="tpu_custom_call"', 0.5, 1.4),
                    op("%while.72 = (f32[3,16,7]) while()", 1.5, 2.8),
                    op(SIM_TOPK, 2.9, 3.1),
                    op("%fusion.40 = f32[6,914,1433] fusion()", 3.2, 3.4),
                    op("%reduce.2 = f32[1433,64] reduce()", 3.6, 3.7),
                    op("%fusion.2 = f32[6,914,7] fusion()", 3.8, 4.5)]
            modules += [op("jit__local_rounds(1)", 0.5, 1.4),
                        op(f"{impute}(4)", 1.5, 3.5),
                        op("jit__aggregate(3)", 3.6, 3.7),
                        op("jit__evaluate(2)", 3.8, 4.5)]
        devices[str(c)] = {"ops": ops, "modules": modules}
    return {"devices": devices, "host": host}


STATS = {"nodes": [400] * 6, "edges": [1800] * 6, "targets": [380] * 6,
         "d": 1433, "hidden": 64, "c": 7, "servers": 3, "top_k": 4}


def readings(ext, chips=1, imputes=True):
    """Every reader of the cell on ``ext``."""
    cell = spec.load_cell(ROOT, CELL)
    ctx = cellrun.ReadContext(cell=cell, trace=devtrace.Trace(ext), stats=STATS,
                              peaks=spec.load_peaks("TPU v5 lite"), build_s=1.5,
                              chips=chips, schedule={**cell.schedule, "imputes": imputes})
    return {m["name"]: cell.reader(m["name"])(ctx) for m in cell.per_layer}


def test_the_cell_reads_every_metric_and_the_new_ones_only_it():
    bench = spec.load_benchmark(ROOT)
    cells = [w["name"] for w in bench["workloads"]]
    assert CELL in cells
    for m in bench["per_layer"]:
        assert m["workloads"] == ([CELL] if m["name"] in NEW else cells), m["name"]
        if m["name"] in NEW:
            assert m["source"] == "device_trace" and m["moves"] == "round_ms"
    read = readings(two_rounds())
    assert set(read) == {m["name"] for m in bench["per_layer"]}
    assert all(v is not None for v in read.values()), read


def test_the_imputation_program_read_by_hand():
    """[1.5, 3.5) ms in each of the two imputation rounds."""
    assert readings(two_rounds())["impute.device_ms"] == pytest.approx(2.0)


def test_the_top_k_kernel_against_its_roofline_by_hand():
    """Each server searches 2 clients of 400 rows against the other's 380
    targets: 2 x 2 x 7 x 400 x 380 FLOPs (0.02 us at 197 TFLOP/s) against
    4 x 7 x (800 + 760) bytes read and 2 x 4 x 4 x 800 written (0.085 us at
    819 GB/s), so memory-bound; 3 servers and 2 rounds over the kernel's
    0.2 ms a round."""
    least = 2 * 3 * (4 * 7 * (800 + 760) + 2 * 4 * 4 * 800) / 819e9
    assert readings(two_rounds())["sim_topk_roofline"] == pytest.approx(
        100 * least / 0.4e-3)


def test_chips_are_averaged():
    """Chip 1's programs and ops each end 0.01 ms later."""
    read = readings(two_rounds(chips=2), chips=2)
    assert read["impute.device_ms"] == pytest.approx((2.0 + 2.01) / 2)
    least = 2 * 3 * (4 * 7 * (800 + 760) + 2 * 4 * 4 * 800) / 819e9
    assert read["sim_topk_roofline"] == pytest.approx(
        100 * least / ((0.4e-3 + 0.42e-3) / 2 * 2))


@pytest.mark.parametrize("module", ["jit_impute", "jit__impute_round", "jit__local_rounds"])
def test_a_program_without_the_imputation_program_reads_nothing(module):
    assert readings(two_rounds(impute=module))["impute.device_ms"] is None


def test_a_trace_without_the_kernel_reads_no_roofline():
    ext = two_rounds()
    for dev in ext["devices"].values():
        dev["ops"] = [o for o in dev["ops"] if o[0] != SIM_TOPK]
    assert readings(ext)["sim_topk_roofline"] is None


def test_rounds_without_imputation_read_nothing():
    read = readings(two_rounds(), imputes=False)
    assert read["impute.device_ms"] is None and read["sim_topk_roofline"] is None


def test_the_top_k_kernel_is_not_the_aggregation_kernel():
    sage = readings(two_rounds())["sage_aggregate_roofline"]
    ext = two_rounds()
    for dev in ext["devices"].values():
        dev["ops"] = [o for o in dev["ops"] if o[0] != SIM_TOPK]
    assert readings(ext)["sage_aggregate_roofline"] == pytest.approx(sage)

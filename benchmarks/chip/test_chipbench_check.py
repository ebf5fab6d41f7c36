"""The comparison that decides ``correct``, driven through a whole run.

Each test skips the harness's look for a chip and drives the rest of a run
of ``cora-fedavg.local-e1`` on the CPU, at a quarter of the graph (677 nodes,
the published 1,433 features) and with the kernels' jnp path, against the
cell's own limits: a sound run is correct; the control (the reference one
precision step below, in the program's place) and every fault planted under
the timed path are not.
"""
import copy
import pathlib
import time

import jax
import pytest

from chipbench import cellrun, compare, faults, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 33 + 12345


@pytest.fixture(scope="module")
def cell():
    c = spec.load_cell(ROOT, "cora-fedavg.local-e1")
    c.config = copy.deepcopy(c.config)
    c.config["fgl_train"]["scale"] = 0.25
    return c


def run(cell, step_patch=None):
    return cellrun.run_cell(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                            impl="reference", devices=jax.devices()[:1],
                            step_patch=step_patch)


def test_a_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"round_ms", "setup_s"}


def test_the_control_is_not_correct(cell):
    program = cellrun.build(cell, "reference")
    n = cell.limits["compare_rounds"]
    ref = cellrun.reference_readings(cellrun.make_reference(cell, program), program, SEED, n)
    ctl = cellrun.reference_readings(cellrun.make_reference(cell, program, "control"),
                                     program, SEED, n)
    checks = compare.checks(ctl, ref, cell.limits)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_under_the_timed_path_is_not_correct(cell, fault):
    res = run(cell, faults.FAULTS[fault])
    assert not res["correct"], res["checks"]

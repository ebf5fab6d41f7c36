"""The reader of the aggregation program's device time, ``aggregate.device_ms``."""
import pathlib

import pytest

from chipbench import cellrun, devtrace, spec

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
MS = 1_000_000  # ns
EXISTING = ("setup.build_s", "round.idle_share", "round.mfu", "local.device_ms",
            "sage_aggregate_roofline", "evaluate.device_ms")


def two_rounds(aggregate="jit__aggregate", chips=1):
    """Two rounds as a TPU records them: step spans [0, 2) and [5, 7) ms,
    syncs after. Each round runs the local program, the aggregation program
    and the evaluation; an op event names its HLO instruction, not its scope.
    Round 0's aggregation runs [1.5, 1.6) ms with ops [1.5, 1.53) and
    [1.52, 1.56); round 1's runs [6.2, 6.3) ms with one op [6.2, 6.25).
    A chip past the first runs each op ``0.01 * chip`` ms longer."""
    host = [["chipbench.step", 0, 2 * MS, 0], ["chipbench.sync", 2 * MS, 5 * MS, 0],
            ["chipbench.step", 5 * MS, 7 * MS, 1], ["chipbench.sync", 7 * MS, 10 * MS, 1]]
    devices = {}
    for c in range(chips):
        longer = 0.01 * c

        def op(name, s, e):
            return [name, s * MS, (e + longer) * MS]
        ops = [op("%jvp_vmap_jit_sage_aggregate___.6 = custom-call(), "
                  'custom_call_target="tpu_custom_call"', 0.5, 1.4),
               op("%reduce.2 = f32[1433,64] reduce()", 1.5, 1.53),
               op("%broadcast.11 = f32[6,1433,64] broadcast()", 1.52, 1.56),
               op("%fusion.2 = f32[6,914,7] fusion()", 1.7, 3.0),
               op("%jvp_vmap_jit_sage_aggregate___.6 = custom-call(), "
                  'custom_call_target="tpu_custom_call"', 5.2, 6.1),
               op("%reduce.2 = f32[1433,64] reduce()", 6.2, 6.25),
               op("%fusion.2 = f32[6,914,7] fusion()", 6.4, 8.0)]
        modules = [["jit__local_rounds(1)", 0.5 * MS, 1.4 * MS],
                   [f"{aggregate}(3)", 1.5 * MS, 1.6 * MS],
                   ["jit__evaluate(2)", 1.7 * MS, 3.0 * MS],
                   ["jit__local_rounds(1)", 5.2 * MS, 6.1 * MS],
                   [f"{aggregate}(3)", 6.2 * MS, 6.3 * MS],
                   ["jit__evaluate(2)", 6.4 * MS, 8.0 * MS]]
        devices[str(c)] = {"ops": ops, "modules": modules}
    return {"devices": devices, "host": host}


def readings(ext, chips=1):
    """Every reader of the cell on ``ext``."""
    cell = spec.load_cell(ROOT, "cora-fedavg.local-e1")
    stats = {"nodes": [400] * 6, "edges": [1800] * 6, "targets": [400] * 6,
             "d": 1433, "hidden": 64, "c": 7, "servers": 1, "top_k": 4}
    ctx = cellrun.ReadContext(cell=cell, trace=devtrace.Trace(ext), stats=stats,
                              peaks=spec.load_peaks("TPU v5 lite"), build_s=1.5,
                              chips=chips, schedule={**cell.schedule, "imputes": False})
    return {m["name"]: cell.reader(m["name"])(ctx) for m in cell.per_layer}


def test_both_cells_read_the_aggregation_program():
    bench = spec.load_benchmark(ROOT)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "aggregate.device_ms"]
    assert entry["source"] == "device_trace" and entry["moves"] == "round_ms"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]


def test_the_aggregation_program_read_by_hand():
    """Round 0: ops [1.5, 1.56) = 0.06 ms; round 1: [6.2, 6.25) = 0.05 ms;
    the kernel called ``sage_aggregate`` runs in the local program and is
    not one of them."""
    assert readings(two_rounds())["aggregate.device_ms"] == pytest.approx(0.11 / 2)


def test_chips_are_averaged():
    """Chip 1's ops each end 0.01 ms later: round 0 [1.5, 1.57), round 1
    [6.2, 6.26), so 0.13 ms against chip 0's 0.11."""
    read = readings(two_rounds(chips=2), chips=2)
    assert read["aggregate.device_ms"] == pytest.approx((0.11 + 0.13) / 2 / 2)


def test_only_the_ops_inside_the_window_count():
    """A run that began before the first step span counts from its start."""
    ext = two_rounds()
    ext["host"][0][1] = 1.54 * MS
    # round 0: [1.54, 1.56) = 0.02 ms; round 1 as before, 0.05 ms
    assert readings(ext)["aggregate.device_ms"] == pytest.approx(0.07 / 2)


@pytest.mark.parametrize("module", [
    "jit_aggregate",        # a jitted partial of Aggregator.aggregate, as before
    "jit__aggregate_phase",  # a longer name that begins the same
    "jit__local_rounds"])   # no aggregation program of its own
def test_a_program_without_the_aggregation_program_reads_nothing(module):
    assert readings(two_rounds(aggregate=module))["aggregate.device_ms"] is None


@pytest.mark.parametrize("name", EXISTING)
def test_the_aggregation_program_leaves_the_existing_readings_as_they_were(name):
    assert readings(two_rounds())[name] == pytest.approx(
        readings(two_rounds(aggregate="jit_aggregate"))[name])

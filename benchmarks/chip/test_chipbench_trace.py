"""The reduction from a device trace to per-layer metrics."""
import pathlib

import pytest

from chipbench import cellrun, devtrace, spec

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
MS = 1_000_000  # ns


def synthetic():
    """Two rounds on one chip: step spans [0, 2) and [5, 7) ms, syncs after."""
    host = [["chipbench.step", 0, 2 * MS, 0], ["chipbench.sync", 2 * MS, 5 * MS, 0],
            ["chipbench.step", 5 * MS, 7 * MS, 1], ["chipbench.sync", 7 * MS, 10 * MS, 1]]
    ops = [["fusion.1", 1 * MS, 3 * MS], ["custom-call.3 jit(_local_rounds)/jit(sage_aggregate)/pallas_call", 2.5 * MS, 4 * MS],
           ["collective-permute-done", 6 * MS, 6.5 * MS], ["fusion.1", 8 * MS, 9 * MS]]
    modules = [["jit__local_rounds(1)", 1 * MS, 4 * MS], ["jit__evaluate(2)", 6 * MS, 9 * MS]]
    return {"devices": {"0": {"ops": ops, "modules": modules}}, "host": host}


def test_busy_idle_and_gaps_by_hand():
    tr = devtrace.Trace(synthetic())
    assert tr.window_s == pytest.approx(0.010)
    # ops cover [1, 4) + [6, 6.5) + [8, 9) ms = 4.5 ms
    assert tr.busy_s("0") == pytest.approx(0.0045)
    assert tr.module_s("0", lambda n: "_local_rounds" in n) == pytest.approx(0.003)
    assert tr.op_s("0", lambda n: "collective" in n) == pytest.approx(0.0005)
    gaps = sorted(tr.idle_gaps("0"), key=lambda g: -g[1])
    # [0,1) step; [4,6), its middle on the first sync's end; [6.5,8) and
    # [9,10) in the second sync
    assert gaps == [("end-of-round sync", pytest.approx(0.002)),
                    ("end-of-round sync", pytest.approx(0.0015)),
                    ("step dispatch", pytest.approx(0.001)),
                    ("end-of-round sync", pytest.approx(0.001))]
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(0.003)]
    assert len(bd["idle_gaps"]) == 4


def test_readers_on_a_synthetic_trace():
    cell = spec.load_cell(ROOT, "cora-fedavg.local-e1")
    stats = {"nodes": [400] * 6, "edges": [1800] * 6, "targets": [400] * 6,
             "d": 1433, "hidden": 64, "c": 7, "servers": 1, "top_k": 4}
    ctx = cellrun.ReadContext(cell=cell, trace=devtrace.Trace(synthetic()), stats=stats,
                              peaks=spec.load_peaks("TPU v5 lite"), build_s=1.5, chips=1,
                              schedule={**cell.schedule, "imputes": False})
    read = {m["name"]: cell.reader(m["name"])(ctx) for m in cell.per_layer}
    assert read["setup.build_s"] == 1.5
    assert read["round.idle_share"] == pytest.approx(55.0)
    assert read["local.device_ms"] == pytest.approx(1.5)
    assert read["evaluate.device_ms"] == pytest.approx(1.5)
    work = cell.work()
    least = work.aggregation_least_time(stats, ctx.schedule, [0, 1], ctx.peaks)
    assert read["sage_aggregate_roofline"] == pytest.approx(100 * least / 0.0015)
    flops = sum(work.round_flops(stats, ctx.schedule, t) for t in (0, 1))
    assert read["round.mfu"] == pytest.approx(100 * flops / (0.010 * 197e12))


def test_a_reader_that_finds_nothing_returns_nothing():
    ext = synthetic()
    ext["devices"]["0"]["ops"] = [o for o in ext["devices"]["0"]["ops"] if "pallas_call" not in o[0]]
    cell = spec.load_cell(ROOT, "cora-fedavg.local-e1")
    ctx = cellrun.ReadContext(cell=cell, trace=devtrace.Trace(ext), stats={}, peaks=None,
                              build_s=0.0, chips=1, schedule=cell.schedule)
    assert cell.reader("sage_aggregate_roofline")(ctx) is None

"""The work functions against counts made by hand on a tiny graph."""
import pathlib

import pytest

from chipbench import spec

WORK = spec.load_module(pathlib.Path(__file__).resolve().parent / "work" / "sage.py")
# Two clients under one server: 3 and 2 real nodes, 4 and 2 directed edges;
# 5 features, hidden 4, 3 classes.
STATS = {"nodes": [3, 2], "edges": [4, 2], "targets": [3, 2], "d": 5, "hidden": 4,
         "c": 3, "servers": 1, "top_k": 2}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_aggregation_counts_real_edges_and_bytes():
    # 2*nnz*d FLOPs; read h and write the output (n*d f32 each), 2 int32 per edge.
    assert WORK.aggregation(3, 4, 5) == (40.0, 4 * 3 * 5 * 2 + 8 * 4)


def test_forward_and_train_step_by_hand():
    # client 1: agg 2*4*5 + 2 dense 3x5x4, agg 2*4*4 + 2 dense 3x4x3
    #   = 40 + 240 + 32 + 144; client 2 = 20 + 160 + 16 + 96
    assert WORK.sage_forward(STATS) == 748.0
    # backward: layer-1 weight grads (2 dense), layer-2 weight and input grads
    # (2 dense each), A^T g for layer 2 = 240+144+144+32 and 160+96+96+16
    assert WORK.sage_train_step(STATS) == 748.0 + 560.0 + 368.0


def test_similarity_counts_other_clients_only():
    # 2*c per (row, target of another client): 2*3*3*2 + 2*3*2*3; reads rows
    # and targets (4*c each), writes k scores and indices per row.
    assert WORK.similarity(STATS) == [(72.0, 4 * 3 * 10 + 2 * 4 * 2 * 5)]


def test_generator_by_hand():
    # 5 rows. enc 3-16-5, dec 5-16-3, assessor 3-128-16-1.
    ae_step = (1280 + 2080) + (1280 + 2560) + (24480 + 24480)
    as_step = 1280 + 1280 + 2 * (24480 + 24480 + 20640)
    assert WORK.generator(STATS) == 3 * (5 * ae_step + 3 * as_step) + 1280


@pytest.mark.parametrize("imputes,t,expected_forwards", [
    (False, 0, 5), (True, 0, 6), (True, 1, 5)])
def test_round_work_follows_the_schedule(imputes, t, expected_forwards):
    sched = {"local_rounds": 4, "imputation_interval": 2, "imputes": imputes}
    assert WORK.forwards_with_kernel(sched, t) == expected_forwards
    flops = 4 * WORK.sage_train_step(STATS) + WORK.sage_forward(STATS)
    if imputes and t == 0:
        flops += WORK.sage_forward(STATS) + WORK.generator(STATS) + 72.0
    assert WORK.round_flops(STATS, sched, t) == flops


def test_least_time_takes_the_binding_roof():
    assert WORK.least_time(100.0, 5.0, PEAKS) == 1.0
    assert WORK.least_time(10.0, 50.0, PEAKS) == 5.0
    per_forward = sum(WORK.least_time(*l1, PEAKS) + WORK.least_time(*l2, PEAKS)
                      for l1, l2 in WORK.aggregation_calls(STATS))
    sched = {"local_rounds": 4, "imputation_interval": 2, "imputes": False}
    assert WORK.aggregation_least_time(STATS, sched, [0, 1], PEAKS) == 10 * per_forward

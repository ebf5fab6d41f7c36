"""Work the equations of a SpreadFGL round with GraphSAGE require.

Counts follow the configuration's equations, not the program: only real
nodes (``node_mask``), only real edges of each client's graph, only the
rounds that ran, and no padding. A dense layer is a matmul and counts
2·rows·d_in·d_out FLOPs; the mean aggregation counts 2·nnz·d FLOPs and the
bytes of reading h, writing its output and reading the edge list (two int32
per directed edge); the similarity search counts 2·c FLOPs for each pair of
a source row and a target slot of another client. Element-wise and
vector-unit work (softmax, masks, Adam, the top-k merge) is not counted.

``stats`` is a dict of per-client lists: ``nodes`` (real node slots) and
``edges`` (nonzero entries of the masked adjacency, i.e. directed edges),
``targets`` (real local slots that may be link targets) and the widths
``d``, ``hidden``, ``c``; ``servers`` is N.
"""
from __future__ import annotations

AE_HIDDEN = 16
ASSESSOR_DIMS = (128, 16, 1)
AE_ITERS, ASSESSOR_ITERS, OUTER_ITERS = 5, 3, 3
EDGE_BYTES = 8
F32 = 4


def dense_flops(rows: int, d_in: int, d_out: int) -> float:
    return 2.0 * rows * d_in * d_out


def aggregation(nodes: int, edges: int, d: int):
    """(FLOPs, bytes) of one mean aggregation over one client graph."""
    return 2.0 * edges * d, float(F32 * 2 * nodes * d + EDGE_BYTES * edges)


def aggregation_calls(stats):
    """(FLOPs, bytes) of each aggregation call of one forward, per client:
    a list of (layer-1 call, layer-2 call) pairs."""
    return [(aggregation(n, e, stats["d"]), aggregation(n, e, stats["hidden"]))
            for n, e in zip(stats["nodes"], stats["edges"])]


def sage_forward(stats) -> float:
    """Both layers of every client: aggregation plus h W_self + agg W_nbr."""
    d, hid, c = stats["d"], stats["hidden"], stats["c"]
    total = 0.0
    for n, e in zip(stats["nodes"], stats["edges"]):
        total += 2.0 * e * d + 2 * dense_flops(n, d, hid)
        total += 2.0 * e * hid + 2 * dense_flops(n, hid, c)
    return total


def sage_train_step(stats) -> float:
    """Forward plus backward. The backward needs every weight gradient, the
    gradient into layer 2's two inputs (h1 and its aggregate), and the
    aggregation's transpose for layer 2; nothing flows into the features."""
    d, hid, c = stats["d"], stats["hidden"], stats["c"]
    back = 0.0
    for n, e in zip(stats["nodes"], stats["edges"]):
        back += 2 * dense_flops(n, d, hid)            # layer-1 weight grads
        back += 2 * dense_flops(n, hid, c)            # layer-2 weight grads
        back += 2 * dense_flops(n, hid, c)            # layer-2 input grads
        back += 2.0 * e * hid                         # A^T g for layer 2
    return sage_forward(stats) + back


def _mlp(rows: int, dims, *, weight_grads: bool, input_grad: bool) -> float:
    """Forward of an MLP over ``dims``, plus the backward asked for."""
    fwd = sum(dense_flops(rows, a, b) for a, b in zip(dims[:-1], dims[1:]))
    if not (weight_grads or input_grad):
        return fwd
    back = 0.0
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        if weight_grads:
            back += dense_flops(rows, a, b)
        if input_grad or i > 0:
            back += dense_flops(rows, a, b)
    return fwd + back


def generator(stats) -> float:
    """The generator round of every server: 3 x (5 autoencoder steps, 3
    assessor steps) on the server's real rows, then X_bar = f(S)."""
    d, c = stats["d"], stats["c"]
    enc = (c, AE_HIDDEN, d)
    dec = (d, AE_HIDDEN, c)
    assessor = (c,) + ASSESSOR_DIMS
    m_per = len(stats["nodes"]) // stats["servers"]
    total = 0.0
    for j in range(stats["servers"]):
        rows = sum(stats["nodes"][j * m_per:(j + 1) * m_per])
        # AE step: S -> f -> h -> assessor, grads into the AE weights and,
        # through the frozen assessor, into its input.
        ae_step = (_mlp(rows, enc, weight_grads=True, input_grad=False)
                   + _mlp(rows, dec, weight_grads=True, input_grad=True)
                   + _mlp(rows, assessor, weight_grads=False, input_grad=True))
        # Assessor step: reconstruction forward, assessor on real and fake.
        as_step = (_mlp(rows, enc, weight_grads=False, input_grad=False)
                   + _mlp(rows, dec, weight_grads=False, input_grad=False)
                   + 2 * _mlp(rows, assessor, weight_grads=True, input_grad=False))
        total += OUTER_ITERS * (AE_ITERS * ae_step + ASSESSOR_ITERS * as_step)
        total += _mlp(rows, enc, weight_grads=False, input_grad=False)
    return total


def similarity(stats):
    """[(FLOPs, bytes)] of the cross-client top-k search, one per server:
    read the source rows and the targets, write k scores and k indices."""
    c, k = stats["c"], stats["top_k"]
    m_per = len(stats["nodes"]) // stats["servers"]
    out = []
    for j in range(stats["servers"]):
        rows = stats["nodes"][j * m_per:(j + 1) * m_per]
        targets = stats["targets"][j * m_per:(j + 1) * m_per]
        total = sum(targets)
        flops = sum(2.0 * c * r * (total - t) for r, t in zip(rows, targets))
        out.append((flops, float(F32 * c * (sum(rows) + total) + 2 * F32 * k * sum(rows))))
    return out


def is_impute_round(t: int, schedule) -> bool:
    return schedule["imputes"] and t % schedule["imputation_interval"] == 0


def round_flops(stats, schedule, t: int) -> float:
    """All matmul-class FLOPs that round ``t`` requires."""
    total = schedule["local_rounds"] * sage_train_step(stats) + sage_forward(stats)
    if is_impute_round(t, schedule):
        total += (sage_forward(stats) + generator(stats)
                  + sum(f for f, _ in similarity(stats)))
    return total


def forwards_with_kernel(schedule, t: int) -> int:
    """Forward passes of round ``t`` whose aggregation the kernel serves:
    one per local step, one for the imputation embeddings, one to evaluate."""
    return schedule["local_rounds"] + int(is_impute_round(t, schedule)) + 1


def least_time(flops: float, nbytes: float, peaks) -> float:
    """Roofline: the larger of compute time at the bf16 peak and memory time."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def aggregation_least_time(stats, schedule, rounds, peaks) -> float:
    """Least time of every kernel-served aggregation call in ``rounds``."""
    per_forward = sum(least_time(*l1, peaks) + least_time(*l2, peaks)
                      for l1, l2 in aggregation_calls(stats))
    return per_forward * sum(forwards_with_kernel(schedule, t) for t in rounds)

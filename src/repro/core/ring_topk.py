"""Candidate-sharded ring top-k for the imputation similarity topology.

The adaptive generator's A̅ = H Hᵀ + cross-subgraph top-k (Sec. III-C) is the
FGL-side compute wall: every single-device path in ``imputation.
similarity_topk`` streams gram slabs against ALL n candidates — O(q·c·n) per
edge server, with the whole candidate set resident on one device. This module
distributes the CANDIDATE axis across the edge mesh instead, reusing the ring
``collective_permute`` schedule idiom of ``core/gossip.block_ring_gossip``:

- Each of the ``size`` mesh devices owns an ``[n/size, c]`` slice of the
  candidate features plus the matching client-id / target-mask slices (and an
  ``[q/size, c]`` slice of the query rows — in production queries ARE the
  candidates, every node needs links).
- Candidate slabs rotate around the ring: ``size`` fold steps, ``size - 1``
  single-neighbor ``collective_permute`` sends, each moving one slab of
  ``ring_rotation_bytes`` — never an all-gather of the candidate set.
- Each device folds the visiting slab into its running (vals, idx) top-k with
  :func:`repro.kernels.sim_topk.topk_merge` — the SAME streaming merge the
  fused Pallas kernel uses — offsetting slab-local columns by
  ``owner · n/size`` to global candidate indices. The merge tie-breaks by
  smallest global index (not arrival order), so the fold is invariant to the
  rotation order the shards arrive in.
- After ``size`` steps NO final gather/reduce of scores is needed: every
  device has already seen every candidate shard, so its partial top-k IS the
  exact global top-k for its query rows. The only output collective is the
  layout-level reassembly of the ``[q, k]`` result.

What the ring guarantees against the single-device reference is
:func:`topk_violations`'s contract, not bit equality: each device's gram
slab is a different dot-product call than the reference's, and XLA may sum
the c products in another order, so scores can differ in the last bits
(up to 9.5e-7 at n=64, c=6 on 2 CPU devices, jax 0.9.0). Scores agree
within :func:`dot_tolerance`; the same slots are empty; and where an index
differs, the chosen candidate is valid and its exact (float64) similarity
is within that tolerance of the reference's score at that rank: a near-tie
resolved the other way. ``tests/test_ring_topk.py`` pins this on 2/4/8
emulated devices, including non-divisible n, fully-masked rows, ties, and
k > valid candidates.

Byte/FLOP accounting for the scaling benchmark
(``benchmarks/bench_sim_scaling.py``) lives at the bottom, next to the
gossip byte model's conventions in ``core/gossip.py``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sim_topk import topk_merge


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int, value) -> jnp.ndarray:
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def fold_slab(run_v: jnp.ndarray, run_i: jnp.ndarray,
              rows: jnp.ndarray, row_cid: jnp.ndarray,
              cand: jnp.ndarray, cand_cid: jnp.ndarray,
              cand_mask: jnp.ndarray, offset) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one candidate slab into the running top-k of the query rows.

    rows: [..., q, c]; cand: [..., m, c]; the gram tile is masked to
    cross-subgraph valid targets and merged via :func:`topk_merge` with
    slab-local columns shifted by ``offset`` to global candidate indices.
    """
    # HIGHEST: f32 products, as in the sim_topk kernel; at default precision
    # a TPU would round the inputs to bf16.
    s = jnp.einsum("...qc,...nc->...qn", rows, cand,
                   precision=jax.lax.Precision.HIGHEST)
    keep = ((row_cid[..., :, None] != cand_cid[..., None, :])
            & (cand_mask[..., None, :] > 0))
    s = jnp.where(keep, s, -jnp.inf)
    col = offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return topk_merge(run_v, run_i, s, col)


def _ring_fold(rows, row_cid, cand, cand_cid, cand_mask, *, k: int,
               axis: Optional[str], size: int):
    """The per-shard ring schedule: ``size`` folds, ``size - 1`` rotations.

    Runs inside ``shard_map`` when ``axis`` names a mesh axis (each argument
    is this device's slice) or standalone with ``axis=None, size=1`` (single
    slab covering the whole candidate axis — the degenerate mesh).
    """
    shard_n = cand.shape[-2]
    run_v = jnp.full(rows.shape[:-1] + (k,), -jnp.inf, jnp.float32)
    run_i = jnp.full(rows.shape[:-1] + (k,), -1, jnp.int32)
    if axis is None or size == 1:
        return fold_slab(run_v, run_i, rows, row_cid,
                         cand, cand_cid, cand_mask, 0)
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % size) for i in range(size)]
    for step in range(size):
        # After ``step`` forward rotations this device holds the slab that
        # started on device (me - step) % size — its global index offset.
        owner = jnp.mod(me - step, size)
        run_v, run_i = fold_slab(run_v, run_i, rows, row_cid,
                                 cand, cand_cid, cand_mask, owner * shard_n)
        if step != size - 1:
            cand = jax.lax.ppermute(cand, axis, perm)
            cand_cid = jax.lax.ppermute(cand_cid, axis, perm)
            cand_mask = jax.lax.ppermute(cand_mask, axis, perm)
    return run_v, run_i


def ring_similarity_topk(h: jnp.ndarray, client_ids: jnp.ndarray,
                         target_mask: jnp.ndarray, k: int, *, mesh,
                         queries: Optional[jnp.ndarray] = None,
                         query_cid: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact global masked top-k with the candidate axis sharded on ``mesh``.

    h: ``[n, c]`` or batched ``[B, n, c]`` candidate features (the stacked
    [N]-server axis of the engine rides along replicated — each batch element
    keeps its own candidate set, never mixed across servers); client_ids
    ``[.., n]`` int; target_mask ``[.., n]`` valid-target mask. ``queries``
    (default: h — every node queries, the production case) may be any
    ``[.., q, c]`` row subset with its ``query_cid``; both axes are padded to
    mesh-size multiples internally (candidate padding carries mask 0, so it
    can never be selected; padded query rows are sliced off).

    Returns RAW (vals [.., q, k] f32 with -inf on missing candidates,
    idx [.., q, k] int32 with -1 where never filled) — the caller
    (``imputation.similarity_topk``) applies the (0.0, -1) convention.
    """
    if queries is None:
        queries, query_cid = h, client_ids
    batched = h.ndim == 3
    if not batched:
        h, client_ids, target_mask = (h[None], client_ids[None],
                                      target_mask[None])
        queries, query_cid = queries[None], query_cid[None]
    q = queries.shape[1]
    size = int(mesh.size)

    cid = client_ids.astype(jnp.int32)
    tmask = target_mask.astype(jnp.float32)
    qcid = query_cid.astype(jnp.int32)
    if size > 1:
        # Pad both axes to mesh-size multiples; padded candidates carry
        # mask 0 (never selected), padded query rows are sliced off below.
        h = _pad_axis(h, 1, size, 0.0)
        cid = _pad_axis(cid, 1, size, -1)
        tmask = _pad_axis(tmask, 1, size, 0.0)
        queries = _pad_axis(queries, 1, size, 0.0)
        qcid = _pad_axis(qcid, 1, size, -1)

        from jax.sharding import PartitionSpec as P
        axis = mesh.axis_names[0]
        sheet = P(None, axis)

        def shard_fn(qry, qc, cand, cc, cm):
            return _ring_fold(qry, qc, cand, cc, cm, k=k, axis=axis,
                              size=size)

        vals, idx = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(None, axis, None), sheet,
                      P(None, axis, None), sheet, sheet),
            out_specs=(P(None, axis, None), P(None, axis, None)),
            check_vma=False)(queries, qcid, h, cid, tmask)
    else:
        vals, idx = _ring_fold(queries, qcid, h, cid, tmask, k=k,
                               axis=None, size=1)
    vals, idx = vals[:, :q], idx[:, :q]
    if not batched:
        vals, idx = vals[0], idx[0]
    return vals, idx


# ---------------------------------------------------------------------------
# The agreement contract between two top-k searches over the same candidates.
# ---------------------------------------------------------------------------

def dot_tolerance(h) -> float:
    """Score tolerance between two f32 evaluations of ``h @ hᵀ``.

    One f32 dot product of length c is off by at most c·u·‖x‖·‖y‖ (u = ε/2
    the unit roundoff), so two summation orders differ by at most
    c·ε·max‖h_v‖². This returns four times that: the margin covers the
    TPU's multi-pass f32 matmul, whose rounding is not IEEE's.
    """
    h = np.asarray(h, np.float64)
    c = h.shape[-1]
    return float(4 * c * np.finfo(np.float32).eps
                 * max(np.max(np.sum(h * h, axis=-1)), 1e-30))


def topk_violations(h, client_ids, target_mask, scores, idx,
                    ref_scores, ref_idx, *, tol: float) -> dict:
    """Check a top-k against a reference top-k of the same candidates.

    All arrays are host arrays in ``imputation.similarity_topk``'s (0.0, -1)
    convention, optionally with a leading batch axis: h ``[.., n, c]``,
    client_ids/target_mask ``[.., n]``, scores/idx ``[.., n, k]``. The
    contract: the same slots are empty (-1); scores agree within ``tol``;
    and wherever an index differs from the reference, the chosen candidate
    is from another client, a valid target, not repeated in its row, and
    its exact float64 similarity is within ``tol`` of the reference's score
    at that rank. Returns ``{"max_score_diff", "index_diffs",
    "violations"}``; the contract holds iff ``violations == 0``.
    """
    h = np.asarray(h, np.float64)
    cid = np.asarray(client_ids)
    tmask = np.asarray(target_mask)
    scores, idx = np.asarray(scores, np.float64), np.asarray(idx)
    ref_scores, ref_idx = np.asarray(ref_scores, np.float64), np.asarray(ref_idx)
    if h.ndim == 2:
        h, cid, tmask = h[None], cid[None], tmask[None]
        scores, idx = scores[None], idx[None]
        ref_scores, ref_idx = ref_scores[None], ref_idx[None]
    diff = np.abs(scores - ref_scores)
    bad = int(np.sum((idx < 0) != (ref_idx < 0))) + int(np.sum(diff > tol))
    moved = (idx != ref_idx) & (idx >= 0)
    b, q, r = np.nonzero(moved)
    j = idx[b, q, r]
    exact = np.sum(h[b, q] * h[b, j], axis=-1)
    bad += int(np.sum(np.abs(exact - ref_scores[b, q, r]) > tol))
    bad += int(np.sum((cid[b, j] == cid[b, q]) | (tmask[b, j] <= 0)))
    srt = np.sort(np.where(idx >= 0, idx, -1 - np.arange(idx.shape[-1])), -1)
    bad += int(np.sum(srt[..., 1:] == srt[..., :-1]))
    return {"max_score_diff": float(diff.max(initial=0.0)),
            "index_diffs": int(np.sum(idx != ref_idx)),
            "violations": bad}


# ---------------------------------------------------------------------------
# Traffic / FLOP accounting (bench_sim_scaling; conventions as core/gossip.py).
# ---------------------------------------------------------------------------

def sim_topk_flops(q: int, n: int, c: int) -> float:
    """MXU FLOPs of the masked top-k sweep: the q×n gram at 2·c each.

    The streaming merge's compares are excluded (vector-unit noise next to
    the gram), matching the fused-kernel accounting in bench_kernels.
    """
    return 2.0 * q * n * c


def ring_rotation_bytes(n: int, c: int, size: int, *,
                        itemsize: int = 4) -> float:
    """Bytes ONE device sends per rotation step: its current candidate slab.

    Each step permutes the [n/size, c] feature slab plus the [n/size]
    client-id (int32) and target-mask (float32) slices to one ring neighbor.
    """
    if size <= 1:
        return 0.0
    shard = (n + size - 1) // size
    return float(shard * (c * itemsize + 4 + 4))


def ring_total_bytes(n: int, c: int, size: int, *, itemsize: int = 4) -> float:
    """Per-device cross-device bytes of one full sweep: size-1 rotations.

    Compare ``allgather_bytes``: rotating slabs moves the same total volume
    as a ring all-gather of the candidates WOULD, but peak per-device
    residency stays at one slab instead of the full [n, c] matrix — that is
    what makes million-node candidate sets fit.
    """
    return (size - 1) * ring_rotation_bytes(n, c, size, itemsize=itemsize)


def allgather_bytes(n: int, c: int, size: int, *, itemsize: int = 4) -> float:
    """Per-device bytes of the rejected alternative: all-gather candidates
    then run the single-device kernel on the full [n, c] locally."""
    if size <= 1:
        return 0.0
    return (size - 1) / size * float(n * (c * itemsize + 4 + 4))

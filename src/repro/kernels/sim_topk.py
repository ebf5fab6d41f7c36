"""Fused masked top-k similarity Pallas kernel for the imputation generator.

The graph imputation generator builds A̅ = H Hᵀ (Sec. III-C) over all nodes an
edge server covers — O(n²c) and the FGL-side hot spot — then keeps only the
top-k most similar *cross-subgraph* candidates per node. The jnp reference
path (imputation.similarity_topk) materializes a [block, n] gram slab in HBM,
masks it, and runs ``jax.lax.top_k`` over all n columns per row block.

This kernel fuses all three steps: each (row-block, col-block) grid step
computes one gram tile on the MXU, applies the same-client mask and the
candidate-target mask in registers, and folds the tile into a running
(values, indices) top-k carried in VMEM scratch across column tiles —
flash-attention style, so the [block_m, n] slab never round-trips through
HBM and the top-k reduction is streamed instead of re-run over all n columns.

The contraction dim c (num classes ≤ 15 in the paper's datasets) is far below
the 128-lane MXU width, so tiles are (block_m × c) @ (c × block_n): the cost
is dominated by streaming H, which the column grid tiles through VMEM.

Masked-out candidates carry -inf values; the running top-k seeds index slots
with -1, so rows with fewer than k valid candidates surface (-inf, -1) pairs
that ``imputation.similarity_topk`` maps to the (0.0, -1) convention. The
streaming merge (:func:`topk_merge`, shared with the candidate-sharded ring
driver in ``core/ring_topk.py``) breaks ties by smallest candidate index,
matching ``jax.lax.top_k``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def topk_merge(run_v: jnp.ndarray, run_i: jnp.ndarray, slab_v: jnp.ndarray,
               slab_i: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold a candidate slab into a running (values, indices) top-k.

    The ONE streaming top-k merge shared by the fused Pallas kernel (column
    tiles arriving left to right), the jnp reference path, and the ring-
    sharded driver (``core/ring_topk.py``, candidate shards arriving in
    rotation order — NOT in index order). ``run_v``/``run_i`` are the
    ``[..., k]`` running top-k (-inf values / -1 indices on unfilled slots);
    ``slab_v``/``slab_i`` are a ``[..., m]`` slab of new candidates with
    -inf on masked entries and their (global) candidate indices.

    Selects the k largest of the k+m candidates with k unrolled argmax
    passes (k is small — the paper uses k ≤ 5 — and Mosaic has no sort/
    top_k primitive). Ties break by SMALLEST candidate index — jax.lax.
    top_k's tie-break on the full row — by value, not by position, so the
    result is independent of the order slabs are folded in: this is the
    invariant that lets per-shard partial top-ks over rotating candidate
    slabs finish with the single-device answer (up to the rounding of
    their own gram tiles, see ``ring_topk.topk_violations``).

    Exhausted rows (best == -inf) select among stale popped entries and
    unfilled -1 slots; the emitted index is forced to -1 either way, so
    rows with fewer than k valid candidates keep the (-inf, -1) convention.
    Live candidates always carry distinct indices (each candidate is folded
    exactly once), so exactly one entry pops per pass.
    """
    k = run_v.shape[-1]
    cand_v = jnp.concatenate([run_v, slab_v], axis=-1)     # [..., k+m]
    cand_i = jnp.concatenate([run_i, slab_i], axis=-1)
    new_v, new_i = [], []
    for _ in range(k):
        best = jnp.max(cand_v, axis=-1, keepdims=True)     # [..., 1]
        at_best = cand_v == best
        sel_i = jnp.min(jnp.where(at_best, cand_i, jnp.int32(2**30)),
                        axis=-1, keepdims=True)
        sel = at_best & (cand_i == sel_i)
        new_v.append(best)
        new_i.append(jnp.where(best > -jnp.inf, sel_i, -1))
        cand_v = jnp.where(sel, -jnp.inf, cand_v)
    return (jnp.concatenate(new_v, axis=-1),
            jnp.concatenate(new_i, axis=-1))


def _sim_topk_kernel(rows_ref, h_ref, row_cid_ref, col_cid_ref, col_mask_ref,
                     vals_ref, idx_ref, vals_scratch, idx_scratch,
                     *, k: int, block_n: int, col_offset: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        vals_scratch[...] = jnp.full_like(vals_scratch, -jnp.inf)
        idx_scratch[...] = jnp.full_like(idx_scratch, -1)

    rows = rows_ref[...].astype(jnp.float32)            # [bm, c]
    h = h_ref[...].astype(jnp.float32)                  # [bn, c]
    # HIGHEST: f32 products whatever the caller's default matmul precision,
    # so scores round as in the ring driver's ``fold_slab``.
    s = jax.lax.dot_general(rows, h, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [bm, bn]

    # Fused masking: cross-subgraph only + valid candidate targets only.
    keep = (row_cid_ref[...] != col_cid_ref[...]) & (col_mask_ref[...] > 0)
    s = jnp.where(keep, s, -jnp.inf)
    # col_offset shifts local column positions to GLOBAL candidate indices
    # when the caller owns one shard of a larger candidate axis.
    col_idx = (col_offset + ki * block_n
               + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    new_v, new_i = topk_merge(vals_scratch[...], idx_scratch[...], s, col_idx)
    vals_scratch[...] = new_v
    idx_scratch[...] = new_i

    @pl.when(ki == nk - 1)
    def _finalize():
        vals_ref[...] = vals_scratch[...].astype(vals_ref.dtype)
        idx_ref[...] = idx_scratch[...]


def sim_topk(rows: jnp.ndarray, h: jnp.ndarray, row_cid: jnp.ndarray,
             col_cid: jnp.ndarray, col_mask: jnp.ndarray, k: int, *,
             block_m: int = 128, block_n: int = 512, col_offset: int = 0,
             interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused masked top-k over the gram similarity rows @ hᵀ.

    rows: [b, c] query nodes; h: [n, c] candidate nodes; row_cid: [b, 1] and
    col_cid: [1, n] owning-client ids; col_mask: [1, n] valid-target mask
    (padding handled by ops.py). ``col_offset`` shifts emitted indices so a
    caller holding one shard of a larger candidate axis (``core/ring_topk``)
    gets GLOBAL candidate indices. Returns (vals [b, k] f32 with -inf on
    missing candidates, idx [b, k] int32 with -1 where never filled).
    """
    b, c = rows.shape
    n, c2 = h.shape
    assert c == c2
    assert b % block_m == 0 and n % block_n == 0, (b, n, block_m, block_n)
    assert 1 <= k <= n, (k, n)

    grid = (b // block_m, n // block_n)
    kernel = functools.partial(_sim_topk_kernel, k=k, block_n=block_n,
                               col_offset=col_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, c), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, c), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, k), jnp.float32),   # running top-k values
            pltpu.VMEM((block_m, k), jnp.int32),     # running top-k indices
        ],
        interpret=interpret,
    )(rows, h, row_cid, col_cid, col_mask)


def _sim_kernel(rows_ref, h_ref, o_ref):
    rows = rows_ref[...].astype(jnp.float32)    # [bm, c]
    h = h_ref[...].astype(jnp.float32)          # [bn, c]
    o_ref[...] = jax.lax.dot_general(
        rows, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def sim_block(rows: jnp.ndarray, h: jnp.ndarray, *, block_m: int = 128,
              block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """rows: [b, c]; h: [n, c] -> [b, n] gram slab (padded by ops.py).

    The unfused building block (no masking, no top-k): kept as the
    micro-benchmark baseline the fused kernel is measured against and for
    callers that need the raw slab.
    """
    b, c = rows.shape
    n, c2 = h.shape
    assert c == c2
    assert b % block_m == 0 and n % block_n == 0, (b, n, block_m, block_n)

    grid = (b // block_m, n // block_n)
    return pl.pallas_call(
        _sim_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, c), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, c), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), rows.dtype),
        interpret=interpret,
    )(rows, h)

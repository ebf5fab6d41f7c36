"""Fused GraphSAGE neighbor aggregation Pallas kernel (Eq. 3 hot spot).

Computes ``(A @ H) / max(rowsum(A), 1)`` in one pass: a tiled matmul over the
neighbor (contraction) dimension that accumulates both the aggregate and the
row degree in VMEM scratch, dividing on the last contraction step. Saves one
full read of A versus materializing the degree separately.

Grid: (row_blocks, col_blocks, k_blocks), k innermost. Tiles default to
128×128 (MXU-aligned); A tiles and H tiles stream HBM→VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sage_kernel(a_ref, h_ref, o_ref, acc_scratch, deg_scratch):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scratch[...] = jnp.zeros_like(acc_scratch)
        deg_scratch[...] = jnp.zeros_like(deg_scratch)

    a = a_ref[...].astype(jnp.float32)   # [bm, bk]
    h = h_ref[...].astype(jnp.float32)   # [bk, bn]
    # HIGHEST: f32 products whatever the caller's default matmul precision.
    acc_scratch[...] += jax.lax.dot_general(
        a, h, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    deg_scratch[...] += jnp.sum(a, axis=-1, keepdims=True)

    @pl.when(ki == nk - 1)
    def _finalize():
        deg = jnp.maximum(deg_scratch[...], 1.0)
        o_ref[...] = (acc_scratch[...] / deg).astype(o_ref.dtype)


def sage_aggregate(adj: jnp.ndarray, h: jnp.ndarray, *, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """adj: [n, n]; h: [n, d]; both padded to block multiples by ops.py."""
    n, n2 = adj.shape
    _, d = h.shape
    assert n2 == h.shape[0]
    assert n % block_m == 0 and n2 % block_k == 0 and d % block_n == 0

    grid = (n // block_m, d // block_n, n2 // block_k)
    return pl.pallas_call(
        _sage_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, 1), jnp.float32),
        ],
        interpret=interpret,
    )(adj, h)

"""Fused GraphSAGE neighbor aggregation Pallas kernel (Eq. 3 hot spot).

Computes ``(A @ H) / max(rowsum(A), 1)`` in one pass: a tiled matmul over the
neighbor (contraction) dimension that accumulates both the aggregate and the
row degree in VMEM scratch, dividing on the last contraction step. Saves one
full read of A versus materializing the degree separately.

Grid: (row_blocks, col_blocks, k_blocks), k innermost; A tiles and H tiles
stream HBM→VMEM. ``ops.sage_tiles`` picks the tiles from the shape and the
chip: multiples of 128 that divide the 128-padded sizes, so a larger tile
never adds padding, up to caps measured per ``device_kind``. The kernel asks
the compiler for twice the VMEM its buffers take (``vmem_limit_bytes``),
room for the temporaries of the f32 dot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE = 128
DEFAULT_SCOPED_VMEM = 16 << 20  # Mosaic's scoped VMEM on v5e when none is asked


def vmem_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """VMEM the kernel's buffers take, reckoned in f32: the double-buffered
    A, H and output tiles, the accumulator, and the degree column (one lane
    tile wide)."""
    return 4 * (2 * (block_m * block_k + block_k * block_n + block_m * block_n)
                + block_m * block_n + block_m * LANE)


def vmem_limit_bytes(block_m: int, block_n: int, block_k: int) -> int:
    """Scoped VMEM the kernel asks for: twice its buffers, at least the
    default."""
    return max(2 * vmem_bytes(block_m, block_n, block_k), DEFAULT_SCOPED_VMEM)


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
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(block_m, block_n, block_k)),
        interpret=interpret,
    )(adj, h)

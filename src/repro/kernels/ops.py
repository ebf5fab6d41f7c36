"""Jitted public wrappers around the Pallas kernels.

Handle padding to block multiples, GQA head broadcast, and the
interpret-mode switch (CPU validation). Models call these; they never touch
pl.pallas_call directly.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import sage_aggregate as _sage
from repro.kernels import sim_topk as _sim


def _round_up(size: int, multiple: int) -> int:
    return -(-size // multiple) * multiple


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0.0) -> jnp.ndarray:
    size = x.shape[axis]
    target = _round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool = True,
        window: Optional[int] = None, block_q: int = 128, block_kv: int = 128,
        interpret: bool = False) -> jnp.ndarray:
    """Multi-head flash attention.

    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] with Hq % Hkv == 0 (GQA).
    Returns [B, Hq, Sq, D].
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    assert causal, "non-causal (cross) attention uses the jnp reference path"
    if hkv != hq:  # broadcast kv heads across their GQA group
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    block_q = min(block_q, max(8, sq))
    qp = _pad_to(q.reshape(b * hq, sq, d), 1, block_q)
    kp = _pad_to(k.reshape(b * hq, skv, d), 1, block_kv)
    vp = _pad_to(v.reshape(b * hq, skv, d), 1, block_kv)
    # Padding keys must never win the softmax: they sit at positions >= skv,
    # beyond every query position, so the causal mask already removes them
    # (ops are always causal here; window only tightens the mask).
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              block_q=block_q, block_kv=block_kv,
                              interpret=interpret)
    return out[:, :sq].reshape(b, hq, sq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _sage_aggregate(adj, h, block_m, block_n, block_k, interpret):
    n, d = h.shape
    adj_p = _pad_to(_pad_to(adj, 0, block_m), 1, block_k)
    h_p = _pad_to(_pad_to(h, 0, block_k), 1, block_n)
    out = _sage.sage_aggregate(adj_p, h_p, block_m=block_m, block_n=block_n,
                               block_k=block_k, interpret=interpret)
    return out[:n, :d]


def _sage_aggregate_fwd(adj, h, block_m, block_n, block_k, interpret):
    return _sage_aggregate(adj, h, block_m, block_n, block_k, interpret), (adj, h)


def _sage_aggregate_bwd(block_m, block_n, block_k, interpret, res, g):
    # pallas_call has no autodiff rule: kernel forward, oracle backward. The
    # oracle computes the same clamped row-normalized mean, so its VJP is the
    # exact gradient of what the kernel produced (classifier training takes
    # grad through aggregation — see FGLTrainer._local_rounds).
    adj, h = res
    return jax.vjp(_ref.sage_aggregate, adj, h)[1](g)


_sage_aggregate.defvjp(_sage_aggregate_fwd, _sage_aggregate_bwd)


# Caps on sage_aggregate's (block_m, block_n, block_k) by device_kind, from
# a sweep on the chip (PERF.md). Kinds not listed, and interpret mode, take
# the default, whose largest tiles fit the default scoped VMEM.
_SAGE_CAPS = {"TPU v5 lite": (512, 1152, 512)}
_SAGE_CAPS_DEFAULT = (512, 512, 512)


def _divisor_tile(size: int, cap: int) -> int:
    """The largest multiple of 128 that divides the 128-padded size and is at
    most ``cap``: a larger tile never adds padding."""
    units = _round_up(size, 128) // 128
    return 128 * max(u for u in range(1, cap // 128 + 1) if units % u == 0)


def sage_tiles(n: int, d: int, device_kind: Optional[str] = None):
    """(block_m, block_n, block_k) of sage_aggregate for adj [n, n], h [n, d]
    on a chip of ``device_kind``."""
    cap_m, cap_n, cap_k = _SAGE_CAPS.get(device_kind, _SAGE_CAPS_DEFAULT)
    return (_divisor_tile(n, cap_m), _divisor_tile(d, cap_n),
            _divisor_tile(n, cap_k))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def sage_aggregate(adj: jnp.ndarray, h: jnp.ndarray, *,
                   block_m: Optional[int] = None, block_n: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: bool = False) -> jnp.ndarray:
    """Row-normalized neighbor aggregation; accepts arbitrary [n,n]/[n,d].
    A block left as None comes from ``sage_tiles`` for this shape and chip."""
    kind = None if interpret else jax.devices()[0].device_kind
    bm, bn, bk = sage_tiles(*h.shape, kind)
    return _sage_aggregate(adj, h, block_m or bm, block_n or bn, block_k or bk,
                           interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def sim_block(rows: jnp.ndarray, h: jnp.ndarray, *, block_m: int = 128,
              block_n: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Gram slab rows @ hᵀ; accepts arbitrary [b,c]/[n,c]."""
    b, n = rows.shape[0], h.shape[0]
    block_m = min(block_m, max(8, b))
    block_n = min(block_n, max(8, n))
    rows_p = _pad_to(rows, 0, block_m)
    h_p = _pad_to(h, 0, block_n)
    out = _sim.sim_block(rows_p, h_p, block_m=block_m, block_n=block_n,
                         interpret=interpret)
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("k", "block_m", "block_n",
                                             "col_offset", "interpret"))
def sim_topk(h: jnp.ndarray, client_ids: jnp.ndarray, target_mask: jnp.ndarray,
             k: int, *, block_m: int = 128, block_n: int = 512,
             col_offset: int = 0, interpret: bool = False):
    """Fused masked top-k similarity; accepts arbitrary [n,c]/[n]/[n].

    Per row of h: the k most similar rows of h whose ``client_ids`` differ
    and whose ``target_mask`` is set. ``col_offset`` shifts emitted indices
    to the global candidate axis when h is one shard of it. Returns (vals
    [n, k] f32 with -inf on missing candidates, idx [n, k] int32 with -1
    where never filled). Column padding gets mask 0, so padded slots can
    never be selected.
    """
    n = h.shape[0]
    block_m = min(block_m, max(8, n))
    block_n = min(block_n, max(8, n))
    rows_p = _pad_to(h, 0, block_m)
    h_p = _pad_to(h, 0, block_n)
    cid = client_ids.astype(jnp.int32)
    row_cid = _pad_to(cid[:, None], 0, block_m)
    col_cid = _pad_to(cid[None, :], 1, block_n)
    col_mask = _pad_to(target_mask.astype(jnp.float32)[None, :], 1, block_n)
    vals, idx = _sim.sim_topk(rows_p, h_p, row_cid, col_cid, col_mask, k,
                              block_m=block_m, block_n=block_n,
                              col_offset=col_offset, interpret=interpret)
    return vals[:n], idx[:n]

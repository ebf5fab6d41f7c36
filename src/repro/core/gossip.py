"""SpreadFGL's load-balanced neighbor aggregation (Eq. 16, Sec. III-E) as gossip.

The paper replaces a single FedAvg point with edge servers that average
parameters only with their topology neighbors (Sec. III-E, Fig. 8/9). Two
deployments of the same math live here:

1. **LM / multi-pod** (``ring_gossip``, ``all_average``, ``maybe_gossip``):
   each pod is an "edge server"; instead of an all-reduce over the ``pod``
   axis every step (classic data parallelism = classic FGL's FedAvg),
   parameters are exchanged with the two ring neighbors via
   ``collective_permute`` every K steps. Cross-pod ICI bytes drop from
   O(2·(P-1)/P · bytes / step) to O(2·bytes/K), and the paper's convergence
   claim (Fig. 8/9) transfers as the gossip-SGD convergence of the averaged
   iterates. These helpers assume they run inside ``shard_map`` with
   ``axis`` a named mesh axis, one server per shard.

2. **FGL / edge mesh** (``block_ring_gossip``, ``adjacency_gossip``): the
   stacked ``[N]`` edge-server axis of the FGL engine, where each mesh shard
   may own a *block* of servers (N need only be a multiple of the mesh
   size). ``strategies.GossipAggregator`` drives these; with ``every_k=1``
   and a ring adjacency they reproduce ``strategies.NeighborAggregator``
   exactly (the allclose regression in ``tests/test_gossip.py`` pins this).

The byte-accounting helpers at the bottom are the single home of the
cross-server traffic math used by ``launch/gossip_dryrun.py`` and
``benchmarks/bench_load_balance.py``.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def ring_gossip(params: PyTree, axis: str) -> PyTree:
    """Eq. 16 with a ring adjacency (self + both neighbors, equal weights)."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return params
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]

    def avg(p):
        left = jax.lax.ppermute(p, axis, perm_fwd)
        right = jax.lax.ppermute(p, axis, perm_bwd)
        return ((p.astype(jnp.float32) + left.astype(jnp.float32)
                 + right.astype(jnp.float32)) / 3.0).astype(p.dtype)

    return jax.tree.map(avg, params)


def all_average(params: PyTree, axis: str) -> PyTree:
    """Classic FedAvg analogue: full average over the axis (all-reduce)."""
    n = jax.lax.axis_size(axis)

    def avg(p):
        return (jax.lax.psum(p.astype(jnp.float32), axis) / n).astype(p.dtype)

    return jax.tree.map(avg, params)


def maybe_gossip(params: PyTree, step: jnp.ndarray, axis: str, *,
                 every: int = 1) -> PyTree:
    """Ring-gossip every ``every`` steps (K of Algorithm 1), identity otherwise."""
    if every <= 1:
        return ring_gossip(params, axis)
    gossiped = ring_gossip(params, axis)
    do = (step + 1) % every == 0
    return jax.tree.map(lambda g, p: jnp.where(do, g, p), gossiped, params)


# ---------------------------------------------------------------------------
# FGL edge-mesh gossip: stacked [N] server axis, block-sharded across devices.
# ---------------------------------------------------------------------------

def block_ring_gossip(params: PyTree, axis: Optional[str] = None) -> PyTree:
    """Eq. 16 ring average over a stacked edge-server axis.

    Every leaf carries servers on its leading axis. With ``axis`` given
    (inside ``shard_map``) the ring spans the full N = axis_size · n_block
    servers: interior neighbors come from the local block, boundary
    neighbors from the adjacent mesh shard via ONE boundary-slice
    ``collective_permute`` each way — so cross-device bytes per exchange are
    2·|W| per shard regardless of how many servers a shard owns. With
    ``axis=None`` the leading axis is the whole ring (single-host / plain
    vmap fallback; numerically identical).

    For a ring adjacency with self-loops (``partition.ring_adjacency``) and
    N ≥ 3 this equals ``strategies.NeighborAggregator`` applied to the
    per-server means: each server becomes (self + left + right) / 3. At
    N = 2 a true ring has the same neighbor on both sides, so the ring
    average (self + 2·other)/3 differs from Eq. 16's (self + other)/2 —
    callers (``GossipAggregator``) route N ≤ 2 through
    :func:`adjacency_gossip` instead.
    """
    def avg(p):
        n_block = p.shape[0]
        f32 = p.astype(jnp.float32)
        if axis is None:
            if n_block == 1:
                return p
            left = jnp.roll(f32, 1, axis=0)
            right = jnp.roll(f32, -1, axis=0)
        else:
            size = jax.lax.axis_size(axis)
            if size * n_block == 1:
                return p
            fwd = [(i, (i + 1) % size) for i in range(size)]
            bwd = [(i, (i - 1) % size) for i in range(size)]
            from_prev = jax.lax.ppermute(f32[-1:], axis, fwd)
            from_next = jax.lax.ppermute(f32[:1], axis, bwd)
            left = jnp.concatenate([from_prev, f32[:-1]], axis=0)
            right = jnp.concatenate([f32[1:], from_next], axis=0)
        return ((f32 + left + right) / 3.0).astype(p.dtype)

    return jax.tree.map(avg, params)


def adjacency_gossip(params: PyTree, adj: jnp.ndarray,
                     axis: Optional[str] = None) -> PyTree:
    """Eq. 16 with arbitrary server-server weights a_rj (star / custom).

    W_j = Σ_r a_rj W_r / Σ_r a_rj over the stacked server axis — exactly
    ``strategies.NeighborAggregator`` applied to per-server means, for ANY
    adjacency. With ``axis`` given (inside ``shard_map``) the local block is
    ``all_gather``-ed to rebuild the full [N] stack before mixing (a general
    adjacency has no static ``collective_permute`` schedule), then the local
    rows are sliced back out.
    """
    adj = jnp.asarray(adj, jnp.float32)
    den = jnp.sum(adj, axis=0)                               # [N]

    def avg(p):
        f32 = p.astype(jnp.float32)
        n_block = p.shape[0]
        if axis is None:
            full = f32
        else:
            full = jax.lax.all_gather(f32, axis, tiled=True)  # [N, ...]
        num = jnp.einsum("rj,r...->j...", adj, full)
        mixed = num / den.reshape((-1,) + (1,) * (num.ndim - 1))
        if axis is not None:
            start = jax.lax.axis_index(axis) * n_block
            mixed = jax.lax.dynamic_slice_in_dim(mixed, start, n_block, axis=0)
        return mixed.astype(p.dtype)

    return jax.tree.map(avg, params)


# ---------------------------------------------------------------------------
# Cross-server traffic accounting (Sec. III-E load-balancing claim).
# The one home of the byte math: gossip_dryrun and bench_load_balance both
# call these instead of re-deriving ratios inline.
# ---------------------------------------------------------------------------

def ring_gossip_bytes_per_round(param_bytes: int, *, every: int = 1) -> float:
    """Cross-server bytes ONE server sends per round under ring gossip.

    Each exchange sends |W| to both ring neighbors; exchanges happen every
    ``every`` rounds, so the per-round amortized cost is 2·|W|/K.
    """
    return 2.0 * param_bytes / max(every, 1)


def dense_neighbor_bytes_per_round(adj, param_bytes: int, *,
                                   every: int = 1) -> float:
    """Per-server cross-server bytes for dense Eq. 16 neighbor exchange.

    Each server sends |W| to every topology neighbor (off-diagonal nonzero
    of its adjacency row) on each exchange round. The max over servers is
    the Sec. III-E peak load.
    """
    import numpy as np
    a = np.asarray(adj)
    if a.shape[0] == 1:
        return 0.0
    neighbors = ((a != 0).sum(axis=1) - (np.diag(a) != 0)).max()
    return float(neighbors) * param_bytes / max(every, 1)


def allreduce_bytes_per_round(param_bytes: int, n: int) -> float:
    """Per-server bytes of a ring all-reduce over N servers: 2·(N-1)/N·|W|.

    The FedAvg analogue (classic FGL's single aggregation point realized as
    a collective) that gossip replaces.
    """
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * param_bytes


def gossip_allreduce_ratio(allreduce_bytes: float, gossip_bytes: float, *,
                           every: int = 1) -> float:
    """Per-step cross-server byte ratio: amortized gossip vs all-reduce."""
    return (gossip_bytes / max(every, 1)) / max(allreduce_bytes, 1)

"""SpreadFGL / FedGL as strategy compositions (Sec. III-B and III-E).

Thin builders over the shared :class:`~repro.core.fedgl.FGLTrainer` engine,
wired exactly as the paper's experiment section configures them and
registered in :mod:`repro.core.registry`:

- ``make_fedgl`` (``"FedGL"``): star topology (one edge server covering all
  clients), FedAvg aggregation, SpreadFGL generator round.
- ``make_spreadfgl`` (``"SpreadFGL"``): N edge servers (3 in the paper's
  testbed) on a ring — or any custom adjacency — Eq. 16 neighbor
  aggregation, Eq. 15 trace regularizer, SpreadFGL generator round.
- ``make_spreadfgl_gossip`` (``"spreadfgl_gossip"``): same composition but
  with :class:`~repro.core.strategies.GossipAggregator` — cross-server
  parameter exchange only every K rounds (``cfg.gossip_every``), executed
  on the edge mesh when one is supplied. K=1 reproduces ``"SpreadFGL"`` to
  float32 tolerance (see ``tests/test_gossip.py``).
- ``make_spreadfgl_async`` (``"spreadfgl_async"``): same layout but with
  :class:`~repro.core.strategies.AsyncAggregator` — FedBuff-style buffered
  aggregation with straggler delays, mid-round dropouts, and staleness
  discounting (``cfg.async_buffer``). B = M with zero delays reproduces
  ``"FedGL"`` / ``"SpreadFGL"``-per-server FedAvg bit-identically (see
  ``tests/test_async_agg.py``).

Every setting comes from ``cfg``; a builder takes only the layout
(``num_servers``, ``adjacency``) and the meshes. All of them accept
``sim_mesh=`` — a jax Mesh to shard the imputation similarity search's
CANDIDATE axis over (``--sim-shard`` in the launchers;
:mod:`repro.core.ring_topk`). Orthogonal to ``edge_mesh``, which places the
[N] server axis; ``launch/fgl_train.py`` reuses one mesh for both when both
flags are set.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core import strategies as S
from repro.core.fedgl import FGLTrainer
from repro.core.registry import register
from repro.core.types import ClientBatch, FGLConfig


@register("FedGL")
def make_fedgl(cfg: FGLConfig, batch: ClientBatch, *, sim_mesh=None,
               **kw) -> FGLTrainer:
    return FGLTrainer(cfg, batch, topology=S.StarTopology(),
                      aggregator=S.FedAvgAggregator(),
                      imputation=S.SpreadImputation(sim_mesh=sim_mesh), **kw)


def _topology(num_servers: int, adjacency: Optional[np.ndarray]) -> S.Topology:
    """The edge layout: the given server adjacency, else a ring of
    ``num_servers`` (one server is a star)."""
    if adjacency is not None:
        if adjacency.shape[0] != num_servers:
            raise ValueError(f"adjacency is {adjacency.shape[0]}x"
                             f"{adjacency.shape[1]} but num_servers={num_servers}")
        return S.CustomTopology(adjacency)
    return S.StarTopology() if num_servers == 1 else S.RingTopology(num_servers)


@register("SpreadFGL")
def make_spreadfgl(cfg: FGLConfig, batch: ClientBatch, *, num_servers: int = 3,
                   adjacency: Optional[np.ndarray] = None, sim_mesh=None,
                   **kw) -> FGLTrainer:
    return FGLTrainer(cfg, batch, topology=_topology(num_servers, adjacency),
                      aggregator=S.NeighborAggregator(),
                      imputation=S.SpreadImputation(sim_mesh=sim_mesh), **kw)


@register("spreadfgl_gossip")
def make_spreadfgl_gossip(cfg: FGLConfig, batch: ClientBatch, *,
                          num_servers: int = 3,
                          adjacency: Optional[np.ndarray] = None,
                          edge_mesh=None, sim_mesh=None, **kw) -> FGLTrainer:
    """SpreadFGL with decentralized gossip training at the edge (Sec. III-E).

    Identical to ``"SpreadFGL"`` except aggregation: servers FedAvg their own
    clients every round but exchange parameters with topology neighbors only
    every ``cfg.gossip_every`` rounds, via collective_permute on the edge
    mesh when ``edge_mesh`` is given. With ``cfg.gossip_every = 1`` the
    histories match ``"SpreadFGL"`` to float32 tolerance (pinned in
    ``tests/test_gossip.py``).
    """
    topology = _topology(num_servers, adjacency)
    kind = "ring" if isinstance(topology, S.RingTopology) else "adjacency"
    aggregator = S.GossipAggregator(topology=kind, every_k=cfg.gossip_every,
                                    mesh=edge_mesh)
    return FGLTrainer(cfg, batch, topology=topology, aggregator=aggregator,
                      imputation=S.SpreadImputation(sim_mesh=sim_mesh),
                      edge_mesh=edge_mesh, **kw)


@register("spreadfgl_async")
def make_spreadfgl_async(cfg: FGLConfig, batch: ClientBatch, *,
                         num_servers: int = 3,
                         adjacency: Optional[np.ndarray] = None,
                         sim_mesh=None, **kw) -> FGLTrainer:
    """SpreadFGL with FedBuff-style async straggler-tolerant aggregation.

    Same edge layout and generator round as ``"SpreadFGL"`` (star when
    ``num_servers == 1``, i.e. async FedGL), but aggregation is the buffered
    :class:`~repro.core.strategies.AsyncAggregator`: client updates arrive
    with per-round delays drawn from ``cfg.delay_dist``, drop out mid-round
    with probability ``cfg.dropout_rate``, and each edge server flushes a
    staleness-discounted mean only once ``cfg.async_buffer`` updates are
    buffered. The schedule is a pure function of ``(cfg.seed, round)`` —
    save/resume mid-buffer is exact. With B = M, zero delays, and no
    dropouts the histories reproduce the synchronous FedAvg compositions
    bit-identically (``tests/test_async_agg.py``).
    """
    buffer = cfg.async_buffer
    if buffer < 1:
        raise ValueError(f"spreadfgl_async needs cfg.async_buffer >= 1, "
                         f"got {buffer}")
    if buffer > batch.num_clients:
        raise ValueError(f"async_buffer={buffer} can never fill: the buffer "
                         f"holds at most one update per client "
                         f"(M={batch.num_clients})")
    aggregator = S.AsyncAggregator(
        buffer_size=buffer, delay_dist=cfg.delay_dist,
        dropout_rate=cfg.dropout_rate, max_delay=cfg.async_max_delay,
        seed=cfg.seed)
    return FGLTrainer(cfg, batch, topology=_topology(num_servers, adjacency),
                      aggregator=aggregator,
                      imputation=S.SpreadImputation(sim_mesh=sim_mesh), **kw)

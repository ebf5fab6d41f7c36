"""Core data structures for federated graph learning (SpreadFGL).

Shapes are static everywhere (padded + masked) so every training loop jits.

Conventions
-----------
- A *global* graph is ``Graph``: dense feature matrix, edge list, labels.
- A *federated* split is ``ClientBatch``: per-client padded subgraphs stacked on
  a leading client axis ``[M, ...]`` so client-local training vmaps.
- Imputation augments each client with ``aug_max`` extra node slots
  (the "graphic patcher" slots of Sec. III-D); they are zero/masked until the
  graph-fixing step fills them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

Array = Any  # jax or numpy array
PyTree = Any


@dataclasses.dataclass
class Graph:
    """A (global) undirected graph with node features and labels."""

    x: Array          # [n, d] float features
    senders: Array    # [e] int32
    receivers: Array  # [e] int32
    y: Array          # [n] int32 labels in [0, c)
    num_classes: int

    @property
    def num_nodes(self) -> int:
        return int(self.x.shape[0])

    @property
    def num_edges(self) -> int:
        return int(self.senders.shape[0])

    @property
    def feature_dim(self) -> int:
        return int(self.x.shape[1])

    def dense_adjacency(self) -> np.ndarray:
        """Dense symmetric 0/1 adjacency (numpy; for small graphs/tests)."""
        n = self.num_nodes
        a = np.zeros((n, n), dtype=np.float32)
        s = np.asarray(self.senders)
        r = np.asarray(self.receivers)
        a[s, r] = 1.0
        a[r, s] = 1.0
        np.fill_diagonal(a, 0.0)
        return a


import jax


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ClientBatch:
    """Per-client padded subgraphs, stacked on a leading [M] axis.

    ``n_pad = n_local_max + aug_max``: the first ``n_local_max`` slots hold real
    local nodes, the trailing ``aug_max`` slots are reserved for imputed
    neighbors written by the graphic patcher (Sec. III-D).

    ``prop`` caches what the client classifier computes from ``x``, ``adj``
    and ``node_mask`` alone (``gnn.propagate``, stacked on ``[M]``), so that
    the rounds' forwards do not recompute it. ``replace`` drops it whenever
    one of those three is replaced; ``FGLTrainer.init`` and
    ``FGLTrainer._impute`` fill it. A forward given a batch with no cache
    computes it itself.
    """

    x: Array           # [M, n_pad, d] features (aug slots overwritten by patcher)
    adj: Array         # [M, n_pad, n_pad] dense 0/1 adjacency (symmetric)
    y: Array           # [M, n_pad] labels (-1 on padding/aug slots)
    node_mask: Array   # [M, n_pad] 1.0 for real local nodes
    train_mask: Array  # [M, n_pad] 1.0 for labeled training nodes
    test_mask: Array   # [M, n_pad] 1.0 for held-out eval nodes
    global_id: Array   # [M, n_pad] int32 index into the global graph (-1 pad)
    num_classes: int = dataclasses.field(metadata=dict(static=True))
    aug_max: int = dataclasses.field(metadata=dict(static=True))
    prop: Any = None   # gnn.propagate of every client, or None

    @property
    def num_clients(self) -> int:
        return int(self.x.shape[0])

    @property
    def n_pad(self) -> int:
        return int(self.x.shape[1])

    @property
    def n_local_max(self) -> int:
        return self.n_pad - self.aug_max

    def replace(self, **kw) -> "ClientBatch":
        """A copy with fields replaced; a new ``x``, ``adj`` or ``node_mask``
        drops the cache ``prop`` unless ``prop`` is given too."""
        if "prop" not in kw and not _CACHE_INPUTS.isdisjoint(kw):
            kw["prop"] = None
        return dataclasses.replace(self, **kw)


_CACHE_INPUTS = frozenset({"x", "adj", "node_mask"})


@dataclasses.dataclass
class FGLConfig:
    """Hyperparameters of FedGL / SpreadFGL (Sec. III, Table/Parameter settings)."""

    # GNN node classifier (GraphSAGE, GCN aggregator, 2 layers in the paper).
    hidden_dim: int = 64
    num_layers: int = 2
    gnn_kind: str = "sage"            # "sage" | "gcn" | "gat"
    dropout: float = 0.0

    # Hot-path kernel implementation, threaded through both compute hot spots
    # (gnn.aggregate in the client classifier and the fused similarity top-k
    # of the imputation round): "reference" (jnp), "pallas" (TPU kernels), or
    # "pallas_interpret" (the Pallas kernels in interpret mode — CPU parity).
    kernel_impl: str = "reference"

    # Federated schedule (Algorithm 1).
    num_edge_servers: int = 1          # N  (1 => FedGL, >1 => SpreadFGL)
    clients_per_server: int = 6        # M_j
    local_rounds: int = 10             # T_l
    global_rounds: int = 30            # T_g
    imputation_interval: int = 5       # K
    # Cross-server exchange interval for the gossip aggregator (Sec. III-E
    # distributed training): servers trade parameters with topology
    # neighbors every `gossip_every` rounds instead of dense per-round
    # Eq. 16 averaging. 1 == exchange every round (== NeighborAggregator on
    # the same adjacency). Only consumed by `spreadfgl_gossip` compositions.
    gossip_every: int = 1
    # Per-round partial client participation ρ ∈ (0, 1]: each global round
    # exactly ceil(ρ·M) clients (sampled without replacement from a key
    # stream independent of the training key) contribute to aggregation —
    # every Aggregator becomes a participation-mask-weighted mean. ρ = 1
    # disables the feature entirely (no mask is sampled, no key is consumed;
    # fixed-seed histories are bit-identical to pre-participation runs).
    # The round-t mask is a pure function of (seed, t), so save/resume
    # reproduces the schedule exactly. CLI: `fgl_train --participation`.
    participation: float = 1.0
    # FedBuff-style async aggregation (Sec. III-E straggler tolerance).
    # async_buffer = B > 0 turns aggregation into a buffered flush: client
    # updates report with per-round arrival delays drawn from `delay_dist`
    # ("zero" | "uniform" | "geometric", capped at async_max_delay) and are
    # lost mid-round with probability dropout_rate; the server aggregates
    # (staleness-discounted, 1/sqrt(1+τ)) only when ≥ B updates are
    # buffered. 0 disables the feature (synchronous aggregation, no async
    # key stream is consumed). B = M with zero delays reproduces FedAvg
    # bit-identically. CLI: `fgl_train --async-buffer/--delay-dist`.
    async_buffer: int = 0
    delay_dist: str = "zero"
    dropout_rate: float = 0.0
    async_max_delay: int = 4
    ae_iters: int = 5                  # T_ae
    assessor_iters: int = 3           # T_as
    ae_outer_iters: int = 3            # "while not convergent" outer loop bound

    # Imputation generator / assessor (Sec. III-C/D).
    top_k_links: int = 5               # k most-similar cross-subgraph links
    ae_hidden: int = 16                # autoencoder bottleneck {c,16,d}/{d,16,c}
    assessor_hidden: tuple = (128, 16) # assessor MLP {c,128,16,1}
    neg_threshold: Optional[float] = None  # theta; default 1/c
    aug_max: int = 16                  # patcher slots per client

    # Optimization.
    lr_classifier: float = 0.01        # Adam, paper Sec. IV-A
    lr_generator: float = 0.001        # Adam for AE + assessor
    trace_reg: float = 1e-4            # Eq. 15 trace-norm coefficient (SpreadFGL)
    label_ratio: float = 0.3

    seed: int = 0

    def theta(self, num_classes: int) -> float:
        return self.neg_threshold if self.neg_threshold is not None else 1.0 / num_classes

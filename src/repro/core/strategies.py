"""Pluggable strategy components of the FGL engine.

Algorithm 1 of SpreadFGL is one outer loop; everything the related work
varies lives on three axes, each a small protocol with concrete
implementations here:

- :class:`Topology` — how clients map onto edge servers and how servers are
  wired to each other (star = FedGL's single aggregation point, ring =
  SpreadFGL's testbed, custom adjacency = anything else). AdaFGL-style
  variants swap this axis.
- :class:`Aggregator` — how client classifiers are combined each round
  (FedAvg, Eq. 16 neighbor aggregation, gossip-SGD over the edge mesh,
  FedBuff-style buffered async aggregation, identity for purely local
  training). FedGTA-style variants swap this axis. Each aggregator's
  ``schedule(t, M)`` gives the round's static flag (exchange/skip for
  gossip every K rounds, flush/skip for buffered async) and its [M] client
  weights; both are pure functions of ``(cfg.seed, round)``, so jit sees
  at most 2 static variants and save/resume mid-schedule is exact. Every
  aggregator reduces through one weighted per-server sum.
- :class:`ImputationStrategy` — what happens on the every-K graph-fixing
  round (the SpreadFGL generator round, FedSage+'s local neighbor
  generation, or nothing).

:class:`~repro.core.fedgl.FGLTrainer` is composed from one of each; the
named compositions live in :mod:`repro.core.registry`. Strategies are
frozen dataclasses (hashable, usable as jit-static closures) and hold no
jax state — per-round state threads through ``FGLState``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import imputation, patcher
from repro.core.partition import group_clients_by_server, ring_adjacency
from repro.core.types import ClientBatch
from repro.optim.adam import Adam

PyTree = Any


# ---------------------------------------------------------------------------
# Topology: client -> edge-server grouping + server-server adjacency.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TopologyLayout:
    """Resolved edge layout for a concrete client count."""

    adjacency: np.ndarray        # [N, N] server-server weights (a_rj of Eq. 16)
    server_of_client: np.ndarray  # [M] owning server of each client
    num_servers: int
    clients_per_server: int


@runtime_checkable
class Topology(Protocol):
    """Client→edge-server layout + server-server adjacency a_rj (Eq. 16,
    Sec. III-E); resolved once per trainer for a concrete client count."""

    def build(self, num_clients: int) -> TopologyLayout: ...


@dataclasses.dataclass(frozen=True)
class StarTopology:
    """One edge server covering every client (FedGL, Sec. III-B)."""

    def build(self, num_clients: int) -> TopologyLayout:
        return TopologyLayout(np.ones((1, 1), dtype=np.float32),
                              np.zeros(num_clients, dtype=np.int32),
                              1, num_clients)


@dataclasses.dataclass(frozen=True)
class RingTopology:
    """N edge servers on a ring (SpreadFGL's testbed, Sec. III-E).

    Ring structure has ONE source: the adjacency comes verbatim from
    :func:`repro.core.partition.ring_adjacency`; the collective_permute
    schedule in :func:`repro.core.gossip.block_ring_gossip` realizes the
    same matrix implicitly (consistency pinned in
    ``tests/test_gossip.py::TestRingSingleSource``).
    """

    num_servers: int = 3

    def build(self, num_clients: int) -> TopologyLayout:
        n = self.num_servers
        if num_clients % n:
            raise ValueError(f"M={num_clients} must divide across N={n} servers")
        return TopologyLayout(ring_adjacency(n),
                              group_clients_by_server(num_clients, n),
                              n, num_clients // n)


@dataclasses.dataclass(frozen=True, eq=False)
class CustomTopology:
    """Arbitrary server-server adjacency a_rj (Eq. 16 supports any weights;
    AdaFGL-style variants supply theirs here); clients grouped contiguously."""

    adjacency: np.ndarray

    def build(self, num_clients: int) -> TopologyLayout:
        adj = np.asarray(self.adjacency, dtype=np.float32)
        if adj.ndim != 2 or adj.shape[0] != adj.shape[1]:
            raise ValueError(f"adjacency must be square, got {adj.shape}")
        n = adj.shape[0]
        if num_clients % n:
            raise ValueError(f"M={num_clients} must divide across N={n} servers")
        return TopologyLayout(adj, group_clients_by_server(num_clients, n),
                              n, num_clients // n)


# ---------------------------------------------------------------------------
# Aggregator: combine client classifiers once per global round.
# ---------------------------------------------------------------------------

def participation_mask(key: jax.Array, num_clients: int, rho: float) -> jnp.ndarray:
    """Sample one round's participating-client mask: [M] float32 0/1.

    Exactly ``ceil(rho * M)`` clients participate, sampled without
    replacement (the classic FedAvg "select a fraction C of clients"
    scheme) — so at least one client always participates and the mask shape
    is static regardless of rho: jit compiles exactly one masked variant,
    never a gather/resize per round.
    """
    if not 0.0 < rho <= 1.0:
        raise ValueError(f"participation must be in (0, 1], got {rho}")
    k = min(num_clients, max(1, int(np.ceil(rho * num_clients - 1e-9))))
    perm = jax.random.permutation(key, num_clients)
    return jnp.zeros((num_clients,), jnp.float32).at[perm[:k]].set(1.0)


def _server_sum(leaf: jnp.ndarray, weights: Optional[jnp.ndarray],
                num_servers: int, m_per: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The weighted sum of each server's clients in one stacked [M, ...]
    leaf, and each server's total weight: ``([N, ...], [N])``.

    ``weights`` is the round's [M] client weights; None means unit weights,
    a constant of the trace, so the multiply folds away and the sum is the
    plain per-server client sum.
    """
    if weights is None:
        weights = jnp.ones((num_servers * m_per,), jnp.float32)
    w = weights.reshape(num_servers, m_per)
    grouped = leaf.reshape((num_servers, m_per) + leaf.shape[1:])
    return (jnp.sum(grouped * w.reshape(w.shape + (1,) * (leaf.ndim - 1)), axis=1),
            jnp.sum(w, axis=1))


def _per_server(v: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """A leading-axis vector shaped to broadcast against ``leaf``."""
    return v.reshape(v.shape + (1,) * (leaf.ndim - 1))


def _divide(num: jnp.ndarray, den: jnp.ndarray, fallback: jnp.ndarray) -> jnp.ndarray:
    """``num / den`` per server, and ``fallback`` where ``den`` is zero."""
    den = _per_server(den, num)
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), fallback)


def _server_mean(leaf: jnp.ndarray, weights: Optional[jnp.ndarray],
                 num_servers: int, m_per: int) -> jnp.ndarray:
    """[N, ...] weighted mean of each server's clients. A server whose
    clients all carry zero weight falls back to its plain mean: the edge
    server re-broadcasts the weights it already holds."""
    total, weight = _server_sum(leaf, weights, num_servers, m_per)
    plain, _ = _server_sum(leaf, None, num_servers, m_per)
    return _divide(total, weight, plain / m_per)


@runtime_checkable
class Aggregator(Protocol):
    """Combine stacked [M] client classifiers once per global round.

    ``schedule(t, M)`` is the round's aggregation decision, made on the
    host: ``(flag, weights)``. ``flag`` is a static 0/1 (exchange or skip
    for gossip, flush or skip for async, a constant for the rest), so the
    jitted aggregation compiles at most two variants. ``weights`` is an [M]
    float vector of client weights, or None for unit weights. Both must be
    pure functions of the aggregator's settings and ``t``, so a state
    restored at round t resumes the schedule exactly.

    ``aggregate`` takes the round's ``flag`` and ``weights``: the engine
    (``FGLTrainer._schedule``) multiplies the participation mask of
    ``cfg.participation < 1`` into the weights, so a client sampled out
    contributes nothing. Every aggregator reduces through one arithmetic
    path, :func:`_server_sum`; with ``weights`` None its unit weights fold
    away in the compiled program.
    """

    def schedule(self, t: int, num_clients: int
                 ) -> Tuple[int, Optional[jnp.ndarray]]: ...

    def aggregate(self, params: PyTree, *, adj: jnp.ndarray,
                  num_servers: int, m_per: int, flag: int = 0,
                  weights: Optional[jnp.ndarray] = None) -> PyTree: ...


@dataclasses.dataclass(frozen=True)
class IdentityAggregator:
    """No aggregation: clients keep their own weights (LocalFGL, Sec. IV-A).

    ``weights`` are accepted and ignored: with no cross-client mixing there
    is nothing for partial participation to gate — a non-participating
    client keeping its own weights is exactly what identity already does.
    """

    def schedule(self, t, num_clients):
        return 0, None

    def aggregate(self, params, *, adj, num_servers, m_per, flag=0, weights=None):
        return params


@dataclasses.dataclass(frozen=True)
class FedAvgAggregator:
    """Per-server FedAvg (McMahan et al.): mean over covered clients,
    broadcast back — classic FGL's single aggregation point when N = 1
    (FedGL, Sec. III-B). With ``weights`` the mean runs over the round's
    weighted clients (all-out servers re-broadcast their plain mean, see
    :func:`_server_mean`)."""

    def schedule(self, t, num_clients):
        return 0, None

    def aggregate(self, params, *, adj, num_servers, m_per, flag=0, weights=None):
        def agg(leaf):
            w = _server_mean(leaf, weights, num_servers, m_per)
            return jnp.repeat(w, m_per, axis=0)
        return jax.tree.map(agg, params)


@dataclasses.dataclass(frozen=True)
class NeighborAggregator:
    """Eq. 16 (Sec. III-E): each server averages itself and its topology
    neighbors *densely, every round*:

    W_j = sum_r a_rj * sum_i W_(r,i) / sum_r a_rj M_r — the SpreadFGL rule
    that removes the single aggregation point. :class:`GossipAggregator`
    computes the identical update on exchange rounds at full participation
    but amortizes the cross-server traffic over K rounds; with
    ``every_k=1`` on the same adjacency the two agree to float32 tolerance
    (``tests/test_gossip.py``). Under partial participation they differ:
    Eq. 16 weights each server by its participating count, gossip mixes
    equal server means.

    With ``weights``, Eq. 16's client count M_r becomes the round's
    participating count m̃_r (weighted sums in both numerator and
    denominator); a neighborhood that entirely sat out falls back to the
    plain Eq. 16 mix.
    """

    def schedule(self, t, num_clients):
        return 0, None

    def aggregate(self, params, *, adj, num_servers, m_per, flag=0, weights=None):
        def mix(leaf, w):
            """Eq. 16's numerator and [N] denominator: sums over neighbors r."""
            total, weight = _server_sum(leaf, w, num_servers, m_per)
            return (jnp.einsum("rj,r...->j...", adj, total),
                    jnp.sum(adj * weight[:, None], axis=0))

        def agg(leaf):
            num, den = mix(leaf, weights)
            plain_num, plain_den = mix(leaf, None)
            w = _divide(num, den, plain_num / _per_server(plain_den, plain_num))
            return jnp.repeat(w, m_per, axis=0)
        return jax.tree.map(agg, params)


@dataclasses.dataclass(frozen=True, eq=False)
class GossipAggregator:
    """Sec. III-E load balancing as gossip-SGD over the edge mesh.

    Each round every server FedAvg-aggregates its own covered clients
    (edge-client traffic only); cross-server parameter exchange happens
    only every ``every_k`` rounds, with topology neighbors (Eq. 16 weights)
    rather than a dense all-to-all — the decentralized-training reading of
    the paper's Fig. 8/9 convergence claim, a la FedGTA's topology-aware
    averaging. Per-round cross-server bytes drop from every-round dense
    Eq. 16 to 2·|W|/K (``core.gossip.ring_gossip_bytes_per_round``).

    ``topology`` picks the exchange kernel: ``"ring"`` uses
    :func:`repro.core.gossip.block_ring_gossip`'s boundary-slice
    ``collective_permute`` schedule (N ≥ 3; N ≤ 2 falls back to the
    adjacency path, where a 2-ring's double edge would otherwise be
    over-counted), ``"adjacency"`` uses
    :func:`repro.core.gossip.adjacency_gossip` (all_gather + Eq. 16 mix)
    for star/custom wiring. With ``mesh`` set (``make_edge_mesh``) the
    exchange runs under ``shard_map`` over the mesh's [N] axis, so the
    neighbor bytes genuinely cross the (emulated) device boundary.

    Equivalences, both pinned in ``tests/test_gossip.py``:

    - ``GossipAggregator(every_k=1)`` == :class:`NeighborAggregator` on the
      same adjacency (ring or custom) at full participation, to float32
      tolerance.
    - On non-exchange rounds it equals :class:`FedAvgAggregator` applied
      per server.

    The schedule's flag is 1 on rounds with ``(t + 1) % every_k == 0`` — a
    pure function of the checkpointed round, so save/resume mid-interval
    keeps the exchange schedule intact.
    """

    topology: str = "ring"        # "ring" | "adjacency"
    every_k: int = 1
    mesh: Any = None              # optional jax Mesh carrying the [N] axis

    def __post_init__(self):
        if self.topology not in ("ring", "adjacency"):
            raise ValueError(f"unknown gossip topology {self.topology!r}; "
                             f"expected 'ring' or 'adjacency'")
        if self.every_k < 1:
            raise ValueError(f"every_k must be >= 1, got {self.every_k}")

    def schedule(self, t, num_clients):
        return int((t + 1) % self.every_k == 0), None

    def aggregate(self, params, *, adj, num_servers, m_per, flag=0, weights=None):
        # Participation gates the edge-client leg only: the per-server mean
        # runs over weighted clients (all-out servers keep their plain
        # mean); the cross-server exchange is unchanged — servers always
        # gossip whatever they aggregated this round.
        w = jax.tree.map(                                          # [N, ...]
            lambda leaf: _server_mean(leaf, weights, num_servers, m_per), params)
        if num_servers > 1 and flag:
            w = self._exchange(w, adj, num_servers)
        return jax.tree.map(lambda leaf: jnp.repeat(leaf, m_per, axis=0), w)

    def _exchange(self, w: PyTree, adj, num_servers: int) -> PyTree:
        from repro.core import gossip

        use_ring = self.topology == "ring" and num_servers >= 3
        if self.mesh is not None and self.mesh.size > 1:
            from jax.sharding import PartitionSpec as P
            axis = self.mesh.axis_names[0]

            def ex(blk):
                if use_ring:
                    return gossip.block_ring_gossip(blk, axis)
                return gossip.adjacency_gossip(blk, adj, axis)

            return jax.shard_map(ex, mesh=self.mesh, in_specs=(P(axis),),
                                 out_specs=P(axis), check_vma=False)(w)
        if use_ring:
            return gossip.block_ring_gossip(w)
        return gossip.adjacency_gossip(w, adj)


# ---------------------------------------------------------------------------
# Async straggler-tolerant aggregation (FedBuff-style).
# ---------------------------------------------------------------------------

ASYNC_DELAY_DISTS = ("zero", "uniform", "geometric")

# Salt for the async delay/dropout key stream. Distinct from the
# participation salt (0x9A57 in FGLTrainer) and never folded into the
# training key threaded through FGLState: enabling async aggregation does
# not perturb any other random stream, and the round-t draws are a pure
# function of (seed, t) — the property that makes mid-buffer resume exact.
_ASYNC_SALT = 0xA57C


def async_delay_stream(seed: int, round: int, num_clients: int, *,
                       delay_dist: str = "zero", max_delay: int = 4,
                       dropout_rate: float = 0.0):
    """Round-``round`` arrival delays and dropout flags, per client.

    Returns ``(delays int32 [M], drops bool [M])`` numpy arrays: ``delays[i]``
    is how many rounds client i's update sent this round stays in flight
    (0 = arrives the same round), ``drops[i]`` marks a mid-round dropout —
    the update is lost at send time and the client retries next round.

    The draws come from ``fold_in(fold_in(key(seed), salt), round)`` — the
    same keyed-stream idiom as :func:`participation_mask` but under a
    different salt, so the two schedules are independent of each other AND
    of the training key. Same (seed, round) always reproduces the same
    delays; a checkpoint restored at round t replays rounds 0..t-1 of the
    stream to rebuild the buffer exactly.

    Distributions: ``"zero"`` — no delay (the synchronous limit);
    ``"uniform"`` — uniform on {0..max_delay}; ``"geometric"`` — p=1/2
    geometric on {0, 1, 2, ...} (mean 1), capped at ``max_delay``.
    """
    if delay_dist not in ASYNC_DELAY_DISTS:
        raise ValueError(f"unknown delay_dist {delay_dist!r}; "
                         f"expected one of {ASYNC_DELAY_DISTS}")
    if max_delay < 0:
        raise ValueError(f"max_delay must be >= 0, got {max_delay}")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), _ASYNC_SALT), round)
    kd, kx = jax.random.split(key)
    if delay_dist == "zero":
        delays = np.zeros(num_clients, np.int32)
    elif delay_dist == "uniform":
        delays = np.asarray(jax.random.randint(kd, (num_clients,), 0,
                                               max_delay + 1), np.int32)
    else:  # geometric, p = 1/2 via inverse transform
        u = np.asarray(jax.random.uniform(kd, (num_clients,)), np.float64)
        delays = np.minimum(np.floor(np.log1p(-u) / np.log(0.5)),
                            max_delay).astype(np.int32)
    drops = np.asarray(jax.random.uniform(kx, (num_clients,)) < dropout_rate)
    return delays, drops


# spec -> incremental replay state; see _async_schedule. Purely a cache:
# entries are reproducible from scratch, so sharing across trainer
# instances (same spec => same schedule) is sound.
_ASYNC_SCHEDULES: dict = {}


def _async_schedule(spec: tuple, round: int):
    """``(flush, weights)`` of round ``round`` for one async spec.

    ``spec = (seed, num_clients, buffer_size, delay_dist, max_delay,
    dropout_rate)``. Replays the deterministic client state machine from
    round 0 (cached incrementally, so sequential training pays O(M) per
    round and a mid-run resume pays one O(t·M) host-side replay):

    - a client with no update in flight sends one every round; the round's
      :func:`async_delay_stream` draw gives its arrival delay, or drops it
      (mid-round dropout — the client just retries next round);
    - an update arriving at round t joins the server buffer with report
      round t (one buffer slot per client — a fresher arrival replaces a
      staler unflushed one, which keeps the buffer a static [M] mask);
    - when >= buffer_size updates sit in the buffer at the end of a round,
      the server flushes: ``weights[i] = 1/sqrt(1 + t - report[i])`` for
      buffered clients (the FedBuff staleness discount), 0 elsewhere, and
      the buffer empties.

    On non-flush rounds weights is None (aggregation is identity).
    """
    seed, m, buffer_size, delay_dist, max_delay, dropout_rate = spec
    cache = _ASYNC_SCHEDULES.setdefault(spec, {
        "next": 0,
        "arrival": np.full(m, -1, np.int64),   # in-flight arrival round
        "report": np.full(m, -1, np.int64),    # buffered report round
        "out": [],
    })
    arrival, report = cache["arrival"], cache["report"]
    while cache["next"] <= round:
        t = cache["next"]
        delays, drops = async_delay_stream(
            seed, t, m, delay_dist=delay_dist, max_delay=max_delay,
            dropout_rate=dropout_rate)
        free = arrival < 0
        send = free & ~drops
        arrival[send] = t + delays[send]
        arrived = arrival == t
        report[arrived] = t
        arrival[arrived] = -1
        buffered = report >= 0
        if int(buffered.sum()) >= buffer_size:
            tau = (t - report).astype(np.float32)
            weights = np.where(buffered,
                               1.0 / np.sqrt(np.float32(1.0) + tau),
                               np.float32(0.0)).astype(np.float32)
            report[:] = -1
            cache["out"].append((True, weights))
        else:
            cache["out"].append((False, None))
        cache["next"] = t + 1
    return cache["out"][round]


@dataclasses.dataclass(frozen=True)
class AsyncAggregator:
    """Buffered straggler-tolerant aggregation (FedBuff, Nguyen et al. '22).

    Every synchronous round in the engine is a barrier: one straggling
    client stalls the whole mesh — exactly the single-point overload the
    paper's edge layer argues against (Sec. I, Sec. III-E). This
    aggregator removes the barrier in simulation: client updates *report*
    to the server with per-round arrival delays and mid-round dropouts
    (:func:`async_delay_stream`), the server buffers reports, and
    aggregation triggers only when the buffer holds at least
    ``buffer_size`` updates — never "when all M clients arrive". On a
    flush each edge server takes the staleness-discounted weighted mean of
    its covered *buffered* clients,

        W_j = sum_i w_i W_(j,i) / sum_i w_i,   w_i = 1 / sqrt(1 + tau_i),

    with tau_i = flush round - report round (the FedBuff discount), and
    broadcasts it to all its clients; a server with no buffered reports
    keeps its clients' weights untouched. Non-flush rounds are identity —
    clients simply keep training locally.

    Determinism contract (the same one ``participation_mask`` and the
    gossip phase honor): the delay/dropout draws come from a key stream =
    f(cfg.seed, absolute round) under a dedicated salt, the buffer is a
    static [M] occupancy (freshest report per client wins — no Python-list
    buffer, no gather/resize), and the flush weights reach the jitted
    aggregation as a traced [M] vector with flush/skip as the only static
    split. The whole delay/buffer/staleness schedule is therefore a pure
    function of the checkpointed round: save/resume mid-buffer replays
    rounds 0..t-1 on the host and continues bit-exactly
    (``tests/test_async_agg.py``).

    Correctness anchor: with ``buffer_size = M``, ``delay_dist="zero"``,
    and ``dropout_rate = 0`` every client reports every round, the buffer
    fills exactly at M, every tau is 0, and every weight is exactly 1.0 —
    the flush reduces to the per-server mean over covered clients and the
    histories reproduce :class:`FedAvgAggregator` bit-identically (pinned
    in ``tests/test_async_agg.py``, the same way K=1 gossip pins dense
    neighbor aggregation).
    """

    buffer_size: int = 1
    delay_dist: str = "zero"      # "zero" | "uniform" | "geometric"
    dropout_rate: float = 0.0     # P(update lost at send), per client-round
    max_delay: int = 4            # delay cap in rounds
    seed: int = 0

    def __post_init__(self):
        if self.buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {self.buffer_size}")
        if self.delay_dist not in ASYNC_DELAY_DISTS:
            raise ValueError(f"unknown delay_dist {self.delay_dist!r}; "
                             f"expected one of {ASYNC_DELAY_DISTS}")
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0, 1), "
                             f"got {self.dropout_rate}")
        if self.max_delay < 0:
            raise ValueError(f"max_delay must be >= 0, got {self.max_delay}")

    def _spec(self, num_clients: int) -> tuple:
        if self.buffer_size > num_clients:
            raise ValueError(
                f"buffer_size={self.buffer_size} can never fill: the buffer "
                f"holds at most one update per client (M={num_clients})")
        return (self.seed, num_clients, self.buffer_size, self.delay_dist,
                self.max_delay, self.dropout_rate)

    def schedule(self, t, num_clients):
        """``(1, [M] float32 staleness weights)`` on flush rounds, ``(0,
        None)`` otherwise."""
        flush, weights = _async_schedule(self._spec(num_clients), t)
        return int(flush), None if weights is None else jnp.asarray(weights)

    def aggregate(self, params, *, adj, num_servers, m_per, flag=0, weights=None):
        """``flag`` is the flush (1) or skip (0) of the round; ``weights``
        the [M] staleness weights (zero = not buffered). Skip rounds are
        identity. ``adj`` is unused: like :class:`FedAvgAggregator` the
        flush is per-server — cross-server spread still happens through
        the shared imputation round."""
        if not flag:
            return params

        def agg(leaf):
            total, weight = _server_sum(leaf, weights, num_servers, m_per)
            w = total / _per_server(jnp.where(weight > 0, weight, 1.0), total)
            keep = jnp.repeat(weight > 0, m_per)
            return jnp.where(_per_server(keep, leaf), jnp.repeat(w, m_per, axis=0), leaf)
        return jax.tree.map(agg, params)


# ---------------------------------------------------------------------------
# ImputationStrategy: the every-K graph-fixing round.
# ---------------------------------------------------------------------------

@runtime_checkable
class ImputationStrategy(Protocol):
    """The every-K graph-fixing round (Algorithm 1 lines 11-24 for
    SpreadFGL; FedSage+'s local generation; or nothing). ``active=False``
    lets the engine skip the round entirely."""

    active: bool

    def impute(self, engine, state): ...


@dataclasses.dataclass(frozen=True)
class NoImputation:
    """Skip graph fixing entirely (LocalFGL / FedAvg-fusion baselines)."""

    active = False

    def impute(self, engine, state):
        return state


@dataclasses.dataclass(frozen=True, eq=False)
class SpreadImputation:
    """SpreadFGL's generator round (Algorithm 1 lines 11-24).

    Fuse client embeddings per server, train the AE/assessor pair
    adversarially, take cross-subgraph top-k similarity links, and fix every
    client graph through the graphic patcher. The [N] server axis is a single
    vmap (shardable across an edge mesh); per-server results are stitched
    back to the global flat index space by
    :func:`patcher.stitch_server_links`.

    With ``sim_mesh`` set (same pattern as ``GossipAggregator.mesh``) the
    similarity top-k is lifted OUT of the vmapped server round and runs once,
    batched over the [N] axis, through the candidate-sharded ring driver
    (:mod:`repro.core.ring_topk`): each mesh device owns an [n/size] slice of
    every server's candidate axis and slabs rotate via collective_permute.
    The ring result agrees with the in-vmap search up to f32 rounding
    (``ring_topk.topk_violations``; pinned in ``tests/test_ring_topk.py``).
    """

    sim_mesh: Any = None          # optional jax Mesh to shard candidates over

    active = True

    @staticmethod
    def _per_server(engine, state):
        """Client embeddings and node masks grouped per server:
        ([N, M_per, n_pad, c], [N, M_per, n_pad])."""
        with jax.named_scope("generator"):  # the generator's inputs
            emb = engine._embeddings(state.params, state.batch)  # [M, n_pad, c]
        n, mp = engine.n_servers, engine.m_per
        return (emb.reshape((n, mp) + emb.shape[1:]),
                state.batch.node_mask.reshape((n, mp) + emb.shape[1:2]))

    def search_inputs(self, engine, state):
        """The [N]-stacked similarity-search inputs of ``state``'s round.

        ``(h, flat_mask, client_ids, target_mask)`` per server
        (``imputation.search_inputs``), built exactly as the round builds
        them, so a caller can rerun or compare the search on the round's
        own embeddings.
        """
        return engine.vmap(
            lambda e, m: imputation.search_inputs(e, m, engine.n_local)
        )(*self._per_server(engine, state))

    def server_outputs(self, engine, state):
        """The vmapped [N] generator round, before graph fixing.

        Returns ``((ae_params, ae_opt, as_params, as_opt, scores, idx,
        x_bar), key)`` with per-server leading [N] axes and the advanced
        round key — the raw link proposals the parity regressions inspect.
        """
        emb_g, mask_g = self._per_server(engine, state)
        keys = jax.random.split(state.key, engine.n_servers + 1)
        key, server_keys = keys[0], keys[1:]
        if self.sim_mesh is None:
            outs = engine.vmap(engine._server_round)(
                server_keys, state.ae_params, state.ae_opt, state.as_params,
                state.as_opt, emb_g, mask_g)
            return outs, key
        # Sharded path: vmap ONLY the generator half; the similarity runs
        # once over the stacked [N, n_flat, c] fused embeddings so shard_map
        # is the outermost transform (vmap-inside-shard_map composes; the
        # reverse does not). Numerically identical: the generator consumes
        # all the round's randomness, similarity is deterministic in h_flat.
        ae, aeo, asr, aso, x_bar, (h, fmask, cid, tmask) = engine.vmap(
            engine._server_round_gen
        )(server_keys, state.ae_params, state.ae_opt, state.as_params,
          state.as_opt, emb_g, mask_g)
        with jax.named_scope("sim_topk"):
            scores, idx = imputation.similarity_topk(
                h, fmask, cid, engine.cfg.top_k_links,
                kernel_impl=engine.kernel_impl, target_mask=tmask,
                mesh=self.sim_mesh)
        return (ae, aeo, asr, aso, scores, idx, x_bar), key

    def impute(self, engine, state):
        (ae_params, ae_opt, as_params, as_opt, scores, idx,
         x_bar), key = self.server_outputs(engine, state)
        with jax.named_scope("patch"):
            scores, idx, x_bar = patcher.stitch_server_links(scores, idx, x_bar)
            batch = patcher.fix_graphs(state.batch, scores, idx, x_bar)
        return dataclasses.replace(state, batch=batch, ae_params=ae_params,
                                   ae_opt=ae_opt, as_params=as_params,
                                   as_opt=as_opt, key=key)

    def impute_reference(self, engine, state):
        """Sequential per-server loop (tests/benchmarks only).

        Preserves the pre-refactor structure — a Python loop running one
        server at a time — but uses the same per-server key derivation as
        :meth:`impute` (one ``split(key, N+1)`` up front), so the two are
        numerically equivalent and the equivalence test isolates exactly the
        loop→vmap change. Also the baseline the load-balance benchmark times
        against.
        """
        batch = state.batch
        emb = engine._embeddings(state.params, batch)       # [M, n_pad, c]
        keys = jax.random.split(state.key, engine.n_servers + 1)
        key, server_keys = keys[0], keys[1:]
        outs = []
        for j in range(engine.n_servers):
            sl = slice(j * engine.m_per, (j + 1) * engine.m_per)
            take_j = lambda t: jax.tree.map(lambda x: x[j], t)
            outs.append(engine._server_round(
                server_keys[j], take_j(state.ae_params), take_j(state.ae_opt),
                take_j(state.as_params), take_j(state.as_opt), emb[sl],
                batch.node_mask[sl]))
        stack = lambda i: jax.tree.map(lambda *x: jnp.stack(x), *[o[i] for o in outs])
        ae_params, ae_opt, as_params, as_opt = (stack(i) for i in range(4))
        scores, idx, x_bar = patcher.stitch_server_links(
            stack(4), stack(5), stack(6))
        batch = patcher.fix_graphs(batch, scores, idx, x_bar)
        return dataclasses.replace(state, batch=batch, ae_params=ae_params,
                                   ae_opt=ae_opt, as_params=as_params,
                                   as_opt=as_opt, key=key)


@dataclasses.dataclass(frozen=True)
class LocalGenImputation:
    """FedSage+-style purely local neighbor generation (Zhang et al. '21).

    Per client: train a linear x -> mean(neighbor x) predictor on the
    client's own neighborhoods, then append one synthetic neighbor for each
    of the ``aug_max`` highest-degree nodes. No cross-client information
    flows — exactly the limitation FedGL/SpreadFGL address (Fig. 1).
    """

    gen_steps: int = 20

    active = True

    def impute(self, engine, state):
        key, kg = jax.random.split(state.key)
        batch = _local_generation(kg, state.batch, self.gen_steps)
        return dataclasses.replace(state, batch=batch, key=key)


def _local_generation(key, batch: ClientBatch, gen_steps: int) -> ClientBatch:
    d = batch.x.shape[-1]
    n_local = batch.n_local_max
    aug = batch.aug_max
    opt = Adam(lr=1e-2)

    def per_client(k, x, adjm, node_mask):
        a = adjm[:n_local, :n_local] * (node_mask[:n_local, None] *
                                        node_mask[None, :n_local])
        deg = jnp.sum(a, axis=-1)
        target = (a @ x[:n_local]) / jnp.maximum(deg[:, None], 1.0)

        def loss_fn(p):
            pred = x[:n_local] @ p["w"] + p["b"]
            mask = (deg > 0).astype(x.dtype)
            return jnp.sum(jnp.square(pred - target) * mask[:, None]) / jnp.maximum(
                jnp.sum(mask), 1.0)

        p = {"w": jnp.zeros((d, d), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}
        st = opt.init(p)

        def step(carry, _):
            p, st = carry
            g = jax.grad(loss_fn)(p)
            p, st = opt.update(g, st, p)
            return (p, st), ()
        (p, _), _ = jax.lax.scan(step, (p, st), None, length=gen_steps)

        # Highest-degree real nodes get one synthetic neighbor each.
        score = jnp.where(node_mask[:n_local] > 0, deg, -jnp.inf)
        _, src = jax.lax.top_k(score, aug)
        feats = x[src] @ p["w"] + p["b"]
        ok = jnp.isfinite(score[src]).astype(x.dtype)
        aug_rows = n_local + jnp.arange(aug)
        x = x.at[aug_rows].set(feats * ok[:, None])
        adjm = adjm.at[n_local:, :].set(0.0)
        adjm = adjm.at[:, n_local:].set(0.0)
        adjm = adjm.at[src, aug_rows].set(ok)
        adjm = adjm.at[aug_rows, src].set(ok)
        node_mask = node_mask.at[aug_rows].set(ok)
        return x, adjm, node_mask

    keys = jax.random.split(key, batch.num_clients)
    x, adjm, node_mask = jax.vmap(per_client)(keys, batch.x, batch.adj,
                                              batch.node_mask)
    return batch.replace(x=x, adj=adjm, node_mask=node_mask)

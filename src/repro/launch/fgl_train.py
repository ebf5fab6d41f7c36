"""FGL training launcher (the paper's experiments from the command line).

  PYTHONPATH=src python -m repro.launch.fgl_train \\
      --dataset cora --method SpreadFGL --clients 6 --servers 3 --rounds 12 \\
      [--local-rounds 4] [--imputation-interval 2] [--top-k 4] \\
      [--partitioner label_prop] [--alpha 1.0] [--participation 1.0] \\
      [--label-ratio 0.3] [--scale 0.15] [--feature-noise 3.0] \\
      [--signal-ratio 0.5] [--seed 0] [--impl reference] [--gossip-every 1] \\
      [--edge-mesh] [--sim-shard] [--json-out hist.json] \\
      [--save-state s.npz] [--resume s.npz] [--profile DIR]

Every method resolves through ``repro.core.registry`` — the same strategy
compositions the benchmarks and examples use (see ``registry.names()`` /
``docs/ARCHITECTURE.md``). ``--save-state`` checkpoints the final
``FGLState``; ``--resume`` restores one and continues Algorithm 1 at the
checkpointed round (true resume: imputation schedule AND gossip round-phase
intact). ``--impl`` selects the hot-path kernels for BOTH the per-client
classifier aggregation and the imputation round's fused similarity top-k:
``reference`` (jnp), ``pallas`` (TPU), or ``pallas_interpret`` (Pallas
kernels in interpret mode — bitwise the same code path as ``pallas``,
runnable on CPU). ``--gossip-every K`` (method ``spreadfgl_gossip``) makes
edge servers exchange parameters with topology neighbors only every K
rounds instead of dense per-round Eq. 16 averaging; combine with
``--edge-mesh`` to place the exchange on the device mesh. ``--sim-shard``
shards the CANDIDATE axis of the imputation similarity top-k across devices
(candidate slabs ring-rotate via collective_permute, ``core/ring_topk.py``);
the result agrees with the single-device search up to f32 rounding
(``ring_topk.topk_violations``), and when combined with ``--edge-mesh`` one
mesh carries both the [N] server axis and the candidate ring.

Heterogeneity axis (``docs/BENCHMARKS.md``): ``--partitioner`` picks the
client-split strategy (``label_prop`` default, ``dirichlet`` label-skew
non-IID with concentration ``--alpha``, ``degree`` degree-skew, ``random``
edge-cut baseline); ``--participation R`` makes only ceil(R·M) clients
contribute to each round's aggregation (partial participation, R in (0,1]).

Straggler axis: ``--async-buffer B`` switches to FedBuff-style buffered
aggregation (method ``spreadfgl_async``; ``--method FedGL`` keeps the star
layout) — each round client updates report with arrival delays drawn from
``--delay-dist`` (``zero`` | ``uniform`` | ``geometric``) and are lost
mid-round with probability ``--dropout-rate``; the server flushes a
staleness-discounted (1/sqrt(1+tau)) weighted mean once B updates are
buffered instead of waiting for all M clients. The whole schedule is a pure
function of (seed, round), so ``--resume`` reproduces it exactly, and
``--async-buffer M --delay-dist zero`` is bit-identical to synchronous
FedAvg.

``--profile DIR`` writes a ``jax.profiler`` trace of the set-up and the
rounds under ``DIR``: the program's ``fgl.*`` host spans and the named
scopes of its device ops (``docs/TRACING.md``).

``parse_args`` + ``build`` are the whole set-up (graph, partition, config,
meshes, registry), so other drivers (``chip_smoke.py``) run exactly this
path; ``main`` adds the fit, the report and the checkpoint files. The
classifier's hidden width is ``FGLConfig``'s paper default (64).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math

import jax
from jax.profiler import TraceAnnotation as span

from repro.checkpoint import io as ckpt_io
from repro.core import registry
from repro.core.partition import (PARTITIONERS, count_missing_links,
                                  label_skew_entropy, make_partitioner,
                                  partition_graph)
from repro.core.types import FGLConfig
from repro.data.synthetic_graphs import DATASETS, make_sbm_graph
from repro.launch import compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the CLI; resolves the method a flag implies."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="cora")
    ap.add_argument("--method", default="SpreadFGL", choices=registry.names())
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--servers", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--local-rounds", type=int, default=4)
    ap.add_argument("--imputation-interval", "-K", type=int, default=2)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--partitioner", default="label_prop",
                    choices=tuple(sorted(PARTITIONERS)),
                    help="client-split strategy (heterogeneity axis): "
                         "label_prop (paper default), dirichlet (label-skew "
                         "non-IID, see --alpha), degree (degree-skew), "
                         "random (edge-cut baseline)")
    ap.add_argument("--alpha", type=float, default=1.0,
                    help="Dirichlet concentration for --partitioner "
                         "dirichlet (small = more label skew)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients participating in each round's "
                         "aggregation (rho in (0,1]; 1.0 = everyone, "
                         "bit-identical to runs without the flag)")
    ap.add_argument("--label-ratio", type=float, default=0.3)
    ap.add_argument("--scale", type=float, default=0.15)
    ap.add_argument("--feature-noise", type=float, default=3.0)
    ap.add_argument("--signal-ratio", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="reference",
                    choices=("reference", "pallas", "pallas_interpret"),
                    help="hot-path kernels for classifier aggregation and the "
                         "fused similarity top-k of the imputation round")
    ap.add_argument("--gossip-every", type=int, default=1,
                    help="cross-server exchange interval K for "
                         "spreadfgl_gossip (1 == dense-equivalent; selecting "
                         "a K forces the spreadfgl_gossip method)")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="FedBuff-style buffered aggregation: flush when B "
                         "client updates are buffered instead of waiting for "
                         "all M (0 = synchronous; selecting B forces the "
                         "spreadfgl_async method)")
    ap.add_argument("--delay-dist", default="zero",
                    choices=("zero", "uniform", "geometric"),
                    help="client arrival-delay distribution for "
                         "--async-buffer (drawn from a key stream "
                         "f(seed, round), independent of the training key)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round probability a client update is lost "
                         "mid-round before reaching the buffer "
                         "(--async-buffer only; in [0, 1))")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--save-state", default="",
                    help="write the final FGLState to this .npz")
    ap.add_argument("--resume", default="",
                    help="restore an FGLState .npz and continue at its round")
    ap.add_argument("--profile", default="",
                    help="write a jax.profiler trace of the set-up and the "
                         "rounds (fgl.* spans, named scopes; docs/TRACING.md) "
                         "under this directory")
    ap.add_argument("--edge-mesh", action="store_true",
                    help="shard the [N] edge-server axis across devices "
                         "(SpreadFGL only)")
    ap.add_argument("--sim-shard", action="store_true",
                    help="shard the CANDIDATE axis of the imputation "
                         "similarity top-k across devices (ring rotation via "
                         "collective_permute, core/ring_topk.py); with "
                         "--edge-mesh the same mesh carries both axes")
    args = ap.parse_args(argv)

    if not 0.0 < args.participation <= 1.0:
        ap.error("--participation must be in (0, 1]")
    if args.gossip_every < 1:
        ap.error("--gossip-every must be >= 1 (1 == exchange every round)")
    if args.gossip_every > 1:
        # Picking an exchange interval means gossip aggregation; only the
        # edge-server compositions have a cross-server exchange to schedule.
        if args.method == "SpreadFGL":
            args.method = "spreadfgl_gossip"
        elif args.method != "spreadfgl_gossip":
            ap.error(f"--gossip-every applies to SpreadFGL/spreadfgl_gossip, "
                     f"not --method {args.method}")
    if args.async_buffer < 0:
        ap.error("--async-buffer must be >= 0 (0 == synchronous)")
    if args.async_buffer > args.clients:
        ap.error(f"--async-buffer {args.async_buffer} can never fill with "
                 f"only {args.clients} clients (one buffer slot per client)")
    if not 0.0 <= args.dropout_rate < 1.0:
        ap.error("--dropout-rate must be in [0, 1)")
    if args.async_buffer > 0:
        # Picking a buffer size means buffered async aggregation; it replaces
        # the synchronous aggregator of the stock compositions. Async FedGL
        # keeps the star layout (one server covering all clients).
        if args.method == "FedGL":
            args.method, args.servers = "spreadfgl_async", 1
        elif args.method == "SpreadFGL":
            args.method = "spreadfgl_async"
        elif args.method != "spreadfgl_async":
            ap.error(f"--async-buffer applies to FedGL/SpreadFGL/"
                     f"spreadfgl_async, not --method {args.method}")
    elif args.method == "spreadfgl_async":
        ap.error("--method spreadfgl_async needs --async-buffer >= 1")
    if args.sim_shard and args.method not in (
            "FedGL", "SpreadFGL", "spreadfgl_gossip", "spreadfgl_async"):
        ap.error(f"--sim-shard needs an imputation round to shard; "
                 f"--method {args.method} has none")
    return args


def build(args: argparse.Namespace):
    """Graph -> client partition -> config -> meshes -> registered trainer.

    Returns ``(trainer, batch)``; prints what it built. Under
    ``jax.profiler`` the set-up is a ``fgl.build`` span with the children
    ``fgl.build/graph``, ``fgl.build/partition`` and ``fgl.build/trainer``
    (``docs/TRACING.md``).
    """
    with span("fgl.build"):
        with span("fgl.build/graph"):
            graph = make_sbm_graph(DATASETS[args.dataset], scale=args.scale,
                                   seed=args.seed + 1, feature_noise=args.feature_noise,
                                   signal_ratio=args.signal_ratio)
        with span("fgl.build/partition"):
            batch = _partition(args, graph)
        with span("fgl.build/trainer"):
            return _trainer(args, batch), batch


def _partition(args: argparse.Namespace, graph):
    part = make_partitioner(args.partitioner, alpha=args.alpha)
    batch, assign = partition_graph(graph, args.clients, aug_max=12,
                                    seed=args.seed, label_ratio=args.label_ratio,
                                    partitioner=part)
    ent = label_skew_entropy(assign, graph.y, args.clients)
    print(f"[fgl] {args.dataset}: {graph.num_nodes} nodes, "
          f"{count_missing_links(graph, assign)} missing cross-client links")
    print(f"[fgl] partitioner={args.partitioner} "
          f"mean client label entropy={ent.mean():.3f} nats")
    return batch


def _trainer(args: argparse.Namespace, batch):
    if args.participation < 1.0:
        n_part = max(1, math.ceil(args.participation * args.clients))
        print(f"[fgl] partial participation: rho={args.participation} "
              f"({n_part} of {args.clients} clients aggregate per round)")
    cfg = FGLConfig(local_rounds=args.local_rounds,
                    imputation_interval=args.imputation_interval,
                    top_k_links=args.top_k, aug_max=12,
                    label_ratio=args.label_ratio, kernel_impl=args.impl,
                    gossip_every=args.gossip_every,
                    async_buffer=args.async_buffer,
                    delay_dist=args.delay_dist,
                    dropout_rate=args.dropout_rate,
                    participation=args.participation, seed=args.seed)
    if args.impl != "reference":
        print(f"[fgl] kernel impl: {args.impl} (fused sim_topk + "
              f"sage_aggregate Pallas kernels)")
    kw = {}
    if args.method in ("SpreadFGL", "spreadfgl_gossip", "spreadfgl_async"):
        kw["num_servers"] = args.servers
        if args.edge_mesh:
            from repro.launch.mesh import make_edge_mesh
            kw["edge_mesh"] = make_edge_mesh(args.servers)
            print(f"[fgl] edge mesh: {kw['edge_mesh'].size} device(s) for "
                  f"N={args.servers}")
    if args.sim_shard:
        if "edge_mesh" in kw:
            # One mesh, two roles: the [N] server axis lives on it as data
            # placement, the candidate axis rotates around it as a ring —
            # mixing two Meshes in one jitted program is the fragile case.
            kw["sim_mesh"] = kw["edge_mesh"]
        else:
            from repro.launch.mesh import make_sim_mesh
            kw["sim_mesh"] = make_sim_mesh()
            if args.impl == "pallas" and kw["sim_mesh"].size > 1:
                # The kernels run per device only under the edge mesh's
                # shard_map (FGLTrainer.vmap); the compiler cannot
                # partition them across a bare candidate ring.
                raise ValueError("--sim-shard with --impl pallas on several "
                                 "devices needs --edge-mesh")
        print(f"[fgl] sim shard: candidate axis over "
              f"{kw['sim_mesh'].size} device(s); the ring folds its slabs "
              f"with an XLA einsum, not the sim_topk kernel")
    if args.method == "spreadfgl_gossip":
        print(f"[fgl] gossip aggregation: cross-server exchange every "
              f"{args.gossip_every} round(s)")
    if args.method == "spreadfgl_async":
        print(f"[fgl] async aggregation: buffer B={args.async_buffer} of "
              f"M={args.clients}, delays={args.delay_dist}, "
              f"dropout={args.dropout_rate}")
    return registry.build(args.method, cfg, batch, **kw)


def main() -> None:
    args = parse_args()
    compile_cache.enable()
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        tr, batch = build(args)
        if args.resume:
            state = ckpt_io.restore(args.resume,
                                    tr.init(jax.random.key(args.seed), batch))
            print(f"[fgl] resumed {args.resume} at round {state.round}")
            state, hist = tr.fit(state=state, rounds=args.rounds)
        else:
            state, hist = tr.fit(jax.random.key(args.seed), batch,
                                 rounds=args.rounds)
    if args.profile:
        print(f"[fgl] profile of the set-up and {args.rounds} rounds "
              f"written under {args.profile}")
    for i, r in enumerate(hist["round"]):
        print(f"[fgl] round {r:3d} loss={hist['loss'][i]:8.4f} "
              f"acc={hist['acc'][i]:.3f} f1={hist['f1'][i]:.3f}")
    print(f"[fgl] best acc={max(hist['acc']):.3f} f1={max(hist['f1']):.3f}")
    if args.save_state:
        ckpt_io.save(args.save_state, state)
        print(f"[fgl] saved FGLState (round {state.round}) to {args.save_state}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(hist, f)


if __name__ == "__main__":
    main()

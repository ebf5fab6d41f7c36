"""SpreadFGL vs FedGL vs baselines: the paper's multi-edge scenario.

  PYTHONPATH=src python examples/spreadfgl_multiserver.py [--impl pallas]

Three edge servers on a ring (the paper's testbed topology), Eq. 16 neighbor
aggregation + Eq. 15 trace regularizer, compared against the centralized
FedGL, the decentralized gossip variant (``spreadfgl_gossip``, cross-server
exchange every ``--gossip-every`` rounds only), and the three baselines of
Sec. IV-A on the same partition. ``--impl`` selects the hot-path kernels
(reference | pallas | pallas_interpret) for every method — the single
``FGLConfig.kernel_impl`` knob covers both classifier aggregation and the
imputation round's fused similarity top-k.

The heterogeneity axis rides along: ``--partitioner dirichlet --alpha 0.1``
skews the client split non-IID and ``--participation 0.5`` lets only half
the clients aggregate per round (see ``docs/BENCHMARKS.md``, heterogeneity
section, for the full sweep).
"""
import argparse

import jax

from repro.core import registry
from repro.core.partition import (PARTITIONERS, label_skew_entropy,
                                  make_partitioner, partition_graph)
from repro.core.types import FGLConfig
from repro.data.synthetic_graphs import DATASETS, make_sbm_graph
from repro.launch.mesh import make_edge_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="reference",
                    choices=("reference", "pallas", "pallas_interpret"))
    ap.add_argument("--gossip-every", type=int, default=4,
                    help="cross-server exchange interval of the gossip row")
    ap.add_argument("--partitioner", default="label_prop",
                    choices=tuple(sorted(PARTITIONERS)),
                    help="client-split strategy (heterogeneity axis)")
    ap.add_argument("--alpha", type=float, default=1.0,
                    help="Dirichlet concentration (--partitioner dirichlet)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="per-round participating-client fraction rho")
    args = ap.parse_args()

    graph = make_sbm_graph(DATASETS["citeseer"], scale=0.15, seed=1,
                           feature_noise=3.0, signal_ratio=0.5)
    part = make_partitioner(args.partitioner, alpha=args.alpha)
    batch, assign = partition_graph(graph, num_clients=6, aug_max=12, seed=0,
                                    partitioner=part)
    ent = label_skew_entropy(assign, graph.y, 6)
    print(f"partitioner={args.partitioner} rho={args.participation} "
          f"mean client label entropy={ent.mean():.3f} nats")
    cfg = FGLConfig(hidden_dim=32, local_rounds=4, imputation_interval=2,
                    top_k_links=4, aug_max=12, kernel_impl=args.impl,
                    participation=args.participation,
                    gossip_every=args.gossip_every)

    # The [N] server axis shards across whatever devices exist (size-1 mesh on
    # a single-device host — identical numbers, no sharding). Every method is
    # a registered strategy composition.
    mesh = make_edge_mesh(3)
    methods = {
        "LocalFGL": registry.build("local", cfg, batch),
        "FedAvg-fusion": registry.build("fedavg_fusion", cfg, batch),
        "FedSage+": registry.build("fedsage_plus", cfg, batch),
        "FedGL": registry.build("FedGL", cfg, batch),
        "SpreadFGL (3 servers, ring)": registry.build(
            "SpreadFGL", cfg, batch, num_servers=3, edge_mesh=mesh),
        f"SpreadFGL-gossip (K={args.gossip_every})": registry.build(
            "spreadfgl_gossip", cfg, batch, num_servers=3, edge_mesh=mesh),
    }
    print(f"{'method':30s} {'best ACC':>9s} {'best F1':>9s} {'final loss':>11s}")
    for name, tr in methods.items():
        _, hist = tr.fit(jax.random.key(0), batch, rounds=12)
        print(f"{name:30s} {max(hist['acc']):9.3f} {max(hist['f1']):9.3f} "
              f"{hist['loss'][-1]:11.4f}")


if __name__ == "__main__":
    main()

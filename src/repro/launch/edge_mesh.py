import os
import sys

for _i, _a in enumerate(sys.argv):  # must precede the first jax import
    if _a == "--devices" and _i + 1 < len(sys.argv):
        _n = sys.argv[_i + 1]
    elif _a.startswith("--devices="):
        _n = _a.split("=", 1)[1]
    else:
        continue
    if _n.isdigit() and int(_n) > 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={_n}").strip()
    break

"""Edge-server device mesh launcher.

Places SpreadFGL's stacked ``[N]`` edge-server axis (core/fedgl.py) on a JAX
device mesh so the vmapped imputation round runs data-parallel across devices:
each device owns ``N / mesh.size`` servers' autoencoder + assessor state and
their slice of the similarity/top-k work.

  # 4 emulated host devices, 4 edge servers, one server per device:
  PYTHONPATH=src python -m repro.launch.edge_mesh --devices 4 --servers 4

  # Decentralized gossip training: neighbor exchange every 4 rounds only,
  # executed as collective_permute across the mesh (Sec. III-E):
  PYTHONPATH=src python -m repro.launch.edge_mesh --devices 4 --servers 4 \\
      --gossip-every 4

On a 1-device host the mesh degenerates to size 1 (plain vmap) — same
numbers, no sharding. The ``--devices`` flag must be handled before the first
jax import (jax locks the device count on first initialization), hence the
header above. ``--gossip-every 0`` (the default) keeps dense per-round
Eq. 16 neighbor aggregation; any K >= 1 switches to the
``spreadfgl_gossip`` composition (K=1 is numerically the dense rule with
the exchange routed through the mesh collectives).

``--sim-shard`` additionally rotates the imputation round's CANDIDATE axis
around the same mesh as a ring (``core/ring_topk.py``): each device streams
every other device's candidate slab through collective_permute and folds it
into its running top-k — the single-device result up to f32 rounding
(``ring_topk.topk_violations``), 1/size candidate residency per device.
"""
import argparse

import jax

from repro.core.partition import partition_graph
from repro.core.spreadfgl import make_spreadfgl, make_spreadfgl_gossip
from repro.core.types import FGLConfig
from repro.data.synthetic_graphs import DATASETS, make_sbm_graph
from repro.launch import compile_cache
from repro.launch.mesh import make_edge_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="emulated host device count (0 = use real devices)")
    ap.add_argument("--dataset", choices=tuple(DATASETS), default="cora")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--servers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--gossip-every", type=int, default=0,
                    help="cross-server exchange interval K (0 = dense "
                         "per-round Eq. 16 aggregation)")
    ap.add_argument("--sim-shard", action="store_true",
                    help="ring-rotate the imputation candidate axis around "
                         "the mesh (core/ring_topk.py)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    compile_cache.enable()
    mesh = make_edge_mesh(args.servers)
    print(f"[edge-mesh] {len(jax.devices())} device(s); mesh size {mesh.size} "
          f"for N={args.servers} edge servers")
    sim_mesh = mesh if args.sim_shard else None
    if args.sim_shard:
        print(f"[edge-mesh] sim shard: candidate slabs ring-rotate over "
              f"{mesh.size} device(s)")

    graph = make_sbm_graph(DATASETS[args.dataset], scale=0.15, seed=args.seed + 1,
                           feature_noise=3.0, signal_ratio=0.5)
    batch, _ = partition_graph(graph, args.clients, aug_max=12, seed=args.seed)
    cfg = FGLConfig(local_rounds=4, imputation_interval=2,
                    top_k_links=4, aug_max=12,
                    gossip_every=max(args.gossip_every, 1))
    if args.gossip_every > 0:
        print(f"[edge-mesh] gossip aggregation: neighbor exchange every "
              f"{args.gossip_every} round(s) over the mesh")
        tr = make_spreadfgl_gossip(cfg, batch, num_servers=args.servers,
                                   edge_mesh=mesh, sim_mesh=sim_mesh)
    else:
        tr = make_spreadfgl(cfg, batch, num_servers=args.servers,
                            edge_mesh=mesh, sim_mesh=sim_mesh)

    state = tr.init(jax.random.key(args.seed), batch)
    placement = {d.id for leaf in jax.tree.leaves(state.ae_params)
                 for d in leaf.devices()}
    print(f"[edge-mesh] stacked generator state spans device(s) {sorted(placement)}")

    _, hist = tr.fit(jax.random.key(args.seed), batch, rounds=args.rounds)
    print(f"[edge-mesh] {args.rounds} rounds — "
          f"best acc={max(hist['acc']):.3f} f1={max(hist['f1']):.3f}")


if __name__ == "__main__":
    main()

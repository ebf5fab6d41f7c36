"""Edge-layer load balancing (Sec. III-E claim): per-server aggregation
traffic and peak load, FedGL (single edge server) vs SpreadFGL (N servers,
ring topology).

Bytes are computed from the actual classifier parameter tree: every
edge-client communication a server receives W from each covered client and
broadcasts back; on imputation rounds SpreadFGL servers additionally exchange
parameters with their ring neighbors (Eq. 16). The paper's claim: the maximum
per-server load drops ~N× — the single aggregation point disappears.

The wall-time section measures the stacked-[N] refactor: one vmapped
imputation round (sharded over the edge mesh when >1 device is available) vs
the seed's sequential per-server loop (``_imputation_round_reference``) for
N ∈ {1, 2, 4, 8} on the same host. Run as a script this emulates 8 host
devices so the mesh actually spreads servers; via ``run.py`` it uses whatever
devices exist.
"""
from __future__ import annotations

import dataclasses
import os

if __name__ == "__main__":  # must precede the first jax import
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from benchmarks.common import ROUNDS, fgl_setup, timeit, write_result
from repro.core import gossip
from repro.core.partition import ring_adjacency
from repro.core.spreadfgl import (make_fedgl, make_spreadfgl,
                                  make_spreadfgl_gossip)
from repro.launch.mesh import make_edge_mesh


def param_bytes(trainer, batch) -> int:
    state = trainer.init(jax.random.key(0), batch)
    one_client = jax.tree.map(lambda p: p[0], state.params)
    return int(sum(np.prod(p.shape) * p.dtype.itemsize
                   for p in jax.tree.leaves(one_client)))


def main(fast: bool = False):
    print("[bench] edge-layer load balance (FedGL vs SpreadFGL)")
    _, batch, cfg = fgl_setup("cora", 6)
    out = {}
    for name, make in (("FedGL(N=1)", lambda: make_fedgl(cfg, batch)),
                       ("SpreadFGL(N=3)", lambda: make_spreadfgl(cfg, batch,
                                                                 num_servers=3))):
        tr = make()
        pb = param_bytes(tr, batch)
        m_per = tr.m_per
        n = tr.n_servers
        # per round: up + down per covered client; + neighbor exchange on
        # K-rounds (byte math shared with core/gossip.py).
        per_round = 2 * m_per * pb
        neighbor = gossip.dense_neighbor_bytes_per_round(
            ring_adjacency(n), pb, every=cfg.imputation_interval)
        out[name] = {"servers": n, "clients_per_server": m_per,
                     "param_bytes": pb,
                     "per_server_bytes_per_round": per_round + neighbor,
                     "peak_load_bytes": per_round + neighbor}
        print(f"  {name:16s} per-server bytes/round = "
              f"{(per_round + neighbor)/1e6:.3f} MB (clients={m_per})")
    ratio = (out["FedGL(N=1)"]["peak_load_bytes"]
             / out["SpreadFGL(N=3)"]["peak_load_bytes"])
    out["peak_load_reduction"] = ratio
    print(f"  peak-load reduction: {ratio:.2f}x")
    out["imputation_walltime"] = bench_imputation_walltime(fast=fast)
    out["impl_sweep"] = bench_impl_sweep(fast=fast)
    out["gossip"] = bench_gossip_aggregation(fast=fast)
    write_result("load_balance", out)
    return out


def bench_gossip_aggregation(fast: bool = False):
    """Gossip-K vs dense Eq. 16 vs FedAvg: bytes/round, wall time, convergence.

    For N ∈ {1, 2, 4, 8} edge servers, reports per-server cross-server
    bytes/round (amortized over the exchange interval; math from
    ``core/gossip.py``) and the measured wall time of one aggregation call —
    on exchange rounds AND on skip rounds, where the gossip aggregator
    lowers to per-server FedAvg with zero cross-server collectives. A
    convergence sweep at a representative N records full accuracy/F1
    histories for gossip-K ∈ {1, 4, 8} against dense neighbor aggregation
    and single-point FedGL. Own results file:
    ``results/gossip_load_balance.json``.
    """
    n_dev = len(jax.devices())
    print(f"[bench] gossip aggregation (K-amortized exchange) on {n_dev} "
          f"device(s)")
    _, batch, cfg = fgl_setup("cora", 8)   # 8 clients: N in {1,2,4,8} divide
    iters = 2 if fast else 5
    ks = (1, 4, 8)
    out = {"devices": n_dev, "gossip_K": list(ks)}

    for n in ((1, 2) if fast else (1, 2, 4, 8)):
        mesh = make_edge_mesh(n) if (n > 1 and n_dev > 1) else None
        tr_d = (make_fedgl(cfg, batch) if n == 1
                else make_spreadfgl(cfg, batch, num_servers=n, edge_mesh=mesh))
        pb = param_bytes(tr_d, batch)
        out.setdefault("param_bytes", pb)
        adj = ring_adjacency(n)
        state_d = tr_d.init(jax.random.key(0), batch)
        rows = {"dense_neighbor": {
            "cross_server_bytes_per_round":
                gossip.dense_neighbor_bytes_per_round(adj, pb),
            "agg_round_us": timeit(
                lambda: tr_d.aggregate(state_d.params, round=0), iters=iters)},
            "fedavg_allreduce": {
            "cross_server_bytes_per_round":
                gossip.allreduce_bytes_per_round(pb, n)}}
        for k in ks:
            tr_g = make_spreadfgl_gossip(dataclasses.replace(cfg, gossip_every=k),
                                         batch, num_servers=n, edge_mesh=mesh)
            state_g = tr_g.init(jax.random.key(0), batch)
            t_ex = timeit(lambda: tr_g.aggregate(state_g.params, round=k - 1),
                          iters=iters)
            t_skip = (t_ex if k == 1 else
                      timeit(lambda: tr_g.aggregate(state_g.params, round=0),
                             iters=iters))
            bytes_pr = (gossip.ring_gossip_bytes_per_round(pb, every=k)
                        if n >= 3 else
                        gossip.dense_neighbor_bytes_per_round(adj, pb, every=k))
            rows[f"gossip_K{k}"] = {
                "cross_server_bytes_per_round": bytes_pr,
                "exchange_round_us": t_ex, "skip_round_us": t_skip,
                "amortized_round_us": (t_ex + (k - 1) * t_skip) / k}
            print(f"  N={n} gossip K={k}: bytes/round {bytes_pr/1e3:8.2f} kB  "
                  f"exchange {t_ex/1e3:7.2f} ms  skip {t_skip/1e3:7.2f} ms")
        dense_b = rows["dense_neighbor"]["cross_server_bytes_per_round"]
        for k in ks:
            gb = rows[f"gossip_K{k}"]["cross_server_bytes_per_round"]
            rows[f"gossip_K{k}"]["bytes_vs_dense"] = (
                gb / dense_b if dense_b else 1.0)
        out[f"N={n}"] = rows

    # Convergence: does K-amortized exchange track dense aggregation?
    n_conv = 2 if fast else 4
    rounds = 4 if fast else ROUNDS
    conv = {"servers": n_conv, "rounds": rounds}
    mesh = make_edge_mesh(n_conv) if (n_conv > 1 and n_dev > 1) else None
    runs = [("FedGL", lambda: make_fedgl(cfg, batch)),
            ("dense_neighbor", lambda: make_spreadfgl(
                cfg, batch, num_servers=n_conv, edge_mesh=mesh))]
    runs += [(f"gossip_K{k}", lambda k=k: make_spreadfgl_gossip(
        dataclasses.replace(cfg, gossip_every=k), batch, num_servers=n_conv,
        edge_mesh=mesh))
        for k in ks]
    for name, make in runs:
        _, hist = make().fit(jax.random.key(0), batch, rounds=rounds)
        conv[name] = hist
        print(f"  convergence N={n_conv} {name:14s} "
              f"best acc={max(hist['acc']):.3f} f1={max(hist['f1']):.3f}")
    out["convergence"] = conv
    write_result("gossip_load_balance", out)
    return out


def bench_impl_sweep(fast: bool = False):
    """kernel_impl sweep over the full imputation round (own results file).

    Times one vmapped SpreadFGL imputation round per impl. On CPU the Pallas
    path runs in interpret mode (``pallas_interpret``), so its wall time is a
    correctness checkpoint, not a speed claim — the compiled ``pallas`` row
    only appears when a TPU is attached.
    """
    print("[bench] kernel_impl sweep over the imputation round")
    _, batch, cfg = fgl_setup("cora", 6)
    on_tpu = jax.default_backend() == "tpu"
    impls = ("reference", "pallas") if on_tpu else ("reference",
                                                    "pallas_interpret")
    iters = 2 if fast else 5
    out = {"backend": jax.default_backend()}
    for impl in impls:
        tr = make_spreadfgl(dataclasses.replace(cfg, kernel_impl=impl), batch,
                            num_servers=3)
        state = tr.init(jax.random.key(0), batch)
        t = timeit(lambda: tr._impute_fn(state), iters=iters)
        out[impl] = {"imputation_round_us": t}
        print(f"  {impl:18s} imputation round {t/1e3:8.1f} ms")
    if "reference" in out and len(out) > 2:
        other = [i for i in impls if i != "reference"][0]
        out["speedup_vs_reference"] = (
            out["reference"]["imputation_round_us"]
            / out[other]["imputation_round_us"])
    write_result("impl_sweep", out)
    return out


def bench_imputation_walltime(fast: bool = False):
    """Per-round wall time of the imputation round, vmapped vs sequential."""
    n_dev = len(jax.devices())
    print(f"[bench] imputation round wall time (vmapped [N] on {n_dev} "
          f"device(s) vs sequential loop)")
    _, batch, cfg = fgl_setup("cora", 8)   # 8 clients: N in {1,2,4,8} all divide
    iters = 2 if fast else 5
    out = {"devices": n_dev}

    for n in ((1, 2) if fast else (1, 2, 4, 8)):
        mesh = make_edge_mesh(n) if (n > 1 and n_dev > 1) else None
        tr_v = (make_fedgl(cfg, batch) if n == 1
                else make_spreadfgl(cfg, batch, num_servers=n, edge_mesh=mesh))
        state_v = tr_v.init(jax.random.key(0), batch)
        t_vmap = timeit(lambda: tr_v._impute_fn(state_v), iters=iters)
        # Sequential baseline: the seed's per-server loop, single device.
        tr_s = (make_fedgl(cfg, batch) if n == 1
                else make_spreadfgl(cfg, batch, num_servers=n))
        state_s = tr_s.init(jax.random.key(0), batch)
        seq_fn = jax.jit(tr_s._imputation_round_reference)
        t_seq = timeit(lambda: seq_fn(state_s), iters=iters)
        out[f"N={n}"] = {"servers": n, "mesh_devices": mesh.size if mesh else 1,
                         "vmapped_round_us": t_vmap,
                         "sequential_round_us": t_seq,
                         "speedup": t_seq / t_vmap}
        print(f"  N={n}: vmapped {t_vmap/1e3:8.1f} ms "
              f"(mesh={mesh.size if mesh else 1})   "
              f"sequential {t_seq/1e3:8.1f} ms   speedup {t_seq/t_vmap:.2f}x")
    return out


if __name__ == "__main__":
    main()

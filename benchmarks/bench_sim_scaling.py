"""Scaling the imputation similarity search: candidate-sharded ring top-k
at n ∈ {10k, 100k, 1M} synthetic nodes.

The question this bench answers: does ``core/ring_topk.py`` make the A̅ =
H Hᵀ similarity sweep (Sec. III-C) — the FGL-side compute wall — scale to
the ROADMAP's million-node regime? For each n it:

- Generates an SBM graph in the scale-up regime of
  ``data/synthetic_graphs.py`` (``scale > 1.0``, vectorized sampler) and
  builds class-probability embeddings H [n, c] from its labels — the same
  kind of softmax-space features the generator round fuses.
- Times the ring-sharded masked top-k of ``q`` query rows against ALL n
  candidates (full-sweep timing at n = 1M is ~2e13 FLOPs — days on host
  CPU — so the sweep is query-subsampled and the full-sweep time is
  reported as the measured-rate extrapolation, labeled as such).
- Validates achieved FLOP/s against the ``repro.roofline`` peak
  (``hw.PEAK_FLOPS_BF16``) — achieved must stay below peak, and the
  fraction is reported — and accounts per-rotation / total ring bytes next
  to the all-gather alternative (byte model in ``core/ring_topk.py``,
  conventions shared with ``core/gossip.py``), plus the per-device
  candidate residency that makes the sharded layout fit at 1M nodes.
- Checks the ring against the single-device search on the smallest n
  before timing anything, under ``ring_topk.topk_violations``'s contract
  (pinned in ``tests/test_ring_topk.py``; this is the bench's own seal).

Run standalone it emulates 8 host devices (flag handled before the first
jax import, same idiom as ``bench_load_balance``); under ``benchmarks.run``
it uses whatever devices exist (a 1-device host degenerates to the unsharded
fold — byte accounting then reports zero cross-device traffic).

``--fast`` caps n at 10k (CI-sized). Results:
``benchmarks/results/sim_scaling.json``.
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # must precede the first jax import
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit, write_result
from repro.core.ring_topk import (allgather_bytes, dot_tolerance,
                                  ring_rotation_bytes, ring_similarity_topk,
                                  ring_total_bytes, sim_topk_flops,
                                  topk_violations)
from repro.data.synthetic_graphs import DatasetStats, make_sbm_graph
from repro.roofline import hw

C = 16            # embedding width (softmax-space class dim, Table-I sized)
K = 8             # top-k links kept per query row
N_CLIENTS = 8     # client id stripes for the cross-subgraph mask


def _embeddings(n: int, seed: int):
    """H [n, C] from a scale-up SBM graph: softmax(class one-hot + noise).

    The graph comes from the documented ``scale > 1.0`` generator path
    (num_nodes = n/2 at scale 2.0), so this bench exercises exactly the
    regime ``tests/test_synthetic_scale.py`` pins.
    """
    stats = DatasetStats("sim_scaling", n // 2, n // 2, 32, C, 0.7)
    g = make_sbm_graph(stats, scale=2.0, seed=seed)
    rng = np.random.default_rng(seed + 1)
    logits = (2.0 * np.eye(C, dtype=np.float32)[g.y]
              + rng.standard_normal((n, C)).astype(np.float32))
    h = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    cid = jnp.asarray(np.arange(n) % N_CLIENTS, jnp.int32)
    tmask = jnp.asarray(rng.random(n) < 0.9, jnp.float32)
    return h, cid, tmask


def _bench_one(n: int, q: int, mesh, iters: int):
    size = int(mesh.size)
    h, cid, tmask = _embeddings(n, seed=n % 1000)
    queries, qcid = h[:q], cid[:q]

    fn = jax.jit(lambda h_, c_, t_, q_, qc_: ring_similarity_topk(
        h_, c_, t_, K, mesh=mesh, queries=q_, query_cid=qc_))
    us = timeit(lambda: fn(h, cid, tmask, queries, qcid),
                warmup=1, iters=iters)
    secs = us / 1e6

    flops = sim_topk_flops(q, n, C)
    achieved = flops / secs
    peak = hw.PEAK_FLOPS_BF16
    assert achieved < peak, (
        f"measured {achieved:.3e} FLOP/s exceeds the roofline peak "
        f"{peak:.3e} — the FLOP model or the timer is wrong")
    row = {
        "n": n, "q": q, "c": C, "k": K, "mesh_devices": size,
        "wall_us": us,
        "flops": flops,
        "achieved_flops_per_s": achieved,
        "peak_flops_per_s": peak,
        "fraction_of_peak": achieved / peak,
        "extrapolated_full_sweep_s": secs * (n / q),
        "bytes_per_rotation": ring_rotation_bytes(n, C, size),
        "ring_total_bytes": ring_total_bytes(n, C, size),
        "allgather_bytes": allgather_bytes(n, C, size),
        "rotation_ici_us": (ring_rotation_bytes(n, C, size)
                            / hw.ICI_BW_PER_LINK * 1e6),
        "candidate_bytes_per_device": float(
            ((n + size - 1) // size) * (C * 4 + 8)),
        "candidate_bytes_unsharded": float(n * (C * 4 + 8)),
    }
    print(f"  n={n:>9,} q={q} devices={size}: {us/1e3:9.1f} ms  "
          f"{achieved/1e9:8.2f} GFLOP/s ({row['fraction_of_peak']:.2e} of "
          f"peak)  rot={row['bytes_per_rotation']/1e6:.2f} MB  "
          f"full-sweep≈{row['extrapolated_full_sweep_s']:.1f}s")
    return row


def _parity_seal(mesh):
    """Ring agrees with the single-device reference on a small case."""
    from repro.core import imputation
    h, cid, tmask = _embeddings(2000, seed=0)
    exp_s, exp_i = imputation.similarity_topk(h, jnp.ones(2000), cid, K,
                                              target_mask=tmask)
    got_s, got_i = jax.jit(lambda h_, c_, t_: imputation.similarity_topk(
        h_, jnp.ones(2000), c_, K, target_mask=t_, mesh=mesh))(h, cid, tmask)
    rep = topk_violations(h, cid, tmask, got_s, got_i, exp_s, exp_i,
                          tol=dot_tolerance(h))
    assert rep["violations"] == 0, rep


def main(fast: bool = False):
    from jax.sharding import Mesh
    n_dev = len(jax.devices())
    print(f"[bench] sim scaling: candidate-sharded ring top-k on {n_dev} "
          f"device(s)")
    mesh = Mesh(np.array(jax.devices()), ("sim",))
    _parity_seal(mesh)
    print(f"  parity seal: ring(size={mesh.size}) agrees with the reference "
          f"at n=2000")

    sizes = (2_000, 10_000) if fast else (10_000, 100_000, 1_000_000)
    q = 256 if fast else 1024
    iters = 2 if fast else 3
    out = {"devices": n_dev, "fast": bool(fast),
           "query_subsample_note":
               "wall_us times q query rows against all n candidates; "
               "extrapolated_full_sweep_s scales the measured rate to q=n",
           "rows": [_bench_one(n, min(q, n), mesh, iters) for n in sizes]}
    write_result("sim_scaling", out)
    return out


if __name__ == "__main__":
    main()

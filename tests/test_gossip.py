"""Gossip aggregation semantics (Sec. III-E decentralized edge training).

Pins the equivalences the ``spreadfgl_gossip`` composition rests on:

- ``GossipAggregator(ring, every_k=1)`` == ``NeighborAggregator`` on a ring
  adjacency (the ISSUE's allclose parity regression), for the raw
  aggregator AND full fixed-seed training histories.
- Skip rounds (round-phase not on the exchange schedule) == per-server
  FedAvg with no cross-server mixing.
- A gossip exchange preserves the server-mean of parameters (the
  doubly-stochastic property the Fig. 8/9 convergence argument needs) —
  under ``shard_map`` on a real multi-device edge mesh (subprocess).
- Save/resume mid-gossip-interval restores the round-phase: fit(6) ==
  fit(3) + checkpoint round-trip + fit(3) with ``every_k=2``.
"""
import dataclasses
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io
from repro.core import gossip, registry
from repro.core import strategies as S
from repro.core.partition import ring_adjacency
from repro.core.spreadfgl import make_spreadfgl, make_spreadfgl_gossip
from repro.core.types import FGLConfig


def stacked_params(key, m):
    """A [M]-stacked classifier-like pytree with distinct per-client values."""
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m, 5, 3)),
            "b": jax.random.normal(k2, (m, 3))}


@pytest.fixture(scope="module")
def small(small_batch):
    # Overrides the session `small` (conftest.py): same shared batch, but
    # K=2 so the gossip round-phase and imputation schedule interleave.
    cfg = FGLConfig(hidden_dim=16, local_rounds=2, imputation_interval=2,
                    top_k_links=3, aug_max=8)
    return small_batch, cfg


class TestAggregatorParity:
    @pytest.mark.parametrize("n,m_per", [(2, 2), (4, 2), (8, 1)])
    def test_k1_ring_matches_neighbor_aggregator(self, n, m_per):
        """The pinned regression: GossipAggregator(ring, every_k=1) ==
        NeighborAggregator on a ring adjacency."""
        params = stacked_params(jax.random.key(0), n * m_per)
        adj = jnp.asarray(ring_adjacency(n))
        dense = S.NeighborAggregator().aggregate(
            params, adj=adj, num_servers=n, m_per=m_per)
        gossiped = S.GossipAggregator(topology="ring", every_k=1).aggregate(
            params, adj=adj, num_servers=n, m_per=m_per, flag=1)
        for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(gossiped)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_k1_adjacency_matches_neighbor_aggregator(self):
        """The star/custom-adjacency variant reproduces Eq. 16 for ANY a_rj."""
        n, m_per = 4, 2
        params = stacked_params(jax.random.key(1), n * m_per)
        adj = jnp.asarray(np.array([[1, 1, 0, 1],
                                    [1, 1, 1, 0],
                                    [0, 1, 1, 1],
                                    [1, 0, 1, 1]], np.float32))
        dense = S.NeighborAggregator().aggregate(
            params, adj=adj, num_servers=n, m_per=m_per)
        gossiped = S.GossipAggregator(topology="adjacency", every_k=1).aggregate(
            params, adj=adj, num_servers=n, m_per=m_per, flag=1)
        for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(gossiped)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_skip_round_is_per_server_fedavg(self):
        """Off-schedule rounds do only within-server averaging."""
        n, m_per = 4, 2
        params = stacked_params(jax.random.key(2), n * m_per)
        adj = jnp.asarray(ring_adjacency(n))
        agg = S.GossipAggregator(topology="ring", every_k=4)
        fedavg = S.FedAvgAggregator().aggregate(
            params, adj=adj, num_servers=n, m_per=m_per)
        for t in (0, 1, 2):    # exchange happens only at round 3
            flag, _ = agg.schedule(t, n * m_per)
            skipped = agg.aggregate(params, adj=adj, num_servers=n,
                                    m_per=m_per, flag=flag)
            for a, b in zip(jax.tree.leaves(fedavg), jax.tree.leaves(skipped)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6)
        exchanged = agg.aggregate(params, adj=adj, num_servers=n,
                                  m_per=m_per, flag=agg.schedule(3, n * m_per)[0])
        assert not np.allclose(np.asarray(exchanged["w"]),
                               np.asarray(fedavg["w"]), rtol=1e-6)

    def test_exchange_preserves_server_mean(self):
        """Ring gossip is doubly stochastic: the mean server model is
        invariant (the convergence argument of Fig. 8/9)."""
        n, m_per = 8, 1
        params = stacked_params(jax.random.key(3), n * m_per)
        agg = S.GossipAggregator(topology="ring", every_k=1)
        out = agg.aggregate(params, adj=jnp.asarray(ring_adjacency(n)),
                            num_servers=n, m_per=m_per, flag=1)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(a).mean(0),
                                       np.asarray(b).mean(0), rtol=1e-5)

    def test_block_ring_matches_per_server_ring(self):
        """block_ring_gossip on the host axis == ring neighbor average."""
        n = 5
        w = {"w": jax.random.normal(jax.random.key(4), (n, 3))}
        out = gossip.block_ring_gossip(w)["w"]
        for j in range(n):
            want = (w["w"][j] + w["w"][(j - 1) % n] + w["w"][(j + 1) % n]) / 3
            np.testing.assert_allclose(np.asarray(out[j]), np.asarray(want),
                                       rtol=1e-6)

    def test_validation(self):
        with pytest.raises(ValueError, match="topology"):
            S.GossipAggregator(topology="mesh")
        with pytest.raises(ValueError, match="every_k"):
            S.GossipAggregator(every_k=0)


class TestRingSingleSource:
    """Ring structure has one source: ``partition.ring_adjacency``.

    ``strategies.RingTopology`` consumes that matrix directly; the implicit
    left/right collective_permute schedule of ``gossip.block_ring_gossip``
    must realize the SAME adjacency — this cross-consistency check is what
    lets the repo keep a matrix-free ring kernel without a second ring
    definition drifting from the first (see both docstrings).
    """

    @pytest.mark.parametrize("n", [3, 5, 8])
    def test_block_ring_gossip_equals_ring_adjacency_mix(self, n):
        w = {"w": jax.random.normal(jax.random.key(2), (n, 4, 3))}
        via_permute = gossip.block_ring_gossip(w)
        via_matrix = gossip.adjacency_gossip(w, jnp.asarray(ring_adjacency(n)))
        np.testing.assert_allclose(np.asarray(via_permute["w"]),
                                   np.asarray(via_matrix["w"]), rtol=1e-6)

    def test_ring_topology_layout_uses_ring_adjacency(self):
        lay = S.RingTopology(num_servers=4).build(8)
        np.testing.assert_array_equal(lay.adjacency, ring_adjacency(4))


class TestEngineParity:
    def test_k1_history_matches_dense_spreadfgl(self, small):
        """Full training: spreadfgl_gossip(K=1) == SpreadFGL round for round."""
        batch, cfg = small
        _, dense = make_spreadfgl(cfg, batch, num_servers=2).fit(
            jax.random.key(0), batch, rounds=4)
        _, gossiped = make_spreadfgl_gossip(
            dataclasses.replace(cfg, gossip_every=1), batch, num_servers=2).fit(
            jax.random.key(0), batch, rounds=4)
        for k in ("loss", "acc", "f1"):
            np.testing.assert_allclose(gossiped[k], dense[k], rtol=1e-4,
                                       atol=1e-6, err_msg=f"history[{k!r}]")

    def test_registry_builds_gossip_method(self, small):
        batch, cfg = small
        assert "spreadfgl_gossip" in registry.names()
        tr = registry.build("spreadfgl_gossip",
                            dataclasses.replace(cfg, gossip_every=3), batch,
                            num_servers=2)
        assert isinstance(tr.aggregator, S.GossipAggregator)
        assert tr.aggregator.every_k == 3
        assert [tr._schedule(t) for t in range(6)] == [(0, None), (0, None), (1, None)] * 2

    def test_gossip_every_defaults_to_cfg(self, small):
        batch, cfg = small
        cfg = dataclasses.replace(cfg, gossip_every=5)
        tr = registry.build("spreadfgl_gossip", cfg, batch, num_servers=2)
        assert tr.aggregator.every_k == 5

    def test_k_gt_1_differs_from_dense(self, small):
        """The schedule is real: K=2 produces a different round-1 state."""
        batch, cfg = small
        _, dense = make_spreadfgl(cfg, batch, num_servers=2).fit(
            jax.random.key(0), batch, rounds=2)
        _, gossiped = make_spreadfgl_gossip(
            dataclasses.replace(cfg, gossip_every=2), batch, num_servers=2).fit(
            jax.random.key(0), batch, rounds=2)
        assert not np.allclose(gossiped["loss"], dense["loss"], rtol=1e-6)


class TestResumeMidInterval:
    def test_resume_restores_gossip_phase(self, small):
        """fit 6 == fit 3 + save/load + fit 3 with every_k=2: the resumed
        run re-enters the exchange schedule at phase round%K (round 3 is an
        exchange round — only hit if the phase survives the checkpoint)."""
        batch, cfg = small
        tr = make_spreadfgl_gossip(dataclasses.replace(cfg, gossip_every=2), batch,
                                   num_servers=2)
        _, full = tr.fit(jax.random.key(0), batch, rounds=6)

        state, first = tr.fit(jax.random.key(0), batch, rounds=3)
        path = os.path.join(tempfile.mkdtemp(), "gossip_resume.npz")
        io.save(path, state)
        restored = io.restore(path, tr.init(jax.random.key(0), batch))
        assert restored.round == 3
        state2, second = tr.fit(state=restored, rounds=3)

        assert first["round"] + second["round"] == full["round"]
        for k in ("loss", "acc", "f1"):
            np.testing.assert_allclose(first[k] + second[k], full[k],
                                       atol=1e-6, err_msg=f"history[{k!r}]")
        assert state2.round == 6


@pytest.mark.slow
def test_gossip_exchange_crosses_edge_mesh_subprocess():
    """GossipAggregator under shard_map on a 4-device edge mesh: the
    exchange matches the mesh-free path and preserves the server mean —
    aggregation bytes genuinely cross the (emulated) device boundary."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import strategies as S
        from repro.core.partition import ring_adjacency

        n, m_per = 4, 2
        key = jax.random.key(0)
        params = {"w": jax.random.normal(key, (n * m_per, 5, 3))}
        adj = jnp.asarray(ring_adjacency(n))
        mesh = Mesh(jax.devices()[:4], ("edge",))
        meshed = S.GossipAggregator(topology="ring", every_k=1, mesh=mesh)
        hosted = S.GossipAggregator(topology="ring", every_k=1)
        a = meshed.aggregate(params, adj=adj, num_servers=n, m_per=m_per, flag=1)
        b = hosted.aggregate(params, adj=adj, num_servers=n, m_per=m_per, flag=1)
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a["w"]).mean(0),
                                   np.asarray(params["w"]).mean(0), rtol=1e-5)
        # Block-sharded: 4 servers on a 2-device mesh (2 servers per shard).
        mesh2 = Mesh(jax.devices()[:2], ("edge",))
        blocked = S.GossipAggregator(topology="ring", every_k=1, mesh=mesh2)
        c = blocked.aggregate(params, adj=adj, num_servers=n, m_per=m_per, flag=1)
        np.testing.assert_allclose(np.asarray(c["w"]), np.asarray(b["w"]),
                                   rtol=1e-6)
        print("GOSSIP-MESH-OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "GOSSIP-MESH-OK" in out.stdout, out.stderr[-2000:]

"""Partial client participation: static-shape masks, weighted
aggregation in all four Aggregators, and the engine threading.

The contract: rho = 1 passes no weights, and the aggregators' unit weights
fold away to the plain unweighted formulas (fixed-seed histories
bit-identical to pre-participation runs — the absolute pin being the
goldens in ``tests/test_strategy_api.py``, which run at the default
``participation=1.0``); rho < 1 samples a static [M] mask per round from a
key stream that is a pure function of (cfg.seed, round), so checkpoints
resume the participation schedule exactly.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io
from repro.core import registry
from repro.core import strategies as S
from repro.core.fedgl import FGLTrainer
from repro.core.partition import ring_adjacency

# `small` comes from the session-scoped fixture in tests/conftest.py.


def _stack_params(key, m, shape=(3, 2)):
    """A toy [M, ...] stacked-client pytree with distinct per-client values."""
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (m,) + shape),
            "b": jax.random.normal(k2, (m, shape[-1]))}


class TestParticipationMask:
    def test_static_shape_and_exact_count(self):
        for rho, want in [(0.5, 3), (0.25, 2), (0.1, 1), (1.0, 6)]:
            mask = S.participation_mask(jax.random.key(0), 6, rho)
            assert mask.shape == (6,) and mask.dtype == jnp.float32
            assert float(mask.sum()) == want, rho
            assert set(np.unique(np.asarray(mask))) <= {0.0, 1.0}

    def test_deterministic_per_key_and_varies_across_keys(self):
        base = jax.random.key(3)
        a = S.participation_mask(jax.random.fold_in(base, 0), 8, 0.5)
        b = S.participation_mask(jax.random.fold_in(base, 0), 8, 0.5)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rounds = [S.participation_mask(jax.random.fold_in(base, t), 8, 0.5)
                  for t in range(6)]
        assert any(np.any(np.asarray(rounds[0]) != np.asarray(r))
                   for r in rounds[1:])

    def test_rejects_out_of_range_rho(self):
        for rho in (0.0, -0.5, 1.5):
            with pytest.raises(ValueError, match="participation"):
                S.participation_mask(jax.random.key(0), 6, rho)


def _unweighted_oracle(agg, params, adj, n, m_per):
    """The aggregators' unweighted formulas in plain jnp: FedAvg's
    ``sum(grouped, 1) / m_per``, Eq. 16's ``einsum(adj, client_sum)`` over
    ``sum(adj, 0) * m_per``, and gossip's exchange of the plain server means."""
    def server(leaf):
        grouped = leaf.reshape((n, m_per) + leaf.shape[1:])
        client_sum = jnp.sum(grouped, axis=1)
        tail = (1,) * (leaf.ndim - 1)
        if isinstance(agg, S.NeighborAggregator):
            den = jnp.sum(adj, axis=0) * m_per
            return jnp.einsum("rj,r...->j...", adj, client_sum) / den.reshape((n,) + tail)
        mean = client_sum / m_per
        if isinstance(agg, S.GossipAggregator):
            mean = jnp.einsum("rj,r...->j...", adj, mean) / (
                jnp.sum(adj, axis=0).reshape((n,) + tail))
        return mean
    return jax.tree.map(lambda leaf: jnp.repeat(server(leaf), m_per, axis=0), params)


class TestMaskedAggregators:
    """weights=None vs all-ones vs genuinely partial, per aggregator."""

    N, M_PER = 2, 3

    def _params(self):
        return _stack_params(jax.random.key(1), self.N * self.M_PER)

    def _kw(self, adj=None):
        return dict(adj=adj if adj is not None
                    else jnp.ones((self.N, self.N), jnp.float32),
                    num_servers=self.N, m_per=self.M_PER)

    @pytest.mark.parametrize("agg", [
        S.FedAvgAggregator(), S.NeighborAggregator(),
        S.GossipAggregator(topology="adjacency", every_k=1)])
    def test_all_ones_mask_matches_unmasked(self, agg):
        """Unit weights and weights=None reproduce the unweighted formulas
        bitwise: multiplying by 1.0 and dividing by the same count change
        nothing. Compiled with weights=None, the unit weights and the
        zero-weight fallback fold away to the oracle's own program."""
        params = self._params()
        kw = dict(self._kw(jnp.asarray(ring_adjacency(self.N))), flag=1)
        want = _unweighted_oracle(agg, params, kw["adj"], self.N, self.M_PER)
        ones = jnp.ones((self.N * self.M_PER,), jnp.float32)
        got = {"none": agg.aggregate(params, **kw),
               "ones": agg.aggregate(params, weights=ones, **kw)}
        compiled = jax.jit(lambda p: agg.aggregate(p, **kw))(params)
        oracle = jax.jit(lambda p: _unweighted_oracle(
            agg, p, kw["adj"], self.N, self.M_PER))(params)
        for a, b in zip(jax.tree.leaves(compiled), jax.tree.leaves(oracle)):
            np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                          np.asarray(b).view(np.uint32))
        for out in got.values():
            for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                              np.asarray(b).view(np.uint32))

    def test_fedavg_weighted_mean_preservation(self):
        """Per-server output is exactly the mean of participating clients."""
        params = self._params()
        mask = jnp.asarray([1, 0, 1, 0, 0, 1], jnp.float32)
        out = S.FedAvgAggregator().aggregate(params, weights=mask, **self._kw())
        w = np.asarray(params["w"])
        np.testing.assert_allclose(np.asarray(out["w"])[0],
                                   (w[0] + w[2]) / 2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["w"])[3], w[5], rtol=1e-6)
        # broadcast back to every covered client, participating or not
        np.testing.assert_array_equal(np.asarray(out["w"])[0],
                                      np.asarray(out["w"])[1])

    def test_fedavg_all_out_server_falls_back_to_plain_mean(self):
        params = self._params()
        mask = jnp.asarray([0, 0, 0, 1, 1, 0], jnp.float32)
        out = S.FedAvgAggregator().aggregate(params, weights=mask, **self._kw())
        w = np.asarray(params["w"])
        np.testing.assert_allclose(np.asarray(out["w"])[0],
                                   w[:3].mean(axis=0), rtol=1e-6)

    def test_neighbor_matches_hand_computed_eq16(self):
        """Eq. 16 with M_r replaced by the participating count m-tilde_r."""
        params = self._params()
        adj = jnp.asarray(ring_adjacency(2))  # N=2: all-to-all incl self
        mask = jnp.asarray([1, 1, 0, 1, 0, 0], jnp.float32)
        out = S.NeighborAggregator().aggregate(params, weights=mask,
                                               **self._kw(adj))
        w = np.asarray(params["w"])
        a = np.asarray(adj)
        csum = np.stack([w[0] + w[1], w[3]])          # masked client sums [N]
        counts = np.asarray([2.0, 1.0])
        for j in range(2):
            num = sum(a[r, j] * csum[r] for r in range(2))
            den = sum(a[r, j] * counts[r] for r in range(2))
            np.testing.assert_allclose(np.asarray(out["w"])[j * 3], num / den,
                                       rtol=1e-6)

    def test_identity_ignores_mask(self):
        params = self._params()
        mask = jnp.asarray([1, 0, 0, 0, 0, 0], jnp.float32)
        out = S.IdentityAggregator().aggregate(params, weights=mask, **self._kw())
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gossip_skip_round_equals_masked_fedavg(self):
        """On non-exchange rounds gossip is per-server FedAvg — with a mask,
        per-server *masked* FedAvg."""
        params = self._params()
        mask = jnp.asarray([1, 0, 1, 0, 1, 1], jnp.float32)
        gossip = S.GossipAggregator(topology="adjacency", every_k=4)
        out_g = gossip.aggregate(params, flag=0, weights=mask, **self._kw())
        out_f = S.FedAvgAggregator().aggregate(params, weights=mask, **self._kw())
        for a, b in zip(jax.tree.leaves(out_g), jax.tree.leaves(out_f)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_gossip_exchange_mixes_masked_server_means(self):
        """Exchange rounds mix the *masked* per-server means over the
        adjacency — participation gates the edge-client leg only."""
        params = self._params()
        adj = jnp.asarray(ring_adjacency(2))
        mask = jnp.asarray([1, 0, 0, 0, 1, 1], jnp.float32)
        gossip = S.GossipAggregator(topology="adjacency", every_k=1)
        out = gossip.aggregate(params, flag=1, weights=mask, **self._kw(adj))
        w = np.asarray(params["w"])
        means = np.stack([w[0], (w[4] + w[5]) / 2])   # masked server means
        want = (means[0] + means[1]) / 2              # N=2 all-to-all mix
        np.testing.assert_allclose(np.asarray(out["w"])[0], want, rtol=1e-6)


class TestEngineThreading:
    def test_rho_one_is_bit_identical_to_default(self, small):
        """participation=1.0 never samples a mask: histories equal the
        default-config run exactly (and therefore the pinned goldens)."""
        batch, cfg = small
        tr_def = registry.build("SpreadFGL", cfg, batch, num_servers=2)
        tr_one = registry.build(
            "SpreadFGL", dataclasses.replace(cfg, participation=1.0),
            batch, num_servers=2)
        _, h_def = tr_def.fit(jax.random.key(0), batch, rounds=3)
        _, h_one = tr_one.fit(jax.random.key(0), batch, rounds=3)
        assert h_def == h_one
        assert tr_one._schedule(0) == (0, None)

    def test_rho_below_one_changes_training_and_stays_finite(self, small):
        batch, cfg = small
        tr = registry.build("SpreadFGL", dataclasses.replace(cfg, participation=0.5),
                            batch, num_servers=2)
        _, h_full = registry.build("SpreadFGL", cfg, batch, num_servers=2
                                   ).fit(jax.random.key(0), batch, rounds=3)
        _, h_half = tr.fit(jax.random.key(0), batch, rounds=3)
        assert np.isfinite(h_half["loss"]).all()
        assert h_half["acc"] != h_full["acc"]

    def test_mask_is_pure_function_of_round(self, small):
        """Same trainer, same round -> same mask; masks vary across rounds."""
        batch, cfg = small
        tr = registry.build("FedGL", dataclasses.replace(cfg, participation=0.5),
                            batch)
        m0a, m0b = tr._schedule(0)[1], tr._schedule(0)[1]
        np.testing.assert_array_equal(np.asarray(m0a), np.asarray(m0b))
        masks = [np.asarray(tr._schedule(t)[1]) for t in range(8)]
        assert any(np.any(masks[0] != m) for m in masks[1:])
        for m in masks:
            assert m.shape == (batch.num_clients,) and m.sum() == 2

    def test_resume_roundtrip_under_partial_participation(self, small):
        """fit 4 == fit 2 + checkpoint + fit 2 with rho < 1: the mask keys
        off the absolute round, like the imputation and gossip schedules."""
        batch, cfg = small
        cfg = dataclasses.replace(cfg, imputation_interval=2,
                                  participation=0.5)
        tr = registry.build("SpreadFGL", cfg, batch, num_servers=2)
        _, full = tr.fit(jax.random.key(0), batch, rounds=4)
        state, first = tr.fit(jax.random.key(0), batch, rounds=2)
        path = os.path.join(tempfile.mkdtemp(), "part_resume.npz")
        io.save(path, state)
        restored = io.restore(path, tr.init(jax.random.key(0), batch))
        _, second = tr.fit(state=restored, rounds=2)
        for k in ("loss", "acc", "f1"):
            np.testing.assert_allclose(first[k] + second[k], full[k],
                                       atol=1e-6)

    def test_rejects_out_of_range(self, small):
        batch, cfg = small
        for rho in (0.0, 1.5, -1.0):
            with pytest.raises(ValueError, match="participation"):
                FGLTrainer(dataclasses.replace(cfg, participation=rho), batch)

    @pytest.mark.parametrize("name,kw", [
        ("local", {}), ("fedavg_fusion", {}), ("fedsage_plus", {}),
        ("FedGL", {}), ("spreadfgl_gossip", {"num_servers": 2})])
    def test_every_registered_method_trains_under_partial(self, small, name,
                                                          kw):
        batch, cfg = small
        cfg = dataclasses.replace(cfg, participation=0.5, gossip_every=2)
        tr = registry.build(name, cfg, batch, **kw)
        _, hist = tr.fit(jax.random.key(0), batch, rounds=2)
        assert np.isfinite(hist["loss"]).all(), name

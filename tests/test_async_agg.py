"""FedBuff-style async aggregation (``strategies.AsyncAggregator``).

The determinism contract under test, the same one ``participation_mask``
and the gossip phase already honor:

- the delay/dropout draws come from a key stream f(cfg.seed, round) under a
  dedicated salt — independent of the training key AND the participation
  stream — so enabling async aggregation never perturbs other randomness;
- the buffer is a static [M] occupancy and the flush weights reach the
  jitted aggregation as a traced [M] vector, flush/skip being the only
  static split (``schedule(t, M) -> (flag, weights)``);
- the whole delay/buffer/staleness schedule is a pure function of the
  absolute round, so save/resume mid-buffer replays it exactly.

Correctness anchor: B = M with zero delays and no dropouts reproduces the
synchronous FedAvg compositions BIT-identically (every weight is exactly
1.0 and the reduction order matches), pinned here both on the raw
aggregator and on full fixed-seed training histories against the FedGL
golden.
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
from repro.core.spreadfgl import make_spreadfgl_async

# `small` comes from the session-scoped fixture in tests/conftest.py.

M = 4  # clients in the `small` fixture

# The pinned fixed-seed FedGL history of tests/test_strategy_api.py
# (fit(key(0), rounds=4) on the `small` fixture), pinned on jax 0.9.0 /
# jaxlib 0.9.0, and re-pinned with it when the generator round began to
# alternate as Algorithm 1 does (the losses moved, accuracy and F1 did not).
# The async anchor must reproduce the SAME run bit-for-bit, so it must also
# match this golden.
GOLDEN_FEDGL = {
    "loss": [0.6811746954917908, 0.05318861082196236,
             0.024074450135231018, 0.014543474651873112],
    "acc": [0.38181817531585693, 0.581818163394928,
            0.6000000238418579, 0.6545454263687134],
    "f1": [0.3721662163734436, 0.5811243653297424,
           0.5967587232589722, 0.6610444188117981],
}


def _sync_cfg(cfg, **kw):
    """The small config with async fields set."""
    return dataclasses.replace(cfg, **kw)


def _schedule_oracle(seed, m, buffer_size, delay_dist, max_delay,
                     dropout_rate, rounds):
    """An independent pure-python replay of the client/buffer state machine.

    Deliberately structured differently from ``strategies._async_schedule``
    (per-client dict state instead of vectorized arrays) so the two can only
    agree if the semantics — send/arrive/freshest-wins/flush — agree.
    """
    in_flight = {}   # client -> arrival round
    buffered = {}    # client -> report round
    out = []
    for t in range(rounds):
        delays, drops = S.async_delay_stream(
            seed, t, m, delay_dist=delay_dist, max_delay=max_delay,
            dropout_rate=dropout_rate)
        for i in range(m):
            if i not in in_flight and not drops[i]:
                in_flight[i] = t + int(delays[i])
        for i in [i for i, arr in in_flight.items() if arr == t]:
            buffered[i] = t          # fresher report replaces a staler one
            del in_flight[i]
        if len(buffered) >= buffer_size:
            w = np.zeros(m, np.float32)
            for i, rep in buffered.items():
                w[i] = 1.0 / np.sqrt(np.float32(1.0) + np.float32(t - rep))
            buffered = {}
            out.append((True, w))
        else:
            out.append((False, None))
    return out


class TestDelayStream:
    def test_zero_dist_has_no_delays(self):
        delays, drops = S.async_delay_stream(0, 3, 8)
        np.testing.assert_array_equal(delays, np.zeros(8, np.int32))
        assert not drops.any()

    @pytest.mark.parametrize("dist", S.ASYNC_DELAY_DISTS)
    def test_same_seed_round_reproduces(self, dist):
        a = S.async_delay_stream(7, 5, 10, delay_dist=dist, dropout_rate=0.3)
        b = S.async_delay_stream(7, 5, 10, delay_dist=dist, dropout_rate=0.3)
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])

    def test_draws_vary_across_rounds(self):
        draws = [S.async_delay_stream(0, t, 16, delay_dist="uniform",
                                      dropout_rate=0.5) for t in range(6)]
        assert any(np.any(draws[0][0] != d[0]) for d in draws[1:])
        assert any(np.any(draws[0][1] != d[1]) for d in draws[1:])

    @pytest.mark.parametrize("dist", ("uniform", "geometric"))
    def test_delays_bounded_by_max_delay(self, dist):
        for t in range(10):
            delays, _ = S.async_delay_stream(1, t, 32, delay_dist=dist,
                                             max_delay=3)
            assert delays.min() >= 0 and delays.max() <= 3

    def test_geometric_mass_at_zero(self):
        """p=1/2 geometric: about half of all draws arrive the same round."""
        all_delays = np.concatenate([
            S.async_delay_stream(0, t, 64, delay_dist="geometric")[0]
            for t in range(16)])
        frac0 = (all_delays == 0).mean()
        assert 0.35 < frac0 < 0.65, frac0

    def test_dropout_zero_never_drops(self):
        for t in range(8):
            _, drops = S.async_delay_stream(2, t, 16, delay_dist="geometric")
            assert not drops.any()

    def test_validation(self):
        with pytest.raises(ValueError, match="delay_dist"):
            S.async_delay_stream(0, 0, 4, delay_dist="pareto")
        with pytest.raises(ValueError, match="max_delay"):
            S.async_delay_stream(0, 0, 4, max_delay=-1)
        with pytest.raises(ValueError, match="dropout_rate"):
            S.async_delay_stream(0, 0, 4, dropout_rate=1.0)

    def test_stream_disjoint_from_participation_and_training_keys(self):
        """The async salt produces a key stream distinct from both the
        participation stream (salt 0x9A57) and the raw training key — no
        accidental correlation between the schedules."""
        seed, t = 0, 5
        k_async = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), S._ASYNC_SALT), t)
        k_part = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), 0x9A57), t)
        k_train = jax.random.key(seed)
        data = lambda k: np.asarray(jax.random.key_data(k))  # noqa: E731
        assert not np.array_equal(data(k_async), data(k_part))
        assert not np.array_equal(data(k_async), data(k_train))
        assert not np.array_equal(data(k_part), data(k_train))


class TestSchedule:
    def test_b_equals_m_zero_delay_flushes_every_round_with_unit_weights(self):
        agg = S.AsyncAggregator(buffer_size=6, delay_dist="zero")
        for t in range(8):
            flag, w = agg.schedule(t, 6)
            assert flag == 1
            w = np.asarray(w)
            np.testing.assert_array_equal(w, np.ones(6, np.float32))

    @pytest.mark.parametrize("dist,drop", [("zero", 0.0), ("uniform", 0.0),
                                           ("geometric", 0.2)])
    def test_matches_independent_oracle(self, dist, drop):
        """The vectorized incremental cache == a from-scratch per-client
        simulator, flush flags AND staleness weights, 24 rounds."""
        agg = S.AsyncAggregator(buffer_size=3, delay_dist=dist,
                                dropout_rate=drop, max_delay=4, seed=11)
        oracle = _schedule_oracle(11, 5, 3, dist, 4, drop, 24)
        for t, (flush, weights) in enumerate(oracle):
            flag, got = agg.schedule(t, 5)
            assert flag == int(flush), t
            if weights is None:
                assert got is None
            else:
                np.testing.assert_array_equal(np.asarray(got), weights)

    def test_weights_are_fedbuff_staleness_discounts(self):
        """Every nonzero weight is exactly 1/sqrt(1+tau) for an integer
        staleness tau in [0, max over the horizon]."""
        agg = S.AsyncAggregator(buffer_size=2, delay_dist="geometric",
                                dropout_rate=0.3, seed=5)
        seen_stale = set()
        for t in range(30):
            _, w = agg.schedule(t, 6)
            if w is None:
                continue
            w = np.asarray(w)
            for wi in w[w > 0]:
                tau = 1.0 / np.float32(wi) ** 2 - 1.0
                assert abs(tau - round(float(tau))) < 1e-5
                seen_stale.add(int(round(float(tau))))
        assert 0 in seen_stale          # fresh reports exist
        assert max(seen_stale) >= 1     # and genuinely stale ones too

    def test_mid_stream_query_replays_from_scratch(self):
        """Querying round 17 on a cold cache (the resume path) equals the
        value the warm sequential walk produced."""
        agg = S.AsyncAggregator(buffer_size=2, delay_dist="uniform",
                                dropout_rate=0.1, seed=9)
        warm = [agg.schedule(t, 4) for t in range(20)]
        S._ASYNC_SCHEDULES.clear()
        cold_f, cold_w = agg.schedule(17, 4)
        assert cold_f == warm[17][0]
        if warm[17][1] is None:
            assert cold_w is None
        else:
            np.testing.assert_array_equal(np.asarray(cold_w),
                                          np.asarray(warm[17][1]))

    def test_phase_is_binary(self):
        agg = S.AsyncAggregator(buffer_size=3, delay_dist="geometric",
                                dropout_rate=0.4, seed=2)
        assert {agg.schedule(t, 8)[0] for t in range(40)} <= {0, 1}

    def test_different_seeds_give_different_schedules(self):
        # Seeds 0 and 1 both flush on all 16 rounds under jax 0.9.0's
        # streams, so the pair proves nothing; seeds 0 and 2 diverge.
        a = S.AsyncAggregator(buffer_size=2, delay_dist="geometric", seed=0)
        b = S.AsyncAggregator(buffer_size=2, delay_dist="geometric", seed=2)
        fa = [a.schedule(t, 6)[0] for t in range(16)]
        fb = [b.schedule(t, 6)[0] for t in range(16)]
        assert fa != fb


class TestAsyncAggregatorUnit:
    N, M_PER = 2, 2

    def _params(self):
        key = jax.random.key(1)
        return {"w": jax.random.normal(key, (4, 3, 2)),
                "b": jax.random.normal(jax.random.fold_in(key, 1), (4, 2))}

    def _kw(self):
        return dict(adj=jnp.eye(self.N), num_servers=self.N,
                    m_per=self.M_PER)

    def test_validation(self):
        with pytest.raises(ValueError, match="buffer_size"):
            S.AsyncAggregator(buffer_size=0)
        with pytest.raises(ValueError, match="delay_dist"):
            S.AsyncAggregator(buffer_size=1, delay_dist="exp")
        with pytest.raises(ValueError, match="dropout_rate"):
            S.AsyncAggregator(buffer_size=1, dropout_rate=1.0)
        with pytest.raises(ValueError, match="max_delay"):
            S.AsyncAggregator(buffer_size=1, max_delay=-2)
        with pytest.raises(ValueError, match="never fill"):
            S.AsyncAggregator(buffer_size=9).schedule(0, 4)

    def test_skip_round_is_identity(self):
        params = self._params()
        agg = S.AsyncAggregator(buffer_size=4)
        out = agg.aggregate(params, flag=0, weights=None, **self._kw())
        assert out is params

    def test_flush_is_hand_computed_weighted_mean(self):
        """Explicit weights [1, .5 | 0, 0]: server 0 mixes 2:1, the
        zero-weight server keeps every client's own params."""
        params = self._params()
        w = jnp.asarray([1.0, 0.5, 0.0, 0.0], jnp.float32)
        agg = S.AsyncAggregator(buffer_size=2)
        out = agg.aggregate(params, flag=1, weights=w, **self._kw())
        pw = np.asarray(params["w"])
        want0 = (1.0 * pw[0] + 0.5 * pw[1]) / 1.5
        np.testing.assert_allclose(np.asarray(out["w"])[0], want0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out["w"])[1], want0, rtol=1e-6)
        # server 1 had nothing buffered: untouched, per client
        np.testing.assert_array_equal(np.asarray(out["w"])[2], pw[2])
        np.testing.assert_array_equal(np.asarray(out["w"])[3], pw[3])

    def test_unit_weights_match_fedavg_bitwise(self):
        """The anchor at the aggregator level: weights all 1.0 == the
        unmasked FedAvg path, bit for bit."""
        params = self._params()
        fedavg = S.FedAvgAggregator().aggregate(params, **self._kw())
        agg = S.AsyncAggregator(buffer_size=4)
        out = agg.aggregate(params, flag=1,
                            weights=jnp.ones(4, jnp.float32), **self._kw())
        for a, b in zip(jax.tree.leaves(fedavg), jax.tree.leaves(out)):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))

    def test_flush_ignores_adjacency(self):
        """The flush is per-server (cross-server spread flows through the
        shared imputation round, like FedAvg): any adj gives the same out."""
        params = self._params()
        w = jnp.asarray([1.0, 1.0, 0.5, 0.0], jnp.float32)
        agg = S.AsyncAggregator(buffer_size=2)
        a = agg.aggregate(params, flag=1, weights=w, adj=jnp.eye(self.N),
                          num_servers=self.N, m_per=self.M_PER)
        b = agg.aggregate(params, flag=1, weights=w,
                          adj=jnp.ones((self.N, self.N)),
                          num_servers=self.N, m_per=self.M_PER)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestGoldenAnchor:
    @pytest.fixture(scope="class")
    def fedgl_run(self, small):
        """One shared synchronous FedGL reference fit (state + history)."""
        batch, cfg = small
        return registry.build("FedGL", cfg, batch).fit(
            jax.random.key(0), batch, rounds=4)

    def test_b_equals_m_star_matches_fedgl_bitwise_and_golden(self, small,
                                                              fedgl_run):
        """spreadfgl_async(B=M, zero delay, 1 server) == FedGL: the full
        4-round histories are equal EXACTLY (not allclose), and both match
        the pinned golden."""
        batch, cfg = small
        _, hist_f = fedgl_run
        cfg_a = _sync_cfg(cfg, async_buffer=M)
        tr_a = registry.build("spreadfgl_async", cfg_a, batch, num_servers=1)
        _, hist_a = tr_a.fit(jax.random.key(0), batch, rounds=4)
        assert hist_a == hist_f                      # bit-identical histories
        for k, want in GOLDEN_FEDGL.items():
            np.testing.assert_allclose(hist_a[k], want, atol=1e-4,
                                       err_msg=f"async anchor[{k!r}] drifted")

    def test_b_equals_m_ring_matches_per_server_fedavg_bitwise(self, small):
        """N=2 anchor: async B=M zero-delay on a ring == the same engine
        with a plain FedAvgAggregator (per-server flush, weights 1.0)."""
        batch, cfg = small
        tr_sync = FGLTrainer(cfg, batch, topology=S.RingTopology(2),
                             aggregator=S.FedAvgAggregator(),
                             imputation=S.SpreadImputation())
        _, hist_s = tr_sync.fit(jax.random.key(0), batch, rounds=4)
        tr_a = make_spreadfgl_async(_sync_cfg(cfg, async_buffer=M), batch,
                                    num_servers=2)
        _, hist_a = tr_a.fit(jax.random.key(0), batch, rounds=4)
        assert hist_a == hist_s

    def test_b_below_m_diverges_without_touching_the_training_key(
            self, small, fedgl_run):
        """B < M under delays/dropouts genuinely changes training — yet after
        equal rounds the async state holds the SAME FGLState.key as the sync
        run: the delay stream is drawn entirely outside it."""
        batch, cfg = small
        st_f, hist_f = fedgl_run
        tr_a = registry.build("spreadfgl_async",
                              _sync_cfg(cfg, async_buffer=2,
                                        delay_dist="geometric",
                                        dropout_rate=0.2),
                              batch, num_servers=1)
        st_a, hist_a = tr_a.fit(jax.random.key(0), batch, rounds=4)
        assert np.isfinite(hist_a["loss"]).all()
        assert hist_a["acc"] != hist_f["acc"]
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(st_f.key)),
            np.asarray(jax.random.key_data(st_a.key)))


class TestResume:
    @pytest.mark.parametrize("dist,drop", [("geometric", 0.2),
                                           ("uniform", 0.0)])
    def test_fit6_equals_fit3_save_load_fit3(self, small, dist, drop):
        """Mid-buffer resume under delays and dropouts: the restored run
        replays the schedule from the checkpointed round exactly."""
        batch, cfg = small
        cfg = _sync_cfg(cfg, imputation_interval=2, async_buffer=2,
                        delay_dist=dist, dropout_rate=drop)
        tr = make_spreadfgl_async(cfg, batch, num_servers=2)
        _, full = tr.fit(jax.random.key(0), batch, rounds=6)
        state, first = tr.fit(jax.random.key(0), batch, rounds=3)
        path = os.path.join(tempfile.mkdtemp(), "async_resume.npz")
        io.save(path, state)
        restored = io.restore(path, tr.init(jax.random.key(0), batch))
        assert restored.round == 3
        # Drop the warm schedule cache: resume must NOT depend on this
        # process having walked rounds 0-2 already.
        S._ASYNC_SCHEDULES.clear()
        _, second = tr.fit(state=restored, rounds=3)
        assert first["loss"] + second["loss"] == full["loss"]
        assert first["acc"] + second["acc"] == full["acc"]
        assert first["f1"] + second["f1"] == full["f1"]

    def test_resume_composes_with_partial_participation(self, small):
        """rho < 1 AND async delays: both key streams key off the absolute
        round, so the combined schedule survives a checkpoint."""
        batch, cfg = small
        cfg = _sync_cfg(cfg, imputation_interval=2, async_buffer=2,
                        delay_dist="geometric", participation=0.5)
        tr = make_spreadfgl_async(cfg, batch, num_servers=2)
        _, full = tr.fit(jax.random.key(0), batch, rounds=4)
        state, first = tr.fit(jax.random.key(0), batch, rounds=2)
        path = os.path.join(tempfile.mkdtemp(), "async_part.npz")
        io.save(path, state)
        restored = io.restore(path, tr.init(jax.random.key(0), batch))
        _, second = tr.fit(state=restored, rounds=2)
        assert first["loss"] + second["loss"] == full["loss"]


class TestEngineThreading:
    def test_agg_mask_multiplies_participation_into_flush_weights(self, small):
        batch, cfg = small
        cfg = _sync_cfg(cfg, async_buffer=M, participation=0.5)
        tr = make_spreadfgl_async(cfg, batch, num_servers=1)
        t = 0   # B = M, zero delay: round 0 flushes with unit weights
        part = np.asarray(S.participation_mask(
            jax.random.fold_in(jax.random.fold_in(jax.random.key(cfg.seed), 0x9A57), t),
            tr.m, 0.5))
        flag, flush = tr.aggregator.schedule(t, tr.m)
        assert flag == 1 and tr._schedule(t)[0] == 1
        np.testing.assert_array_equal(np.asarray(tr._schedule(t)[1]),
                                      part * np.asarray(flush))

    def test_agg_mask_none_on_skip_rounds(self, small):
        batch, cfg = small
        cfg = _sync_cfg(cfg, async_buffer=M, delay_dist="uniform", seed=4)
        tr = make_spreadfgl_async(cfg, batch, num_servers=1)
        skip = [t for t in range(12) if tr._schedule(t)[0] == 0]
        assert skip, "uniform delays must produce at least one skip round"
        assert tr._schedule(skip[0])[1] is None

    def test_builder_validation(self, small):
        batch, cfg = small
        with pytest.raises(ValueError, match="async_buffer"):
            make_spreadfgl_async(cfg, batch)           # cfg.async_buffer = 0
        with pytest.raises(ValueError, match="never fill"):
            make_spreadfgl_async(_sync_cfg(cfg, async_buffer=99), batch)

    def test_one_server_uses_star_topology(self, small):
        batch, cfg = small
        tr = make_spreadfgl_async(_sync_cfg(cfg, async_buffer=2), batch,
                                  num_servers=1)
        assert isinstance(tr.topology, S.StarTopology)
        assert isinstance(tr.aggregator, S.AsyncAggregator)

    def test_registry_name_resolves(self):
        assert "spreadfgl_async" in registry.names()

    @pytest.mark.parametrize("name,kw", [
        ("local", {}), ("fedavg_fusion", {}), ("fedsage_plus", {}),
        ("FedGL", {}), ("SpreadFGL", {"num_servers": 2}),
        ("spreadfgl_gossip", {"num_servers": 2}),
        ("spreadfgl_async", {"num_servers": 2}),
    ])
    def test_every_registered_method_trains_with_async_buffer_set(
            self, small, name, kw):
        """cfg.async_buffer is inert for synchronous compositions and
        activates the buffered aggregator for spreadfgl_async — either way
        every registry method still trains."""
        batch, cfg = small
        cfg = _sync_cfg(cfg, async_buffer=2, delay_dist="geometric",
                        dropout_rate=0.1, gossip_every=2)
        tr = registry.build(name, cfg, batch, **kw)
        _, hist = tr.fit(jax.random.key(0), batch, rounds=2)
        assert np.isfinite(hist["loss"]).all(), name

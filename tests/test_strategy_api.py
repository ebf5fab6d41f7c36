"""The strategy-based engine API: init/step/fit(state=) lifecycle, true
resume through checkpoint round-trips, the method registry, and fixed-seed
history regressions pinning the redesign to the pre-refactor engine."""
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
from repro.core.baselines import REGISTRY as BASELINES
from repro.core.fedgl import FGLTrainer
from repro.core.spreadfgl import make_spreadfgl


# The `small` fixture (this exact graph/partition/config) is session-scoped
# in tests/conftest.py and shared across suites.

# Fixed-seed histories of fit(jax.random.key(0), batch, rounds=4) on the
# `small` fixture. Originally captured at the commit before the strategy
# redesign; re-pinned once after the aug-slot link-target bugfix (link
# targets are now restricted to real local slots, so every fixing round
# AFTER the first selects slightly different links — round 0, where no aug
# slot is populated yet, is bit-identical to the pre-fix goldens, which
# also pins that dropping the generator's dead per-iteration key plumbing
# changed nothing). Re-pinned on jax 0.9.0 / jaxlib 0.9.0: that release's
# random streams and XLA:CPU numerics moved every history (round-0 loss
# 1.4747 -> 0.7381); the engine code did not change. Re-pinned once more
# when the generator round began to alternate as Algorithm 1 does (each
# outer pass trains the autoencoder against the current assessor, where it
# had trained every pass against the first pass's): the losses moved in the
# fourth significant figure (0.7381 -> 0.7380 at round 0), accuracy and F1
# did not.
GOLDEN_SPREADFGL = {
    "loss": [0.7379666566848755, 0.0530419759452343,
             0.02626529522240162, 0.01649140752851963],
    "acc": [0.38181817531585693, 0.581818163394928,
            0.6181818246841431, 0.6363636255264282],
    "f1": [0.3721662163734436, 0.5811243653297424,
           0.6132214665412903, 0.6441271901130676],
}
GOLDEN_FEDGL = {
    "loss": [0.6811746954917908, 0.05318861082196236,
             0.024074450135231018, 0.014543474651873112],
    "acc": [0.38181817531585693, 0.581818163394928,
            0.6000000238418579, 0.6545454263687134],
    "f1": [0.3721662163734436, 0.5811243653297424,
           0.5967587232589722, 0.6610444188117981],
}


class TestHistoryRegression:
    """Fixed-seed histories are unchanged across the strategy redesign."""

    @pytest.mark.parametrize("name,kw,golden", [
        ("SpreadFGL", {"num_servers": 2}, GOLDEN_SPREADFGL),
        ("FedGL", {}, GOLDEN_FEDGL),
    ])
    def test_fit_matches_pre_refactor_golden(self, small, name, kw, golden):
        batch, cfg = small
        tr = registry.build(name, cfg, batch, **kw)
        _, hist = tr.fit(jax.random.key(0), batch, rounds=4)
        for k, want in golden.items():
            np.testing.assert_allclose(hist[k], want, atol=1e-4,
                                       err_msg=f"{name} history[{k!r}] drifted")

    def test_step_matches_fit(self, small):
        """Driving step() by hand reproduces fit() exactly."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        _, hist = tr.fit(jax.random.key(0), batch, rounds=3)
        state = tr.init(jax.random.key(0), batch)
        for i in range(3):
            state, m = tr.step(state)
            assert m["round"] == i == hist["round"][i]
            np.testing.assert_array_equal(float(m["loss"]), hist["loss"][i])
            np.testing.assert_array_equal(float(m["acc"]), hist["acc"][i])
        assert state.round == 3

    def test_step_counts_the_links_it_imputes(self, small):
        """``links`` is in every round's metrics: the filled augmentation
        slots after an imputation round, 0 on a round without one."""
        batch, cfg = small
        cfg = dataclasses.replace(cfg, imputation_interval=2)
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        n_local = batch.n_local_max
        for t in range(3):
            state, m = tr.step(state)
            filled = int(np.sum(np.asarray(state.batch.node_mask)[:, n_local:] > 0))
            assert int(m["links"]) == (filled if t % 2 == 0 else 0)
        assert filled > 0
        _, hist = tr.fit(jax.random.key(0), batch, rounds=3)
        assert hist["links"][0] > 0 and hist["links"][1] == 0

    def test_step_does_not_mutate_input_state(self, small):
        """step() never rebinds a field of the caller's state object, and it
        donates the caller's classifiers and their optimizer state to the
        new state's: those arrays of the old state are deleted."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        fields = {f.name: getattr(state, f.name) for f in dataclasses.fields(state)}
        new, _ = tr.step(state)
        assert state.round == 0 and new.round == 1
        for name, value in fields.items():
            assert getattr(state, name) is value, name
        assert all(leaf.is_deleted()
                   for leaf in jax.tree.leaves((state.params, state.opt_state)))
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves((new.params, new.opt_state)))

    def test_an_imputation_round_takes_over_the_generator_state(self, small):
        """On an imputation round step() donates the old state's generator
        state and its batch's cache to the new one, as it donates the
        classifiers every round, and computes what the undonated programs
        do; the batch's own fields stay the caller's."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        want, _ = jax.jit(tr._impute)(dataclasses.replace(
            state, params=jax.jit(tr._local_rounds)(
                state.params, state.opt_state, state.batch)[0]))
        new, m = tr.step(state)
        assert int(m["links"]) > 0
        donated = (state.params, state.opt_state, state.ae_params, state.ae_opt,
                   state.as_params, state.as_opt, state.batch.prop)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(donated))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(
            (state.batch.replace(prop=None), new)) if isinstance(leaf, jax.Array))
        for a, b in zip(jax.tree.leaves((new.ae_params, new.ae_opt, new.as_params,
                                         new.as_opt, new.batch)),
                        jax.tree.leaves((want.ae_params, want.ae_opt, want.as_params,
                                         want.as_opt, want.batch))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.filterwarnings("error:Some donated buffers were not usable")
    @pytest.mark.parametrize("name,kw", [("fedavg_fusion", {}),
                                         ("SpreadFGL", {"num_servers": 2})])
    def test_donating_steps_equal_undonated_programs(self, small, name, kw):
        """Three step() rounds, which donate the classifier state to the
        local and aggregation programs, equal to the bit a loop of the same
        programs jitted without donation; every round deletes the old
        state's classifiers and optimizer state, and every donation is
        usable."""
        batch, cfg = small
        tr = registry.build(name, cfg, batch, **kw)
        key = jax.random.key(0)
        local, impute = jax.jit(tr._local_rounds), jax.jit(tr._impute)
        state = tr.init(key, batch)
        want = []
        for t in range(3):
            params, opt_state = local(state.params, state.opt_state, state.batch)
            state = dataclasses.replace(state, params=params, opt_state=opt_state)
            if tr.imputation.active and t % tr.cfg.imputation_interval == 0:
                state, _ = impute(state)
            flag, weights = tr._schedule(t)
            state.params = tr._agg_fn(state.params, flag=flag, weights=weights)
            want.append(jax.device_get((state.params, state.opt_state,
                                        tr._eval_fn(state.params, state.batch))))
        state = tr.init(key, batch)
        got = []
        for _ in range(3):
            old = state
            state, m = tr.step(state)
            assert all(leaf.is_deleted()
                       for leaf in jax.tree.leaves((old.params, old.opt_state)))
            # copied on the device: a host view of a CPU array would hold
            # its buffer, and the next step could not take it over
            got.append(jax.tree.map(jnp.copy, (state.params, state.opt_state,
                                               (m["loss"], m["acc"], m["f1"]))))
        got = jax.device_get(got)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(a, b)


class TestResume:
    def test_resume_roundtrip_matches_uninterrupted_fit(self, small):
        """fit 6 == fit 3 + checkpoint save/load + fit(state=restored) 3.

        K=2 here, so the schedule imputes at rounds 0, 2, 4: the resumed run
        only matches if fit(state=...) keys imputation off the *absolute*
        round index (round 4 falls in the second half).
        """
        batch, cfg = small
        cfg = dataclasses.replace(cfg, imputation_interval=2)
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        _, full = tr.fit(jax.random.key(0), batch, rounds=6)

        state, first = tr.fit(jax.random.key(0), batch, rounds=3)
        path = os.path.join(tempfile.mkdtemp(), "resume.npz")
        io.save(path, state)
        restored = io.restore(path, tr.init(jax.random.key(0), batch))
        assert restored.round == 3
        state2, second = tr.fit(state=restored, rounds=3)

        assert first["round"] + second["round"] == full["round"] == list(range(6))
        for k in ("loss", "acc", "f1"):
            np.testing.assert_allclose(first[k] + second[k], full[k], atol=1e-6)
        assert state2.round == 6

    def test_fit_requires_state_or_key_and_batch(self, small):
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        with pytest.raises(ValueError, match="state="):
            tr.fit(rounds=1)

    def test_fit_rejects_state_plus_key_batch(self, small):
        """Passing both is ambiguous: the state's own key/batch would win."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        with pytest.raises(ValueError, match="resumes"):
            tr.fit(jax.random.key(1), batch, state=state, rounds=1)


class TestRegistry:
    def test_all_methods_registered(self):
        assert set(registry.names()) >= {"FedGL", "SpreadFGL", "local",
                                         "fedavg_fusion", "fedsage_plus"}

    def test_unknown_method_lists_available(self, small):
        batch, cfg = small
        with pytest.raises(KeyError, match="SpreadFGL"):
            registry.build("nope", cfg, batch)

    def test_baselines_are_pure_compositions(self, small):
        """Sec. IV-A baselines: plain FGLTrainer + strategies, no subclasses,
        no overridden engine internals."""
        batch, cfg = small
        expected = {
            "local": (S.IdentityAggregator, S.NoImputation),
            "fedavg_fusion": (S.FedAvgAggregator, S.NoImputation),
            "fedsage_plus": (S.FedAvgAggregator, S.LocalGenImputation),
        }
        for name, build in BASELINES.items():
            tr = build(cfg, batch)
            assert type(tr) is FGLTrainer, name
            agg_t, imp_t = expected[name]
            assert type(tr.aggregator) is agg_t
            assert type(tr.imputation) is imp_t
            assert isinstance(tr.topology, S.StarTopology)

    def test_registry_and_baselines_agree(self, small):
        batch, cfg = small
        for name in ("local", "fedavg_fusion", "fedsage_plus"):
            via_registry = registry.build(name, cfg, batch)
            direct = BASELINES[name](cfg, batch)
            assert type(via_registry.aggregator) is type(direct.aggregator)
            assert type(via_registry.imputation) is type(direct.imputation)


class TestStrategies:
    def test_star_topology_layout(self):
        lay = S.StarTopology().build(6)
        assert lay.num_servers == 1 and lay.clients_per_server == 6
        np.testing.assert_array_equal(lay.server_of_client, np.zeros(6))

    def test_ring_topology_rejects_indivisible(self):
        with pytest.raises(ValueError, match="divide"):
            S.RingTopology(num_servers=4).build(6)

    def test_custom_topology_via_make_spreadfgl(self, small):
        batch, cfg = small
        adj = np.ones((2, 2), dtype=np.float32)
        tr = make_spreadfgl(cfg, batch, num_servers=2, adjacency=adj)
        assert isinstance(tr.topology, S.CustomTopology)
        assert tr.n_servers == 2

    def test_custom_topology_shape_mismatch(self, small):
        batch, cfg = small
        with pytest.raises(ValueError, match="num_servers"):
            make_spreadfgl(cfg, batch, num_servers=4,
                           adjacency=np.ones((2, 2), np.float32))

    def test_custom_topology_rejects_non_square(self):
        with pytest.raises(ValueError, match="square"):
            S.CustomTopology(np.ones((2, 3), np.float32)).build(4)

    def test_custom_topology_rejects_indivisible(self):
        with pytest.raises(ValueError, match="divide"):
            S.CustomTopology(np.ones((3, 3), np.float32)).build(4)

    def test_custom_topology_layout(self):
        adj = np.asarray([[1, 0], [0, 1]], np.float32)
        lay = S.CustomTopology(adj).build(6)
        assert lay.num_servers == 2 and lay.clients_per_server == 3
        np.testing.assert_array_equal(lay.adjacency, adj)
        np.testing.assert_array_equal(lay.server_of_client,
                                      np.repeat(np.arange(2), 3))

    def test_identity_aggregator_ignores_round_and_mask(self):
        """Identity stays identity under every (flag, weights) combination —
        the `local` baseline must be untouched by participation or phase."""
        params = {"w": np.arange(12.0).reshape(6, 2)}
        for flag, weights in [(0, None), (1, None),
                              (0, np.asarray([1, 0, 1, 0, 1, 0], np.float32))]:
            out = S.IdentityAggregator().aggregate(
                params, adj=np.eye(2, dtype=np.float32), num_servers=2,
                m_per=3, flag=flag, weights=weights)
            np.testing.assert_array_equal(np.asarray(out["w"]),
                                          params["w"])

    @pytest.mark.parametrize("agg", [
        S.IdentityAggregator(), S.FedAvgAggregator(), S.NeighborAggregator(),
        S.GossipAggregator(every_k=3),
        S.AsyncAggregator(buffer_size=2, delay_dist="geometric",
                          dropout_rate=0.2, seed=3)],
        ids=lambda a: type(a).__name__)
    def test_schedule_is_a_pure_function_of_the_round(self, agg):
        """The same (settings, t) give the same (flag, weights) in any query
        order, from a cold cache too; the flag is a static 0/1 and the
        weights are None or a static [M] float vector."""
        m, rounds = 6, 12
        forward = [agg.schedule(t, m) for t in range(rounds)]
        S._ASYNC_SCHEDULES.clear()
        backward = {t: agg.schedule(t, m) for t in reversed(range(rounds))}
        for t, (flag, weights) in enumerate(forward):
            assert type(flag) is int and flag in (0, 1), (t, flag)
            assert backward[t][0] == flag, t
            if weights is None:
                assert backward[t][1] is None, t
            else:
                assert weights.shape == (m,) and weights.dtype == np.float32
                np.testing.assert_array_equal(np.asarray(backward[t][1]),
                                              np.asarray(weights))

    def test_identity_aggregator_never_mixes(self, small):
        batch, cfg = small
        tr = registry.build("local", cfg, batch)
        state = tr.init(jax.random.key(0), batch)
        perturbed = jax.tree.map(
            lambda p: p + np.arange(p.shape[0], dtype=np.float32).reshape(
                (-1,) + (1,) * (p.ndim - 1)), state.params)
        agg = tr.aggregate(perturbed)
        for a, b in zip(jax.tree.leaves(agg), jax.tree.leaves(perturbed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_no_imputation_is_inert(self, small):
        batch, cfg = small
        tr = registry.build("fedavg_fusion", cfg, batch)
        assert not tr.imputation.active
        state = tr.init(jax.random.key(0), batch)
        assert tr.imputation.impute(tr, state) is state

    def test_metrics_stay_on_device_until_fetched(self, small):
        """step() metrics are jax arrays (no per-round host sync in fit)."""
        batch, cfg = small
        tr = registry.build("fedavg_fusion", cfg, batch)
        state = tr.init(jax.random.key(0), batch)
        _, m = tr.step(state)
        for k in ("loss", "acc", "f1"):
            assert isinstance(m[k], jax.Array), k
        assert isinstance(m["round"], int)

"""Regression tests for the stacked [N] edge-server layout.

The vmapped imputation round must be numerically equivalent to the seed's
sequential per-server loop (kept as ``_imputation_round_reference``), the
stacked state must contain no Python lists, checkpoints must round-trip, and
the Pallas kernel wrappers must survive non-block-multiple shapes via the
``ops.py`` padding path (the shapes the vmapped round actually feeds them).
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io
from repro.core import assessor as assessor_lib
from repro.core import imputation, patcher
from repro.core.partition import partition_graph
from repro.core.spreadfgl import make_spreadfgl
from repro.core.types import FGLConfig
from repro.data.synthetic_graphs import DATASETS, make_sbm_graph
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def setup2(small):
    """Fixed-seed 2-server / 4-client trainer + state on the shared batch."""
    batch, cfg = small
    tr = make_spreadfgl(cfg, batch, num_servers=2)
    state = tr.init(jax.random.key(0), batch)
    return tr, state


class TestStackedEquivalence:
    def test_vmapped_matches_sequential_loop(self, setup2):
        """vmap over the [N] axis == the seed's per-server Python loop."""
        tr, state = setup2
        out_v, _ = tr._impute_fn(state)
        out_s = jax.jit(tr._imputation_round_reference)(state)
        # batch (graph fixing), generator params + opt states all agree.
        for field in ("batch", "ae_params", "ae_opt", "as_params", "as_opt"):
            for a, b in zip(jax.tree.leaves(getattr(out_v, field)),
                            jax.tree.leaves(getattr(out_s, field))):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32), atol=1e-5)

    def test_state_has_no_python_lists(self, setup2):
        _, state = setup2
        for tree in (state.ae_params, state.ae_opt, state.as_params,
                     state.as_opt):
            assert not isinstance(tree, (list, tuple)) or hasattr(tree, "_fields")
            for leaf in jax.tree.leaves(tree):
                assert leaf.shape[0] == 2  # leading [N] axis

    def test_stacked_init_matches_per_server_init(self, setup2):
        """Stacked init is bit-identical to fold_in-per-server seed init."""
        tr, state = setup2
        k_cls, k_ae, k_as, k_run = jax.random.split(jax.random.key(0), 4)
        for j in range(2):
            ae_j = imputation.init_autoencoder(
                jax.random.fold_in(k_ae, j), tr.num_classes, tr.feature_dim,
                tr.cfg.ae_hidden)
            as_j = assessor_lib.init_assessor(
                jax.random.fold_in(k_as, j), tr.num_classes,
                tr.cfg.assessor_hidden)
            for a, b in zip(jax.tree.leaves(ae_j),
                            jax.tree.leaves(jax.tree.map(lambda x: x[j],
                                                         state.ae_params))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(as_j),
                            jax.tree.leaves(jax.tree.map(lambda x: x[j],
                                                         state.as_params))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stitch_server_links_offsets(self):
        n, n_flat, k, d = 3, 4, 2, 5
        scores = jnp.ones((n, n_flat, k))
        idx = jnp.tile(jnp.array([[0, -1]], jnp.int32), (n, n_flat, 1))
        x_bar = jnp.zeros((n, n_flat, d))
        s2, i2, x2 = patcher.stitch_server_links(scores, idx, x_bar)
        assert s2.shape == (n * n_flat, k) and x2.shape == (n * n_flat, d)
        i2 = np.asarray(i2)
        for j in range(n):
            block = i2[j * n_flat:(j + 1) * n_flat]
            assert (block[:, 0] == j * n_flat).all()   # offset applied
            assert (block[:, 1] == -1).all()           # invalid stays -1

    def test_fit_metrics_single_compiled_eval(self, setup2):
        """fit() metrics come from the fused (loss, acc, f1) eval call."""
        tr, state = setup2
        loss, acc, f1 = tr._eval_fn(state.params, state.batch)
        expect = float(tr._client_loss(state.params, state.batch)) / tr.m
        np.testing.assert_allclose(float(loss), expect, rtol=1e-6)
        assert np.isfinite(float(acc)) and np.isfinite(float(f1))


class TestCheckpointStackedState:
    def test_fgl_state_roundtrips(self, setup2):
        tr, state = setup2
        path = os.path.join(tempfile.mkdtemp(), "fgl_state.npz")
        io.save(path, state)
        restored = io.restore(path, state)
        for a, b in zip(jax.tree.leaves(jax.random.key_data(state.key)),
                        jax.tree.leaves(jax.random.key_data(restored.key))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        skip = {id(state.key)}
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            if id(a) in skip:
                continue
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_restored_state_continues_training(self, setup2):
        tr, state = setup2
        path = os.path.join(tempfile.mkdtemp(), "fgl_state.npz")
        io.save(path, state)
        restored = io.restore(path, state)
        out, _ = tr._impute_fn(restored)
        for leaf in jax.tree.leaves(out.batch):
            assert np.isfinite(np.asarray(leaf, np.float32)).all()


class TestEdgeMesh:
    def test_make_edge_mesh_divides_servers(self):
        from repro.launch.mesh import make_edge_mesh
        mesh = make_edge_mesh(4)
        assert 4 % mesh.size == 0
        assert mesh.axis_names == ("edge",)

    def test_trainer_with_edge_mesh_runs(self):
        from repro.launch.mesh import make_edge_mesh
        g = make_sbm_graph(DATASETS["cora"], scale=0.08, seed=1)
        batch, _ = partition_graph(g, 4, aug_max=8, seed=0)
        cfg = FGLConfig(hidden_dim=16, local_rounds=2, imputation_interval=1,
                        top_k_links=3, aug_max=8)
        tr = make_spreadfgl(cfg, batch, num_servers=2,
                            edge_mesh=make_edge_mesh(2))
        _, hist = tr.fit(jax.random.key(0), batch, rounds=2)
        assert np.isfinite(hist["loss"]).all()

    def test_indivisible_mesh_rejected(self):
        import types
        g = make_sbm_graph(DATASETS["cora"], scale=0.08, seed=1)
        batch, _ = partition_graph(g, 6, aug_max=8, seed=0)
        cfg = FGLConfig(hidden_dim=16, aug_max=8)
        fake_mesh = types.SimpleNamespace(size=2)  # 3 servers % 2 devices != 0
        with pytest.raises(ValueError, match="divide"):
            make_spreadfgl(cfg, batch, num_servers=3, edge_mesh=fake_mesh)


class TestKernelPaddingPaths:
    """Interpret-mode kernels on shapes that are NOT block multiples."""

    @pytest.mark.parametrize("b,n,c,bm,bn", [(33, 70, 7, 16, 32),
                                             (5, 200, 10, 8, 64),
                                             (96, 96, 6, 128, 512)])
    def test_sim_block_non_multiple(self, b, n, c, bm, bn):
        key = jax.random.key(b + n)
        rows = jax.random.normal(key, (b, c))
        h = jax.random.normal(jax.random.fold_in(key, 1), (n, c))
        out = ops.sim_block(rows, h, block_m=bm, block_n=bn, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.sim_block(rows, h)),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("n,d,bm", [(75, 19, 32), (130, 33, 64), (40, 12, 128)])
    def test_sage_aggregate_non_multiple(self, n, d, bm):
        key = jax.random.key(n + d)
        a = (jax.random.uniform(key, (n, n)) < 0.2).astype(jnp.float32)
        h = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
        out = ops.sage_aggregate(a, h, block_m=bm, block_n=bm, block_k=bm,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.sage_aggregate(a, h)),
                                   atol=1e-5, rtol=1e-5)

    def test_vmapped_similarity_topk_matches_loop(self):
        """similarity_topk under vmap (the [N] axis) == per-server calls."""
        key = jax.random.key(0)
        n_srv, m_per, n_pad, c, k = 2, 2, 16, 5, 3
        h = jax.random.normal(key, (n_srv, m_per * n_pad, c))
        mask = jnp.ones((n_srv, m_per * n_pad))
        cid = imputation.client_of_flat(m_per, n_pad)
        s_v, i_v = jax.vmap(
            lambda hj, mj: imputation.similarity_topk(hj, mj, cid, k, block=8)
        )(h, mask)
        for j in range(n_srv):
            s_j, i_j = imputation.similarity_topk(h[j], mask[j], cid, k, block=8)
            np.testing.assert_allclose(np.asarray(s_v[j]), np.asarray(s_j),
                                       atol=1e-5)
            np.testing.assert_array_equal(np.asarray(i_v[j]), np.asarray(i_j))

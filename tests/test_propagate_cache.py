"""The batch's cache of the classifier's input-only work (``ClientBatch.prop``).

``gnn.propagate`` is what a classifier kind computes from a client's graph
before any weight enters: for GraphSAGE and GCN the normalized adjacency and
layer 1's aggregate of the features. ``FGLTrainer.init`` fills the cache,
``FGLTrainer._impute`` fills it anew after the patcher has written its links,
and ``ClientBatch.replace`` drops it when a graph input changes. Checked
here: the cache always equals a fresh ``gnn.propagate`` of its batch, and a
run that reads it equals, to the bit, a plain loop whose forwards never see
it.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import io
from repro.core import fedgl, gnn
from repro.core import strategies as S
from repro.core.fedgl import FGLTrainer
from repro.core.spreadfgl import make_spreadfgl


def fresh(kind, batch):
    """``gnn.propagate`` of every client of ``batch``."""
    return jax.jit(jax.vmap(functools.partial(gnn.propagate, kind)))(
        batch.x, batch.adj, batch.node_mask)


def assert_bit_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def assert_fresh(kind, batch):
    assert batch.prop is not None
    assert_bit_equal(batch.prop, fresh(kind, batch))


@pytest.mark.parametrize("imputation", ["spread", "local_gen"])
def test_the_cache_is_fresh_after_init_each_imputation_and_a_restore(
        small, tmp_path, imputation):
    batch, cfg = small
    strategy = {"spread": S.SpreadImputation(),
                "local_gen": S.LocalGenImputation(gen_steps=3)}[imputation]
    tr = FGLTrainer(cfg, batch, topology=S.RingTopology(2),
                    aggregator=S.NeighborAggregator(), imputation=strategy)
    state = tr.init(jax.random.key(0), batch)
    assert batch.prop is None            # init leaves the caller's batch alone
    assert_fresh(cfg.gnn_kind, state.batch)
    before = jax.device_get(state.batch.prop)   # step donates the cache
    for _ in range(2):                   # imputation_interval 1: every round
        state, m = tr.step(state)
        assert int(m["links"]) > 0
        assert_fresh(cfg.gnn_kind, state.batch)
    assert not np.array_equal(before["a_norm"], np.asarray(state.batch.prop["a_norm"]))
    io.save(tmp_path / "ckpt.npz", state)
    restored = io.restore(tmp_path / "ckpt.npz", tr.init(jax.random.key(1), batch))
    assert_fresh(cfg.gnn_kind, restored.batch)
    assert_bit_equal(restored.batch.prop, state.batch.prop)


def test_replacing_a_graph_input_drops_the_cache(small):
    batch, cfg = small
    tr = make_spreadfgl(cfg, batch, num_servers=2)
    cached = tr.init(jax.random.key(0), batch).batch
    assert cached.replace(train_mask=cached.train_mask * 0).prop is cached.prop
    for field in ("x", "adj", "node_mask"):
        assert cached.replace(**{field: getattr(cached, field)}).prop is None
    assert cached.replace(x=cached.x, prop=cached.prop).prop is cached.prop


@pytest.mark.parametrize("kind", ["sage", "gcn", "gat"])
def test_a_forward_with_the_cache_equals_one_without(small, kind):
    batch, _ = small
    x, adj, mask = batch.x[0], batch.adj[0], batch.node_mask[0]
    params = gnn.init_classifier(jax.random.key(3), kind, [x.shape[-1], 16, 7])
    prop = gnn.propagate(kind, x, adj, mask)
    assert (prop is None) == (kind == "gat")
    assert_bit_equal(gnn.apply_classifier(params, kind, x, adj, mask, prop=prop),
                     gnn.apply_classifier(params, kind, x, adj, mask))


def test_gat_is_unchanged(small):
    """The attention kind caches nothing, and its forward is the plain
    Eq. (2) computation it always was."""
    batch, _ = small
    x, adj, mask = batch.x[0], batch.adj[0], batch.node_mask[0]
    params = gnn.init_classifier(jax.random.key(4), "gat", [x.shape[-1], 16, 7])
    h = x * mask[:, None]
    a = (adj + jnp.eye(adj.shape[-1])) * (mask[:, None] * mask[None, :])
    for li, layer in enumerate(params["layers"]):
        z = h @ layer["w"]
        e = jax.nn.leaky_relu(z @ layer["a_src"] + (z @ layer["a_dst"]).T, 0.2)
        att = jnp.where(a > 0, jax.nn.softmax(jnp.where(a > 0, e, -1e9), -1), 0.0)
        h = att @ z + layer["b"]
        h = (jax.nn.elu(h) if li == 0 else h) * mask[:, None]
    np.testing.assert_array_equal(
        np.asarray(gnn.apply_classifier(params, "gat", x, adj, mask)), np.asarray(h))
    cfg = dataclasses.replace(small[1], gnn_kind="gat")
    tr = make_spreadfgl(cfg, batch, num_servers=2)
    state = tr.init(jax.random.key(0), batch)
    assert state.batch.prop is None
    state, m = tr.step(state)
    assert state.batch.prop is None and np.isfinite(float(m["loss"]))


# -- a run that reads the cache against a plain loop that never does -------------

ROUNDS = 3


@pytest.fixture(scope="module")
def quarter_cora_argv():
    """``cora-sage-n3m6`` with ``impute-k1``, at a quarter of Cora."""
    return ["--dataset", "cora", "--servers", "3", "--clients", "6", "--scale", "0.25",
            "--local-rounds", "1", "--imputation-interval", "1", "--top-k", "4"]


def plain_run(tr: FGLTrainer, state, rounds):
    """Algorithm 1's rounds, each forward a ``gnn.apply_classifier`` call on a
    batch with no cache. Imputation and aggregation are the trainer's own
    (their results do not read the cache); the batch is stripped of the cache
    the imputation round fills before anything reads it again."""
    cfg = tr.cfg
    assert tr.is_spread and cfg.trace_reg > 0   # the loss below adds Eq. 15

    def strip(b):
        return b.replace(prop=None)

    def client_losses(params, b):
        def one(p, x, adj, y, node_mask, train_mask):
            logits = gnn.apply_classifier(p, cfg.gnn_kind, x, adj, node_mask,
                                          impl=tr.kernel_impl)
            return (fedgl._cross_entropy(logits, y, train_mask)
                    + cfg.trace_reg * fedgl._trace_reg(p))
        return jax.vmap(one)(params, b.x, b.adj, b.y, b.node_mask, b.train_mask)

    @jax.jit
    def local(params, opt_state, b):
        grads = jax.grad(lambda p: jnp.sum(client_losses(p, b)))(params)
        return tr.opt.update(grads, opt_state, params)

    loss_fn = jax.jit(lambda params, b: jnp.sum(client_losses(params, b)) / tr.m)
    impute = jax.jit(tr._impute)
    state = dataclasses.replace(state, batch=strip(state.batch))
    read = {"loss": [], "moment": [], "weights": []}
    for t in range(rounds):
        params, opt_state = local(state.params, state.opt_state, state.batch)
        state = dataclasses.replace(state, params=params, opt_state=opt_state)
        state, _ = impute(state)
        state = dataclasses.replace(state, batch=strip(state.batch))
        flag, weights = tr._schedule(t)
        state.params = tr._agg_fn(state.params, flag=flag, weights=weights)
        read["loss"].append(loss_fn(state.params, state.batch))
        read["moment"].append((state.opt_state.mu, state.ae_opt.mu, state.as_opt.mu))
        read["weights"].append((state.params, state.ae_params, state.as_params))
    return read


def cached_run(tr: FGLTrainer, state, rounds):
    read = {"loss": [], "moment": [], "weights": []}
    for _ in range(rounds):
        state, m = tr.step(state)
        assert state.batch.prop is not None
        # the next step donates this round's generator state: read it now
        read["loss"].append(jax.device_get(m["loss"]))
        read["moment"].append(jax.device_get(
            (state.opt_state.mu, state.ae_opt.mu, state.as_opt.mu)))
        read["weights"].append(jax.device_get(
            (state.params, state.ae_params, state.as_params)))
    return read


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_a_cached_run_equals_a_plain_loop_to_the_bit(quarter_cora_argv, impl):
    from repro.launch import fgl_train
    tr, batch = fgl_train.build(fgl_train.parse_args(quarter_cora_argv + ["--impl", impl]))
    assert (tr.n_servers, tr.m, tr.cfg.local_rounds, tr.cfg.imputation_interval) == (3, 6, 1, 1)
    key = jax.random.key(20240711)
    want = jax.device_get(plain_run(tr, tr.init(key, batch), ROUNDS))
    got = jax.device_get(cached_run(tr, tr.init(key, batch), ROUNDS))
    assert_bit_equal(got, want)

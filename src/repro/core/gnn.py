"""GNN node classifiers on dense padded adjacency (Sec. II-A, Eq. 1-3).

Functional init/apply modules (no flax offline). All ops are masked so padded
node slots neither contribute to nor receive messages. The GraphSAGE layer with
the GCN (mean) aggregator is the paper's local node classifier F_i^j.

The neighbor aggregation ``A_norm @ h`` is the per-client compute hot spot; on
TPU it is served by the ``sage_aggregate`` Pallas kernel (kernels/), selected
via the engine-wide ``kernel_impl`` knob (``FGLConfig.kernel_impl`` /
``fgl_train --impl``), which reaches this module as the ``impl=`` argument.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

PyTree = Dict


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[1]
    lim = jnp.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, minval=-lim, maxval=lim, dtype=jnp.float32)


def normalize_adjacency(adj: jnp.ndarray, node_mask: jnp.ndarray) -> jnp.ndarray:
    """Row-normalized masked adjacency (GCN mean aggregator), no self loop."""
    mask2d = node_mask[..., :, None] * node_mask[..., None, :]
    a = adj * mask2d
    deg = jnp.sum(a, axis=-1, keepdims=True)
    return a / jnp.maximum(deg, 1.0)


def aggregate(a_norm: jnp.ndarray, h: jnp.ndarray, impl: str = "reference") -> jnp.ndarray:
    """Neighbor mean aggregation AGG(h_v) = A_norm @ h."""
    if impl == "reference":
        return a_norm @ h
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.sage_aggregate(a_norm, h, interpret=(impl == "pallas_interpret"))
    raise ValueError(f"unknown aggregate impl {impl!r}")


# ---------------------------------------------------------------------------
# GraphSAGE (GCN aggregator), Eq. (3): h' = sigma([h || AGG(h)] W)
# ---------------------------------------------------------------------------

def init_sage(key, dims: Sequence[int]) -> PyTree:
    """dims = [d_in, hidden, ..., c]; each layer has self + neighbor weights."""
    params: List[Dict] = []
    keys = jax.random.split(key, len(dims) - 1)
    for i, k in enumerate(keys):
        k1, k2 = jax.random.split(k)
        params.append({
            "w_self": _glorot(k1, (dims[i], dims[i + 1])),
            "w_nbr": _glorot(k2, (dims[i], dims[i + 1])),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        })
    return {"layers": params}


def propagate_sage(x, adj, node_mask, *, impl: str = "reference") -> PyTree:
    """The input-only work of ``apply_sage``: the normalized adjacency and
    layer 1's aggregate of the masked features, which no weight enters."""
    a_norm = normalize_adjacency(adj, node_mask)
    return {"a_norm": a_norm, "agg1": aggregate(a_norm, x * node_mask[..., None], impl)}


def apply_sage(params: PyTree, x, adj, node_mask, *, impl: str = "reference",
               prop: PyTree = None):
    """Returns per-node logits [n, c]. Masked: padded rows output zeros.
    ``prop`` is ``propagate_sage`` of the same inputs, computed here if None."""
    if prop is None:
        prop = propagate_sage(x, adj, node_mask, impl=impl)
    h = x * node_mask[..., None]
    agg = prop["agg1"]
    n_layers = len(params["layers"])
    for li, layer in enumerate(params["layers"]):
        if li:
            agg = aggregate(prop["a_norm"], h, impl)
        # [h || agg] W  ==  h W_self + agg W_nbr
        h = h @ layer["w_self"] + agg @ layer["w_nbr"] + layer["b"]
        if li < n_layers - 1:
            h = jax.nn.relu(h)
        h = h * node_mask[..., None]
    return h


# ---------------------------------------------------------------------------
# GCN, Eq. (1)
# ---------------------------------------------------------------------------

def init_gcn(key, dims: Sequence[int]) -> PyTree:
    params = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        params.append({"w": _glorot(k, (dims[i], dims[i + 1])),
                       "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    return {"layers": params}


def propagate_gcn(x, adj, node_mask, *, impl: str = "reference") -> PyTree:
    """``propagate_sage`` on the graph with self loops: row normalization of
    ``adj + I``."""
    eye = jnp.eye(adj.shape[-1], dtype=adj.dtype)
    return propagate_sage(x, adj + eye, node_mask, impl=impl)


def apply_gcn(params: PyTree, x, adj, node_mask, *, impl: str = "reference",
              prop: PyTree = None):
    if prop is None:
        prop = propagate_gcn(x, adj, node_mask, impl=impl)
    h = x * node_mask[..., None]
    n_layers = len(params["layers"])
    for li, layer in enumerate(params["layers"]):
        agg = aggregate(prop["a_norm"], h, impl) if li else prop["agg1"]
        h = agg @ layer["w"] + layer["b"]
        if li < n_layers - 1:
            h = jax.nn.relu(h)
        h = h * node_mask[..., None]
    return h


# ---------------------------------------------------------------------------
# GAT, Eq. (2) (single attention head per layer; enough for ablations)
# ---------------------------------------------------------------------------

def init_gat(key, dims: Sequence[int]) -> PyTree:
    params = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        k1, k2, k3 = jax.random.split(k, 3)
        params.append({
            "w": _glorot(k1, (dims[i], dims[i + 1])),
            "a_src": _glorot(k2, (dims[i + 1], 1)),
            "a_dst": _glorot(k3, (dims[i + 1], 1)),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        })
    return {"layers": params}


def propagate_gat(x, adj, node_mask, *, impl: str = "reference") -> None:
    """Nothing: GAT's first layer already reads its weights."""
    del x, adj, node_mask, impl


def apply_gat(params: PyTree, x, adj, node_mask, *, impl: str = "reference",
              prop: PyTree = None):
    del impl, prop
    mask2d = node_mask[..., :, None] * node_mask[..., None, :]
    eye = jnp.eye(adj.shape[-1], dtype=adj.dtype)
    a = (adj + eye) * mask2d
    h = x * node_mask[..., None]
    n_layers = len(params["layers"])
    for li, layer in enumerate(params["layers"]):
        z = h @ layer["w"]
        e = z @ layer["a_src"] + jnp.swapaxes(z @ layer["a_dst"], -1, -2)
        e = jax.nn.leaky_relu(e, 0.2)
        e = jnp.where(a > 0, e, -1e9)
        att = jax.nn.softmax(e, axis=-1)
        att = jnp.where(a > 0, att, 0.0)
        h = att @ z + layer["b"]
        if li < n_layers - 1:
            h = jax.nn.elu(h)
        h = h * node_mask[..., None]
    return h


KINDS = {
    "sage": (init_sage, apply_sage, propagate_sage),
    "gcn": (init_gcn, apply_gcn, propagate_gcn),
    "gat": (init_gat, apply_gat, propagate_gat),
}


def init_classifier(key, kind: str, dims: Sequence[int]) -> PyTree:
    return KINDS[kind][0](key, dims)


def propagate(kind: str, x, adj, node_mask, *, impl: str = "reference") -> PyTree:
    """What classifier ``kind`` computes from one client's graph alone, before
    any weight enters: ``apply_classifier``'s ``prop``."""
    return KINDS[kind][2](x, adj, node_mask, impl=impl)


def apply_classifier(params: PyTree, kind: str, x, adj, node_mask, *,
                     impl: str = "reference", prop: PyTree = None):
    """Logits of classifier ``kind``. ``prop``, where given, is ``propagate``
    of the same ``x``, ``adj`` and ``node_mask``; where None the forward
    computes it, to the same values."""
    return KINDS[kind][1](params, x, adj, node_mask, impl=impl, prop=prop)

"""Adaptive graph imputation generator (Sec. III-C).

Pipeline, run at the edge server every K edge-client communications:

1. Fuse client embeddings H^(j,i) (softmax-space GNN outputs) into the
   globally-shared information H^j (Eq. 9).
2. Build the global similarity topology A̅ = H Hᵀ and keep, per node, the
   top-k most similar *cross-subgraph* nodes as imputed links E̅.
3. An autoencoder maps a random noise matrix S through encoder f ({c,16,d})
   to imputed node features X̅ = f(S) and decoder h ({d,16,c}) back to the
   reconstruction H̄ = h(f(S)) (Eq. 10), trained adversarially against the
   versatile assessor (assessor.py).

The gram-matrix step is the FGL-side compute hot spot (n² in the number of
nodes an edge server covers); ``kernel_impl="pallas"`` routes it through the
fused masked top-k ``sim_topk`` Pallas kernel (``kernel_impl="pallas_interpret"``
runs the same kernel in interpret mode for CPU validation).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.gnn import _glorot

PyTree = Dict


# ---------------------------------------------------------------------------
# Eq. (9): fusion of client embeddings.
# ---------------------------------------------------------------------------

def fuse_embeddings(client_h: jnp.ndarray, node_mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[M, n_pad, c] client embeddings -> flat global H^j  [M*n_pad, c].

    Returns (h_global, flat_mask). Padded slots keep mask 0 so downstream
    similarity/top-k ignores them; flattening keeps a static shape.
    """
    m, n_pad, c = client_h.shape
    return client_h.reshape(m * n_pad, c), node_mask.reshape(m * n_pad)


def client_of_flat(num_clients: int, n_pad: int) -> jnp.ndarray:
    """[M*n_pad] owning-client id of each flattened global slot."""
    return jnp.repeat(jnp.arange(num_clients, dtype=jnp.int32), n_pad)


# ---------------------------------------------------------------------------
# Similarity topology A̅ = H Hᵀ + cross-subgraph top-k links.
# ---------------------------------------------------------------------------

KERNEL_IMPLS = ("reference", "pallas", "pallas_interpret")


def similarity_topk(h: jnp.ndarray, flat_mask: jnp.ndarray, client_ids: jnp.ndarray,
                    k: int, *, kernel_impl: str = "reference", block: int = 256,
                    target_mask: jnp.ndarray = None, mesh=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k most-similar cross-subgraph nodes per node.

    Thin dispatcher over paths that never materialize the full n×n gram
    matrix:

    - ``"reference"``: jnp row blocks — each [block, n] slab is masked and
      reduced with ``jax.lax.top_k`` immediately. The same-client mask is
      likewise built per row block ([block, n]), never as a full [n, n]
      intermediate (pinned by a jaxpr regression in tests/test_ring_topk.py).
    - ``"pallas"`` / ``"pallas_interpret"``: the fused masked top-k kernel
      (kernels/sim_topk.py) — gram tile, same-client + target masking, and a
      running top-k all stay in VMEM across column tiles.
    - ``mesh is not None``: the candidate-sharded ring driver
      (core/ring_topk.py) — candidate slabs rotate around the mesh ring via
      collective_permute and each device streams them into its partial top-k,
      which after ``mesh.size`` steps is the global answer: it agrees with
      ``"reference"`` up to f32 rounding, as ``ring_topk.topk_violations``
      states. ``h``/masks may carry a leading batch axis here (one element
      per edge server), which rides along replicated.

    ``flat_mask`` marks valid *source* rows; ``target_mask`` (defaults to
    ``flat_mask``) marks slots allowed as link targets — the engine restricts
    it to real local slots so imputed aug nodes are never re-linked.

    Returns (scores [.., n, k], idx [.., n, k]); rows with mask 0 and
    unfilled candidate slots get idx -1 / score 0.
    """
    if target_mask is None:
        target_mask = flat_mask
    n = h.shape[-2]
    if mesh is not None:
        from repro.core.ring_topk import ring_similarity_topk
        scores, idx = ring_similarity_topk(h, client_ids, target_mask, k,
                                           mesh=mesh)
    elif kernel_impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        scores, idx = kops.sim_topk(h, client_ids, target_mask, k,
                                    block_m=block,
                                    interpret=(kernel_impl == "pallas_interpret"))
    elif kernel_impl == "reference":
        num_blocks = (n + block - 1) // block
        pad_n = num_blocks * block
        h_pad = jnp.pad(h, ((0, pad_n - n), (0, 0)))
        cid_pad = jnp.pad(client_ids, (0, pad_n - n))

        def one_block(bi):
            rows = jax.lax.dynamic_slice_in_dim(h_pad, bi * block, block, axis=0)
            gram = rows @ h.T
            # Same-client mask per [block, n] slab — never the [n, n] matrix.
            rcid = jax.lax.dynamic_slice_in_dim(cid_pad, bi * block, block)
            same = rcid[:, None] == client_ids[None, :]
            gram = jnp.where(same, -jnp.inf, gram)           # cross-subgraph only
            gram = jnp.where(target_mask[None, :] > 0, gram, -jnp.inf)
            return jax.lax.top_k(gram, k)

        scores, idx = jax.lax.map(one_block, jnp.arange(num_blocks))
        scores = scores.reshape(pad_n, k)[:n]
        idx = idx.reshape(pad_n, k)[:n]
    else:
        raise ValueError(f"unknown kernel_impl {kernel_impl!r}; "
                         f"expected one of {KERNEL_IMPLS}")
    valid = (flat_mask[..., None] > 0) & jnp.isfinite(scores)
    idx = jnp.where(valid, idx.astype(jnp.int32), -1)
    scores = jnp.where(valid, scores, 0.0)
    return scores, idx


def local_slot_mask(num_clients: int, n_pad: int, n_local: int) -> jnp.ndarray:
    """[num_clients*n_pad] mask of *real local* slots (aug slots excluded).

    Link targets must come from this set: the graphic patcher sets
    ``node_mask=1`` on augmented slots it fills, so masking targets with the
    node mask alone would let later fixing rounds pick synthetic nodes as
    cross-subgraph link targets (and re-impute already-imputed features).
    """
    local = (jnp.arange(n_pad) < n_local).astype(jnp.float32)
    return jnp.tile(local, num_clients)


def search_inputs(client_h: jnp.ndarray, node_mask: jnp.ndarray, n_local: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One edge server's similarity-search inputs from its clients' slices.

    client_h [M, n_pad, c] and node_mask [M, n_pad] -> (h [M*n_pad, c],
    flat_mask, client_ids, target_mask), the arguments of
    :func:`similarity_topk`: Eq. 9 fusion, the owning client of each flat
    slot, and link targets restricted to real local slots
    (:func:`local_slot_mask`).
    """
    m, n_pad = node_mask.shape
    h, flat_mask = fuse_embeddings(client_h, node_mask)
    return (h, flat_mask, client_of_flat(m, n_pad),
            flat_mask * local_slot_mask(m, n_pad, n_local))


# ---------------------------------------------------------------------------
# Eq. (10): autoencoder S -> X̅ = f(S) -> H̄ = h(X̅).
# ---------------------------------------------------------------------------

def init_autoencoder(key, c: int, d: int, hidden: int = 16) -> PyTree:
    ks = jax.random.split(key, 4)
    return {
        "enc": [
            {"w": _glorot(ks[0], (c, hidden)), "b": jnp.zeros((hidden,))},
            {"w": _glorot(ks[1], (hidden, d)), "b": jnp.zeros((d,))},
        ],
        "dec": [
            {"w": _glorot(ks[2], (d, hidden)), "b": jnp.zeros((hidden,))},
            {"w": _glorot(ks[3], (hidden, c)), "b": jnp.zeros((c,))},
        ],
    }


def init_stacked_autoencoder(key, n_servers: int, c: int, d: int,
                             hidden: int = 16) -> PyTree:
    """N per-server autoencoders as one pytree with a leading [N] axis.

    Server j's weights match ``init_autoencoder(fold_in(key, j), ...)`` so the
    stacked layout is bit-identical to the seed's per-server list.
    """
    keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(jnp.arange(n_servers))
    return jax.vmap(lambda k: init_autoencoder(k, c, d, hidden))(keys)


def encode(params: PyTree, s: jnp.ndarray) -> jnp.ndarray:
    """X̅ = f(S): imputed potential features."""
    h = jax.nn.relu(s @ params["enc"][0]["w"] + params["enc"][0]["b"])
    return h @ params["enc"][1]["w"] + params["enc"][1]["b"]


def decode(params: PyTree, x_bar: jnp.ndarray) -> jnp.ndarray:
    """H̄ = h(X̅); softmax last layer (paper: Softmax activation in the AE head)."""
    h = jax.nn.relu(x_bar @ params["dec"][0]["w"] + params["dec"][0]["b"])
    logits = h @ params["dec"][1]["w"] + params["dec"][1]["b"]
    return jax.nn.softmax(logits, axis=-1)


def reconstruct(params: PyTree, s: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x_bar = encode(params, s)
    return x_bar, decode(params, x_bar)


def sample_noise(key, n: int, c: int) -> jnp.ndarray:
    """Random noise S (privacy: the AE never sees raw features)."""
    return jax.random.normal(key, (n, c), dtype=jnp.float32)

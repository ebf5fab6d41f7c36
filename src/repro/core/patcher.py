"""Graph fixing via local graphic patchers (Sec. III-D).

The edge server splits the learnable potential graph G̅ = (V, E̅, X̅) back into
per-client pieces; each client's patcher P_i^j merges its piece into the local
subgraph: imputed cross-subgraph neighbors become *augmented node slots*
(features from X̅ = f(S)) wired to the local nodes they were matched with.
This restores multi-hop feature propagation without ever moving raw features
between clients — only AE-generated ones.

Static shapes: every client owns ``aug_max`` augmentation slots; each fixing
round overwrites them (links from previous rounds are superseded, which matches
the paper's per-round regeneration of G̅).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import ClientBatch


def stitch_server_links(scores: jnp.ndarray, idx: jnp.ndarray, x_bar: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Per-server imputation results -> the global flat index space.

    Each edge server emits link targets as *server-local* flat slots in
    ``[0, M_per * n_pad)``; server j's slots live at global offset
    ``j * M_per * n_pad`` (clients are grouped contiguously per server).

    Args:
      scores: [N, M_per*n_pad, k] link similarities.
      idx: [N, M_per*n_pad, k] server-local flat targets, -1 where invalid.
      x_bar: [N, M_per*n_pad, d] imputed features X̅.

    Returns (scores [M*n_pad, k], idx [M*n_pad, k] global flats, x_bar
    [M*n_pad, d]).
    """
    n, n_flat, k = idx.shape
    offsets = (jnp.arange(n, dtype=idx.dtype) * n_flat)[:, None, None]
    idx = jnp.where(idx >= 0, idx + offsets, -1)
    return (scores.reshape(n * n_flat, k), idx.reshape(n * n_flat, k),
            x_bar.reshape(n * n_flat, x_bar.shape[-1]))


def fix_graphs(batch: ClientBatch, link_scores: jnp.ndarray, link_idx: jnp.ndarray,
               x_bar: jnp.ndarray) -> ClientBatch:
    """Apply graph fixing to every client.

    Args:
      batch: current federated batch (aug slots will be overwritten).
      link_scores: [M*n_pad, k] similarity of imputed links (0 = invalid).
      link_idx: [M*n_pad, k] flat global slot of the matched cross-subgraph
        node, -1 where invalid.
      x_bar: [M*n_pad, d] imputed potential features X̅ (AE encoder output).

    Returns a new ClientBatch with aug slots populated.
    """
    m, n_pad = batch.x.shape[0], batch.x.shape[1]
    aug_max = batch.aug_max
    n_local = n_pad - aug_max
    d = batch.x.shape[2]

    scores = link_scores.reshape(m, n_pad, -1)
    idx = link_idx.reshape(m, n_pad, -1)
    k = scores.shape[-1]

    def fix_one(x, adj, node_mask, sc, ix):
        # Candidate links from this client's *real local* nodes.
        src = jnp.broadcast_to(jnp.arange(n_pad)[:, None], (n_pad, k)).reshape(-1)
        tgt = ix.reshape(-1)
        s = sc.reshape(-1)
        is_local_src = (src < n_local) & (node_mask[src] > 0)
        valid = (tgt >= 0) & is_local_src
        s = jnp.where(valid, s, -jnp.inf)
        # Strongest aug_max links win the augmentation slots.
        top_s, top_i = jax.lax.top_k(s, aug_max)
        chosen_src = src[top_i]
        chosen_tgt = tgt[top_i]
        chosen_ok = jnp.isfinite(top_s)

        aug_rows = n_local + jnp.arange(aug_max)
        safe_tgt = jnp.maximum(chosen_tgt, 0)
        feats = x_bar[safe_tgt] * chosen_ok[:, None]

        # Reset aug region, then write features + symmetric links.
        x = x.at[n_local:].set(0.0)
        x = x.at[aug_rows].set(feats.astype(x.dtype))
        adj = adj.at[n_local:, :].set(0.0)
        adj = adj.at[:, n_local:].set(0.0)
        w = chosen_ok.astype(adj.dtype)
        adj = adj.at[chosen_src, aug_rows].set(w)
        adj = adj.at[aug_rows, chosen_src].set(w)
        node_mask = node_mask.at[aug_rows].set(w)
        return x, adj, node_mask

    x, adj, node_mask = jax.vmap(fix_one)(batch.x, batch.adj, batch.node_mask,
                                          scores, idx)
    return batch.replace(x=x, adj=adj, node_mask=node_mask)


def link_count(batch: ClientBatch) -> jnp.ndarray:
    """Imputed links wired into the clients' graphs: one per filled
    augmentation slot (each slot holds one imputed neighbor and its link)."""
    return jnp.sum(batch.node_mask[:, batch.n_local_max:] > 0, dtype=jnp.int32)


def clear_augmentation(batch: ClientBatch) -> ClientBatch:
    """Drop all imputed nodes/links (used by baselines and ablations)."""
    n_local = batch.n_local_max
    x = batch.x.at[:, n_local:].set(0.0) if hasattr(batch.x, "at") else batch.x
    adj = batch.adj
    if hasattr(adj, "at"):
        adj = adj.at[:, n_local:, :].set(0.0)
        adj = adj.at[:, :, n_local:].set(0.0)
    mask = batch.node_mask
    if hasattr(mask, "at"):
        mask = mask.at[:, n_local:].set(0.0)
    return batch.replace(x=x, adj=adj, node_mask=mask)

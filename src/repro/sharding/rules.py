"""Logical-axis -> mesh-axis sharding rules (MaxText-style, divisibility-aware).

Each parameter leaf carries a tuple of logical axis names (models/*.axes_*).
``logical_to_spec`` maps them to a PartitionSpec given the mesh, FALLING BACK
to replication when the dimension size does not divide the mesh axis — this is
what lets hymba's 25 heads or xlstm's 4 heads coexist with a 16-way model axis
(their ff/inner dims carry the axis instead).

Default rules (tensor parallel on "model", data parallel on ("pod","data")):
  vocab      -> model      (embedding/unembedding sharded over vocab)
  heads      -> model      (attention q heads)
  kv_heads   -> model      (falls back to replicated when kv < axis)
  ff         -> model      (dense MLP hidden)
  expert_ff  -> model      (MoE expert hidden; used when experts don't divide)
  experts    -> model      (expert parallelism when num_experts % axis == 0)
  inner      -> model      (mamba/mLSTM expanded inner dim)
  embed      -> data       (FSDP/ZeRO-3: weight d_model dim sharded over data;
                            all-gathered per layer under the scan)
  layers     -> None       (scan stack dim)
  batch      -> (pod, data)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

DEFAULT_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "expert_ff": "model",
    "experts": "model",
    "inner": "model",
    "embed": "data",   # FSDP: the d_model dim of weights shards over data
    "layers": None,
    "batch": "data",     # expanded to ("pod","data") when the mesh has pods
}


def _mesh_axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return int(mesh.shape[name])


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def logical_to_spec(axes: Tuple, shape: Tuple[int, ...], mesh: Mesh,
                    rules: Optional[Dict[str, Optional[str]]] = None) -> P:
    """Map one leaf's logical axes to a PartitionSpec (divisibility fallback)."""
    rules = rules or DEFAULT_RULES
    entries = []
    used = set()
    for dim, name in zip(shape, axes):
        target = rules.get(name) if name is not None else None
        if name == "batch":
            target = batch_axes(mesh)
        if target is None:
            entries.append(None)
            continue
        if isinstance(target, str):
            target_t = (target,)
        else:
            target_t = tuple(target)
        if any(t not in mesh.shape for t in target_t):
            entries.append(None)
            continue
        if any(t in used for t in target_t):
            entries.append(None)  # an axis can shard only one dim
            continue
        if dim % _mesh_axis_size(mesh, target_t) != 0:
            entries.append(None)  # divisibility fallback -> replicate
            continue
        used.update(target_t)
        entries.append(target_t if len(target_t) > 1 else target_t[0])
    return P(*entries)


def spec_tree(axes_tree: PyTree, shape_tree: PyTree, mesh: Mesh,
              rules: Optional[Dict[str, Optional[str]]] = None) -> PyTree:
    """PartitionSpec pytree for a params tree.

    ``axes_tree`` leaves are tuples of logical names; ``shape_tree`` leaves are
    array-likes (or ShapeDtypeStructs) with .shape.
    """
    is_axes_leaf = lambda a: isinstance(a, tuple) and all(
        x is None or isinstance(x, str) for x in a)
    return jax.tree.map(
        lambda a, s: logical_to_spec(a, s.shape, mesh, rules),
        axes_tree, shape_tree, is_leaf=is_axes_leaf)


def sharding_tree(axes_tree: PyTree, shape_tree: PyTree, mesh: Mesh,
                  rules: Optional[Dict[str, Optional[str]]] = None) -> PyTree:
    specs = spec_tree(axes_tree, shape_tree, mesh, rules)
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))

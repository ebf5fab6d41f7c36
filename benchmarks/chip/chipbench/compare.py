"""The comparison that decides ``correct``: program readings vs the reference.

Three numbers, each against the cell's limit (``limits/<workload>.json``):

- ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the compared rounds.
- ``grad_gap``: by the worst leaf, the gap between the norms of the
  optimizers' first moments after round 0 (the gradients as the optimizers
  got them), over the reference's norm of that leaf or of the median leaf,
  whichever is larger; the median is over leaves the reference moves.
- ``change_gap``: the same for the weights' change over the compared
  rounds, leaving out leaves whose reference moment is under a thousandth of
  the median leaf's (such leaves move by round-off alone).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict

import jax
import numpy as np

QUIET_LEAF = 1e-3


def leaf_norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(v, np.float64)))
            for p, v in flat}


def worst_leaf_gap(prog, ref, keep=None) -> float:
    pn, rn = leaf_norms(prog), leaf_norms(ref)
    if set(pn) != set(rn):
        raise ValueError(f"leaves differ: {sorted(set(pn) ^ set(rn))}")
    names = [n for n in rn if keep is None or n in keep]
    med = median_moving(rn[n] for n in names)
    gaps = [abs(pn[n] - rn[n]) / max(rn[n], med) for n in names]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def median_moving(norms) -> float:
    """Median of the nonzero norms: leaves the reference never moves (an
    optimizer with no round to run) do not pull it to zero."""
    moving = [v for v in norms if v > 0]
    return statistics.median(moving) if moving else 1.0


def change(read):
    return jax.tree.map(lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
                        read["final"], read["init"])


def gaps(read, ref) -> Dict[str, float]:
    """The three numbers for a program's (or a control's) readings."""
    losses = [abs(a - b) / abs(b) for a, b in zip(read["loss"], ref["loss"])]
    loss_gap = max(losses) if all(math.isfinite(v) for v in losses) else math.inf
    moment = leaf_norms(ref["moment"])
    med = median_moving(moment.values())
    moved = {n for n, v in moment.items() if v >= QUIET_LEAF * med}
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(read["moment"], ref["moment"]),
            "change_gap": worst_leaf_gap(change(read), change(ref), keep=moved)}


def checks(read, ref, limits) -> Dict[str, Dict[str, float]]:
    return {name: {"value": value, "limit": float(limits[name])}
            for name, value in gaps(read, ref).items()}

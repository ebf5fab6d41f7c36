"""Production mesh definitions (TPU v5e target).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 chips as (pod=2, data=16, model=16) — the "pod" axis
carries SpreadFGL's edge-server topology (core/gossip.py).

Functions, not module constants: importing this module never touches jax
device state (dryrun.py must set XLA_FLAGS before the first jax call).

Every mesh here has ``AxisType.Auto`` axes: the sharding rules
(``sharding/rules.py``) and the shard_map bodies assume the compiler
propagates shardings, not the explicit-sharding type system that
``jax.make_mesh`` defaults to.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_edge_mesh(num_servers: int, *, devices: int = 0) -> Mesh:
    """1-D mesh carrying SpreadFGL's stacked [N] edge-server axis.

    Uses the largest divisor of ``num_servers`` that fits the available
    devices, so the vmapped imputation round always shards evenly (a 1-device
    host degenerates to a size-1 mesh, i.e. plain vmap).
    """
    n_dev = min(devices or len(jax.devices()), len(jax.devices()))
    size = max(d for d in range(1, min(num_servers, n_dev) + 1)
               if num_servers % d == 0)
    return Mesh(jax.devices()[:size], ("edge",))


def make_sim_mesh(*, devices: int = 0) -> Mesh:
    """1-D mesh carrying the CANDIDATE axis of the imputation similarity
    search (``core/ring_topk.py``; ``--sim-shard`` in the launchers).

    Unlike :func:`make_edge_mesh` there is no divisibility constraint — the
    ring driver pads the candidate axis to a mesh-size multiple — so this
    simply takes the first ``devices`` devices (default: all of them).
    """
    n = min(devices or len(jax.devices()), len(jax.devices()))
    return Mesh(jax.devices()[:n], ("sim",))


def make_host_mesh(*, model: int = 1, data: int = 0, pod: int = 0) -> Mesh:
    """Small mesh over whatever host devices exist (tests/examples)."""
    n = len(jax.devices())
    if pod:
        data = data or max(1, n // (model * pod))
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    data = data or max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))

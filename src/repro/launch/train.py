"""LM training launcher (host-scale; the production mesh path is dryrun.py).

Runs real steps on whatever devices exist, with the same sharding rules as the
production mesh. ``--aggregation spread`` exercises the paper's gossip
aggregation across a ``pod`` axis (requires multiple host devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --variant smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.lm_data import token_batches
from repro.launch.mesh import make_host_mesh
from repro.optim.adam import Adam, cosine_schedule
from repro.train.step import init_state, make_train_step
from repro.checkpoint import io as ckpt_io


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="xlstm-125m")
    ap.add_argument("--variant", choices=("full", "smoke"), default="smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--aggregation", choices=("allreduce", "spread"),
                    default="allreduce")
    ap.add_argument("--gossip-every", type=int, default=4)
    ap.add_argument("--pods", type=int, default=0,
                    help="pod axis size for --aggregation spread")
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, args.variant)
    opt = Adam(lr=args.lr, clip_norm=1.0,
               schedule=cosine_schedule(max(args.steps // 10, 1), args.steps))
    state = init_state(jax.random.key(0), cfg, opt)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{len(jax.devices())} devices, aggregation={args.aggregation}")

    if args.aggregation == "spread":
        from jax.sharding import PartitionSpec as P
        pods = args.pods or len(jax.devices())
        mesh = make_host_mesh(pod=pods, data=1, model=1)
        step_inner = make_train_step(cfg, opt, aggregation="spread",
                                     gossip_every=args.gossip_every,
                                     pod_axis="pod")

        def per_pod(state_blk, batch_blk):
            # state stacked [pods, ...]; each pod sees its [1, ...] block.
            st = jax.tree.map(lambda t: t[0], state_blk)
            st, metrics = step_inner(st, batch_blk)
            return jax.tree.map(lambda t: t[None], st), metrics

        step = jax.jit(jax.shard_map(per_pod, mesh=mesh,
                                     in_specs=(P("pod"), P("pod")),
                                     out_specs=(P("pod"), P("pod")),
                                     check_vma=False))
        # replicate the initial state across pods (they diverge between gossips)
        state = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (pods,) + t.shape).copy(), state)
    else:
        step = jax.jit(make_train_step(cfg, opt))

    data = token_batches(cfg, batch=args.batch, seq_len=args.seq)
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        state, metrics = step(state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            loss = float(jnp.mean(metrics["loss"]))
            print(f"[train] step {i:4d} loss {loss:.4f} "
                  f"({time.time()-t0:.1f}s)")
    if args.checkpoint:
        ckpt_io.save(args.checkpoint, state.params)
        print(f"[train] saved params -> {args.checkpoint}")


if __name__ == "__main__":
    main()

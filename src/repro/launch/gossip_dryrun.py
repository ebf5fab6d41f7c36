import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Cross-pod aggregation dry-run: SpreadFGL gossip (Eq. 16) vs all-reduce.

Lowers BOTH aggregation schedules for a given architecture on the multi-pod
mesh and reports their collective traffic:

  allreduce : classic data parallelism — every step, psum of params/grads
              over the 'pod' axis (the FedAvg analogue, DESIGN.md §3).
  spread    : ring gossip — collective_permute with both ring neighbors,
              applied every K steps (the paper's edge-layer aggregation).

The per-step cross-pod byte ratio (gossip/K vs all-reduce) is the §Perf
measurement for the paper-representative hillclimb pair. All byte/ratio
math lives in ``repro.core.gossip`` — this CLI only lowers the two
schedules and reports. The *FGL engine* equivalent (gossip as a first-class
Aggregator strategy over the stacked [N] edge-server axis) is the
``spreadfgl_gossip`` registry method; ``benchmarks/bench_load_balance.py``
measures that path.

  PYTHONPATH=src python -m repro.launch.gossip_dryrun --arch qwen3-4b -K 8
"""
import argparse
import json
import pathlib

import jax

from repro import configs
from repro.core import gossip
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.roofline import analysis
from repro.sharding import rules, specs as S


def lower_aggregation(cfg, mesh, mode: str):
    params_specs = S.param_specs(cfg, mesh)
    shapes = jax.eval_shape(lambda: jax.tree.map(lambda s: s, params_specs))
    axes = transformer.model_axes(cfg)
    pspecs = rules.spec_tree(axes, params_specs, mesh)

    def agg(params):
        if mode == "spread":
            return gossip.ring_gossip(params, "pod")
        return gossip.all_average(params, "pod")

    fn = jax.shard_map(agg, mesh=mesh, in_specs=(pspecs,), out_specs=pspecs,
                       check_vma=False)
    with jax.sharding.set_mesh(mesh):
        return jax.jit(fn).lower(params_specs).compile()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS, default="qwen3-4b")
    ap.add_argument("-K", "--gossip-every", type=int, default=8)
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    cfg = configs.get_config(args.arch, "full")
    mesh = make_production_mesh(multi_pod=True)
    out = {}
    for mode in ("allreduce", "spread"):
        compiled = lower_aggregation(cfg, mesh, mode)
        coll = analysis.collective_bytes(compiled.as_text())
        out[mode] = coll
        print(f"[gossip-dryrun] {args.arch} {mode}: {coll}")

    ar = sum(out["allreduce"].values())
    sp = sum(out["spread"].values())
    k = args.gossip_every
    # The byte-ratio math lives in core/gossip.py (shared with
    # benchmarks/bench_load_balance.py); this CLI is a thin caller.
    ratio = gossip.gossip_allreduce_ratio(ar, sp, every=k)
    print(f"[gossip-dryrun] per-step cross-pod bytes: allreduce={ar/1e9:.3f}GB "
          f"spread(K={k})={sp/k/1e9:.3f}GB ratio={ratio:.3f}")
    rec = {"arch": args.arch, "K": k, "allreduce_bytes": ar,
           "spread_bytes_per_application": sp,
           "spread_bytes_per_step": sp / k, "ratio": ratio,
           "detail": out}
    p = pathlib.Path(args.out)
    p.mkdir(parents=True, exist_ok=True)
    (p / f"gossip_{args.arch}_K{k}.json").write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()

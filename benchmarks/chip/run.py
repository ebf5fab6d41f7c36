"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload cora-fedavg.local-e1 --seed 7 \\
      --seconds 10 --trace 0

From the root of a checkout. ``--trace 0`` measures the cell's end-to-end
metrics over ``--seconds``; ``--trace 1`` traces a few whole rounds on the
device and reports the per-layer metrics. Both check the rounds the program
ran against the plain reference and print, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``breakdown`` with ``--trace 1``), and last
``checks``, each compared number beside its limit; the same numbers end
standard error. The run refuses (exit 2, no result) where JAX finds no TPU,
fewer chips than the cell asks for, a device kind missing from
``peaks.json``, or no program (``src/repro``) in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def refuse(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def finite(v: float) -> float:
    return v if math.isfinite(v) else 1e300


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return refuse(f"{ROOT} holds no program (src/repro)")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    from chipbench import cellrun, spec
    cell = spec.load_cell(ROOT, args.workload)

    import jax
    from repro.launch import compile_cache
    if jax.default_backend() != "tpu":
        return refuse(f"JAX found no TPU (backend {jax.default_backend()!r})")
    devices = jax.devices()
    if len(devices) < cell.chips:
        return refuse(f"{args.workload} needs {cell.chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    try:
        peaks = spec.load_peaks(kind)
    except KeyError as e:
        return refuse(str(e))
    cache = compile_cache.enable()
    # Every program, however quick to compile, is kept: a later run of the
    # cell loads all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[chipbench] {args.workload} seed {args.seed} on {len(devices)} x {kind}; "
          f"compile cache {cache}", flush=True)

    res = cellrun.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, peaks=peaks,
                           devices=devices[:cell.chips])
    device = {"platform": devices[0].platform, "kind": kind, "count": cell.chips,
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
    checks = {k: {"value": finite(v["value"]), "limit": v["limit"]}
              for k, v in res["checks"].items()}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"], "device": device}
    if args.trace:
        line["breakdown"] = res["breakdown"]
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

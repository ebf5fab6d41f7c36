"""Readings that the comparison limits of a cell are set from.

  python3 benchmarks/chip/calibrate.py --workload cora-fedavg.local-e1 \\
      --seeds 12 [--control-seeds 3] [--fault-seeds 3] \\
      [--config NAME --traffic NAME]

One process builds the cell once, as a run does (``--impl pallas``), and,
for each seed, runs the compared rounds through the program and through the
plain reference at the configuration's stated precision, and prints the
three numbers (``chipbench.compare``) as one JSON line. Then the control
(the reference one precision step below, in the program's place) and each
fault of ``chipbench.faults`` planted in the program, on their own seeds.
The last line sums up: the largest program reading of each number (the
lower reading), the smallest control and fault readings (the upper ones),
and, for a cell of ``BENCHMARK.json``, the numbers each kind fails against
the cell's limits. ``--config``/``--traffic`` name a pairing that is not a
cell. Like ``run.py`` it refuses (exit 2) where JAX finds no TPU or a device
kind missing from ``peaks.json``; the benchmark's runs never call it.
"""
import argparse
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--config", default="")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    import jax
    from chipbench import cellrun, compare, faults, spec
    from repro.launch import compile_cache
    if jax.default_backend() != "tpu":
        print(f"calibrate: JAX found no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    try:
        spec.load_peaks(jax.devices()[0].device_kind)
    except KeyError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.workload:
        cell = spec.load_cell(ROOT, args.workload)
    else:
        cell = spec.pairing(args.config, args.traffic)
    n = int(cell.limits["compare_rounds"])
    program = cellrun.build(cell, "pallas")
    tr = program.trainer
    refs = {p: cellrun.make_reference(cell, program, p) for p in ("stated", "control")}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    summary, fails = {}, {}

    def record(kind, seed, read, ref):
        """One reading; for a cell, also the numbers that fail its limits,
        by the comparison a run makes."""
        got = compare.gaps(read, ref)
        line = {"kind": kind, "seed": seed, **got}
        if args.workload:
            line["fails"] = sorted(name for name, c in compare.checks(
                read, ref, cell.limits).items() if not c["value"] <= c["limit"])
            fails.setdefault(kind, []).append(bool(line["fails"]))
        print(json.dumps(line), flush=True)
        for name, value in got.items():
            summary.setdefault(kind, {}).setdefault(name, []).append(value)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        state = tr.init(cellrun.key_from_seed(seed), program.batch)
        state, read = cellrun.first_rounds(state, tr.step, n, n)
        del state
        ref = cellrun.reference_readings(refs["stated"], program, seed, n)
        record("program", seed, read, ref)
        if i < args.control_seeds:
            ctl = cellrun.reference_readings(refs["control"], program, seed, n)
            record("control", seed, ctl, ref)
        if i < args.fault_seeds:
            for name, plant in faults.FAULTS.items():
                state = tr.init(cellrun.key_from_seed(seed), program.batch)
                state, got = cellrun.first_rounds(state, plant(tr), n, n)
                del state
                record(f"fault:{name}", seed, got, ref)
        print(f"[calibrate] seed {seed}: {time.perf_counter() - t0:.3f} s",
              file=sys.stderr, flush=True)
    out = {}
    for kind, nums in summary.items():
        pick = max if kind == "program" else min
        out[kind] = {name: pick(v) for name, v in nums.items()}
    dev = jax.devices()[0]
    print(json.dumps({"summary": out, "failing_seeds": {k: sum(v) for k, v in fails.items()},
                      "device": dev.device_kind, "cell": cell.name}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

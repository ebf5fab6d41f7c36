"""Everything the harness knows about a cell, found by name on disk.

``BENCHMARK.json`` at the checkout's root names the cells; each cell names
a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); a configuration names its model kind, whose
work functions are ``work/<model>.py`` and whose plain reference is
``reference/<model>.py``; each per-layer metric is read by
``metrics/<metric>.py``; the comparison limits of a cell are
``limits/<workload>.json``; the chips' peaks are ``peaks.json``. Adding a
cell, a mix or a metric is adding files and entries; no code names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
from typing import Any, Dict, List

HERE = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, Any]
    bench_dir: pathlib.Path

    @property
    def flags(self) -> Dict[str, Any]:
        """The cell's ``fgl_train`` flags: the configuration's, then the mix's."""
        return {**self.config["fgl_train"], **self.traffic["fgl_train"]}

    def fgl_train_argv(self, impl: str) -> List[str]:
        """``fgl_train`` command-line arguments of the cell."""
        argv = []
        for key, value in {**self.flags, "impl": impl}.items():
            flag = "--" + key.replace("_", "-")
            if value is True:
                argv.append(flag)
            elif value is not False:
                argv += [flag, str(value)]
        return argv

    @property
    def schedule(self) -> Dict[str, int]:
        """The round's schedule; a flag the cell leaves out has
        ``fgl_train``'s default."""
        f = self.flags
        return {"local_rounds": int(f.get("local_rounds", 4)),
                "imputation_interval": int(f.get("imputation_interval", 2)),
                "gossip_every": int(f.get("gossip_every", 1)),
                "top_k": int(f.get("top_k", 4))}

    def period(self, imputes: bool) -> int:
        """Rounds after which the schedule repeats: the exchange, and the
        imputation round where the method runs one."""
        s = self.schedule
        return math.lcm(s["imputation_interval"] if imputes else 1, s["gossip_every"])

    def work(self):
        return load_module(self.bench_dir / "work" / f"{self.config['model']}.py")

    def reference(self):
        return load_module(self.bench_dir / "reference" / f"{self.config['model']}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    spec = importlib.util.spec_from_file_location(
        "chipbench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    return json.loads(path.read_text())


def load_benchmark(root: pathlib.Path) -> Dict[str, Any]:
    return read_json(root / "BENCHMARK.json")


def load_cell(root: pathlib.Path, workload: str,
              bench_dir: pathlib.Path = HERE) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its files."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(root / conf_entry["file"])
    traffic = read_json(bench_dir / "traffic" / f"{entry['traffic']}.json")

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                limits=read_json(bench_dir / "limits" / f"{workload}.json"),
                bench_dir=bench_dir)


def pairing(config: str, traffic: str, bench_dir: pathlib.Path = HERE) -> Cell:
    """A configuration under a traffic mix that ``BENCHMARK.json`` does not
    list (for calibration, which drives the chips it finds); it compares the
    usual rounds and has no limits."""
    conf = read_json(bench_dir / "configs" / f"{config}.json")
    return Cell(name=f"{config}+{traffic}", chips=1, config=conf,
                traffic=read_json(bench_dir / "traffic" / f"{traffic}.json"),
                end_to_end=[], per_layer=[], limits={"compare_rounds": 3},
                bench_dir=bench_dir)


def load_peaks(device_kind: str, bench_dir: pathlib.Path = HERE) -> Dict[str, Any]:
    """The peaks of ``device_kind``; a kind missing from the table is an error."""
    table = read_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[device_kind]

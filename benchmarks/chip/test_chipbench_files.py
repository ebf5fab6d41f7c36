"""The benchmark's files: found by name, well formed, and open to additions."""
import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_benchmark_json_keeps_its_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert bench["paths"] == ["benchmarks/chip"]
    assert 1 <= bench["run_seconds"] <= 51
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in bench["workloads"]] \
        + [c["name"] for c in bench["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        conf = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert cell.config["name"] == conf["name"]
        assert cell.config["reduced"] == conf["reduced"]
        assert {"source", "assumed", "precision", "deployment"} <= set(cell.config)
        assert cell.fgl_train_argv("pallas")[-2:] == ["--impl", "pallas"]
        assert {"compare_rounds", "loss_gap", "grad_gap", "change_gap"} <= set(cell.limits)
        assert hasattr(cell.work(), "round_flops")
        assert hasattr(cell.reference(), "Reference")
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "round_ms"}


def test_peaks_table_refuses_an_unknown_chip():
    assert spec.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")


def test_a_cell_mix_and_metric_are_added_as_new_files(tmp_path, bench):
    """A new configuration, traffic mix, per-layer metric and cell need new
    files and entries only: no file of the benchmark is edited."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*.py"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    conf = json.loads((bench_dir / "configs" / "cora-sage-fedavg-m6.json").read_text())
    conf.update(name="citeseer-sage-fedavg-m6")
    conf["fgl_train"] = dict(conf["fgl_train"], dataset="citeseer")
    (bench_dir / "configs" / "citeseer-sage-fedavg-m6.json").write_text(json.dumps(conf))
    traffic = {"why": "one local step", "fgl_train": {"local_rounds": 1, "top_k": 4}}
    (bench_dir / "traffic" / "fedsgd-1.json").write_text(json.dumps(traffic))
    (bench_dir / "metrics" / "round.host_ms.py").write_text(
        "def read(ctx):\n    return ctx.per_round_ms(ctx.trace.window_s)\n")
    (bench_dir / "limits" / "citeseer.fedsgd-1.json").write_text(json.dumps(
        {"compare_rounds": 3, "loss_gap": 1, "grad_gap": 1, "change_gap": 1}))
    new = dict(bench)
    new["configs"] = bench["configs"] + [{
        "name": conf["name"], "source": conf["source"], "reduced": [],
        "file": "benchmarks/chip/configs/citeseer-sage-fedavg-m6.json", "why": "new"}]
    new["workloads"] = bench["workloads"] + [{
        "name": "citeseer.fedsgd-1", "config": conf["name"], "traffic": "fedsgd-1",
        "chips": 1, "why": "new"}]
    new["per_layer"] = bench["per_layer"] + [{
        "name": "round.host_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "round loop and device (FGLTrainer.step)", "moves": "round_ms",
        "workloads": ["citeseer.fedsgd-1"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.load_cell(tmp_path, "citeseer.fedsgd-1", bench_dir=bench_dir)
    assert cell.schedule["local_rounds"] == 1
    assert "--dataset" in cell.fgl_train_argv("pallas")
    assert cell.fgl_train_argv("pallas")[cell.fgl_train_argv("pallas").index("--dataset") + 1] == "citeseer"
    assert "round.host_ms" in [m["name"] for m in cell.per_layer]

    class Ctx:
        class trace:
            window_s = 0.5
        rounds = [0, 1]

        def per_round_ms(self, s):
            return s / len(self.rounds) * 1e3
    assert cell.reader("round.host_ms")(Ctx()) == 250.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _run(cwd, *args, script="run.py"):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(cwd)}
    return subprocess.run([sys.executable, f"benchmarks/chip/{script}", *args],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script,args", [
    ("run.py", ["--seed", "5", "--seconds", "1", "--trace", "0"]),
    ("calibrate.py", ["--seeds", "1"])])
def test_off_tpu_a_run_refuses_and_prints_no_result(script, args):
    res = _run(ROOT, "--workload", "cora-fedavg.local-e1", *args, script=script)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_without_the_program_a_run_refuses(tmp_path):
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    res = _run(tmp_path, "--workload", "cora-fedavg.local-e1", "--seed", "5",
               "--seconds", "1", "--trace", "0")
    assert res.returncode != 0 and res.stdout.strip() == ""

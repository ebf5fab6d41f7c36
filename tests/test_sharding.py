"""Sharding rules + spec construction (divisibility fallbacks, mesh plumbing)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding import rules


class FakeMesh:
    """Duck-typed mesh exposing .shape mapping (enough for rules)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


class TestLogicalToSpec:
    def test_basic_tp(self):
        mesh = FakeMesh(data=16, model=16)
        spec = rules.logical_to_spec(("embed", "heads"), (4096, 4096), mesh)
        assert spec == P("data", "model")

    def test_divisibility_fallback(self):
        mesh = FakeMesh(data=16, model=16)
        # 25 heads don't divide 16 -> replicated
        spec = rules.logical_to_spec(("embed", "heads"), (1600, 25 * 64), mesh)
        assert spec == P("data", "model")  # 1600/16 ok, 1600 total head dim ok
        spec = rules.logical_to_spec((None, "heads"), (7, 25), mesh)
        assert spec == P(None, None)

    def test_axis_used_once(self):
        mesh = FakeMesh(data=16, model=16)
        spec = rules.logical_to_spec(("ff", "heads"), (1024, 1024), mesh)
        assert spec == P("model", None)  # second 'model' consumer loses

    def test_experts_shard_when_divisible(self):
        mesh = FakeMesh(data=16, model=16)
        spec = rules.logical_to_spec(("experts", "embed", "expert_ff"),
                                     (64, 2048, 1024), mesh)
        assert spec == P("model", "data", None)  # model consumed by experts

    def test_experts_fallback_mixtral(self):
        mesh = FakeMesh(data=16, model=16)
        spec = rules.logical_to_spec(("experts", "embed", "expert_ff"),
                                     (8, 4096, 14336), mesh)
        assert spec == P(None, "data", "model")

    def test_batch_axes_multi_pod(self):
        mesh = FakeMesh(pod=2, data=16, model=16)
        assert rules.batch_axes(mesh) == ("pod", "data")
        spec = rules.logical_to_spec(("batch", None), (256, 4096), mesh)
        assert spec == P(("pod", "data"), None)

    def test_layers_never_sharded(self):
        mesh = FakeMesh(data=16, model=16)
        spec = rules.logical_to_spec(("layers", "embed", "ff"),
                                     (32, 4096, 14336), mesh)
        assert spec == P(None, "data", "model")


@pytest.mark.slow
def test_multi_device_lowering_subprocess():
    """End-to-end spec plumbing on 8 forced host devices (subprocess so the
    main test process keeps its single-device jax). The mesh comes from
    ``launch/mesh.py``, whose Auto axes let the compiler reshard where the
    rules put one mesh axis on two dims of an intermediate (the embedding
    gather of a batch-sharded token array)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs import get_config, INPUT_SHAPES, InputShape
        from repro.launch.dryrun import build_lowerable
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=4)
        cfg = get_config("qwen3-4b", "smoke")
        shape = InputShape("t", 64, 8, "train")
        fn, args = build_lowerable(cfg, shape, mesh)
        with jax.sharding.set_mesh(mesh):
            compiled = jax.jit(fn).lower(*args).compile()
        print("OK", compiled.cost_analysis()["flops"] > 0)
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "OK True" in out.stdout, out.stderr[-2000:]


def test_collective_bytes_parser():
    from repro.roofline.analysis import collective_bytes
    text = """
  %all-gather = f32[512,128]{1,0} all-gather(%p), replica_groups=[4,4]<=[4,4]T(1,0), dimensions={0}
  %all-reduce = f32[128,512]{1,0} all-reduce(%d), replica_groups=[4,4]<=[4,4]T(1,0), to_apply=%add
  %reduce-scatter = bf16[32,16]{1,0} reduce-scatter(%q), replica_groups=[2,8]<=[16]
  %cp = f32[64]{0} collective-permute(%x), source_target_pairs={{0,1}}
  %other = f32[9] add(%a, %b)
"""
    got = collective_bytes(text)
    assert got["all-gather"] == 512 * 128 * 4 // 4
    assert got["all-reduce"] == 128 * 512 * 4
    assert got["reduce-scatter"] == 32 * 16 * 2 * 8
    assert got["collective-permute"] == 64 * 4
    assert got["all-to-all"] == 0


def test_roofline_terms():
    from repro.configs import INPUT_SHAPES
    from repro.roofline.analysis import RooflineRecord
    rec = RooflineRecord(arch="x", shape="train_4k", mesh="single", chips=256,
                         flops=197e12, hbm_bytes=819e9, coll_bytes={"all-reduce": 50e9},
                         model_flops=197e12 * 256)
    assert abs(rec.compute_s - 1.0) < 1e-9
    assert abs(rec.memory_s - 1.0) < 1e-9
    assert abs(rec.collective_s - 1.0) < 1e-9
    assert rec.useful_flops_ratio == 1.0
    assert rec.dominant in ("compute", "memory", "collective")


@pytest.mark.slow
def test_hlo_cost_loop_correction_subprocess():
    """Loop-aware analyzer: scanned and unrolled lowerings of the same model
    must report (near-)identical FLOPs, while XLA's cost_analysis undercounts
    the scanned one."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from repro import configs
        from repro.train.step import make_train_step, init_state
        from repro.optim.adam import Adam
        from repro.roofline.hlo_cost import analyze_text

        def measure(scan):
            cfg = dataclasses.replace(configs.get_config("qwen3-4b", "smoke"),
                                      scan_layers=scan, remat=True)
            opt = Adam(lr=1e-3)
            state = init_state(jax.random.key(0), cfg, opt)
            batch = {"tokens": jnp.zeros((4, 64), jnp.int32)}
            comp = jax.jit(make_train_step(cfg, opt)).lower(state, batch).compile()
            return analyze_text(comp.as_text())["flops"]

        a, b = measure(True), measure(False)
        assert abs(a / b - 1.0) < 0.05, (a, b)
        print("HLO-COST-OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "HLO-COST-OK" in out.stdout, out.stderr[-2000:]

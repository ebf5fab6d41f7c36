"""Checkpointing, serving engine, data pipeline, gossip semantics."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import io as ckpt
from repro.data.lm_data import memory_stub, token_batches
from repro.models import transformer
from repro.optim.adam import Adam
from repro.serve.engine import ServeEngine
from repro.train.step import init_state


class TestCheckpoint:
    def test_roundtrip_params(self, tmp_path):
        cfg = configs.get_config("xlstm-125m", "smoke")
        params = transformer.init_model(jax.random.key(0), cfg)
        path = tmp_path / "ckpt.npz"
        ckpt.save(path, params)
        zeros = jax.tree.map(jnp.zeros_like, params)
        restored = ckpt.restore(path, zeros)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_roundtrip_train_state(self, tmp_path):
        cfg = configs.get_config("qwen3-4b", "smoke")
        state = init_state(jax.random.key(0), cfg, Adam(lr=1e-3))
        path = tmp_path / "state.npz"
        ckpt.save(path, state)
        restored = ckpt.restore(path, jax.tree.map(jnp.zeros_like, state))
        np.testing.assert_array_equal(np.asarray(restored.step),
                                      np.asarray(state.step))

    def test_shape_mismatch_raises(self, tmp_path):
        ckpt.save(tmp_path / "x.npz", {"a": jnp.ones((3,))})
        with pytest.raises(ValueError):
            ckpt.restore(tmp_path / "x.npz", {"a": jnp.ones((4,))})

    def test_missing_leaf_raises(self, tmp_path):
        ckpt.save(tmp_path / "x.npz", {"a": jnp.ones((3,))})
        with pytest.raises(KeyError):
            ckpt.restore(tmp_path / "x.npz", {"b": jnp.ones((3,))})


class TestServeEngine:
    @pytest.mark.parametrize("arch", ["qwen3-4b", "xlstm-125m", "hymba-1.5b"])
    def test_generate_shapes(self, arch):
        cfg = configs.get_config(arch, "smoke")
        params = transformer.init_model(jax.random.key(0), cfg)
        eng = ServeEngine(cfg, params, max_len=64)
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(2, 16)).astype(np.int32)
        out = eng.generate(prompts, steps=8)
        assert out.shape == (2, 8)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()

    def test_greedy_deterministic(self):
        cfg = configs.get_config("qwen3-4b", "smoke")
        params = transformer.init_model(jax.random.key(0), cfg)
        eng = ServeEngine(cfg, params, max_len=48)
        prompts = np.random.default_rng(1).integers(
            0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
        a = eng.generate(prompts, steps=6)
        b = eng.generate(prompts, steps=6)
        np.testing.assert_array_equal(a, b)

    def test_memory_archs_serve(self):
        cfg = configs.get_config("whisper-medium", "smoke")
        params = transformer.init_model(jax.random.key(0), cfg)
        eng = ServeEngine(cfg, params, max_len=48)
        prompts = np.zeros((2, 4), np.int32)
        mem = memory_stub(cfg, 2)
        out = eng.generate(prompts, steps=4, memory=mem)
        assert out.shape == (2, 4)


class TestData:
    def test_token_batches_shapes_and_range(self):
        cfg = configs.get_config("qwen3-4b", "smoke")
        it = token_batches(cfg, batch=3, seq_len=17)
        b = next(it)
        assert b["tokens"].shape == (3, 17)
        assert b["tokens"].min() >= 0 and b["tokens"].max() < cfg.vocab_size

    def test_memory_stub_only_for_modal_archs(self):
        assert memory_stub(configs.get_config("qwen3-4b", "smoke"), 2) is None
        m = memory_stub(configs.get_config("whisper-medium", "smoke"), 2)
        assert m.shape == (2, 32, 128)
        v = memory_stub(configs.get_config("llama-3.2-vision-11b", "smoke"), 2)
        assert v.shape == (2, 16, 128)


@pytest.mark.slow
def test_gossip_preserves_mean_subprocess():
    """ring_gossip is doubly-stochastic: the pod-average of parameters is
    invariant (the SpreadFGL convergence argument relies on this)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import gossip
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(pod=8, data=1, model=1)
        x = jnp.arange(8.0 * 5).reshape(8, 5)

        def f(blk):
            out = gossip.ring_gossip({"w": blk[0]}, "pod")
            return out["w"][None]

        y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"),),
                                  out_specs=P("pod"), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(y.mean(0)), np.asarray(x.mean(0)),
                                   rtol=1e-6)
        # each row is the average of itself and its ring neighbors
        for i in range(8):
            expect = (x[i] + x[(i-1) % 8] + x[(i+1) % 8]) / 3.0
            np.testing.assert_allclose(np.asarray(y[i]), np.asarray(expect),
                                       rtol=1e-6)
        print("GOSSIP-OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "GOSSIP-OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_location_subprocess(tmp_path, env_dir):
    """Entry points cache compiles in $JAX_COMPILATION_CACHE_DIR when set,
    else in the checkout's fixed ``.jax_cache``. A subprocess, so this test
    process never turns the cache on; nothing is compiled."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import jax
        from repro.launch import compile_cache
        print("DIR", compile_cache.enable(), jax.config.jax_compilation_cache_dir)
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=root)
    assert f"DIR {want} {want}" in out.stdout, out.stderr[-2000:]

"""Compile-only guards: the main path's Pallas kernels at real widths.

Each test compiles a kernel for one chip of a described (not attached) TPU
v5e and asserts the program holds the Mosaic kernel (``tpu_custom_call``).
Nothing runs, so this proves only that the chip's compiler accepts the
kernel at that shape: block alignment, VMEM limits, the lane-axis
concatenate of ``topk_merge``. Interpret-mode tests cannot see any of that.

Shapes are those of ``chip_smoke.py`` (client splits of the synthetic
Table-I graphs at full size, label-propagation partition, 12 aug slots):

- ``sim_topk``: one edge server's fused candidates, n = M_per * n_pad.
  Cora with N=3, M=6 gives n_pad = 914, n = 1828, c = 7; N=4, M=8 gives
  n_pad = 689, n = 1378; CoauthorCS with N=3, M=6 gives n_pad = 6123,
  n = 12246, c = 15. n = 300 is a graph small enough that ``ops`` shrinks
  the column block below 512 and off the 128-lane grid.
- ``sage_aggregate``: forward and gradient at the input width and the hidden
  width (64), with the tiles ``ops.sage_tiles`` picks for a v5e: one Cora
  client, adj [914, 914] at 1,433 features; and CoauthorCS's six clients,
  adj [6, 6123, 6123] at 6,805 features, vmapped as ``FGLTrainer`` runs
  them. That proves the chip's compiler accepts the tiles within the VMEM
  the kernel asks for.
- the imputation program (``FGLTrainer._impute``, module ``jit__impute``)
  as ``fgl_train --impl pallas`` builds it for the benchmark's configuration
  ``cora-sage-n3m6`` (``benchmarks/chip/configs/``) with one local step and
  an imputation round a round: Cora, N=3, M=6, n_pad 914, 12 aug slots,
  k 4. It holds both kernels: ``sage_aggregate`` for the embeddings,
  ``sim_topk`` for the links.
- the round's programs of that configuration, counted for ``sage_aggregate``
  calls: layer 1's aggregate is the batch's cache (``ClientBatch.prop``), so
  the local and evaluation programs run the kernel once (layer 2), and the
  imputation program twice (layer 2 of its embeddings, and layer 1 of the
  patched graphs as it fills the cache anew).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip can be written to the persistent cache
    # but not read back without one; keep these tests out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,c", [
    (1828, 7),     # Cora, N=3, M=6
    (1378, 7),     # Cora, N=4, M=8 (the four-chip run's single-chip side)
    (12246, 15),   # CoauthorCS, N=3, M=6
    (300, 7),      # small graph: column block of 300 lanes
], ids=["cora", "cora_n4", "coauthor_cs", "small"])
def test_sim_topk_compiles(one_chip, n, c):
    # block_m=256 is what imputation.similarity_topk passes.
    fn = jax.jit(lambda h, cid, mask: ops.sim_topk(h, cid, mask, 4,
                                                   block_m=256))
    compiled = fn.lower(_spec((n, c), jnp.float32, one_chip),
                        _spec((n,), jnp.int32, one_chip),
                        _spec((n,), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("n,d,clients", [
    (914, 1433, None),   # one Cora client
    (914, 64, None),
    (6123, 6805, 6),     # CoauthorCS, six clients
    (6123, 64, 6),
], ids=["input_width", "hidden_width", "coauthor_cs_input_width",
        "coauthor_cs_hidden_width"])
def test_sage_aggregate_forward_and_grad_compile(one_chip, n, d, clients):
    # A test process sees the CPU, so pass the v5e's choice explicitly.
    bm, bn, bk = ops.sage_tiles(n, d, "TPU v5 lite")

    def agg(adj, h):
        return ops.sage_aggregate(adj, h, block_m=bm, block_n=bn, block_k=bk)

    def loss(adj, h, g):
        out = agg(adj, h) if clients is None else jax.vmap(agg)(adj, h)
        return jnp.sum(out * g)

    lead = () if clients is None else (clients,)
    fn = jax.jit(jax.value_and_grad(loss, argnums=1))
    compiled = fn.lower(_spec(lead + (n, n), jnp.float32, one_chip),
                        _spec(lead + (n, d), jnp.float32, one_chip),
                        _spec(lead + (n, d), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.fixture()
def cora_n3m6(one_chip, monkeypatch):
    """The trainer ``fgl_train --impl pallas`` builds for ``cora-sage-n3m6``
    with ``impute-k1``, and its initial state as shapes on the v5e."""
    from repro.launch import fgl_train
    # The process sees the CPU: give it the v5e's sage_aggregate tiles.
    monkeypatch.setitem(ops._SAGE_CAPS, jax.devices()[0].device_kind,
                        ops._SAGE_CAPS["TPU v5 lite"])
    args = fgl_train.parse_args([
        "--dataset", "cora", "--servers", "3", "--clients", "6", "--scale", "1.0",
        "--local-rounds", "1", "--imputation-interval", "1", "--top-k", "4",
        "--impl", "pallas"])
    tr, batch = fgl_train.build(args)
    assert (tr.n_servers, tr.m, batch.n_pad, batch.aug_max) == (3, 6, 914, 12)
    shapes = jax.eval_shape(tr.init, jax.random.key(0), batch)
    return tr, jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), shapes)


def _kernel_calls(text):
    return [line for line in text.splitlines() if "tpu_custom_call" in line]


def test_imputation_program_compiles(cora_n3m6):
    tr, state = cora_n3m6
    text = tr._impute_fn.lower(state).compile().as_text()
    assert text.startswith("HloModule jit__impute")
    kernels = _kernel_calls(text)
    assert any("sim_topk" in line for line in kernels)
    assert any("sage_aggregate" in line for line in kernels)


@pytest.mark.parametrize("program,module,calls", [
    ("_local_fn", "jit__local_rounds", 1),
    ("_eval_fn", "jit__evaluate", 1),
    ("_impute_fn", "jit__impute", 2),
])
def test_the_rounds_read_layer_1_from_the_batch_cache(cora_n3m6, program, module,
                                                       calls):
    tr, state = cora_n3m6
    assert state.batch.prop is not None
    args = {"_local_fn": (state.params, state.opt_state, state.batch),
            "_eval_fn": (state.params, state.batch),
            "_impute_fn": (state,)}[program]
    text = getattr(tr, program).lower(*args).compile().as_text()
    assert text.startswith(f"HloModule {module}")
    sage = [line for line in _kernel_calls(text) if "sage" in line]
    assert len(sage) == calls, sage

"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (assert_allclose)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import sage_aggregate as sage_kernel

KEY = jax.random.key(42)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,s,d,window", [
    (1, 2, 2, 128, 64, None),
    (2, 4, 2, 256, 64, None),      # GQA 2:1
    (1, 8, 1, 128, 128, None),     # MQA
    (2, 4, 4, 200, 64, 64),        # ragged seq + sliding window
    (1, 2, 2, 384, 32, 128),
])
def test_flash_attention_matches_oracle(b, hq, hkv, s, d, window, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, s + d + hq), 3)
    q = _rand(ks[0], (b, hq, s, d), dtype)
    k = _rand(ks[1], (b, hkv, s, d), dtype)
    v = _rand(ks[2], (b, hkv, s, d), dtype)
    out = ops.mha(q, k, v, causal=True, window=window, interpret=True)
    kk = jnp.repeat(k, hq // hkv, axis=1)
    vv = jnp.repeat(v, hq // hkv, axis=1)
    expect = ref.flash_attention(q, kk, vv, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_first_row_attends_self_only():
    q = _rand(KEY, (1, 1, 128, 32), jnp.float32)
    k = _rand(jax.random.fold_in(KEY, 1), (1, 1, 128, 32), jnp.float32)
    v = _rand(jax.random.fold_in(KEY, 2), (1, 1, 128, 32), jnp.float32)
    out = ops.mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,clients", [
    (64, 32, None), (100, 70, None), (256, 128, None), (300, 129, None),
    (37, 5, None),
    (1100, 1700, None),   # tiles (384, 256, 384) over a (3, 7, 3) grid
    (600, 300, 3),        # vmapped over clients, as FGLTrainer runs it
], ids=["64-32", "100-70", "256-128", "300-129", "37-5", "1100-1700",
        "600-300-x3"])
def test_sage_aggregate_matches_oracle(n, d, clients, dtype):
    lead = () if clients is None else (clients,)
    a = (jax.random.uniform(jax.random.fold_in(KEY, n), lead + (n, n)) < 0.15
         ).astype(dtype)
    h = _rand(jax.random.fold_in(KEY, n + d), lead + (n, d), dtype)
    agg = functools.partial(ops.sage_aggregate, interpret=True)
    if clients is None:
        out = agg(a, h)
        expect = ref.sage_aggregate(a, h)
    else:
        out = jax.vmap(agg)(a, h)
        expect = jax.vmap(ref.sage_aggregate)(a, h)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def _pallas_call(fn, *shapes):
    """The pallas_call equation in the jaxpr of ``fn`` at ``shapes`` (f32)."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", None)
                if inner is not None:
                    found = find(getattr(inner, "jaxpr", inner))
                    if found is not None:
                        return found
        return None
    specs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return find(jax.make_jaxpr(fn)(*specs).jaxpr)


def _pad128(size):
    return -(-size // 128) * 128


V5E_VMEM_BUDGET = 64 << 20   # half of a v5e core's 128 MiB of VMEM


@pytest.mark.parametrize("kind", ["TPU v5 lite", None], ids=["v5e", "default"])
@pytest.mark.parametrize("n,d", [(914, 1433), (914, 64), (6123, 6805),
                                 (6123, 64), (689, 1433), (300, 1433)],
                         ids=["cora", "cora_hidden", "coauthor_cs",
                              "coauthor_cs_hidden", "cora_n4", "small"])
def test_sage_tiles_divide_the_padded_shape(n, d, kind):
    """Tiles are multiples of 128 that divide the 128-padded sizes, so the
    kernel runs on exactly the 128-padded operands, and its VMEM fits."""
    bm, bn, bk = tiles = ops.sage_tiles(n, d, kind)
    n_pad, d_pad = _pad128(n), _pad128(d)
    for tile, size in zip(tiles, (n_pad, d_pad, n_pad)):
        assert tile % 128 == 0 and size % tile == 0
    if kind is None:   # interpret mode takes the default choice itself
        fn = functools.partial(ops.sage_aggregate, interpret=True)
    else:
        fn = functools.partial(ops.sage_aggregate, block_m=bm, block_n=bn,
                               block_k=bk, interpret=True)
    eqn = _pallas_call(fn, (n, n), (n, d))
    assert [v.aval.shape for v in eqn.invars] == [(n_pad, n_pad), (n_pad, d_pad)]
    assert eqn.params["grid_mapping"].grid == (n_pad // bm, d_pad // bn,
                                               n_pad // bk)
    limit = sage_kernel.vmem_limit_bytes(*tiles)
    if kind is None:
        assert limit == sage_kernel.DEFAULT_SCOPED_VMEM
    else:
        assert limit <= V5E_VMEM_BUDGET
    vmem = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    assert vmem == limit


@pytest.mark.parametrize("blocks,grid", [
    ({"block_m": 128, "block_n": 128, "block_k": 128}, (8, 12, 8)),
    ({"block_n": 128}, (2, 12, 2)),
    ({"block_m": 256, "block_k": 1024}, (4, 3, 1)),
], ids=["all", "block_n", "block_m_k"])
def test_sage_aggregate_explicit_blocks_override(blocks, grid):
    """An explicit block is honoured; the others keep the chosen tiles."""
    eqn = _pallas_call(functools.partial(ops.sage_aggregate, interpret=True,
                                         **blocks), (914, 914), (914, 1433))
    assert ops.sage_tiles(914, 1433) == (512, 512, 512)
    assert eqn.params["grid_mapping"].grid == grid


def test_sage_aggregate_isolated_nodes_zero():
    """Zero-degree rows must output zeros (degree clamp, not NaN)."""
    n, d = 64, 16
    a = jnp.zeros((n, n), jnp.float32)
    h = _rand(KEY, (n, d), jnp.float32)
    out = ops.sage_aggregate(a, h, interpret=True)
    assert np.all(np.asarray(out) == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,n,c", [(64, 300, 7), (128, 1024, 15), (10, 33, 6),
                                   (256, 512, 10)])
def test_sim_block_matches_oracle(b, n, c, dtype):
    rows = _rand(jax.random.fold_in(KEY, b), (b, c), dtype)
    h = _rand(jax.random.fold_in(KEY, b + n), (n, c), dtype)
    out = ops.sim_block(rows, h, interpret=True)
    expect = ref.sim_block(rows, h)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_sim_block_gram_symmetry():
    h = _rand(KEY, (96, 7), jnp.float32)
    gram = ops.sim_block(h, h, interpret=True)
    np.testing.assert_allclose(np.asarray(gram), np.asarray(gram).T,
                               atol=1e-5, rtol=1e-5)


def _sim_topk_oracle(h, cid, tmask, k):
    """Unfused ground truth: full masked gram + jax.lax.top_k."""
    gram = h.astype(jnp.float32) @ h.astype(jnp.float32).T
    gram = jnp.where(cid[:, None] == cid[None, :], -jnp.inf, gram)
    gram = jnp.where(tmask[None, :] > 0, gram, -jnp.inf)
    return jax.lax.top_k(gram, k)


@pytest.mark.parametrize("n,c,k,bm,bn", [
    (64, 5, 3, 16, 32),
    (128, 15, 5, 128, 512),     # block-multiple fast path
    (100, 7, 5, 32, 64),        # non-block-multiple n
    (37, 4, 3, 8, 16),          # tiny + non-multiple
])
def test_sim_topk_fused_matches_oracle(n, c, k, bm, bn):
    ks = jax.random.split(jax.random.fold_in(KEY, n + c), 2)
    h = _rand(ks[0], (n, c), jnp.float32)
    cid = (jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) // max(n // 4, 1)
           ).squeeze(-1)
    tmask = (jax.random.uniform(ks[1], (n,)) < 0.7).astype(jnp.float32)
    vals, idx = ops.sim_topk(h, cid, tmask, k, block_m=bm, block_n=bn,
                             interpret=True)
    ovals, oidx = _sim_topk_oracle(h, cid, tmask, k)
    fin = np.isfinite(np.asarray(ovals))
    np.testing.assert_allclose(np.asarray(vals)[fin], np.asarray(ovals)[fin],
                               atol=1e-5, rtol=1e-5)
    # idx only comparable where the score is real; the fused kernel keeps -1
    # on unfilled slots while top_k emits arbitrary indices there.
    np.testing.assert_array_equal(np.asarray(idx)[fin], np.asarray(oidx)[fin])
    assert np.all(np.isneginf(np.asarray(vals)[~fin]))
    assert np.all(np.asarray(idx)[~fin] == -1)


def test_sim_topk_fused_fully_masked_rows_keep_minus_one():
    n, c, k = 24, 4, 3
    h = _rand(KEY, (n, c), jnp.float32)
    cid = jnp.zeros((n,), jnp.int32)            # everything same client
    vals, idx = ops.sim_topk(h, cid, jnp.ones((n,)), k, block_m=8, block_n=8,
                             interpret=True)
    assert np.all(np.asarray(idx) == -1)
    assert np.all(np.isneginf(np.asarray(vals)))


def test_sim_topk_fused_unfilled_slots_stay_minus_one_across_tiles():
    """One valid candidate, k=3, several column tiles: the merge must not
    resurrect stale indices for exhausted slots in later tiles."""
    n, c, k = 32, 4, 3
    h = _rand(KEY, (n, c), jnp.float32)
    cid = jnp.zeros((n,), jnp.int32).at[5].set(1)   # node 5 is the only target
    vals, idx = ops.sim_topk(h, cid, jnp.ones((n,)), k, block_m=8, block_n=16,
                             interpret=True)
    idx_np, vals_np = np.asarray(idx), np.asarray(vals)
    assert np.all(idx_np[:5, 0] == 5) and np.all(idx_np[6:, 0] == 5)
    assert np.all(idx_np[:, 1:][np.isneginf(vals_np[:, 1:])] == -1)
    assert np.all(vals_np[:5, 1:] == -np.inf)


def test_sim_topk_fused_under_vmap():
    """The [N] server axis: vmapped fused kernel == per-slice calls."""
    n_srv, n, c, k = 3, 40, 5, 4
    h = _rand(KEY, (n_srv, n, c), jnp.float32)
    cid = jnp.repeat(jnp.arange(2, dtype=jnp.int32), n // 2)
    tmask = jnp.ones((n,))
    f = jax.vmap(lambda hj: ops.sim_topk(hj, cid, tmask, k, block_m=8,
                                         block_n=16, interpret=True))
    vals, idx = f(h)
    for j in range(n_srv):
        v_j, i_j = ops.sim_topk(h[j], cid, tmask, k, block_m=8, block_n=16,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(vals[j]), np.asarray(v_j),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(idx[j]), np.asarray(i_j))


class TestKernelPipelineIntegration:
    """Kernels swapped into the real FGL pipeline (interpret mode)."""

    def test_sage_kernel_in_classifier(self):
        from repro.core import gnn
        key = jax.random.key(0)
        n, d, c = 40, 12, 5
        params = gnn.init_classifier(key, "sage", [d, 16, c])
        x = jax.random.normal(key, (n, d))
        adj = (jax.random.uniform(jax.random.fold_in(key, 1), (n, n)) < 0.2
               ).astype(jnp.float32)
        adj = jnp.maximum(adj, adj.T)
        mask = jnp.ones((n,))
        ref_out = gnn.apply_classifier(params, "sage", x, adj, mask,
                                       impl="reference")
        pls_out = gnn.apply_classifier(params, "sage", x, adj, mask,
                                       impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(ref_out), np.asarray(pls_out),
                                   atol=1e-4, rtol=1e-4)

    def test_sim_kernel_in_imputation(self):
        from repro.core import imputation
        key = jax.random.key(0)
        c = 5
        h = jax.nn.softmax(jax.random.normal(key, (64, c)), -1)
        fm = jnp.ones((64,))
        cid = imputation.client_of_flat(4, 16)
        s1, i1 = imputation.similarity_topk(h, fm, cid, 3,
                                            kernel_impl="reference", block=32)
        s2, i2 = imputation.similarity_topk(h, fm, cid, 3,
                                            kernel_impl="pallas_interpret",
                                            block=32)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    def test_chunked_attention_matches_reference(self):
        from repro.models.attention import _sdpa, _sdpa_chunked
        key = jax.random.key(0)
        for (b, h, s, d, w) in [(1, 2, 256, 32, 0), (2, 4, 128, 16, 48)]:
            q = jax.random.normal(key, (b, h, s, d))
            k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, d))
            v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, d))
            a = _sdpa(q, k, v, causal=True, window=w)
            c = _sdpa_chunked(q, k, v, causal=True, window=w, chunk=64)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=1e-5, rtol=1e-5)

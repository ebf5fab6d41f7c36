"""Candidate-sharded ring top-k (core/ring_topk.py).

The contract under test, layer by layer:

- ``kernels.sim_topk.topk_merge`` is the ONE streaming merge shared by the
  Pallas kernel and the ring driver: it matches ``jax.lax.top_k`` including
  its smallest-index tie-break, and is invariant to the order candidate
  slabs are folded in — the invariant that makes rotation-order-independent
  sharding possible at all.
- ``ring_similarity_topk`` on a size-1 mesh is bit-identical to the
  ``"reference"`` path of ``imputation.similarity_topk``; real multi-device
  sharding (2/4/8 emulated devices, non-divisible n, fully-masked rows,
  k > valid candidates, tie-breaks) meets ``ring_topk.topk_violations``'s
  contract (scores within ``dot_tolerance``, differing indices only at
  near-ties) and, on every case tested, returns the reference's indices
  exactly, duplicated-row ties included; it runs in a subprocess so the
  device count can be forced before jax initializes.
- ``topk_violations`` itself accepts a near-tie swap and rejects a wrong
  candidate.
- The engine's sharded layout (``SpreadImputation(sim_mesh=...)``: vmap the
  generator half, one batched ring call outside) produces the same link
  proposals and fixed batch as the default in-vmap layout.
- Regression for the reference path: no [n, n]-shaped intermediate in its
  jaxpr (the same-client mask used to be materialized full-size).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import imputation
from repro.core.ring_topk import (allgather_bytes, dot_tolerance,
                                  ring_rotation_bytes, ring_similarity_topk,
                                  ring_total_bytes, sim_topk_flops,
                                  topk_violations)
from repro.core.spreadfgl import make_spreadfgl
from repro.core.partition import partition_graph
from repro.core.types import FGLConfig
from repro.data.synthetic_graphs import DATASETS, make_sbm_graph
from repro.kernels.sim_topk import topk_merge


class _Mesh1:
    """Degenerate stand-in: size-1 mesh without touching device state."""
    size = 1


def _rand_case(rng, n, c, n_clients=3, mask_p=0.5):
    h = jnp.asarray(rng.standard_normal((n, c)), jnp.float32)
    cid = jnp.asarray(rng.integers(0, n_clients, n), jnp.int32)
    mask = jnp.asarray((rng.random(n) < mask_p), jnp.float32)
    return h, cid, mask


class TestTopkMerge:
    def test_matches_lax_topk_single_fold(self):
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.standard_normal((6, 17)), jnp.float32)
        k = 5
        run_v = jnp.full((6, k), -jnp.inf, jnp.float32)
        run_i = jnp.full((6, k), -1, jnp.int32)
        idx = jnp.broadcast_to(jnp.arange(17, dtype=jnp.int32), vals.shape)
        got_v, got_i = topk_merge(run_v, run_i, vals, idx)
        exp_v, exp_i = jax.lax.top_k(vals, k)
        np.testing.assert_array_equal(np.asarray(got_v), np.asarray(exp_v))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(exp_i))

    def test_ties_resolve_to_smallest_index(self):
        # Three identical maxima at global indices 2, 9, 11: lax.top_k
        # returns them ascending; so must the merge.
        vals = jnp.zeros((1, 12), jnp.float32).at[0, jnp.array([2, 9, 11])].set(5.0)
        idx = jnp.arange(12, dtype=jnp.int32)[None, :]
        run_v = jnp.full((1, 3), -jnp.inf, jnp.float32)
        run_i = jnp.full((1, 3), -1, jnp.int32)
        _, got_i = topk_merge(run_v, run_i, vals, idx)
        np.testing.assert_array_equal(np.asarray(got_i), [[2, 9, 11]])

    @pytest.mark.parametrize("perm_seed", [0, 1, 2])
    def test_fold_order_invariance(self, perm_seed):
        """Folding slabs in ANY order gives the same result — with ties."""
        rng = np.random.default_rng(3)
        n, k, slabs = 48, 4, 4
        vals = rng.standard_normal((5, n)).astype(np.float32)
        vals[:, ::7] = 1.5                     # planted ties across slabs
        chunks = np.split(vals, slabs, axis=1)
        offsets = [i * (n // slabs) for i in range(slabs)]
        order = np.random.default_rng(perm_seed).permutation(slabs)

        def fold(sequence):
            rv = jnp.full((5, k), -jnp.inf, jnp.float32)
            ri = jnp.full((5, k), -1, jnp.int32)
            for s in sequence:
                idx = offsets[s] + jnp.arange(n // slabs, dtype=jnp.int32)
                rv, ri = topk_merge(rv, ri, jnp.asarray(chunks[s]),
                                    jnp.broadcast_to(idx, chunks[s].shape))
            return rv, ri

        v_seq, i_seq = fold(range(slabs))
        v_perm, i_perm = fold(order)
        np.testing.assert_array_equal(np.asarray(i_perm), np.asarray(i_seq))
        np.testing.assert_array_equal(np.asarray(v_perm), np.asarray(v_seq))
        exp_v, exp_i = jax.lax.top_k(jnp.asarray(vals), k)
        np.testing.assert_array_equal(np.asarray(i_seq), np.asarray(exp_i))
        np.testing.assert_array_equal(np.asarray(v_seq), np.asarray(exp_v))

    def test_underfilled_rows_keep_sentinels(self):
        vals = jnp.full((1, 6), -jnp.inf, jnp.float32).at[0, 4].set(1.0)
        idx = jnp.arange(6, dtype=jnp.int32)[None, :]
        rv = jnp.full((1, 3), -jnp.inf, jnp.float32)
        ri = jnp.full((1, 3), -1, jnp.int32)
        got_v, got_i = topk_merge(rv, ri, vals, idx)
        np.testing.assert_array_equal(np.asarray(got_i), [[4, -1, -1]])
        assert np.asarray(got_v)[0, 0] == 1.0
        assert np.isneginf(np.asarray(got_v)[0, 1:]).all()


class TestRingDriverSingleDevice:
    @pytest.mark.parametrize("n,k", [(64, 3), (37, 4), (10, 12)])
    def test_size1_matches_reference(self, n, k):
        rng = np.random.default_rng(n)
        h, cid, mask = _rand_case(rng, n, 5)
        kk = min(k, n)
        exp_s, exp_i = imputation.similarity_topk(
            h, jnp.ones(n), cid, kk, target_mask=mask)
        got_s, got_i = imputation.similarity_topk(
            h, jnp.ones(n), cid, kk, target_mask=mask, mesh=_Mesh1())
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(exp_i))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(exp_s))

    def test_batched_equals_per_element(self):
        rng = np.random.default_rng(7)
        hb = jnp.asarray(rng.standard_normal((3, 21, 4)), jnp.float32)
        cb = jnp.asarray(rng.integers(0, 3, (3, 21)), jnp.int32)
        mb = jnp.asarray(rng.integers(0, 2, (3, 21)), jnp.float32)
        vb, ib = ring_similarity_topk(hb, cb, mb, 4, mesh=_Mesh1())
        for b in range(3):
            v1, i1 = ring_similarity_topk(hb[b], cb[b], mb[b], 4, mesh=_Mesh1())
            np.testing.assert_array_equal(np.asarray(ib[b]), np.asarray(i1))
            np.testing.assert_array_equal(np.asarray(vb[b]), np.asarray(v1))

    def test_fully_masked_rows_emit_sentinels(self):
        rng = np.random.default_rng(9)
        h, cid, _ = _rand_case(rng, 30, 5)
        s, i = imputation.similarity_topk(h, jnp.ones(30), cid, 3,
                                          target_mask=jnp.zeros(30),
                                          mesh=_Mesh1())
        assert (np.asarray(i) == -1).all()
        assert (np.asarray(s) == 0.0).all()


class TestReferencePathMemory:
    def test_no_full_nn_intermediate_in_jaxpr(self):
        """The reference path must never build an [n, n] array — neither the
        gram matrix nor (the regression) the same-client mask."""
        n, c, block = 300, 5, 64
        h = jnp.zeros((n, c), jnp.float32)
        ones = jnp.ones(n, jnp.float32)
        cid = jnp.zeros(n, jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda h_, m_, c_: imputation.similarity_topk(
                h_, m_, c_, 4, kernel_impl="reference", block=block)
        )(h, ones, cid)

        offending = []

        def subjaxprs(v):
            if hasattr(v, "jaxpr"):                 # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):                # bare Jaxpr
                yield v
            elif isinstance(v, (list, tuple)):
                for item in v:
                    yield from subjaxprs(item)

        def walk(jp):
            for eqn in jp.eqns:
                for var in eqn.outvars:
                    shape = getattr(getattr(var, "aval", None), "shape", ())
                    if len(shape) >= 2 and tuple(shape[-2:]) == (n, n):
                        offending.append((eqn.primitive.name, shape))
                for v in eqn.params.values():
                    for sub in subjaxprs(v):
                        walk(sub)

        walk(jaxpr.jaxpr)
        assert not offending, f"[n, n] intermediates found: {offending}"


class TestEngineShardedLayout:
    @pytest.fixture(scope="class")
    def small(self):
        g = make_sbm_graph(DATASETS["cora"], scale=0.10, seed=1,
                           feature_noise=3.0, signal_ratio=0.5)
        batch, _ = partition_graph(g, 4, aug_max=8, seed=0, label_ratio=0.3)
        cfg = FGLConfig(hidden_dim=16, local_rounds=2, imputation_interval=1,
                        top_k_links=3, aug_max=8)
        return batch, cfg

    def test_sim_mesh_layout_matches_default(self, small):
        """vmap-the-generator + one batched ring call == all-in-vmap, down
        to the fixed batch (size-1 mesh here; multi-device in subprocess)."""
        from jax.sharding import Mesh
        batch, cfg = small
        mesh = Mesh(np.array(jax.devices()[:1]), ("sim",))
        tr_ref = make_spreadfgl(cfg, batch, num_servers=2)
        tr_sh = make_spreadfgl(cfg, batch, num_servers=2, sim_mesh=mesh)
        state = tr_ref.init(jax.random.key(0), batch)
        (_, _, _, _, s_r, i_r, x_r), _ = tr_ref.imputation.server_outputs(
            tr_ref, state)
        (_, _, _, _, s_s, i_s, x_s), _ = tr_sh.imputation.server_outputs(
            tr_sh, state)
        np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_r))
        np.testing.assert_array_equal(np.asarray(s_s), np.asarray(s_r))
        np.testing.assert_array_equal(np.asarray(x_s), np.asarray(x_r))
        out_r, links_r = tr_ref._impute_fn(state)
        out_s, links_s = tr_sh._impute_fn(state)
        assert int(links_s) == int(links_r)
        for name in ("x", "adj", "node_mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out_s.batch, name)),
                np.asarray(getattr(out_r.batch, name)),
                err_msg=f"fixed batch .{name} diverged")


    def test_search_inputs_are_the_rounds(self, small):
        """``SpreadImputation.search_inputs`` rebuilds exactly what the
        round searches: searching them gives the round's link proposals."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        (_, _, _, _, s_r, i_r, _), _ = tr.imputation.server_outputs(tr, state)
        h, fmask, cid, tmask = tr.imputation.search_inputs(tr, state)
        assert h.shape == (2, tr.m_per * batch.n_pad, h.shape[-1])
        s, i = jax.vmap(lambda *a: imputation.similarity_topk(
            a[0], a[1], a[2], cfg.top_k_links, target_mask=a[3]))(
                h, fmask, cid, tmask)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_r))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_r))


class TestTrafficModel:
    def test_rotation_bytes_and_flops(self):
        n, c, size = 1024, 32, 4
        per_rot = ring_rotation_bytes(n, c, size)
        assert per_rot == 256 * (32 * 4 + 8)
        assert ring_total_bytes(n, c, size) == 3 * per_rot
        assert ring_rotation_bytes(n, c, 1) == 0.0
        assert sim_topk_flops(10, n, c) == 2.0 * 10 * n * c
        # Ring total matches the ring all-gather volume for divisible n.
        assert ring_total_bytes(n, c, size) == allgather_bytes(n, c, size)


class TestAgreementContract:
    def _case(self):
        rng = np.random.default_rng(5)
        h, cid, mask = _rand_case(rng, 40, 4, mask_p=0.8)
        s, i = imputation.similarity_topk(h, jnp.ones(40), cid, 3,
                                          target_mask=mask)
        return (np.asarray(h), np.asarray(cid), np.asarray(mask),
                np.asarray(s), np.asarray(i))

    def test_reference_agrees_with_itself(self):
        h, cid, mask, s, i = self._case()
        got = topk_violations(h, cid, mask, s, i, s, i,
                              tol=dot_tolerance(h))
        assert got == {"max_score_diff": 0.0, "index_diffs": 0,
                       "violations": 0}

    def test_near_tie_swap_is_accepted_and_wrong_candidate_is_not(self):
        h, cid, mask, s, i = self._case()
        # Row 0's ranks 1 and 2 swapped, with their scores declared equal:
        # a tie resolved the other way is within the contract.
        i_swap, s_swap = i.copy(), s.copy()
        i_swap[0, [1, 2]] = i[0, [2, 1]]
        s_swap[0, 1] = s_swap[0, 2] = s[0, 1]
        exact = (h[0] @ h[i[0, 2]]).item()
        tol = abs(exact - s[0, 1]) + 1e-6
        ok = topk_violations(h, cid, mask, s_swap, i_swap, s_swap, i,
                             tol=tol)
        assert ok["violations"] == 0 and ok["index_diffs"] == 2
        # The same swap judged at a tolerance far below the score gap fails.
        bad = topk_violations(h, cid, mask, s_swap, i_swap, s_swap, i,
                              tol=dot_tolerance(h))
        assert bad["violations"] > 0
        # A same-client candidate is never acceptable.
        same = int(np.flatnonzero(cid == cid[0])[1])
        i_wrong = i.copy()
        i_wrong[0, 0] = same
        assert topk_violations(h, cid, mask, s, i_wrong, s, i,
                               tol=1e9)["violations"] > 0


@pytest.mark.slow
def test_ring_parity_on_emulated_devices_subprocess():
    """The agreement contract on REAL multi-device meshes: 2/4/8 emulated
    devices, non-divisible n, fully-masked rows, k > valid candidates, and
    duplicated rows (exact ties) across shard counts {1, 2, 4, 8}."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import imputation
        from repro.core.ring_topk import dot_tolerance, topk_violations

        rng = np.random.default_rng(0)
        cases = []
        for n in (64, 37, 11):                    # divisible / ragged / tiny
            h = jnp.asarray(rng.standard_normal((n, 6)), jnp.float32)
            cid = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
            mask = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
            cases.append((h, cid, mask, 4))
            cases.append((h, cid, jnp.zeros(n), 4))          # fully masked
            cases.append((h, cid, mask, min(n, 16)))         # k > valid cands
        # Tie case: duplicated feature rows => equal similarities.
        base = rng.standard_normal((6, 4)).astype(np.float32)
        h_tie = jnp.asarray(np.tile(base, (4, 1)))
        cid_tie = jnp.asarray(np.arange(24) % 2, jnp.int32)
        cases.append((h_tie, cid_tie, jnp.ones(24), 5))

        for h, cid, mask, k in cases:
            n = h.shape[0]
            exp_s, exp_i = imputation.similarity_topk(
                h, jnp.ones(n), cid, k, target_mask=mask)
            for nd in (1, 2, 4, 8):
                mesh = Mesh(np.array(jax.devices()[:nd]), ("sim",))
                # Jitted: run eagerly, shard_map compiles op by op (minutes).
                got_s, got_i = jax.jit(
                    lambda h_, c_, m_: imputation.similarity_topk(
                        h_, jnp.ones(n), c_, k, target_mask=m_, mesh=mesh)
                )(h, cid, mask)
                rep = topk_violations(h, cid, mask, got_s, got_i, exp_s,
                                      exp_i, tol=dot_tolerance(h))
                assert rep["violations"] == 0, (n, k, nd, rep)
                # Stronger than the contract, and true of every case here
                # (jax 0.9.0, CPU): the reference's indices, so the
                # cross-shard smallest-index tie-break holds exactly.
                assert rep["index_diffs"] == 0, (n, k, nd, rep)
        print("RING-TOPK-OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "RING-TOPK-OK" in out.stdout

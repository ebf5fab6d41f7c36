"""kernel_impl dispatch through the FGL hot loop.

The single ``FGLConfig.kernel_impl`` knob must (a) actually reach both hot
paths — classifier aggregation and the imputation round's fused similarity
top-k — and (b) be numerically interchangeable: one full SpreadFGL imputation
round under ``pallas_interpret`` matches ``reference`` on the raw link
proposals (scores, idx, x̄) and on the fixed batch, including shapes that are
not multiples of the kernel block sizes. Also pins the aug-slot target
bugfix: imputed (synthetic) nodes are never chosen as link targets.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import imputation, registry
from repro.core.fedgl import FGLTrainer
from repro.core.spreadfgl import make_fedgl, make_spreadfgl

# `small` comes from the session-scoped fixture in tests/conftest.py; its
# n_flat = M_per * n_pad is NOT a multiple of the kernel block sizes, which
# exercises the ops.py padding path in situ.


def _round_outputs(tr, state):
    """(scores, idx, x_bar) per server plus the fixed state, via the real
    strategy path (SpreadImputation.server_outputs + impute)."""
    (_, _, _, _, scores, idx, x_bar), _ = tr.imputation.server_outputs(tr, state)
    return scores, idx, x_bar, tr._impute_fn(state)[0]


class TestImputationRoundParity:
    @pytest.mark.parametrize("build,kw", [
        (make_spreadfgl, {"num_servers": 2}),   # n_flat = 2 * n_pad per server
        (make_fedgl, {}),                       # star: n_flat = 4 * n_pad
    ])
    def test_full_round_interpret_matches_reference(self, small, build, kw):
        batch, cfg = small
        tr_ref = build(cfg, batch, **kw)
        tr_pls = build(dataclasses.replace(cfg, kernel_impl="pallas_interpret"),
                       batch, **kw)
        state = tr_ref.init(jax.random.key(0), batch)
        s_ref, i_ref, x_ref, out_ref = _round_outputs(tr_ref, state)
        s_pls, i_pls, x_pls, out_pls = _round_outputs(tr_pls, state)

        np.testing.assert_allclose(np.asarray(s_pls), np.asarray(s_ref),
                                   atol=1e-4, err_msg="link scores diverged")
        np.testing.assert_array_equal(np.asarray(i_pls), np.asarray(i_ref),
                                      err_msg="link targets diverged")
        np.testing.assert_allclose(np.asarray(x_pls), np.asarray(x_ref),
                                   atol=1e-4, err_msg="imputed X̅ diverged")
        for name in ("x", "adj", "node_mask"):
            np.testing.assert_allclose(
                np.asarray(getattr(out_pls.batch, name), np.float32),
                np.asarray(getattr(out_ref.batch, name), np.float32),
                atol=1e-4, err_msg=f"fixed batch .{name} diverged")

    def test_second_round_parity_after_graph_fixing(self, small):
        """Parity survives a second round on the already-fixed batch."""
        batch, cfg = small
        tr_ref = make_spreadfgl(cfg, batch, num_servers=2)
        tr_pls = make_spreadfgl(
            dataclasses.replace(cfg, kernel_impl="pallas_interpret"),
            batch, num_servers=2)
        state, _ = tr_ref._impute_fn(tr_ref.init(jax.random.key(0), batch))
        _, i_ref, _, out_ref = _round_outputs(tr_ref, state)
        _, i_pls, _, out_pls = _round_outputs(tr_pls, state)
        np.testing.assert_array_equal(np.asarray(i_pls), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(out_pls.batch.x, np.float32),
                                   np.asarray(out_ref.batch.x, np.float32),
                                   atol=1e-4)


class TestAugSlotTargets:
    @pytest.mark.parametrize("kernel_impl", ["reference", "pallas_interpret"])
    def test_no_link_targets_aug_slots_across_rounds(self, small, kernel_impl):
        """Two consecutive fixing rounds never link to synthetic nodes.

        After round one the patcher sets node_mask=1 on the aug slots it
        filled; without the local-slot target restriction, round two's
        similarity top-k could select those synthetic nodes as cross-subgraph
        targets and re-impute features of imputed slots.
        """
        batch, cfg = small
        tr = make_spreadfgl(dataclasses.replace(cfg, kernel_impl=kernel_impl),
                            batch, num_servers=2)
        n_pad, n_local = batch.n_pad, batch.n_local_max
        state = tr.init(jax.random.key(0), batch)
        for rnd in range(2):
            (_, _, _, _, _, idx, _), _ = tr.imputation.server_outputs(tr, state)
            chosen = np.asarray(idx)
            chosen = chosen[chosen >= 0]        # server-local flat slots
            assert (chosen % n_pad < n_local).all(), \
                f"round {rnd}: aug slot chosen as link target"
            state, links = tr._impute_fn(state)
            # round 1 precondition: the patcher did fill aug slots
            assert float(jnp.sum(state.batch.node_mask[:, n_local:])) > 0
            assert int(links) == int(jnp.sum(state.batch.node_mask[:, n_local:] > 0))

    def test_aug_rows_do_not_source_links(self, small):
        """Aug-slot rows are invalid sources: their idx rows stay -1 after
        the patcher marked them real (flat_mask covers them, target_mask and
        fix_graphs' source filter keep them out)."""
        batch, cfg = small
        tr = make_spreadfgl(cfg, batch, num_servers=2)
        state, _ = tr._impute_fn(tr.init(jax.random.key(0), batch))
        emb = tr._embeddings(state.params, state.batch)
        _, flat_mask, _, tmask = imputation.search_inputs(
            emb[:tr.m_per], state.batch.node_mask[:tr.m_per], tr.n_local)
        assert float(jnp.sum(flat_mask) - jnp.sum(tmask)) > 0  # aug slots real


class TestKernelImplKnob:
    def test_unknown_impl_rejected_at_construction(self, small):
        batch, cfg = small
        with pytest.raises(ValueError, match="kernel_impl"):
            make_fedgl(dataclasses.replace(cfg, kernel_impl="triton"), batch)

    def test_registry_passes_kernel_impl(self, small):
        batch, cfg = small
        for name in ("FedGL", "local", "fedavg_fusion"):
            tr = registry.build(
                name, dataclasses.replace(cfg, kernel_impl="pallas_interpret"), batch)
            assert isinstance(tr, FGLTrainer)
            assert tr.kernel_impl == "pallas_interpret"

    def test_training_step_runs_under_interpret(self, small):
        """A full step() (local training + impute + aggregate + eval) runs
        end-to-end through the Pallas kernels in interpret mode."""
        batch, cfg = small
        tr = make_spreadfgl(
            dataclasses.replace(cfg, kernel_impl="pallas_interpret",
                                local_rounds=1),
            batch, num_servers=2)
        state = tr.init(jax.random.key(0), batch)
        state, m = tr.step(state)
        assert np.isfinite(float(m["loss"]))
        assert state.round == 1

"""The generator round alternates as Algorithm 1 lines 16-23 do.

``FGLTrainer._train_generator`` is checked against a plain Python loop of
the same lines on seeded random weights: each outer pass trains the
autoencoder for ``ae_iters`` Adam steps against the assessor as it stands at
the start of the pass, then the assessor for ``assessor_iters`` steps
against that pass's autoencoder. A program that trained every pass against
the first pass's counterparts agrees after one pass and not after two, so
the check runs three.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assessor as assessor_lib
from repro.core import imputation
from repro.core import strategies as S
from repro.core.fedgl import FGLTrainer
from repro.optim.adam import Adam

ROWS = 96
# Both sides run the same f32 operations in the same order at HIGHEST
# precision; only XLA's fusion of the jitted scans and the loop's op-by-op
# dispatch differ, by a few ulps a step. After 24 Adam steps every leaf and
# moment agreed within 4.5e-6 (CPU, jax 0.9.0, by ``worst_gap``), so the
# limit leaves 4x room; a counterpart left stale moves them by 1e-2 and more.
RTOL = 2e-5


def plain_generator(cfg, c, use_ns, use_assessor, key, ae, ae_opt, asr, as_opt,
                    h_real, flat_mask):
    """Algorithm 1 lines 16-23, one Python statement per step."""
    opt = Adam(lr=cfg.lr_generator)
    e = (h_real > cfg.theta(c)).astype(h_real.dtype) if use_ns else jnp.ones_like(h_real)
    _, ks = jax.random.split(key)
    s = imputation.sample_noise(ks, h_real.shape[0], c)

    def ae_loss(p, frozen_as):
        if use_assessor:
            return assessor_lib.autoencoder_loss(p, frozen_as, s, h_real, e, flat_mask)
        _, h_fake = imputation.reconstruct(p, s)
        rec = jnp.sum((h_real - h_fake) ** 2, -1)
        return jnp.sum(rec * flat_mask) / jnp.maximum(jnp.sum(flat_mask), 1.0)

    def as_loss(p, h_fake):
        if use_ns:
            return assessor_lib.assessor_loss(p, h_real, h_fake, e, flat_mask)
        return assessor_lib.assessor_loss_plain(p, h_real, h_fake, flat_mask)

    for _ in range(cfg.ae_outer_iters):
        frozen_as = asr
        for _ in range(cfg.ae_iters):
            ae, ae_opt = opt.update(jax.grad(ae_loss)(ae, frozen_as), ae_opt, ae)
        if use_assessor:
            _, h_fake = imputation.reconstruct(ae, s)
            for _ in range(cfg.assessor_iters):
                asr, as_opt = opt.update(jax.grad(as_loss)(asr, h_fake), as_opt, asr)
    return ae, ae_opt, asr, as_opt, s


def worst_gap(got, want):
    """Largest norm of a leaf's difference over the larger of that leaf's
    norm and the median leaf norm of ``want`` (a bias that has barely left
    zero is measured against the tree's scale, not its own)."""
    norm = lambda v: float(np.linalg.norm(np.asarray(v, np.float64)))
    diffs = [norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    sizes = [norm(b) for b in jax.tree.leaves(want)]
    med = float(np.median([v for v in sizes if v > 0] or [1.0]))
    return max(d / max(s, med) for d, s in zip(diffs, sizes))


@pytest.mark.parametrize("use_ns,use_assessor", [
    (True, True), (False, True), (True, False)],
    ids=["spreadfgl", "without-negative-sampling", "without-assessor"])
def test_generator_alternates_like_a_plain_loop(small, use_ns, use_assessor):
    batch, cfg = small
    cfg = dataclasses.replace(cfg, ae_outer_iters=3)
    tr = FGLTrainer(cfg, batch, topology=S.RingTopology(2),
                    imputation=S.SpreadImputation(),
                    use_negative_sampling=use_ns, use_assessor=use_assessor)
    c, d = tr.num_classes, tr.feature_dim
    k_ae, k_as, k_h, k_m, k_run = jax.random.split(jax.random.key(7), 5)
    ae = imputation.init_autoencoder(k_ae, c, d, cfg.ae_hidden)
    asr = assessor_lib.init_assessor(k_as, c, cfg.assessor_hidden)
    h_real = jax.nn.softmax(2.0 * jax.random.normal(k_h, (ROWS, c)), axis=-1)
    flat_mask = (jax.random.uniform(k_m, (ROWS,)) < 0.8).astype(jnp.float32)
    args = (k_run, ae, tr.gen_opt.init(ae), asr, tr.gen_opt.init(asr), h_real, flat_mask)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(tr._train_generator)(*args)
        want = plain_generator(cfg, c, use_ns, use_assessor, *args)
    names = ("ae", "ae_opt", "asr", "as_opt", "s_noise")
    for name, g, w in zip(names, got, want):
        assert worst_gap(g, w) < RTOL, name
    if use_assessor:   # the assessor trained, and against a trained autoencoder
        assert worst_gap(got[2], asr) > 1e-4

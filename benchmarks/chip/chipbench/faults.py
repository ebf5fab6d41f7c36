"""Faults planted under a run's timed path, to show the check catches them.

Each fault takes the built trainer and returns the step the run then drives
in place of ``FGLTrainer.step``; the program's compiled programs are reused.

- ``unchanged``: a step that returns its state unchanged (round advanced,
  metrics of the unchanged weights).
- ``half_batch``: local training sees half of each client's labelled nodes,
  the mean loss taken over the rest.
- ``altered``: the round's answer, the aggregated classifier weights,
  altered by 1% where the step produces them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def unchanged(trainer):
    def step(state):
        m = {"round": int(state.round), **trainer.evaluate(state)}
        return dataclasses.replace(state, round=int(state.round) + 1), m
    return step


def half_batch(trainer):
    local = trainer._local_fn

    def half(params, opt_state, batch):
        keep = (jnp.arange(batch.train_mask.shape[-1]) % 2 == 0).astype(jnp.float32)
        return local(params, opt_state, batch.replace(train_mask=batch.train_mask * keep))

    def step(state):
        trainer._local_fn = half
        try:
            return trainer.step(state)
        finally:
            trainer._local_fn = local
    return step


def altered(trainer):
    def step(state):
        state, m = trainer.step(state)
        return dataclasses.replace(
            state, params=jax.tree.map(lambda p: p * 1.01, state.params)), m
    return step


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "altered": altered}

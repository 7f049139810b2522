"""Faults planted in the timed path, to show that the check catches them.

Each ``plant_<name>(alg)`` wraps one strategy instance's ``run_round`` or
``setup`` before the driver starts (``harness.run(..., plant=...)``); the
compiled round programs are the same as in a sound run.

- ``unchanged``: the round computes, then hands back the state it was given.
- ``half_cohort``: the round's mean is taken over the first half of its
  clients only (the other half of the round's batch of updates left out).
- ``half_batch``: every other row of each client's shard carries the
  padding label -1, so each minibatch of the teachers' and the students'
  steps trains on about half its rows, the mean taken over the rest.
- ``no_exchange``: the exchange between lanes is left out: the global
  student is the first lane's own local model, as if the all-gather and
  contraction never ran.
- ``altered``: the round's answer is altered where it is produced: the
  aggregated student's output layer has its class columns reversed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _wrap(alg, before=None, after=None):
    run_round = alg.run_round

    def wrapped(plan, rnd):
        if before is not None:
            plan = before(plan)
        out = run_round(plan, rnd)
        if after is not None:
            after()
        return out
    alg.run_round = wrapped


def _reweight(plan, keep):
    w = np.where(keep, plan.slot_weight, 0.0)
    return dataclasses.replace(
        plan, slot_weight=(w / w.sum()).astype(np.float32))


def plant_unchanged(alg):
    run_round = alg.run_round

    def wrapped(plan, rnd):
        state = (alg.sp_global, alg.tp_k, alg.ts_k)
        out = run_round(plan, rnd)
        alg.sp_global, alg.tp_k, alg.ts_k = state
        return out
    alg.run_round = wrapped


def plant_half_cohort(alg):
    def before(plan):
        idx = np.flatnonzero(plan.active)
        keep = np.zeros(plan.n_slots, bool)
        keep[idx[:max(1, len(idx) // 2)]] = True
        return _reweight(plan, keep)
    _wrap(alg, before=before)


def plant_half_batch(alg):
    setup = alg.setup

    def wrapped(ds, shards, cfg, key):
        masked = []
        for sh in shards:
            y = np.array(sh.y)
            y[::2] = -1
            masked.append(dataclasses.replace(sh, y=y))
        return setup(ds, masked, cfg, key)
    alg.setup = wrapped


def plant_no_exchange(alg):
    def before(plan):
        keep = np.zeros(plan.n_slots, bool)
        keep[np.flatnonzero(plan.active)[0]] = True
        return _reweight(plan, keep)
    _wrap(alg, before=before)


def plant_altered(alg):
    import jax

    def after():
        leaves, tree = jax.tree_util.tree_flatten(alg.sp_global)
        leaves[-1] = leaves[-1][..., ::-1]
        alg.sp_global = jax.tree_util.tree_unflatten(tree, leaves)
    _wrap(alg, after=after)


FAULTS = {"unchanged": plant_unchanged, "half_cohort": plant_half_cohort,
          "half_batch": plant_half_batch, "no_exchange": plant_no_exchange,
          "altered": plant_altered}

"""Minimal optax-style optimizers (optax is not installed offline).

An ``Optimizer`` is (init, update):  state = init(params);
updates, state = update(grads, state, params).  Apply with ``apply_updates``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable


def apply_updates(params, updates):
    return jax.tree_util.tree_map(lambda p, u: (p + u).astype(p.dtype), params, updates)


# ----------------------------------------------------------------------- sgd
class SGDState(NamedTuple):
    momentum: object
    count: jax.Array


def sgd(lr: float | Callable[[jax.Array], jax.Array], momentum: float = 0.0) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        mom = jax.tree_util.tree_map(jnp.zeros_like, params) if momentum else None
        return SGDState(mom, jnp.zeros((), jnp.int32))

    def update(grads, state: SGDState, params=None):
        del params
        step_lr = lr_fn(state.count)
        if momentum:
            mom = jax.tree_util.tree_map(
                lambda m, g: momentum * m + g, state.momentum, grads)
            upd = jax.tree_util.tree_map(lambda m: -step_lr * m, mom)
            return upd, SGDState(mom, state.count + 1)
        upd = jax.tree_util.tree_map(lambda g: -step_lr * g, grads)
        return upd, SGDState(None, state.count + 1)

    return Optimizer(init, update)


# --------------------------------------------------------------------- adamw
class AdamState(NamedTuple):
    mu: object
    nu: object
    count: jax.Array


def adamw(
    lr: float | Callable[[jax.Array], jax.Array],
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    *,
    state_dtype=jnp.float32,
) -> Optimizer:
    """AdamW.  ``state_dtype=bfloat16`` halves optimizer-state HBM for the
    giant assigned archs (``launch/steps.make_optimizer``, above 8B params)."""
    lr_fn = lr if callable(lr) else (lambda _: lr)

    def init(params):
        z = lambda p: jnp.zeros(p.shape, state_dtype)
        return AdamState(jax.tree_util.tree_map(z, params),
                         jax.tree_util.tree_map(z, params),
                         jnp.zeros((), jnp.int32))

    def update(grads, state: AdamState, params):
        count = state.count + 1
        step_lr = lr_fn(count)
        mu = jax.tree_util.tree_map(
            lambda m, g: (b1 * m.astype(jnp.float32) + (1 - b1) * g.astype(jnp.float32)
                          ).astype(state_dtype), state.mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: (b2 * v.astype(jnp.float32)
                          + (1 - b2) * jnp.square(g.astype(jnp.float32))
                          ).astype(state_dtype), state.nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)

        def upd(m, v, p):
            mhat = m.astype(jnp.float32) / c1
            vhat = v.astype(jnp.float32) / c2
            u = -step_lr * (mhat / (jnp.sqrt(vhat) + eps)
                            + weight_decay * p.astype(jnp.float32))
            return u.astype(p.dtype)

        return (jax.tree_util.tree_map(upd, mu, nu, params),
                AdamState(mu, nu, count))

    return Optimizer(init, update)


# ----------------------------------------------------------------- schedules
def cosine_schedule(base_lr: float, warmup: int, total: int, floor: float = 0.0):
    def lr(count):
        count = count.astype(jnp.float32)
        warm = base_lr * count / jnp.maximum(warmup, 1)
        frac = jnp.clip((count - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
        cos = floor + 0.5 * (base_lr - floor) * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(count < warmup, warm, cos)

    return lr


# ------------------------------------------------------------------ fedprox
def fedprox_penalty(params, global_params, mu: float) -> jax.Array:
    """(mu/2)||w - w_g||^2 proximal term (Li et al. 2020), added to the local
    loss by the FedProx baseline round engine."""
    sq = jax.tree_util.tree_map(
        lambda p, g: jnp.sum((p.astype(jnp.float32) - g.astype(jnp.float32)) ** 2),
        params, global_params)
    return 0.5 * mu * sum(jax.tree_util.tree_leaves(sq))

"""Client-side training steps for the federated runtime (paper's CNNs or any
(init, fwd) model pair): plain CE, FedProx proximal, and the FedSiKD
teacher/student distillation step.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro import perf
from repro.core.distill import distillation_loss, softmax_cross_entropy
from repro.kernels import ops
from repro.optim import Optimizer, apply_updates, fedprox_penalty


def make_steps(fwd: Callable, opt: Optimizer, *, kd_temperature: float = 2.0,
               kd_alpha: float = 0.5, prox_mu: float = 0.0):
    """Returns dict of jitted steps: ce / prox / distill / eval."""

    def ce_loss(params, batch, key):
        logits = fwd(params, batch["x"], train=True, key=key)
        return softmax_cross_entropy(logits, batch["y"])

    @jax.jit
    def ce_step(params, opt_state, batch, key):
        loss, grads = jax.value_and_grad(ce_loss)(params, batch, key)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    @jax.jit
    def prox_step(params, opt_state, batch, key, global_params):
        def loss_fn(p):
            return ce_loss(p, batch, key) + fedprox_penalty(p, global_params,
                                                            prox_mu)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    def make_distill_step(teacher_fwd: Callable, *, fused: bool = False):
        """Student step with a (possibly different-architecture) teacher.

        ``fused=True`` swaps the pure-jnp reference loss for the Pallas
        ``kernels.ops.kd_distillation_loss`` kernel (identical objective and
        gradient; one streaming pass over the logits — the hot path the
        sharded engine uses)."""

        @jax.jit
        def distill_step(params, opt_state, batch, key, teacher_params):
            t_logits = teacher_fwd(teacher_params, batch["x"], train=False,
                                   key=None)

            def loss_fn(p):
                s_logits = fwd(p, batch["x"], train=True, key=key)
                if fused:
                    return ops.kd_distillation_loss(
                        s_logits, t_logits, batch["y"],
                        kd_temperature, kd_alpha, None)
                loss, _ = distillation_loss(
                    s_logits, t_logits, batch["y"],
                    temperature=kd_temperature, alpha=kd_alpha)
                return loss

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, loss

        return distill_step

    @functools.partial(jax.jit, static_argnames=())
    def eval_batch(params, x, y):
        with jax.named_scope("eval_forward"):
            logits = fwd(params, x, train=False, key=None)
            loss = softmax_cross_entropy(logits, y)
            acc = jnp.mean(
                (jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return acc, loss

    return {"ce": ce_step, "prox": prox_step, "make_distill": make_distill_step,
            "eval": eval_batch}


def evaluate(eval_batch, params, x, y, batch_size: int = 256):
    """Dataset accuracy/loss via batched eval (last partial batch included).

    Per batch, ``perf`` records ``eval_step`` (slice, implicit transfer of
    the host batch, dispatch) and ``sync`` (the two blocking reads)."""
    accs, losses, ns = [], [], []
    for s in range(0, len(y), batch_size):
        with perf.span("eval_step"):
            xa, ya = x[s:s + batch_size], y[s:s + batch_size]
            perf.count_bytes("h2d_bytes", xa, ya)
            a, l = eval_batch(params, xa, ya)
        with perf.span("sync"):
            perf.count("host_syncs", 2)
            accs.append(float(a) * len(ya))
            losses.append(float(l) * len(ya))
        ns.append(len(ya))
    n = sum(ns)
    return sum(accs) / n, sum(losses) / n

"""Client-side training steps for the federated runtime (paper's CNNs or any
(init, fwd) model pair): plain CE, FedProx proximal, and the FedSiKD
teacher/student distillation step.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import perf
from repro.core.distill import distillation_loss, softmax_cross_entropy
from repro.kernels import ops
from repro.optim import Optimizer, apply_updates, fedprox_penalty

EVAL_BATCH = 256        # rows per step of the eval program


def make_steps(fwd: Callable, opt: Optimizer, *, kd_temperature: float = 2.0,
               kd_alpha: float = 0.5, prox_mu: float = 0.0):
    """Returns dict of jitted steps: ce / prox / distill / eval (the
    staged test set's sums, ``evaluate``)."""

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

    @jax.jit
    def eval_set(params, xs, ys):
        """Sums over a staged test set (``stage_test_set``): correct
        predictions, loss and valid rows (label >= 0), one batch of the
        ``[n_batches, batch, ...]`` view at a time, so memory holds one
        batch's activations."""
        with jax.named_scope("eval_forward"):
            def batch_sums(xy):
                x, y = xy
                logits = fwd(params, x, train=False, key=None)
                valid = y >= 0
                n = jnp.sum(valid.astype(jnp.int32))
                hits = jnp.sum(
                    ((jnp.argmax(logits, -1) == y) & valid).astype(jnp.int32))
                loss = softmax_cross_entropy(logits, y) * n
                return hits, loss, n

            hits, loss, n = jax.lax.map(batch_sums, (xs, ys))
        return jnp.sum(hits), jnp.sum(loss), jnp.sum(n)

    return {"ce": ce_step, "prox": prox_step, "make_distill": make_distill_step,
            "eval": eval_set}


def stage_test_set(x, y, mesh=None):
    """The test set on the device, once per strategy: ``x`` padded with
    zero rows and ``y`` with -1 (padding to the ``eval`` program) to a whole
    number of batches, as ``[n_batches, EVAL_BATCH, ...]``.  Replicated over
    ``mesh`` where the strategy has one (the placement its global params
    carry after a round), else on the default device.  The bytes count as
    ``perf``'s ``h2d_bytes``."""
    n_batches = -(-len(y) // EVAL_BATCH)
    pad = n_batches * EVAL_BATCH - len(y)
    xs = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    ys = np.concatenate([y, np.full(pad, -1, y.dtype)])
    xs = xs.reshape((n_batches, EVAL_BATCH) + x.shape[1:])
    ys = ys.reshape(n_batches, EVAL_BATCH)
    perf.count_bytes("h2d_bytes", xs, ys)
    where = None if mesh is None else NamedSharding(mesh, P())
    return jax.device_put((xs, ys), where)


def evaluate(eval_set, params, test_set):
    """(accuracy, loss) of ``params`` over a staged test set: one program
    and one read.  ``perf`` records ``eval_step`` (the dispatch) and
    ``sync`` (the read)."""
    with perf.span("eval_step"):
        out = eval_set(params, *test_set)
    with perf.span("sync"):
        perf.count("host_syncs")
        hits, loss, n = jax.device_get(out)
    return int(hits) / int(n), float(loss) / int(n)

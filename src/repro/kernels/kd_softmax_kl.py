"""Fused temperature-softmax KL + CE distillation loss — Pallas TPU kernel.

The FedSiKD student objective per token is
    loss = (1-alpha) * CE(s, y) + alpha * tau^2 * KL(softmax(t/tau) || softmax(s/tau))
For LLM-scale students the vocab V reaches 256k: materialising three softmax
distributions (student@tau, teacher@tau, student@1) in HBM makes the loss
memory-bound.  This kernel streams teacher/student logits through VMEM in
vocab blocks with online (flash-style) max/sum rescaling, producing per-token
loss in ONE pass — logits are read exactly once.

Identity used:   KL = sum_j p_t_j (t_j - s_j)/tau + logZ_s - logZ_t
with p_t = softmax(t/tau); accumulators carry running max m, sum l for
(teacher@tau, student@tau, student@1) plus the weighted difference U and the
label logit.

Grid: (T/BT, V/BV) — vocab axis innermost, so VMEM scratch persists across
vocab blocks of one token block (sequential TPU grid).  The backward pass
(kd_softmax_kl_bwd) recomputes probabilities blockwise from the saved stats;
ops.py wires both into a custom_vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fwd_kernel(s_ref, t_ref, y_ref, loss_ref, stats_ref,
                m_t, l_t, m_s, l_s, m_1, l_1, u_acc, picked,
                *, tau: float, alpha: float, nv: int, bv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for r in (m_t, m_s, m_1):
            r[...] = jnp.full_like(r[...], NEG)
        for r in (l_t, l_s, l_1, u_acc, picked):
            r[...] = jnp.zeros_like(r[...])

    s = s_ref[...].astype(jnp.float32)           # (BT, BV)
    t = t_ref[...].astype(jnp.float32)
    y = y_ref[...]                               # (BT, 1)

    def online(m_ref, l_ref, x):
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(x, axis=-1, keepdims=True))
        scale = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * scale + jnp.sum(
            jnp.exp(x - m_new), axis=-1, keepdims=True)
        m_ref[...] = m_new
        return m_new, scale

    # teacher @ tau — also rescale the weighted-difference accumulator
    m_new, scale = online(m_t, l_t, t / tau)
    w = jnp.exp(t / tau - m_new)                                # unnorm p_t
    u_acc[...] = u_acc[...] * scale + jnp.sum(w * (t - s) / tau, axis=-1,
                                              keepdims=True)
    online(m_s, l_s, s / tau)                                   # student @ tau
    online(m_1, l_1, s)                                         # student @ 1

    # label logit (appears in exactly one vocab block)
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    hit = cols == y
    picked[...] = picked[...] + jnp.sum(jnp.where(hit, s, 0.0), axis=-1,
                                        keepdims=True)

    @pl.when(j == nv - 1)
    def _final():
        logz_t = m_t[...] + jnp.log(l_t[...])
        logz_s = m_s[...] + jnp.log(l_s[...])
        logz_1 = m_1[...] + jnp.log(l_1[...])
        kl = u_acc[...] / l_t[...] + logz_s - logz_t
        ce = logz_1 - picked[...]
        valid = (y >= 0).astype(jnp.float32)
        loss_ref[...] = ((1.0 - alpha) * ce + alpha * tau * tau * kl) * valid
        stats_ref[:, 0:1] = logz_t
        stats_ref[:, 1:2] = logz_s
        stats_ref[:, 2:3] = logz_1


# Per-token operands (labels, loss, g) travel as (T, 1) columns with
# (block_t, 1) blocks: a 1-D (block_t,) block turns into an illegal
# (1, block_t) tile when ``vmap`` adds a lane axis (the packed engine's
# call), while (block_t, 1) stays (multiple of 8, full dim).
@functools.partial(jax.jit, static_argnames=("tau", "alpha", "block_t",
                                             "block_v", "interpret"))
def kd_loss_fwd(student_logits, teacher_logits, labels, *, tau: float = 2.0,
                alpha: float = 0.5, block_t: int = 128, block_v: int = 512,
                interpret: bool = True):
    """Per-token fused distillation loss.  (T,V),(T,V),(T,) -> ((T,), (T,3)).

    T and V must be divisible by the block sizes (pad at the call site —
    ops.py handles this)."""
    T, V = student_logits.shape
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    nt, nv = T // block_t, V // block_v
    grid = (nt, nv)
    col = pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))
    loss, stats = pl.pallas_call(
        functools.partial(_fwd_kernel, tau=tau, alpha=alpha, nv=nv, bv=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            col,
        ],
        out_specs=[
            col,
            pl.BlockSpec((block_t, 3), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((T, 3), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 1), jnp.float32)
                        for _ in range(8)],
        interpret=interpret,
        name="kd_loss_fwd",
    )(student_logits, teacher_logits, labels.reshape(T, 1))
    return loss.reshape(T), stats


def _bwd_kernel(s_ref, t_ref, y_ref, stats_ref, g_ref, ds_ref,
                *, tau: float, alpha: float, bv: int):
    """d loss / d student_logits for one (token, vocab) block:
       ds = g * [ (1-alpha)(softmax1(s) - onehot(y))
                  + (alpha * tau) (softmax_tau(s) - softmax_tau(t)) ]."""
    j = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    y = y_ref[...]                               # (BT, 1)
    logz_t = stats_ref[:, 0:1]
    logz_s = stats_ref[:, 1:2]
    logz_1 = stats_ref[:, 2:3]
    p1 = jnp.exp(s - logz_1)
    ps = jnp.exp(s / tau - logz_s)
    pt = jnp.exp(t / tau - logz_t)
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (cols == y).astype(jnp.float32)
    valid = (y >= 0).astype(jnp.float32)
    ds = (1.0 - alpha) * (p1 - onehot) + (alpha * tau) * (ps - pt)
    ds_ref[...] = (g_ref[...] * ds * valid).astype(ds_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tau", "alpha", "block_t",
                                             "block_v", "interpret"))
def kd_loss_bwd(student_logits, teacher_logits, labels, stats, g, *,
                tau: float = 2.0, alpha: float = 0.5, block_t: int = 128,
                block_v: int = 512, interpret: bool = True):
    T, V = student_logits.shape
    nt, nv = T // block_t, V // block_v
    col = pl.BlockSpec((block_t, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tau=tau, alpha=alpha, bv=block_v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
            col,
            pl.BlockSpec((block_t, 3), lambda i, j: (i, 0)),
            col,
        ],
        out_specs=pl.BlockSpec((block_t, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, V), student_logits.dtype),
        interpret=interpret,
        name="kd_loss_bwd",
    )(student_logits, teacher_logits, labels.reshape(T, 1), stats,
      g.reshape(T, 1))

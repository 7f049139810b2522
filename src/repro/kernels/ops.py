"""Public jit'd wrappers around the Pallas kernels.

These are the entry points the rest of the repo (and external callers)
should use; the raw kernels in ``kd_softmax_kl.py`` / ``flash_attention.py``
/ ``kmeans_assign.py`` have strict divisibility requirements that the
wrappers hide.  Every wrapper provides:

- **Shape padding** — inputs are padded up to the kernel block sizes and
  outputs cropped back, so callers can pass arbitrary T/V/N.  Logit padding
  uses a large negative fill (``NEG``) so padded vocab columns carry zero
  softmax mass; padded tokens get label ``-1`` which the kernels treat as
  "ignore" (contributes 0 loss and 0 gradient).
- **Batch-dim flattening** — leading batch axes are folded into the row
  axis where the kernel is 2-D (see ``kd_distillation_loss``).
- **custom_vjp wiring** — ``kd_distillation_loss`` pairs the forward kernel
  with the analytic blockwise backward kernel instead of differentiating
  through the online-softmax recurrence.
- **Backend-resolved interpret mode** — ``interpret=None`` (the default)
  resolves via backend detection: TPU runs the compiled Pallas kernel, the
  CPU runs the kernel in Pallas interpret mode (numerically identical, a
  correctness harness, not a performance path), and any other backend
  raises instead of quietly interpreting.

All wrappers are safe under ``jit``, ``grad``, ``vmap``, ``lax.scan`` and
``jax.shard_map`` — ``shard_map`` callers pass ``check_vma=False``:
``pallas_call`` has no replication rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import kd_softmax_kl as _kd
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_merge as _fm
from repro.kernels import kmeans_assign as _km

NEG = -1e30


def _interpret_default() -> bool:
    """True on the CPU (Pallas interpret mode), False on the TPU; any other
    backend has no compiled kernels and no business interpreting them."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for the TPU and interpret on the CPU; "
            f"backend {backend!r} is neither (pass interpret= explicitly)")
    return backend == "cpu"


def _pad_to(x, axis, mult, value):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------- kd loss
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def kd_distillation_loss(student_logits, teacher_logits, labels,
                         tau: float = 2.0, alpha: float = 0.5,
                         interpret: bool | None = None):
    """Fused FedSiKD distillation loss (mean over tokens with label >= 0).

        loss = (1-alpha) * CE(student, y)
             + alpha * tau^2 * KL(softmax(teacher/tau) || softmax(student/tau))

    Contract:
      student_logits, teacher_logits : (..., V) float32/bfloat16, identical
                                       shapes; any number of leading axes
                                       (they are flattened into the token
                                       axis internally).
      labels                         : (...) int32/int64 matching the leading
                                       axes; ``-1`` marks padding tokens,
                                       which contribute neither loss nor
                                       gradient (the mean divides by the
                                       count of valid tokens only).
      tau, alpha, interpret          : POSITIONAL static args (custom_vjp
                                       nondiff); pass them positionally.
      returns                        : () float32 scalar.

    Differentiable in ``student_logits`` only (teacher gradient is defined
    as zero — the teacher is a constant target, as in Alg. 1).  T and V are
    padded to the (128, 512-or-V) kernel blocks internally; see module
    docstring for padding and interpret-mode semantics.  Matches
    ``core.distill.distillation_loss`` / ``kernels.ref.kd_loss_ref`` to
    float32 tolerance while reading the logits exactly once on TPU.
    """
    loss, _ = _kd_fwd_impl(student_logits, teacher_logits, labels, tau, alpha,
                           interpret)
    return loss


def kd_distillation_loss_batched(student_logits, teacher_logits, labels,
                                 *, tau: float = 2.0, alpha: float = 0.5,
                                 interpret: bool | None = None):
    """Batched-leading-dim alias of ``kd_distillation_loss`` for per-device
    use under ``shard_map`` (keyword-friendly; not a custom_vjp itself, so
    ``tau``/``alpha`` can be passed by name).

    Contract: student/teacher logits (B, T, V) — or any (..., V) — plus
    labels (B, T); returns the scalar mean loss over valid tokens of the
    whole batch.  Inside ``shard_map`` each device computes the loss of its
    local (B, T, V) block; combine across devices with ``lax.pmean`` if a
    global mean is wanted.  This is the entry point the sharded FedSiKD
    engine calls inside its ``lax.scan`` student step (fed/sharded.py).
    """
    if student_logits.shape != teacher_logits.shape:
        raise ValueError(
            "student/teacher logit shapes differ: "
            f"{student_logits.shape} vs {teacher_logits.shape}")
    if labels.shape != student_logits.shape[:-1]:
        raise ValueError(
            f"labels shape {labels.shape} != logit leading axes "
            f"{student_logits.shape[:-1]}")
    return kd_distillation_loss(student_logits, teacher_logits, labels,
                                tau, alpha, interpret)


def _blocks(V):
    bv = 512 if V % 512 == 0 or V > 512 else V
    return 128, bv


def _kd_fwd_impl(s, t, y, tau, alpha, interpret):
    interpret = _interpret_default() if interpret is None else interpret
    V = s.shape[-1]
    sf = s.reshape(-1, V)
    tf = t.reshape(-1, V)
    yf = y.reshape(-1)
    bt, bv = _blocks(V)
    sf = _pad_to(_pad_to(sf, 0, bt, 0.0), 1, bv, NEG)
    tf = _pad_to(_pad_to(tf, 0, bt, 0.0), 1, bv, NEG)
    yf = _pad_to(yf, 0, bt, -1)
    per_tok, stats = _kd.kd_loss_fwd(sf, tf, yf, tau=tau, alpha=alpha,
                                     block_t=bt, block_v=bv,
                                     interpret=interpret)
    denom = jnp.maximum(jnp.sum((yf >= 0).astype(jnp.float32)), 1.0)
    return jnp.sum(per_tok) / denom, (stats, denom)


def _kd_vjp_fwd(s, t, y, tau, alpha, interpret):
    loss, (stats, denom) = _kd_fwd_impl(s, t, y, tau, alpha, interpret)
    return loss, (s, t, y, stats, denom)


def _kd_vjp_bwd(tau, alpha, interpret, res, g):
    s, t, y, stats, denom = res
    interpret = _interpret_default() if interpret is None else interpret
    V = s.shape[-1]
    sf = s.reshape(-1, V)
    tf = t.reshape(-1, V)
    yf = y.reshape(-1)
    bt, bv = _blocks(V)
    T0 = sf.shape[0]
    sfp = _pad_to(_pad_to(sf, 0, bt, 0.0), 1, bv, NEG)
    tfp = _pad_to(_pad_to(tf, 0, bt, 0.0), 1, bv, NEG)
    yfp = _pad_to(yf, 0, bt, -1)
    gf = jnp.full((sfp.shape[0],), 1.0, jnp.float32) * (g / denom)
    ds = _kd.kd_loss_bwd(sfp, tfp, yfp, stats, gf, tau=tau, alpha=alpha,
                         block_t=bt, block_v=bv, interpret=interpret)
    ds = ds[:T0, :V].reshape(s.shape).astype(s.dtype)
    return ds, None, None


kd_distillation_loss.defvjp(_kd_vjp_fwd, _kd_vjp_bwd)


# --------------------------------------------------------- flash attention
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool | None = None):
    """Streaming (flash-style) attention.

    Contract:
      q       : (B, T, H, hd)   — layer layout, heads on axis 2.
      k, v    : (B, S, KVH, hd) — KVH must divide H (grouped-query
                attention: each KV head serves H/KVH query heads).
      returns : (B, T, H, hd), same dtype as ``q``.

    ``causal=True`` applies a RIGHT-ALIGNED causal mask (query i attends to
    keys up to S - T + i), so cross-length decode shapes (T < S) work;
    ``window > 0`` additionally limits attention to the last ``window``
    keys.  T and S are padded to block multiples internally.  The kernel's
    right-aligned mask is computed on the PADDED lengths, which matches the
    true mask only when T and S pad by the SAME amount — for causal calls
    with unequal pad amounts (e.g. T=64, S=200: padded keys would become
    visible and absorb softmax mass) this wrapper raises rather than
    returning silently-wrong attention; use lengths that are 128-multiples
    (or both under 128 with T == S, or equal-pad pairs).  NON-causal
    callers must pad/mask S themselves.  dtype: float32 or bfloat16
    (accumulation is float32 either way).  ``interpret=None`` resolves by
    backend (see module docstring).
    """
    interpret = _interpret_default() if interpret is None else interpret
    qt = jnp.moveaxis(q, 2, 1)                       # (B,H,T,hd)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    T, S = qt.shape[2], kt.shape[2]
    bq = min(128, T) if T % 128 else 128
    bk = min(128, S) if S % 128 else 128
    pad_t, pad_s = (-T) % bq, (-S) % bk
    if causal and pad_t != pad_s:
        raise ValueError(
            f"causal flash_attention with T={T}, S={S} pads queries by "
            f"{pad_t} but keys by {pad_s}; the right-aligned causal mask is "
            f"computed on padded lengths and would mis-mask {abs(pad_s - pad_t)} "
            "keys.  Use T/S that pad equally (e.g. 128-multiples).")
    qt = _pad_to(qt, 2, bq, 0.0)
    kt = _pad_to(kt, 2, bk, 0.0)
    vt = _pad_to(vt, 2, bk, 0.0)
    # equal pads + right alignment => padded keys sit past every query's
    # visible range, so the causal mask hides them automatically
    out = _fa.flash_attention(qt, kt, vt, causal=causal,
                              window=window, block_q=bq, block_k=bk,
                              interpret=interpret)
    out = out[:, :, :T]
    return jnp.moveaxis(out, 1, 2)


# ------------------------------------------------------------ fused merge
def fused_merge(stacked, weights, staleness=None, *, decay: float = 0.0,
                interpret: bool | None = None):
    """Grouped weighted mean with staleness decay, in one kernel pass.

    Contract:
      stacked   : (N, ...) — N client copies of one model leaf (any shape,
                  any float dtype; flattened to (N, D) internally).
      weights   : (N,) non-negative base weights, not necessarily
                  normalised (at least one must be positive).
      staleness : (N,) staleness in rounds, or None (== all zeros).
      decay     : the exponent a in (1 + s)^-a (0 = plain weighted mean).
      returns   : (...) float32 — the decayed, renormalised weighted mean
                  sum_i w_i(1+s_i)^-a x_i / sum_j w_j(1+s_j)^-a (callers
                  cast back to the leaf dtype).

    D is padded to the 512-column kernel block and N to an 8-row multiple
    (pad rows carry weight 0, so the in-kernel normalisation ignores them).
    Matches ``kernels.ref.fused_merge_ref`` to float32 tolerance.
    ``interpret=None`` resolves by backend (see module docstring) —
    production CPU callers (``core.aggregation``) use an equivalent single
    jitted jnp contraction instead, keeping interpret-mode Pallas out of
    the round hot path.
    """
    interpret = _interpret_default() if interpret is None else interpret
    N = stacked.shape[0]
    xf = stacked.reshape(N, -1)
    w = jnp.asarray(weights, jnp.float32)
    s = (jnp.zeros(N, jnp.float32) if staleness is None
         else jnp.asarray(staleness, jnp.float32))
    D = xf.shape[1]
    bd = min(512, D) if D % 512 else 512
    xf = _pad_to(xf, 1, bd, 0.0)
    xf = _pad_to(xf, 0, 8, 0.0)
    w = _pad_to(w, 0, 8, 0.0)
    s = _pad_to(s, 0, 8, 0.0)
    out = _fm.fused_merge(xf, w, s, decay=float(decay), block_d=bd,
                          interpret=interpret)
    return out[:D].reshape(stacked.shape[1:])


# ----------------------------------------------------------------- kmeans
def kmeans_assign(x, cents, *, interpret: bool | None = None):
    """Nearest-centroid assignment (the k-means E-step).

    Contract:
      x       : (N, F) float32 points.
      cents   : (K, F) float32 centroids (K is small; the kernel streams
                points in 128-row blocks against the full centroid table).
      returns : (assignments (N,) int32, sq_distance-to-assigned (N,)
                float32).

    N is padded to a 128-multiple internally and cropped on return; ties
    resolve to the lowest centroid index (argmin semantics, matching
    ``kernels.ref.kmeans_assign_ref``).  ``interpret=None`` resolves by
    backend (see module docstring).
    """
    interpret = _interpret_default() if interpret is None else interpret
    N = x.shape[0]
    bn = min(128, N) if N % 128 else 128
    xp = _pad_to(x, 0, bn, 0.0)
    a, d = _km.kmeans_assign(xp, cents, block_n=bn, interpret=interpret)
    return a[:N], d[:N]

"""Step builders used by launch/train.py and the LM examples:

  - train_step          — LM next-token training (AdamW, optional grad accum)
  - prefill_step        — serving prefill: last logits + decode cache
  - decode_step         — one-token decode with cache
  - fedsikd_distill_step— the paper's technique at LLM scale: per-dp-shard
    student replicas distilling a shared frozen teacher, with intra-cluster
    gradient aggregation expressed as an averaging-matrix contraction on the
    replica axis (lowers to grouped collectives under SPMD; DESIGN.md §3/§5).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.distill import kl_teacher_student
from repro.models import encdec as ed
from repro.models import transformer as tf
from repro.optim import adamw, apply_updates


def _loss_mod(cfg: ModelConfig):
    return ed if cfg.arch_type == "audio" else tf


def make_optimizer(cfg: ModelConfig, *, lr: float = 1e-4):
    """bf16 moments above 8B params (HBM; DESIGN.md §4–§5)."""
    big = cfg.param_count() > 8_000_000_000
    return adamw(lr, state_dtype=jnp.bfloat16 if big else jnp.float32)


def make_train_step(cfg: ModelConfig, *, lr: float = 1e-4, accum: int = 1):
    opt = make_optimizer(cfg, lr=lr)
    mod = _loss_mod(cfg)

    def loss_fn(params, batch):
        loss, aux = mod.lm_loss(params, cfg, batch)
        return loss

    def train_step(params, opt_state, batch):
        if accum > 1:
            def micro(carry, mb):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (jax.tree_util.tree_map(jnp.add, g_acc, g),
                        l_acc + l), None

            mbs = jax.tree_util.tree_map(
                lambda a: a.reshape((accum, a.shape[0] // accum) + a.shape[1:]),
                batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, l_sum), _ = jax.lax.scan(micro, (zeros, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: (g / accum).astype(
                jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32), g_sum)
            loss = l_sum / accum
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    # Donation contract (DESIGN.md §13): (params, opt_state) flow through
    # the step unchanged in shape/sharding, so jit sites can donate them and
    # update in place instead of holding two copies of the model.  The
    # builders return UN-jitted steps, so donation rides along as an
    # attribute for the jit site (launch/train.py) to consume.
    train_step.donate_argnums = (0, 1)
    return train_step, opt


def make_prefill_step(cfg: ModelConfig):
    if cfg.arch_type == "audio":
        def prefill_step(params, batch):
            memory = ed.encode(params, cfg, batch["frames"])
            logits, _ = ed.forward(params, cfg, batch)
            return logits[:, -1, :], memory
        return prefill_step

    def prefill_step(params, batch):
        return tf.prefill(params, cfg, batch)

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    if cfg.arch_type == "audio":
        def decode_step(params, cache, tokens, pos):
            logits, cache = ed.decode_step(params, cfg, cache, tokens, pos)
            return logits[:, -1, :], cache
        return decode_step

    def decode_step(params, cache, tokens, pos):
        logits, cache = tf.decode_step(params, cfg, cache, tokens, pos)
        return logits[:, -1, :], cache

    return decode_step


# ------------------------------------------------------- FedSiKD at scale
def averaging_matrices(cluster_of: np.ndarray):
    """(A_intra, A_global) on the replica axis.

    A_intra[d,e] = 1/|C_k| if replicas d,e share cluster k (grouped
    all-reduce of Alg.1 line 16);  A_global[d,e] = 1/(K*|C_{k(e)}|)
    (two-level FedSiKD mean, Alg.1 line 18)."""
    cluster_of = np.asarray(cluster_of)
    D = len(cluster_of)
    ks, counts = np.unique(cluster_of, return_counts=True)
    size = {k: c for k, c in zip(ks, counts)}
    K = len(ks)
    intra = np.zeros((D, D), np.float32)
    glob = np.zeros((D, D), np.float32)
    for d in range(D):
        for e in range(D):
            if cluster_of[d] == cluster_of[e]:
                intra[d, e] = 1.0 / size[cluster_of[d]]
            glob[d, e] = 1.0 / (K * size[cluster_of[e]])
    return jnp.asarray(intra), jnp.asarray(glob)


def chunked_kd_loss(h_s, w_s, h_t, w_t, labels, *, tau: float, alpha: float,
                    chunk: int = 8192):
    """Distillation loss computed in VOCAB CHUNKS from final hidden states —
    the pure-jnp mirror of kernels/kd_softmax_kl: per-chunk logits are
    produced inside a (remat'd) scan with flash-style online max/sum
    accumulators, so the (tokens, V) student/teacher logits are NEVER
    materialised in HBM (hillclimb C take-2).

    h_s/h_t: (T, d) final hidden states; w_s/w_t: (d, V) lm heads;
    labels: (T,).  V % chunk need not hold (the tail pads with -inf logits).
    """
    T, d = h_s.shape
    V = w_s.shape[1]
    pad = (-V) % chunk
    n = (V + pad) // chunk

    def wchunks(w):
        wt = jnp.pad(w, ((0, 0), (0, pad)))
        return jnp.moveaxis(wt.reshape(d, n, chunk), 1, 0)   # (n, d, chunk)

    ws = wchunks(w_s)
    wt = wchunks(w_t)
    NEG = -1e30
    col_pad_mask = jnp.arange(chunk)                          # used per chunk

    def body(carry, xs):
        m_t, l_t, m_s, l_s, m_1, l_1, u, picked = carry
        w_s_c, w_t_c, ci = xs
        valid = (ci * chunk + col_pad_mask) < V               # (chunk,)
        s = (h_s @ w_s_c).astype(jnp.float32)
        t = (h_t @ w_t_c).astype(jnp.float32)
        s = jnp.where(valid[None, :], s, NEG)
        t = jnp.where(valid[None, :], t, NEG)

        def online(m, l, x):
            m_new = jnp.maximum(m, x.max(-1))
            l_new = l * jnp.exp(m - m_new) + jnp.exp(
                x - m_new[:, None]).sum(-1)
            return m_new, l_new

        m_t_new = jnp.maximum(m_t, (t / tau).max(-1))
        scale = jnp.exp(m_t - m_t_new)
        w_unnorm = jnp.exp(t / tau - m_t_new[:, None])
        u = u * scale + (w_unnorm * jnp.where(valid[None, :],
                                              (t - s) / tau, 0.0)).sum(-1)
        l_t = l_t * scale + w_unnorm.sum(-1)
        m_t = m_t_new
        m_s, l_s = online(m_s, l_s, s / tau)
        m_1, l_1 = online(m_1, l_1, s)
        cols = ci * chunk + col_pad_mask[None, :]
        hit = cols == labels[:, None]
        picked = picked + jnp.where(hit, s, 0.0).sum(-1)
        return (m_t, l_t, m_s, l_s, m_1, l_1, u, picked), None

    z = jnp.zeros((T,), jnp.float32)
    neg = jnp.full((T,), NEG, jnp.float32)
    carry = (neg, z, neg, z, neg, z, z, z)
    (m_t, l_t, m_s, l_s, m_1, l_1, u, picked), _ = jax.lax.scan(
        jax.checkpoint(body), carry, (ws, wt, jnp.arange(n)))
    logz_t = m_t + jnp.log(l_t)
    logz_s = m_s + jnp.log(l_s)
    logz_1 = m_1 + jnp.log(l_1)
    kl = u / l_t + logz_s - logz_t
    ce = logz_1 - picked
    mask = (labels >= 0).astype(jnp.float32)
    per_tok = ((1.0 - alpha) * ce + alpha * tau * tau * kl) * mask
    return per_tok.sum() / jnp.maximum(mask.sum(), 1.0)


def make_fedsikd_distill_step(cfg: ModelConfig, cluster_of, *,
                              lr: float = 1e-4, kd_alpha: float = 0.5,
                              kd_tau: float = 2.0,
                              teacher_in_grad: bool = False,
                              vocab_chunk: int = 0):
    """students: per-replica pytree (leading D axis, sharded over dp);
    teacher: shared frozen full-depth model.  One FL step = local distill
    grad -> intra-cluster grouped mean -> AdamW.  ``sync`` applies the
    two-level global mean (end of round).

    ``teacher_in_grad=True`` keeps the teacher forward inside the student's
    grad/remat closure (the naive formulation — §Perf hillclimb C baseline):
    remat then RECOMPUTES the frozen teacher in the backward pass.  The
    default computes teacher logits once, outside the vjp."""
    s_cfg = cfg.as_student()
    opt = make_optimizer(s_cfg, lr=lr)
    A_intra, A_global = averaging_matrices(cluster_of)
    D = len(np.asarray(cluster_of))
    mod = _loss_mod(cfg)

    def kd_loss(s_logits, t_logits, labels):
        logf = s_logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logf, -1)
        picked = jnp.take_along_axis(
            logf, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        ce = jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1.0)
        kl = kl_teacher_student(jax.lax.stop_gradient(t_logits), s_logits,
                                temperature=kd_tau, mask=labels >= 0)
        return (1.0 - kd_alpha) * ce + kd_alpha * kl

    def _student_logits(student, batch):
        s_logits, _ = mod.forward(student, s_cfg, batch)
        if cfg.prefix_len:
            s_logits = s_logits[:, cfg.prefix_len:]
        return s_logits

    def one_loss_naive(student, teacher, batch):
        t_logits, _ = mod.forward(teacher, cfg, batch)
        if cfg.prefix_len:
            t_logits = t_logits[:, cfg.prefix_len:]
        return kd_loss(_student_logits(student, batch), t_logits,
                       batch["labels"])

    def one_loss(student, t_logits, batch):
        return kd_loss(_student_logits(student, batch), t_logits,
                       batch["labels"])

    def _head(params):
        return params["embed"].T if cfg.tie_embeddings else params["lm_head"]

    def one_loss_chunked(student, t_hidden, teacher, batch):
        """Vocab-chunked loss from final hidden states — (T,V) logits never
        materialise (hillclimb C take-2)."""
        s_hidden, _ = mod.forward(student, s_cfg, batch, return_hidden=True)
        if cfg.prefix_len:
            s_hidden = s_hidden[:, cfg.prefix_len:]
            t_hidden = t_hidden[:, cfg.prefix_len:]
        B2, T2, d2 = s_hidden.shape
        return chunked_kd_loss(
            s_hidden.reshape(B2 * T2, d2), _head(student),
            t_hidden.reshape(B2 * T2, d2),
            jax.lax.stop_gradient(_head(teacher)),
            batch["labels"].reshape(-1), tau=kd_tau, alpha=kd_alpha,
            chunk=vocab_chunk)

    def distill_step(students, opt_state, teacher, batch):
        """batch leaves: (D, B/D, ...) — one microbatch per replica."""
        if teacher_in_grad:
            losses, grads = jax.vmap(
                jax.value_and_grad(one_loss_naive), in_axes=(0, None, 0))(
                    students, teacher, batch)
        elif vocab_chunk:
            def t_fwd(b):
                h, _ = mod.forward(teacher, cfg, b, return_hidden=True)
                return h
            t_hidden = jax.lax.stop_gradient(jax.vmap(t_fwd)(batch))
            losses, grads = jax.vmap(
                jax.value_and_grad(one_loss_chunked),
                in_axes=(0, 0, None, 0))(students, t_hidden, teacher, batch)
        else:
            # teacher forward once, outside the vjp/remat of the student
            def t_fwd(b):
                t_logits, _ = mod.forward(teacher, cfg, b)
                if cfg.prefix_len:
                    t_logits = t_logits[:, cfg.prefix_len:]
                return t_logits
            t_logits = jax.lax.stop_gradient(jax.vmap(t_fwd)(batch))
            losses, grads = jax.vmap(
                jax.value_and_grad(one_loss), in_axes=(0, 0, 0))(
                    students, t_logits, batch)
        # intra-cluster grouped aggregation as a replica-axis contraction
        grads = jax.tree_util.tree_map(
            lambda g: jnp.einsum("de,e...->d...", A_intra,
                                 g.astype(jnp.float32)).astype(g.dtype), grads)
        updates, opt_state = jax.vmap(opt.update)(grads, opt_state, students)
        students = apply_updates(students, updates)
        return students, opt_state, losses.mean()

    def sync(students):
        """End-of-round two-level FedSiKD mean across replicas."""
        return jax.tree_util.tree_map(
            lambda w: jnp.einsum("de,e...->d...", A_global,
                                 w.astype(jnp.float32)).astype(w.dtype),
            students)

    def init_students(key):
        init = ed.init_encdec if cfg.arch_type == "audio" else tf.init_lm
        return jax.vmap(lambda k: init(k, s_cfg))(jax.random.split(key, D))

    # (students, opt_state) update in place under donation; the TEACHER is
    # deliberately NOT donated — it is frozen and re-read every step
    distill_step.donate_argnums = (0, 1)
    return distill_step, sync, init_students, opt, s_cfg

"""Plain reference of a FedSiKD run: the check that decides ``correct``.

It imports nothing of the program and takes nothing it made.  From the seed
and the configuration it makes the split, the clients' statistics and their
clusters (k-means++ and Lloyd's algorithm, K by the three-metric vote), the
weights and the model of the configuration's dataset module
(``models/<dataset>.py``), the teacher warm-up, and then each round one
client at a time: the teacher refresh on the cluster leader's shard, each
client's distillation from its cluster teacher with a fresh Adam state, the
cluster-weighted mean of the students.  Every step is one jitted call on
one batch; nothing is packed, masked or fused.

``dtype=float32`` computes at HIGHEST matmul precision.  The control runs
the same code in bfloat16 (weights, optimizer state and activations): the
precision below what the configuration states.

Only full participation is modelled (every client, every round, no
stragglers): the cells that compare against it run that traffic.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import models
from bench.data import client_epochs, dirichlet_partition

F32 = jnp.float32


# ------------------------------------------------------------------ losses
def cross_entropy(logits, y):
    """Mean over rows with a label; label -1 is a padding row."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(y, 0)[:, None], -1)[:, 0]
    mask = (y >= 0).astype(logits.dtype)
    return jnp.sum((logz - picked) * mask) / jnp.maximum(mask.sum(), 1)


def kd_loss(s_logits, t_logits, y, tau, alpha):
    """(1 - alpha) CE + alpha tau^2 KL(softmax(t/tau) || softmax(s/tau)),
    both means over the labelled rows."""
    mask = (y >= 0).astype(s_logits.dtype)
    lt = jax.nn.log_softmax(t_logits / tau, -1)
    ls = jax.nn.log_softmax(s_logits / tau, -1)
    kl = jnp.sum(jnp.exp(lt) * (lt - ls), -1)
    kl = jnp.sum(kl * mask) / jnp.maximum(mask.sum(), 1)
    return (1 - alpha) * cross_entropy(s_logits, y) + alpha * tau * tau * kl


# --------------------------------------------------------------- clustering
def client_features(xs):
    """Per-client (mean, std, skewness) of every input feature, then each
    column standardised over the clients (float64)."""
    rows = []
    for x in xs:
        x = x.reshape(len(x), -1).astype(np.float64)
        mu = x.mean(0)
        c = x - mu
        sd = np.sqrt((c ** 2).mean(0))
        skew = (c ** 3).mean(0) / np.maximum(sd, 1e-8) ** 3
        rows.append(np.concatenate([mu, sd, skew]))
    f = np.stack(rows)
    return (f - f.mean(0)) / np.maximum(f.std(0), 1e-8)


def _sq(x, c):
    return ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)


def kmeans(key, x, k, iters=50):
    """k-means++ seeding (draws from ``key``), then Lloyd's algorithm in
    float64; an empty cluster keeps its centroid.  Returns assignments."""
    x = np.asarray(x, np.float64)
    n = len(x)
    cents = [x[int(jax.random.randint(key, (), 0, n))]]
    for _ in range(1, k):
        key, sub = jax.random.split(key)
        d = _sq(x, np.stack(cents)).min(axis=1)
        total = d.sum()
        p = d / total if total > 1e-9 else np.full(n, 1.0 / n)
        cents.append(x[int(jax.random.choice(sub, n, p=jnp.asarray(p, F32)))])
    c = np.stack(cents)
    for _ in range(iters):
        a = np.argmin(_sq(x, c), axis=1)
        c = np.stack([x[a == j].mean(0) if (a == j).any() else c[j]
                      for j in range(k)])
    return np.argmin(_sq(x, c), axis=1)


def _scores(x, a, k):
    """(silhouette, Calinski-Harabasz, Davies-Bouldin) of assignment a."""
    x = np.asarray(x, np.float64)
    n = len(x)
    d = np.sqrt(_sq(x, x))
    counts = np.bincount(a, minlength=k)
    sil = np.zeros(n)
    for i in range(n):
        own = counts[a[i]]
        if own <= 1:
            continue
        ai = d[i, a == a[i]].sum() / (own - 1)
        bs = [d[i, a == j].mean() for j in range(k)
              if j != a[i] and counts[j] > 0]
        if bs:
            bi = min(bs)
            sil[i] = (bi - ai) / max(ai, bi, 1e-9)
    cents = np.stack([x[a == j].mean(0) if counts[j] else np.zeros(x.shape[1])
                      for j in range(k)])
    ssb = (counts * ((cents - x.mean(0)) ** 2).sum(1)).sum()
    ssw = ((x - cents[a]) ** 2).sum()
    ch = (ssb / max(k - 1, 1)) / max(ssw / max(n - k, 1), 1e-9)
    spread = np.array([np.sqrt(((x[a == j] - cents[j]) ** 2).sum(1)).mean()
                       if counts[j] else 0.0 for j in range(k)])
    occ = [j for j in range(k) if counts[j]]
    db = np.mean([max([(spread[i] + spread[j])
                       / max(np.sqrt(((cents[i] - cents[j]) ** 2).sum()),
                             1e-9) for j in occ if j != i] or [0.0])
                  for i in occ])
    return sil.mean(), ch, db


def cluster(feats, seed, k_range):
    """Cluster labels by the paper's rule: k-means for each K in k_range,
    each metric votes for its best K (ties to the smaller), the majority
    wins; then k-means at that K, labels renumbered over occupied ones."""
    key = jax.random.PRNGKey(seed + 17)
    x = np.asarray(feats, np.float32)
    ks = list(range(k_range[0], min(k_range[1], len(feats) - 1) + 1))
    table = {}
    for k in ks:
        a = kmeans(jax.random.fold_in(key, k), x, k)
        table[k] = _scores(feats, a, k)
    votes = [max(ks, key=lambda k: table[k][0]),
             max(ks, key=lambda k: table[k][1]),
             min(ks, key=lambda k: table[k][2])]
    k = max(set(votes), key=lambda v: (votes.count(v), -v))
    a = kmeans(key, x, k)
    return np.searchsorted(np.unique(a), a)


# ------------------------------------------------------------------ training
def _adam(lr, dtype):
    b1, b2, eps = 0.9, 0.999, 1e-8
    tm = jax.tree_util.tree_map

    def init(p):
        z = tm(lambda a: jnp.zeros(a.shape, dtype), p)
        return {"mu": z, "nu": z, "count": jnp.zeros((), jnp.int32)}

    def update(p, s, g):
        count = s["count"] + 1
        mu = tm(lambda m, g: (b1 * m + (1 - b1) * g).astype(dtype),
                s["mu"], g)
        nu = tm(lambda v, g: (b2 * v + (1 - b2) * g * g).astype(dtype),
                s["nu"], g)
        c1 = 1 - b1 ** count.astype(F32)
        c2 = 1 - b2 ** count.astype(F32)
        p = tm(lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
                                ).astype(dtype), p, mu, nu)
        return p, {"mu": mu, "nu": nu, "count": count}

    return init, update


class Reference:
    """FedSiKD (Alg. 1) one client at a time.  ``run(rounds)`` returns the
    numbers the program is compared on (see ``check.py``)."""

    def __init__(self, config, traffic, data, seed, dtype=F32):
        self.cfg, self.fed, self.seed = config, traffic["fed"], seed
        self.table_seed = traffic["table_seed"]
        self.dtype = dtype
        self.xt, self.yt, self.xv, self.yv = data
        model = config["model"]
        init, fwd = models.of(config).init, models.of(config).forward
        ncls = config["dataset"]["num_classes"]
        self.t_init = lambda k: init(k, model["teacher_filters"], ncls)
        self.s_init = lambda k: init(k, model["student_filters"], ncls)
        prec = (jax.lax.Precision.HIGHEST if dtype == F32
                else jax.lax.Precision.DEFAULT)
        tr = config["training"]
        tau, alpha = tr["kd_temperature"], tr["kd_alpha"]
        cast = lambda a: a.astype(dtype)
        t_opt_init, t_upd = _adam(tr["lr"], dtype)
        s_opt_init, s_upd = _adam(tr["student_lr"], dtype)
        self.t_opt_init, self.s_opt_init = t_opt_init, s_opt_init

        @jax.jit
        def teacher_step(p, s, total, x, y):
            def loss(p):
                return cross_entropy(fwd(p, cast(x), prec), y)
            val, g = jax.value_and_grad(loss)(p)
            p, s = t_upd(p, s, g)
            return p, s, total + val.astype(F32)

        @jax.jit
        def student_step(p, s, total, tp, x, y):
            t_logits = fwd(tp, cast(x), prec)

            def loss(p):
                return kd_loss(fwd(p, cast(x), prec), t_logits, y, tau,
                               alpha)
            val, g = jax.value_and_grad(loss)(p)
            p, s = s_upd(p, s, g)
            return p, s, total + val.astype(F32)

        @jax.jit
        def eval_sums(p, x, y):
            logits = fwd(p, cast(x), prec).astype(F32)
            logz = jax.nn.logsumexp(logits, -1)
            ce = logz - jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
            return ce.sum(), (jnp.argmax(logits, -1) == y).sum()

        self.teacher_step, self.student_step = teacher_step, student_step
        self.eval_sums = eval_sums

    # ------------------------------------------------------------ pieces
    def _cast(self, tree):
        return jax.tree_util.tree_map(lambda a: a.astype(self.dtype), tree)

    def _train(self, step, p, s, feed, extra=()):
        """All of a feed's steps; returns (p, s, mean step loss)."""
        xs, ys = jax.device_put(feed)
        total = jnp.zeros((), F32)
        for i in range(len(ys)):
            p, s, total = step(p, s, total, *extra, xs[i], ys[i])
        return p, s, float(total) / len(ys)

    def _eval(self, p):
        ce, hit = 0.0, 0
        for lo in range(0, len(self.yv), 2048):
            c, h = self.eval_sums(p, self.xv[lo:lo + 2048],
                                  self.yv[lo:lo + 2048])
            ce, hit = ce + float(c), hit + int(h)
        return hit / len(self.yv), ce / len(self.yv)

    def run(self, rounds: int = 3):
        fed, tr, seed = self.fed, self.cfg["training"], self.seed
        if fed.get("participation", "full") != "full" or fed.get(
                "async_mode") or fed.get("dropout_rate") or fed.get(
                "universe"):
            raise NotImplementedError(
                "the reference models full, synchronous participation")
        n = fed["num_clients"]
        B, E = tr["batch_size"], tr["local_epochs"]
        parts = dirichlet_partition(self.yt, n, fed["alpha"], seed=seed,
                                    table_seed=self.table_seed)
        xs = [self.xt[p] for p in parts]
        ys = [self.yt[p] for p in parts]
        sizes = np.asarray([len(p) for p in parts])
        labels = cluster(client_features(xs), seed, tr["k_range"])
        K = int(labels.max()) + 1
        members = [np.flatnonzero(labels == k) for k in range(K)]
        leaders = [int(m[np.argmax(sizes[m])]) for m in members]
        # the cluster's weight (|C_k| / N, or 1 / K) split evenly over its
        # members: the two-level mean as one weighted sum
        count = np.asarray([len(m) for m in members])
        cluster_w = (count / n if tr["cluster_weighting"] == "size"
                     else np.full(K, 1.0 / K))
        weight = cluster_w[labels] / count[labels]

        key = jax.random.PRNGKey(seed)
        student = self._cast(self.s_init(key))
        teachers = [self._cast(self.t_init(jax.random.fold_in(key, 100 + k)))
                    for k in range(K)]
        out = {"labels": labels, "init": {"student": student,
                                          "teachers": list(teachers)},
               "teacher_loss": [], "student_loss": [], "eval_loss": [],
               "eval_acc": []}
        t_states = [self.t_opt_init(t) for t in teachers]

        def feed(c, epochs):
            return client_epochs(xs[c], ys[c], c, epochs, seed, B)

        for k in range(K):
            teachers[k], t_states[k], _ = self._train(
                self.teacher_step, teachers[k], t_states[k],
                feed(leaders[k], tr["teacher_warmup_epochs"]))
        for r in range(1, rounds + 1):
            t_loss = 0.0
            for k in range(K):
                teachers[k], t_states[k], lk = self._train(
                    self.teacher_step, teachers[k], t_states[k],
                    feed(leaders[k], E))
                t_loss += lk * len(members[k]) / n
            locals_, s_loss = [], 0.0
            for c in range(n):
                p, _, lc = self._train(
                    self.student_step, student, self.s_opt_init(student),
                    feed(c, E), extra=(teachers[labels[c]],))
                locals_.append(p)
                s_loss += lc / n
            student = jax.tree_util.tree_map(
                lambda *ls: sum(w * l.astype(F32) for w, l in
                                zip(weight, ls)).astype(self.dtype),
                *locals_)
            acc, loss = self._eval(student)
            out["teacher_loss"].append(t_loss)
            out["student_loss"].append(s_loss)
            out["eval_loss"].append(loss)
            out["eval_acc"].append(acc)
            if r == 1:
                out["after1"] = {"student": student}
        out["after"] = {"student": student, "teachers": teachers}
        out["steps"] = int(math.ceil(sizes.max() / B) * E)
        return out

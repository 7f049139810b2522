"""The benchmark's inputs, made from ``--seed``: the dataset twin of the
configuration (``models/<dataset>.py``), the Dirichlet split of its
training set over the clients, and each client's batch order.

The split and the batch order are copies of the program's own
(``data/dirichlet.py``, ``data/pipeline.py``), kept here so that no later
change to the program can change what the benchmark feeds it or what the
reference trains on.  The split's table of counts comes from the traffic's
own seed, so that with the twins' equal class counts every ``--seed`` gets
the same shard sizes, and so the same work per round, in another order.
"""
from __future__ import annotations

import numpy as np

from bench import models

SALT_BATCH = 0xB0     # the per-epoch batch-order stream of a client shard


def make_dataset(config: dict, seed: int):
    """(x_train, y_train, x_test, y_test) of the configuration's dataset."""
    d = config["dataset"]
    return models.of(config).twin(seed, d["n_train"], d["n_test"])


def dirichlet_partition(labels, num_clients, alpha, *, seed, table_seed,
                        min_per_client=8):
    """Per-class Dirichlet(alpha) split of the example indices over the
    clients, redrawn until every client holds ``min_per_client``.

    How many examples of each class each client holds is drawn from
    ``table_seed`` (the traffic's), which examples from ``seed``: with the
    twins' fixed class counts every seed gets the same shard sizes, and so
    the same work per round, in another order."""
    labels = np.asarray(labels)
    table = np.random.default_rng(table_seed)
    classes, counts = np.unique(labels, return_counts=True)
    for _ in range(100):
        cuts = [(np.cumsum(table.dirichlet(np.full(num_clients, alpha)))[:-1]
                 * n).astype(int) for n in counts]
        sizes = sum(np.diff(np.concatenate([[0], c, [n]]))
                    for c, n in zip(cuts, counts))
        if sizes.min() >= min_per_client:
            break
    else:
        raise ValueError(f"no Dirichlet({alpha}) split over {num_clients} "
                         f"clients gives each {min_per_client} examples")
    order = np.random.default_rng(seed)
    shards = [[] for _ in range(num_clients)]
    for c, cut in zip(classes, cuts):
        idx = order.permutation(np.flatnonzero(labels == c))
        for shard, part in zip(shards, np.split(idx, cut)):
            shard.extend(part.tolist())
    return [np.asarray(sorted(s), np.int64) for s in shards]


def client_batches(x, y, client_id, epoch, seed, batch_size):
    """One epoch of a client's batches in its seeded order; the last batch
    is padded with zero rows labelled -1."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [seed & 0xFFFFFFFF, client_id & 0xFFFFFFFF, SALT_BATCH,
         epoch & 0xFFFFFFFF]))
    order = rng.permutation(len(y))
    out = []
    for start in range(0, len(y), batch_size):
        idx = order[start:start + batch_size]
        bx, by = x[idx], y[idx]
        pad = batch_size - len(idx)
        if pad:
            bx = np.concatenate([bx, np.zeros((pad,) + x.shape[1:], x.dtype)])
            by = np.concatenate([by, np.full(pad, -1, y.dtype)])
        out.append((bx, by))
    return out


def client_epochs(x, y, client_id, epochs, seed, batch_size):
    """``epochs`` epochs of batches stacked: ((steps, B, ...), (steps, B))."""
    bs = [b for e in range(epochs)
          for b in client_batches(x, y, client_id, e, seed, batch_size)]
    return np.stack([b[0] for b in bs]), np.stack([b[1] for b in bs])

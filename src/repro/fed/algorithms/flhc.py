"""FL+HC (Briggs 2020): one pre-round of local training, agglomerative
clustering of the updates, then per-cluster FedAvg forever after.

Only the clustering pre-round stays special-cased (``setup``, which IS the
run's round 1: ``setup_rounds = 1``).  The post-clustering rounds ride the
shared ``RoundDriver``, which gives FL+HC what the inlined implementation
never had: partial participation, client dropout, unified acc+loss
progress reporting, and checkpoint/resume.

Lifecycle note: FL+HC is the one algorithm WITHOUT a client-lifecycle path
(``FedConfig`` rejects join_schedule/leave_rate/recluster_every for it at
construction): its cluster assignment is a function of every client's
FIRST-round model update, so a mid-run joiner has no update to cluster —
re-clustering would mean re-running the full pre-round, which is the
run's round 1 by definition.  The stats-based strategies re-cluster from
shareable statistics instead (DESIGN.md §11).

Resume note: ``setup`` re-runs the (deterministic) pre-round on restart —
the cluster assignment must be recomputed to rebuild the scheduler and to
re-validate the checkpoint fingerprint against silent data/config drift,
exactly like the clustered-KD strategies recompute their stats clustering.
The restored ``cluster_models`` then overwrite the recomputed ones, so the
resumed tail is bit-identical (tests/test_fault_tolerance.py).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import aggregation as agg
from repro.core import hierarchical
from repro.fed import schedule
from repro.fed.algorithms.base import Algorithm, local_epochs, tree_copy
from repro.fed.client import evaluate, make_steps
from repro.models.cnn import make_model
from repro.optim import adamw


class FLHC(Algorithm):
    name = "flhc"
    engine = "loop"
    setup_rounds = 1       # the clustering pre-round is the run's round 1

    def setup(self, ds, shards, cfg, key):
        self.ds, self.shards, self.cfg, self.key = ds, shards, cfg, key
        self.opt = adamw(cfg.lr)
        t_init, t_fwd = make_model(ds.name, student=False)
        self.steps = make_steps(t_fwd, self.opt, prox_mu=cfg.prox_mu)
        global_params = t_init(key)
        locals_, updates = [], []
        for i, sh in enumerate(shards):
            p = tree_copy(global_params)
            o = self.opt.init(p)
            p, _ = local_epochs(sh, p, o, jax.random.fold_in(key, i),
                                cfg, step_fn=self.steps["ce"])
            locals_.append(p)
            updates.append(hierarchical.flatten_update(
                agg.tree_sub(p, global_params)))
        k = cfg.num_clusters or 4
        labels = hierarchical.agglomerative(np.stack(updates), n_clusters=k)
        self.labels = labels
        self.clusters = [np.flatnonzero(labels == c)
                         for c in np.unique(labels)]
        self.cluster_models = [
            agg.fedavg([locals_[i] for i in c],
                       [shards[i].num_examples for i in c])
            for c in self.clusters]
        self.scheduler = schedule.RoundScheduler(
            labels, participation=cfg.participation,
            clients_per_round=cfg.clients_per_round,
            dropout_rate=cfg.dropout_rate, seed=cfg.seed)
        self.stage_test_set(ds)

    def run_round(self, plan, rnd):
        cfg, key = self.cfg, self.key
        part = set(int(i) for i in plan.participants)
        for ci, members in enumerate(self.clusters):
            sel = [i for i in members if int(i) in part]
            if not sel:
                continue     # no sampled/surviving member: model untouched
            locs = []
            for i in sel:
                p = tree_copy(self.cluster_models[ci])
                o = self.opt.init(p)
                p, _ = local_epochs(
                    self.shards[i], p, o,
                    jax.random.fold_in(key, rnd * 777 + i), cfg,
                    step_fn=self.steps["ce"])
                locs.append(p)
            self.cluster_models[ci] = agg.fedavg(
                locs, [self.shards[i].num_examples for i in sel])
        return {}

    def eval(self):
        # client-weighted mean over cluster models on the global test set
        # (full-population cluster sizes, independent of this round's sample)
        accs, losses, ws = [], [], []
        for cm, c in zip(self.cluster_models, self.clusters):
            a, l = evaluate(self.steps["eval"], cm, self.test_set)
            w = sum(self.shards[i].num_examples for i in c)
            accs.append(a * w)
            losses.append(l * w)
            ws.append(w)
        return sum(accs) / sum(ws), sum(losses) / sum(ws)

    def checkpoint_arrays(self):
        return {"cluster_models": self.cluster_models}

    def restore_arrays(self, arrays):
        self.cluster_models = arrays["cluster_models"]

    def history_extras(self):
        return {"num_clusters": len(self.clusters)}

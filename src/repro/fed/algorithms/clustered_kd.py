"""Clustered-KD strategies: FedSiKD (Alg. 1) and RandomCluster, on both
engines.

``LoopClusteredKD`` is the sequential per-client reference (the semantic
ground truth); ``ShardedClusteredKD`` maps the same phases onto the packed
client mesh (`fed/sharded.py`, DESIGN.md §3/§8): per-cluster teacher
replicas, packed teacher sync, fused Pallas KD student steps inside
``lax.scan``, grouped plan-weighted aggregation.  Both consume the same
deterministic ``RoundPlan``s, so loop/sharded parity extends to sampled
rounds and dropout (tests/test_schedule.py, tests/test_sharded_kd.py).

Client lifecycle (DESIGN.md §11): the stats front-end is batched (ONE
jitted segment-sum program for the whole roster's (mu, sigma, gamma), one
vmapped DP-noise program), so ``apply_lifecycle`` can re-cluster cheaply on
every join/leave event and on the periodic cadence.  Re-clustering keeps
the teacher count K fixed at its setup value: k-means is warm-started from
the previous centroids (``kmeans_warm``), each post-event cluster j adopts
the teacher of the nearest previously-OCCUPIED centroid (usually itself —
warm starts drift, they don't jump), and the scheduler/teacher-feed/slot
staging are rebuilt for the new roster.  Fixing K keeps every checkpoint
array shape stable across events, which is what lets a mid-lifecycle
resume restore into the same structure.

Checkpoint payload (both engines, same keys): the global student, the
per-cluster teachers WITH their optimizer states — the loop engine as
lists, the sharded engine as ``(K, ...)`` stacked host pytrees (packed slot
state is derived, never persisted: the next round's gather re-scatters) —
plus the CURRENT cluster labels (and, for FedSiKD, centroids), because
lifecycle re-clustering evolves them past what setup can recompute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import perf
from repro.core import aggregation as agg
from repro.core import kmeans, stats
from repro.fed import schedule
from repro.fed.algorithms.base import (Algorithm, cluster_epochs,
                                       fold_losses, local_epochs,
                                       merge_arrivals_only, packed_async_row,
                                       staleness_merge, tree_copy)
from repro.fed.driver import AsyncUpdate
from repro.fed.client import evaluate, make_steps
from repro.models.cnn import make_model
from repro.optim import adamw


def stat_features(shards, cfg, roster=None) -> jax.Array:
    """Alg. 1 phase 1, batched: the (R, 3F) raw statistics matrix for the
    ``roster`` clients (global ids; None = everyone) via ONE jitted
    segment-sum program plus one vmapped DP-noise program — no per-client
    Python loop.  DP keys fold the GLOBAL client id, so a client's noise is
    identical no matter when it joins or how often the server re-clusters."""
    if roster is None:
        roster = np.arange(len(shards))
    roster = np.asarray(roster)
    xs = [shards[int(i)].x.reshape(shards[int(i)].num_examples, -1)
          for i in roster]
    sizes = [len(x) for x in xs]
    x_cat = jnp.asarray(np.concatenate(xs, axis=0), jnp.float32)
    cid = jnp.asarray(np.repeat(np.arange(len(roster)), sizes))
    mean, std, skew = stats.batched_moments(x_cat, cid,
                                            num_segments=len(roster))
    if cfg.dp_noise > 0:
        key = jax.random.PRNGKey(cfg.seed + 17)
        # roster-shaped by design: recompiles only on membership events,
        # never in the steady-state round loop
        keys = jnp.stack([jax.random.fold_in(key, int(i))
                          for i in roster])  # fedlint: allow=FL005 -- roster-shaped by design: recompiles only on membership events, never in the steady round loop
        mean, std, skew = stats.privatize_batched(
            mean, std, skew, noise_multiplier=cfg.dp_noise, keys=keys)
    return jnp.concatenate([mean, std, skew], axis=1)


def cluster_by_stats(shards, cfg) -> np.ndarray:
    """Alg. 1 phases 1-2 over the full roster: client statistics sharing
    (+ optional DP noise) -> k-means cluster formation with metric-voted K."""
    key = jax.random.PRNGKey(cfg.seed + 17)
    feats = stats.standardize(stat_features(shards, cfg))
    if cfg.num_clusters is None:
        k, _ = kmeans.select_k(key, feats, *cfg.k_range)
    else:
        k = cfg.num_clusters
    res = kmeans.kmeans(key, feats, k)
    return np.asarray(res.assignments)


class _ClusteredKDBase(Algorithm):
    """Shared setup: clustering, leaders, scheduler, models/optimizers."""

    def setup(self, ds, shards, cfg, key):
        from repro.data.pipeline import ClientStore
        if not isinstance(shards, ClientStore):
            shards = ClientStore(shards, universe=cfg.universe)
        self.ds, self.shards, self.cfg, self.key = ds, shards, cfg, key
        self.store = shards
        self.name = cfg.algorithm
        self._stats_key = jax.random.PRNGKey(cfg.seed + 17)
        active0 = self.initial_active(cfg)
        roster = np.flatnonzero(active0)
        if cfg.algorithm == "fedsikd":
            # with a virtual universe, statistics sharing + clustering run
            # over the materialised BASE pool (the only distinct data
            # distributions that exist) and labels broadcast to the virtual
            # clients through the store's aliasing map — a 100k universe
            # must not build a 100k-row feature matrix at setup
            stat_roster = (roster if cfg.universe is None
                           else np.arange(shards.n_base))
            raw = stat_features(shards, cfg, stat_roster)
            # ONE standardization space (initial-roster statistics) for the
            # whole run: warm-started centroids and teacher-migration
            # distances stay comparable across re-clustering events
            self._feat_mu, self._feat_sd = stats.standardize_params(raw)
            feats = stats.apply_standardize(raw, self._feat_mu, self._feat_sd)
            if cfg.num_clusters is None:
                k, _ = kmeans.select_k(self._stats_key, feats, *cfg.k_range)
            else:
                k = cfg.num_clusters
            res = kmeans.kmeans(self._stats_key, feats, k)
            lab = np.asarray(res.assignments)
            occ = np.unique(lab)
            # compact to the OCCUPIED clusters: exactly one teacher per
            # occupied cluster, K fixed for the rest of the run
            self.K0 = len(occ)
            self.centroids = np.asarray(res.centroids)[occ]
            self._base_labels = None
            lab = np.searchsorted(occ, lab)
            if cfg.universe is not None:
                lab = lab[shards.row_of[roster]]
        else:                          # random-cluster ablation baseline
            rng = np.random.default_rng(cfg.seed + 3)
            k = cfg.num_clusters or 4
            base = rng.integers(0, k, cfg.total_clients)
            occ = np.unique(base)      # teachers for universe-occupied values
            base = np.searchsorted(occ, base)
            self.K0 = len(occ)
            self.centroids = None
            self._base_labels = base
            lab = base[roster]
        labels_full = np.full(cfg.total_clients, -1, np.int64)
        labels_full[roster] = lab
        self._rebuild_structures(labels_full)
        self.opt = adamw(cfg.lr)
        self.s_opt = adamw(cfg.student_lr)
        self.t_model = make_model(ds.name, student=False)
        self.s_model = make_model(ds.name, student=True)
        self._setup_engine()
        self.stage_test_set(ds)

    # ------------------------------------------------------ roster plumbing
    def _rebuild_structures(self, labels_full) -> None:
        """Derive every roster-dependent structure from the (C,) label
        array: cluster membership, leaders, compact->teacher-row map, and a
        fresh ``RoundScheduler``.  Called at setup, on every lifecycle
        event, and on checkpoint restore."""
        cfg = self.cfg
        self.labels = np.asarray(labels_full)
        occ = np.unique(self.labels[self.labels >= 0])
        # scheduler cluster index i (compact, occupied only) hosts teacher
        # row cluster_ids[i] — a re-clustered roster can leave teacher rows
        # temporarily empty, and those keep their state untouched
        self.cluster_ids = occ.astype(np.int64)
        self.clusters = [np.flatnonzero(self.labels == c) for c in occ]
        # leader (teacher host) = most-data client in cluster (DESIGN.md §7)
        # — argmax over the store's vectorised size table, not a per-member
        # shard dereference loop (O(universe) at 100k clients)
        sizes = self.store.sizes
        self.leaders = [int(c[np.argmax(sizes[c])]) for c in self.clusters]
        self.scheduler = schedule.RoundScheduler(
            self.labels, participation=cfg.participation,
            clients_per_round=self.clamped_clients_per_round(cfg, self.labels),
            pack=cfg.pack, n_devices=self.forced_devices(cfg),
            waves=cfg.waves,
            weighting=cfg.cluster_weighting, dropout_rate=cfg.dropout_rate,
            seed=cfg.seed, async_mode=cfg.async_mode,
            round_deadline=cfg.round_deadline,
            straggler_frac=cfg.straggler_frac,
            latency_dist=cfg.latency_dist)

    def apply_lifecycle(self, event):
        cfg = self.cfg
        old_labels = self.labels
        roster = np.flatnonzero(event.active)
        migrate = np.arange(self.K0)
        if cfg.algorithm == "fedsikd":
            raw = stat_features(self.shards, cfg, roster)
            feats = stats.apply_standardize(raw, self._feat_mu, self._feat_sd)
            res = kmeans.kmeans_warm(feats, jnp.asarray(self.centroids))
            new_cent = np.asarray(res.centroids)
            lab = np.asarray(res.assignments)
            # teacher migration: cluster j warm-starts from the teacher of
            # the nearest previously-OCCUPIED centroid (identity for
            # clusters that merely drifted)
            occupied_old = np.unique(old_labels[old_labels >= 0])
            d = ((new_cent[:, None, :] - self.centroids[None, :, :]) ** 2
                 ).sum(-1)
            penalty = np.full(self.K0, np.inf)
            penalty[occupied_old] = 0.0
            migrate = np.argmin(d + penalty[None, :], axis=1)
            self._migrate_teachers(migrate)
            self.centroids = new_cent
        else:                          # random baseline: labels are sticky
            lab = self._base_labels[roster]
        labels_full = np.full(cfg.num_clients, -1, np.int64)
        labels_full[roster] = lab
        both = (old_labels >= 0) & (labels_full >= 0)
        shift = (float(np.mean(old_labels[both] != labels_full[both]))
                 if both.any() else 0.0)
        self._rebuild_structures(labels_full)
        self._post_lifecycle()
        return {"recluster": 1.0, "cluster_shift": shift,
                "active_clients": float(event.active.sum()),
                "migrated_teachers": float(
                    int((migrate != np.arange(self.K0)).sum()))}

    # ----------------------------------------------------------- engine API
    def _setup_engine(self):
        raise NotImplementedError

    def _migrate_teachers(self, migrate: np.ndarray) -> None:
        raise NotImplementedError

    def _post_lifecycle(self) -> None:
        """Engine hook after a roster rebuild (packed engine re-stages the
        teacher feed; the loop engine reads ``clusters``/``leaders`` live)."""

    def history_extras(self):
        return {"num_clusters": len(self.clusters)}


# ---------------------------------------------------------------- loop engine
class LoopClusteredKD(_ClusteredKDBase):
    """Sequential reference: Alg. 1 phases 3-4 as a per-client Python loop."""

    engine = "loop"

    def _setup_engine(self):
        cfg, key = self.cfg, self.key
        t_init, t_fwd = self.t_model
        s_init, _s_fwd = self.s_model
        self.teacher_steps = make_steps(t_fwd, self.opt, prox_mu=cfg.prox_mu)
        self.student_steps = make_steps(
            self.s_model[1], self.s_opt, kd_temperature=cfg.kd_temperature,
            kd_alpha=cfg.kd_alpha)
        self.distill_step = self.student_steps["make_distill"](t_fwd)
        self.global_student = s_init(key)
        self.teachers = [t_init(jax.random.fold_in(key, 100 + k))
                         for k in range(self.K0)]
        self.t_opts = [self.opt.init(t) for t in self.teachers]

    def _migrate_teachers(self, migrate):
        if np.array_equal(migrate, np.arange(self.K0)):
            return
        self.teachers = [self.teachers[int(m)] for m in migrate]
        self.t_opts = [self.t_opts[int(m)] for m in migrate]

    def _teacher_shards(self, ci, members=None):
        # "cluster" mode pools the round's SAMPLED members only (None =
        # all, for warm-up): the packed engine trains teacher replicas
        # on participating slots' shards, and non-participants' raw data
        # must not reach the teacher in a round they sat out
        if self.cfg.teacher_data == "cluster":
            sel = self.clusters[ci] if members is None else members
            return [self.shards[i] for i in sel]
        return [self.shards[self.leaders[ci]]]

    def warmup(self):
        cfg, key = self.cfg, self.key
        if not cfg.teacher_warmup_epochs:
            return
        # KD establishment phase (pre-round teacher warm-up, Alg. 1)
        for ci in range(len(self.clusters)):
            t = int(self.cluster_ids[ci])
            self.teachers[t], self.t_opts[t] = cluster_epochs(
                self._teacher_shards(ci), self.teachers[t], self.t_opts[t],
                jax.random.fold_in(key, 9000 + ci), cfg,
                step_fn=self.teacher_steps["ce"],
                epochs=cfg.teacher_warmup_epochs)

    def run_round(self, plan, rnd):
        cfg, key = self.cfg, self.key
        part = set(int(i) for i in plan.participants)
        weight_of = plan.weight_of()
        delay_of = plan.delay_of()
        new_params, weights = [], []
        for ci, members in enumerate(self.clusters):
            sel = [i for i in members if int(i) in part]
            if not sel:
                continue           # no sampled member: teacher untouched
            t = int(self.cluster_ids[ci])
            # Alg.1 line 12: teacher trains on (sampled) cluster data —
            # teachers are edge-hosted, so they stay SYNCHRONOUS even when
            # a member's student update straggles (DESIGN.md §12)
            self.teachers[t], self.t_opts[t] = cluster_epochs(
                self._teacher_shards(ci, sel), self.teachers[t],
                self.t_opts[t], jax.random.fold_in(key, rnd * 1000 + ci),
                cfg, step_fn=self.teacher_steps["ce"], epochs=cfg.local_epochs)
            for i in sel:
                sp = tree_copy(self.global_student)
                so = self.s_opt.init(sp)
                sp, _ = local_epochs(
                    self.shards[i], sp, so,
                    jax.random.fold_in(key, rnd * 1000 + 500 + i), cfg,
                    step_fn=self.distill_step, extra=(self.teachers[t],))
                d = delay_of[int(i)]
                if d > 0:          # straggler: update lands d rounds late
                    self.buffer.push(AsyncUpdate(
                        client=int(i), birth=rnd, arrival=rnd + d,
                        weight=weight_of[int(i)], params=sp))
                else:
                    new_params.append(sp)
                    weights.append(weight_of[int(i)])
        if self.arrivals or plan.stragglers.any():
            # semi-async merge: on-time + buffered arrivals under the
            # staleness-decayed, renormalised weights
            if new_params or self.arrivals:
                self.global_student = staleness_merge(
                    new_params, weights, self.arrivals, cfg.staleness_decay)
        elif new_params:
            # the plan's weights ARE the two-level FedSiKD mean, extended
            # unbiasedly to the sampled subset (schedule.RoundPlan docstring)
            self.global_student = agg.weighted_average(new_params, weights)
        # else: every invited client dropped out — a no-op round
        return {}

    def eval(self):
        return evaluate(self.student_steps["eval"], self.global_student,
                        self.test_set)

    def checkpoint_arrays(self):
        arrs = {"student": self.global_student, "teachers": self.teachers,
                "t_opts": self.t_opts,
                "labels": jnp.asarray(self.labels, jnp.int32)}
        if self.centroids is not None:
            arrs["centroids"] = jnp.asarray(self.centroids, jnp.float32)
        return arrs

    def restore_arrays(self, arrays):
        self.global_student = arrays["student"]
        self.teachers = arrays["teachers"]
        self.t_opts = arrays["t_opts"]
        if "centroids" in arrays:
            self.centroids = np.asarray(arrays["centroids"])
        self._rebuild_structures(np.asarray(arrays["labels"]))
        self._post_lifecycle()


# ------------------------------------------------------------- sharded engine
class ShardedClusteredKD(_ClusteredKDBase):
    """Alg. 1 on the packed client mesh (C = devices x pack clients in one
    jitted program per round; fed/sharded.py owns the collective programs).

    Canonical state lives per CLUSTER between rounds (teachers: a (K, ...)
    stacked pytree; student: one global pytree).  How a round refreshes the
    teachers follows ``teacher_data`` (DESIGN.md §7):

    - ``"leader"``: every member of a cluster would train the same teacher
      on the same leader batches with the same key, so the round program
      refreshes each cluster's teacher ONCE, as a row of the (K, ...)
      stacks, on the leader feed staged on the device
      (``sh.make_leader_kd_round``); each lane reads its cluster's row.
    - ``"cluster"``: data-parallel teacher training over the members'
      own shards — the strategy gathers a teacher replica onto every
      participating slot, the program syncs the replicas
      (``sh.make_packed_kd_round``), and the strategy scatters each
      refreshed teacher back from its cluster's first active slot.

    Clusters with no sampled member keep their teacher untouched — exactly
    like the loop engine skipping them (DESIGN.md §8).

    Lifecycle events re-scatter slot state for free — slot state is derived
    per round from the canonical (K, ...) stacks — so ``_post_lifecycle``
    only has to re-stage the leader feed (leaders may have changed).  The
    mesh itself is sized for the client UNIVERSE at setup
    (``Algorithm.forced_devices``), so the compiled round program survives
    every join."""

    engine = "sharded"

    def _setup_engine(self):
        from repro.fed import sharded as sh
        from repro.launch.mesh import make_fed_client_mesh
        cfg, key = self.cfg, self.key
        self.sh = sh
        scheduler = self.scheduler
        if scheduler.n_waves > 1 and cfg.teacher_data == "cluster":
            raise ValueError(
                "teacher_data='cluster' needs the whole cluster on the mesh "
                "at once; wave-scheduled rounds require "
                "teacher_data='leader'")
        self.leader_mode = cfg.teacher_data == "leader"
        # the mesh hosts ONE wave; the cohort streams through it
        self.mesh = make_fed_client_mesh(scheduler.wave_slots,
                                         pack=cfg.pack,
                                         n_devices=scheduler.n_devices)
        self.S = scheduler.wave_slots
        self.K = self.K0

        t_init, t_fwd = self.t_model
        s_init, s_fwd = self.s_model
        # canonical per-cluster teacher state: (K, ...) stacked pytrees
        single_teachers = [t_init(jax.random.fold_in(key, 100 + k))
                           for k in range(self.K)]
        self.tp_k = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                           *single_teachers)
        self.ts_k = jax.vmap(self.opt.init)(self.tp_k)
        self.sp_global = s_init(key)
        self.student_steps = make_steps(
            s_fwd, self.s_opt, kd_temperature=cfg.kd_temperature,
            kd_alpha=cfg.kd_alpha)

        # static per-client step budgets (mirror the loop engine's batch
        # counts) and the one-off (R, steps, B, ...) staging of the BASE
        # data pool — virtual clients stage through the store's row map at
        # gather time, so host memory scales with the pool, never the
        # universe (DESIGN.md §15).  Every slot streams its own shard: the
        # students' feed, and in "cluster" mode the teacher replicas' too.
        store = self.store
        self._base_counts = sh.client_step_counts(store.base, cfg.batch_size,
                                                  cfg.local_epochs)
        self.s_steps_all = self._base_counts[store.row_of]
        self.sx_all, self.sy_all = sh.stack_client_data(
            store.base, int(self._base_counts.max()), cfg.batch_size,
            seed=cfg.seed)
        self.stager = sh.WaveStager(
            self.mesh, self.sx_all, self.sy_all,
            row_maps=(store.row_of, store.row_of),
            capacity=scheduler.n_waves + 1)
        self._feed_rows = None
        self._restage_teacher_feed()

        kd = dict(kd_temperature=cfg.kd_temperature, kd_alpha=cfg.kd_alpha,
                  kd_impl=cfg.kd_impl, donate=cfg.donate)
        if self.leader_mode:
            self.round_fn = sh.make_leader_kd_round(
                self.mesh, cfg.pack, t_fwd, s_fwd, self.opt, self.s_opt,
                **kd)
        else:
            self.round_fn = sh.make_packed_kd_round(
                self.mesh, cfg.pack, t_fwd, s_fwd, self.opt, self.s_opt,
                **kd)
        self._build_prep_finish()

    def _build_prep_finish(self):
        """The pre-round GATHER and post-round MERGE as jitted programs.
        Eagerly, these are hundreds of per-leaf dispatches on sharded arrays
        (~30ms each — the profiled hot spot: the scatter alone cost
        ~19s/round); jitted they are fixed-shape programs whose index
        operands are traced inputs, so sampled rounds never recompile.

        ``prep`` emits every (S, ...) output with the packed slot sharding,
        which is what makes the round program's donation usable: the round
        consumes prep's outputs in place.  It broadcasts the global student
        to every slot with a fresh optimizer state; in "cluster" mode it
        also gathers each slot's teacher replica from the (K, ...) stacks.
        ``finish`` merges the refreshed teachers into the (K, ...)
        accumulators (leader mode: the program's (K,) rows where their
        budget was non-zero; cluster mode: scattered from each cluster's
        first active slot) and extracts the aggregated student.  It donates
        the round's outputs but NEVER the canonical (K, ...) stacks — the
        async checkpoint writer may still hold references to those from a
        previous round's submit (DESIGN.md §13)."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        cfg, sh = self.cfg, self.sh
        S, K = self.S, self.K
        s_opt = self.s_opt
        tree_map = jax.tree_util.tree_map
        slot_sh = NamedSharding(self.mesh, P(sh.AXIS))
        donate = (0, 1, 2) if cfg.donate else ()

        def students(sp_global):
            sp_s = tree_map(
                lambda a: jnp.broadcast_to(a, (S,) + a.shape), sp_global)
            ss_s = jax.vmap(s_opt.init)(sp_s)   # fresh student opt (loop too)
            return sp_s, ss_s

        def merge(new, old, refreshed):
            def upd(n, o):
                return jnp.where(
                    refreshed.reshape((K,) + (1,) * (o.ndim - 1)), n, o)
            return tree_map(upd, new, old)

        if self.leader_mode:
            self._prep = jax.jit(students, out_shardings=slot_sh)

            def finish(tp_w, ts_w, sp_s, tp_k, ts_k, t_n):
                refreshed = t_n > 0
                return (merge(tp_w, tp_k, refreshed),
                        merge(ts_w, ts_k, refreshed),
                        tree_map(lambda a: a[0], sp_s))

            self._finish = jax.jit(finish, donate_argnums=donate)
            return

        def prep(tp_k, ts_k, sp_global, kidx):
            tp_s = tree_map(lambda a: a[kidx], tp_k)
            ts_s = tree_map(lambda a: a[kidx], ts_k)
            return (tp_s, ts_s) + students(sp_global)

        self._prep = jax.jit(prep, out_shardings=slot_sh)

        def scatter(new, old, refreshed, safe):
            return merge(tree_map(lambda n: n[safe], new), old, refreshed)

        def finish(tp_s, ts_s, sp_s, tp_k, ts_k, refreshed, safe):
            tp_k = scatter(tp_s, tp_k, refreshed, safe)
            ts_k = scatter(ts_s, ts_k, refreshed, safe)
            sp0 = tree_map(lambda a: a[0], sp_s)
            return tp_k, ts_k, sp0

        self._finish = jax.jit(finish, donate_argnums=donate)

        def finish_warm(tp_s, ts_s, tp_k, ts_k, refreshed, safe):
            return (scatter(tp_s, tp_k, refreshed, safe),
                    scatter(ts_s, ts_k, refreshed, safe))

        donate_w = (0, 1) if cfg.donate else ()
        self._finish_warm = jax.jit(finish_warm, donate_argnums=donate_w)

    def _restage_teacher_feed(self):
        """Leader mode: per teacher row, the compact cluster index that
        hosts it (its keys fold it), its leader's step budget, and the
        leader's (steps, B, ...) feed staged on the device once, replicated
        over the mesh as (K, ...) rows and counted under ``h2d_bytes`` — at
        setup and after a roster rebuild that changes a leader (a
        re-clustering that keeps every leader, the common drift case,
        stages nothing).  A row no cluster holds carries row 0's feed with
        a zero budget: it never trains.  Cluster mode streams each slot's
        own shard, which the stager already holds."""
        if not self.leader_mode:
            return
        K, store, hosted = self.K, self.store, self.cluster_ids
        self._row_cluster = np.zeros(K, np.int64)
        self._row_cluster[hosted] = np.arange(len(hosted))
        rows = np.zeros(K, np.int64)
        rows[hosted] = store.row_of[np.asarray(self.leaders, np.int64)]
        self._leader_steps = np.zeros(K, np.int32)
        self._leader_steps[hosted] = self._base_counts[rows[hosted]]
        if self._feed_rows is not None \
                and np.array_equal(rows, self._feed_rows):
            return
        self._feed_rows = rows
        self.tx_k, self.ty_k = self.sh.replicate(
            self.mesh, self.sx_all[rows], self.sy_all[rows])

    def _post_lifecycle(self):
        self._restage_teacher_feed()

    def _migrate_teachers(self, migrate):
        if np.array_equal(migrate, np.arange(self.K0)):
            return
        idx = jnp.asarray(migrate)
        self.tp_k = jax.tree_util.tree_map(lambda a: a[idx], self.tp_k)
        self.ts_k = jax.tree_util.tree_map(lambda a: a[idx], self.ts_k)

    # ------------------------------------------------- slot gather/scatter
    def _teacher_row(self, plan):
        """(S,) teacher row hosted by each slot: the scheduler's compact
        cluster index mapped through ``cluster_ids`` (idle slots row 0)."""
        comp = np.where(plan.active, plan.slot_cluster, 0)
        return np.where(plan.active, self.cluster_ids[comp], 0)

    def _row_steps(self, plan):
        """(K,) leader-mode teacher budgets: each row's leader budget where
        ``plan`` holds an active slot of its cluster, else 0 (that teacher
        stays frozen)."""
        t_n = np.zeros(self.K, np.int32)
        rows = self._teacher_row(plan)[plan.active]
        t_n[rows] = self._leader_steps[rows]
        return t_n

    def _scatter_src(self, plan):
        """Cluster-mode scatter operands for ``_finish``: which teacher rows
        a round refreshed (``refreshed``, (K,) bool) and the first active
        slot sourcing each (``safe``, (K,) int; untouched rows read slot 0
        but are masked out).  Traced inputs to the jitted scatter — index
        changes never recompile."""
        K, S = self.K, self.S
        row = self._teacher_row(plan)
        src = np.full(K, -1, np.int64)
        for s in range(S - 1, -1, -1):
            if plan.slot_client[s] >= 0:
                src[row[s]] = s
        refreshed = src >= 0
        safe = np.where(refreshed, src, 0)
        return self.sh.to_device(refreshed), self.sh.to_device(safe)

    def _student_keys(self, salt, plan):
        """Per-slot training keys, folded by client id (sh.slot_client_keys:
        stable under slot re-assignment across rounds).  The salt lands on
        device explicitly so the eager fold_in stays guard-legal."""
        salt = self.sh.to_device(np.uint32(salt))
        return self.sh.slot_client_keys(jax.random.fold_in(self.key, salt),
                                        plan)

    def _teacher_keys(self, salt, plan):
        """Teacher-step keys.  Leader mode: one per teacher row, folded by
        the compact cluster index hosting it (sh.cluster_keys), whichever
        slots the cluster's members hold.  Cluster mode: per-client keys,
        offset 10_000 to stay disjoint from the student stream (each slot
        steps on its own client's shard anyway)."""
        base = jax.random.fold_in(self.key,
                                  self.sh.to_device(np.uint32(salt)))
        if self.leader_mode:
            return self.sh.cluster_keys(base, self._row_cluster)
        return self.sh.slot_client_keys(base, plan, offset=10_000)

    # ------------------------------------------------------------- lifecycle
    def warmup(self):
        """Alg. 1 KD-establishment: teacher warm-up before round 1 as a
        separate jitted program (a checkpoint's teacher state already
        includes it, so the driver skips this on resume)."""
        cfg, sh = self.cfg, self.sh
        if cfg.teacher_warmup_epochs <= 0:
            return
        planw = self.scheduler.warmup_plan()

        def warm_of(steps):             # round budgets -> warm-up budgets
            return ((steps // max(cfg.local_epochs, 1))
                    * cfg.teacher_warmup_epochs).astype(np.int32)

        if self.leader_mode:
            # each wave of the warm plan would refresh its clusters from
            # the same snapshot with the same feed and keys, so one pass
            # over the rows the plan holds gives the same teachers
            w_n = warm_of(self._row_steps(planw))
            wx, wy = sh.stack_client_data(
                [self.store.base[int(r)] for r in self._feed_rows],
                max(int(w_n.max()), 1), cfg.batch_size, seed=cfg.seed)
            phase = sh.make_leader_teacher_phase(self.mesh, self.t_model[1],
                                                 self.opt)
            self.tp_k, self.ts_k, wl = phase(
                self.tp_k, self.ts_k, *sh.replicate(self.mesh, wx, wy),
                sh.to_device(w_n), self._teacher_keys(9001, planw))
        else:
            # one wave: cluster mode needs the whole cluster on the mesh
            wp = planw.wave(0)
            w_steps_all = warm_of(self.s_steps_all)
            wx_all, wy_all = sh.stack_client_data(
                self.store.base, int(w_steps_all.max()), cfg.batch_size,
                seed=cfg.seed)
            warm = sh.make_packed_teacher_phase(self.mesh, cfg.pack,
                                                self.t_model[1], self.opt,
                                                donate=cfg.donate)
            # prep's slot-sharded gather (sp/ss ride along unused) keeps the
            # warm program's donation usable, exactly as in run_round
            tp_s, ts_s, _sp, _ss = self._prep(
                self.tp_k, self.ts_k, self.sp_global,
                jnp.asarray(self._teacher_row(wp)))
            row_of = self.store.row_of
            wx, wy = sh.stage_on_slots(self.mesh, wp, wx_all, wy_all,
                                       row_maps=(row_of, row_of))
            tp_s, ts_s, wl = warm(
                tp_s, ts_s, wx, wy, jnp.asarray(wp.steps_for(w_steps_all)),
                self._teacher_keys(9001, wp), jnp.asarray(wp.sync_matrix()))
            refreshed, safe = self._scatter_src(wp)
            self.tp_k, self.ts_k = self._finish_warm(
                tp_s, ts_s, self.tp_k, self.ts_k, refreshed, safe)
        if self.progress:
            print(f"  warmup  teacher_loss={float(wl):.4f}")

    def prefetch(self, plan):
        """Overlap the NEXT round's slot staging with the current round's
        device compute (plans are pure functions of (seed, round), so
        peeking ahead is side-effect free; a lifecycle rebuild in between
        just invalidates the prefetch key and stage() falls back)."""
        if plan is not None and plan.active.any():
            self.stager.prefetch(plan.wave(0))

    def warm_async_merge(self):
        # zero-scale fold + N=1 stacked merge on the live student tree:
        # compiles the per-leaf arrival-fold programs during warm-in so a
        # first arrival inside the guarded window reuses the cache
        g = self.sp_global
        agg.add_scaled(g, g, 0.0)
        agg.staleness_weighted_average([g], [1.0], [1],
                                       decay=self.cfg.staleness_decay)

    def run_round(self, plan, rnd):
        cfg, sh = self.cfg, self.sh
        arrivals = self.arrivals
        if not plan.active.any():
            # every invited client dropped out: canonical state untouched —
            # unless buffered updates arrive, which merge host-side alone
            if arrivals:
                self.sp_global = merge_arrivals_only(arrivals,
                                                     cfg.staleness_decay)
            return {"teacher_loss": 0.0, "student_loss": 0.0}
        has_async = bool(arrivals) or bool(plan.stragglers.any())
        # the (L,) aggregation row is computed over the FULL plan (weights
        # and staleness renormalise globally) and SLICED per wave: each
        # wave's on-mesh contraction then yields an unnormalised partial
        # sum, and the partials fold exactly (agg.fold_partials)
        if not has_async:
            row, scales = plan.agg_row(), []
        elif plan.on_time.any() or arrivals:
            # split merge: the program contracts the on-time lanes with
            # ``row``; the host folds each arrival with its ``scale``
            row, scales = packed_async_row(plan.slot_weight, plan.on_time,
                                           arrivals, cfg.staleness_decay)
        else:
            # every active slot straggled and nothing arrived: zero row —
            # the program still trains the stragglers (buffered below), but
            # its aggregate is discarded and the global student holds
            row, scales = np.zeros(plan.n_slots, np.float32), []
        # Wave loop (DESIGN.md §15): every wave refreshes the teachers of
        # the clusters it holds from the round-start snapshots, streams
        # through the ONE compiled program, and folds into host-side
        # accumulators.  Teachers: leader-mode waves refresh a cluster
        # bitwise-reproducibly from the snapshot, so repeated merges agree.
        # Student: per-wave partial sums, folded below.
        tp0, ts0, sp_start = self.tp_k, self.ts_k, self.sp_global
        tp_acc, ts_acc = self.tp_k, self.ts_k
        partials, losses = [], []
        ws = plan.wave_slots or plan.n_slots
        n_waves = plan.n_waves
        for w in range(n_waves):
            wp = plan.wave(w)
            if not wp.active.any():
                continue
            with perf.span("stage"):
                with perf.span("stager"):
                    sx, sy = self.stager.stage(wp)
                with perf.span("prep"):
                    if self.leader_mode:
                        tp_s, ts_s = tp0, ts0
                        sp_s, ss_s = self._prep(sp_start)
                    else:
                        tp_s, ts_s, sp_s, ss_s = self._prep(
                            tp0, ts0, sp_start,
                            sh.to_device(self._teacher_row(wp)))
            with perf.span("compute"):
                with perf.span("dispatch"):
                    # the teacher operands: leader mode the (K,) rows'
                    # staged leader feed and budgets, and each slot's row
                    # (-1 idle); cluster mode each slot's own feed and
                    # budget, and the sync operator
                    s_n = wp.steps_for(self.s_steps_all)
                    if self.leader_mode:
                        t_row = self._teacher_row(wp)
                        tx, ty, t_n = self.tx_k, self.ty_k, self._row_steps(wp)
                        route = np.where(wp.active, t_row, -1).astype(np.int32)
                        t_lanes = wp.active & (t_n[t_row] > 0)
                    else:
                        tx, ty, t_n, route = sx, sy, s_n, wp.sync_matrix()
                        t_lanes = t_n > 0
                    perf.count("teacher_lanes", len(t_n))
                    t_n = sh.to_device(t_n)
                    # disjoint even/odd salts keep teacher and student PRNG
                    # streams from colliding on clients whose id equals
                    # their cluster index (sh.to_device: explicit transfers,
                    # legal under the guards); keys fold client/cluster
                    # ids, so a client's stream is invariant to its wave
                    # placement
                    (tp_s, ts_s, sp_s, sp_local, _ss_s, t_loss,
                     s_loss) = self.round_fn(
                        tp_s, ts_s, sp_s, ss_s, tx, ty, t_n, sx, sy,
                        sh.to_device(s_n), self._teacher_keys(2 * rnd, wp),
                        self._student_keys(2 * rnd + 1, wp),
                        sh.to_device(route),
                        sh.to_device(np.ascontiguousarray(
                            row[w * ws:(w + 1) * ws])))
                if w + 1 < n_waves:
                    # double-buffer: wave w+1's host gather + device_put
                    # run behind wave w's (async-dispatched) compute
                    self.stager.prefetch(plan.wave(w + 1))
                losses.append((t_loss, t_lanes.sum(), s_loss,
                               (s_n > 0).sum()))
            with perf.span("aggregate"):
                # leader mode merges the rows whose budget was non-zero;
                # cluster mode scatters from each cluster's first slot
                merge_ops = ((t_n,) if self.leader_mode
                             else self._scatter_src(wp))
                tp_acc, ts_acc, sp0_w = self._finish(
                    tp_s, ts_s, sp_s, tp_acc, ts_acc, *merge_ops)
                partials.append(sp0_w)
            if has_async:
                # straggler lanes: pre-aggregation students into the
                # buffer, each with its birth-round plan weight
                for t in np.flatnonzero(wp.stragglers):
                    self.buffer.push(AsyncUpdate(
                        client=int(wp.slot_client[t]), birth=rnd,
                        arrival=rnd + int(wp.delays[t]),
                        weight=float(wp.slot_weight[t]),
                        params=sh.take_rows(sp_local,
                                            jax.device_put(int(t)))))
        self.tp_k, self.ts_k = tp_acc, ts_acc
        # device values: the driver reads them a round later
        t_loss = fold_losses([(tl, tc) for tl, tc, _, _ in losses])
        s_loss = fold_losses([(sl, sc) for _, _, sl, sc in losses])
        # one wave: its aggregate IS the cohort mean, untouched (bit-
        # identical to the monolithic path); else fold the partial sums
        sp0 = partials[0] if len(partials) == 1 else agg.fold_partials(
            partials)
        if not has_async:
            self.sp_global = sp0
            return {"teacher_loss": t_loss, "student_loss": s_loss}
        if plan.on_time.any():
            acc = sp0
            for u, sc in zip(arrivals, scales):
                acc = agg.add_scaled(acc, u.params, sc)
            self.sp_global = acc
        elif arrivals:
            self.sp_global = merge_arrivals_only(arrivals,
                                                 cfg.staleness_decay)
        # else: all-straggler round with an empty buffer — student holds
        # (sp0 was the zero-row aggregate and is discarded)
        return {"teacher_loss": t_loss, "student_loss": s_loss}

    def eval(self):
        return evaluate(self.student_steps["eval"], self.sp_global,
                        self.test_set)

    def checkpoint_arrays(self):
        arrs = {"student": self.sp_global, "teachers": self.tp_k,
                "t_opts": self.ts_k,
                "labels": jnp.asarray(self.labels, jnp.int32)}
        if self.centroids is not None:
            arrs["centroids"] = jnp.asarray(self.centroids, jnp.float32)
        return arrs

    def restore_arrays(self, arrays):
        self.sp_global = arrays["student"]
        self.tp_k = arrays["teachers"]
        self.ts_k = arrays["t_opts"]
        if "centroids" in arrays:
            self.centroids = np.asarray(arrays["centroids"])
        self._rebuild_structures(np.asarray(arrays["labels"]))
        self._post_lifecycle()

    def history_extras(self):
        return {"num_clusters": self.K, "pack": self.scheduler.pack,
                "teacher_loss": [], "student_loss": []}

"""FedAvg / FedProx baseline strategies — loop reference AND packed mesh.

The paper's headline claims are comparative (FedSiKD vs FedAvg/FedProx at
alpha in {0.1, 0.5}), so the baselines deserve the same scalable runtime as
FedSiKD: ``PackedBaseline`` runs C = devices x pack clients in ONE jitted
collective program per round (`fed/sharded.py::make_packed_baseline_round`),
with the prox term computed against the broadcast global params and masked
per slot, and aggregation as a single all-clients grouped contraction
(``cluster_collectives.packed_weighted_mean`` with the plan's
example-weighted row ``RoundPlan.example_row``) — no cluster structure,
one group spanning every active slot.

Parity with the loop engine is by construction (DESIGN.md §2): the packed
engine stages the SAME per-client batch sequences, freezes each client's
carry after the same per-client step budget, starts every round from the
same broadcast global params with a fresh Adam state, and aggregates with
the same example weights (tests/test_baseline_parity.py: <= 1pt on full,
sampled, and dropout rounds).

Checkpoint payload (both engines): ``{"student": global_params}`` — local
opt state is per-round-fresh, so it is correctly absent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import perf
from repro.core import aggregation as agg
from repro.data.pipeline import ClientStore
from repro.fed import schedule
from repro.fed.algorithms.base import (Algorithm, local_epochs,
                                       merge_arrivals_only, packed_async_row,
                                       staleness_merge, tree_copy)
from repro.fed.driver import AsyncUpdate
from repro.fed.client import evaluate, make_steps
from repro.models.cnn import make_model
from repro.optim import adamw


class _BaselineBase(Algorithm):
    """Shared setup: single pseudo-cluster scheduler (uniform == stratified;
    the plan is just "which clients train this round"), the paper's teacher
    CNN as the federated model, example-weighted FedAvg aggregation."""

    def setup(self, ds, shards, cfg, key):
        if not isinstance(shards, ClientStore):
            shards = ClientStore(shards, universe=cfg.universe)
        self.ds, self.shards, self.cfg, self.key = ds, shards, cfg, key
        self.store = shards
        self.name = cfg.algorithm
        self.is_prox = cfg.algorithm == "fedprox"
        self.roster_labels = self._roster_labels(self.initial_active(cfg))
        self.scheduler = self._make_scheduler(cfg, self.roster_labels)
        self.opt = adamw(cfg.lr)
        t_init, t_fwd = make_model(ds.name, student=False)
        self.t_fwd = t_fwd
        self.steps = make_steps(t_fwd, self.opt, prox_mu=cfg.prox_mu)
        self.global_params = t_init(key)
        self.sizes = np.asarray(shards.sizes)
        self._setup_engine()
        self.stage_test_set(ds)

    def _roster_labels(self, active) -> np.ndarray:
        """Single pseudo-cluster label array over the CURRENT roster (-1
        marks off-roster clients, fed/lifecycle.py)."""
        return np.where(np.asarray(active), 0, -1).astype(np.int32)

    def apply_lifecycle(self, event):
        """No cluster structure to migrate: a roster change just rebuilds
        the scheduler over the active clients (periodic re-cluster cadence
        hits are no-ops beyond that)."""
        self.roster_labels = self._roster_labels(event.active)
        self.scheduler = self._make_scheduler(self.cfg, self.roster_labels)
        return {"active_clients": float(event.active.sum())}

    def _make_scheduler(self, cfg, labels):
        return schedule.RoundScheduler(
            labels, participation=cfg.participation,
            clients_per_round=self.clamped_clients_per_round(cfg, labels),
            dropout_rate=cfg.dropout_rate, seed=cfg.seed,
            async_mode=cfg.async_mode, round_deadline=cfg.round_deadline,
            straggler_frac=cfg.straggler_frac,
            latency_dist=cfg.latency_dist)

    def _setup_engine(self):
        pass

    def eval(self):
        return evaluate(self.steps["eval"], self.global_params, self.test_set)

    def checkpoint_arrays(self):
        # the roster rides the checkpoint: a resume past a lifecycle event
        # must rebuild the scheduler for the roster AS OF the checkpoint
        # round, not the initial one
        return {"student": self.global_params,
                "labels": jnp.asarray(self.roster_labels, jnp.int32)}

    def restore_arrays(self, arrays):
        self.global_params = arrays["student"]
        self.roster_labels = np.asarray(arrays["labels"])
        self.scheduler = self._make_scheduler(self.cfg, self.roster_labels)


# ---------------------------------------------------------------- loop engine
class LoopBaseline(_BaselineBase):
    """Sequential reference: per-client CE (FedAvg) or proximal-CE (FedProx)
    local epochs, example-weighted global mean."""

    engine = "loop"

    def run_round(self, plan, rnd):
        cfg, key = self.cfg, self.key
        delay_of = plan.delay_of()
        locals_, sizes = [], []
        for i in (int(i) for i in plan.participants):
            sh = self.shards[i]
            p = tree_copy(self.global_params)
            o = self.opt.init(p)
            if self.is_prox:
                p, _ = local_epochs(sh, p, o,
                                    jax.random.fold_in(key, rnd * 31 + i),
                                    cfg, step_fn=self.steps["prox"],
                                    extra=(self.global_params,))
            else:
                p, _ = local_epochs(sh, p, o,
                                    jax.random.fold_in(key, rnd * 31 + i),
                                    cfg, step_fn=self.steps["ce"])
            d = delay_of[i]
            if d > 0:              # straggler: update lands d rounds late
                self.buffer.push(AsyncUpdate(
                    client=i, birth=rnd, arrival=rnd + d,
                    weight=float(sh.num_examples), params=p))
            else:
                locals_.append(p)
                sizes.append(sh.num_examples)
        if self.arrivals or plan.stragglers.any():
            # semi-async merge under staleness-decayed example weights
            if locals_ or self.arrivals:
                self.global_params = staleness_merge(
                    locals_, [float(n) for n in sizes], self.arrivals,
                    cfg.staleness_decay)
        elif locals_:
            self.global_params = agg.fedavg(locals_, sizes)
        # else: an all-dropout round is a no-op (params unchanged)
        return {}


# ------------------------------------------------------------- packed engine
class PackedBaseline(_BaselineBase):
    """FedAvg/FedProx on the packed client mesh: every participating client
    runs its masked-scan local steps in one jitted program, then one
    all-clients example-weighted grouped mean broadcasts the new global
    model to every slot.  The global params enter the program replicated
    (P() spec) so FedProx's proximal term reads the ROUND-START anchor on
    every slot, exactly like the loop engine's ``extra=(global_params,)``.

    Wave scheduling (DESIGN.md §15): when the cohort exceeds one mesh-load
    (``cfg.waves`` / ``cfg.n_devices``), the round streams through the SAME
    compiled program wave by wave; every wave broadcasts the round-start
    global params, its contraction row is a slice of the globally-normalised
    example row, and ``aggregation.fold_partials`` sums the per-wave partial
    aggregates into the exact cohort mean."""

    engine = "sharded"

    def _make_scheduler(self, cfg, labels):
        return schedule.RoundScheduler(
            labels, participation=cfg.participation,
            clients_per_round=self.clamped_clients_per_round(cfg, labels),
            pack=cfg.pack, n_devices=self.forced_devices(cfg),
            waves=cfg.waves,
            dropout_rate=cfg.dropout_rate, seed=cfg.seed,
            async_mode=cfg.async_mode, round_deadline=cfg.round_deadline,
            straggler_frac=cfg.straggler_frac,
            latency_dist=cfg.latency_dist)

    def _setup_engine(self):
        from repro.fed import sharded as sh
        from repro.launch.mesh import make_fed_client_mesh
        cfg = self.cfg
        self.sh = sh
        store = self.store
        # the mesh holds ONE WAVE of the plan (DESIGN.md §15); multi-wave
        # rounds stream the cohort through it in wave_slots-sized chunks
        self.mesh = make_fed_client_mesh(self.scheduler.wave_slots,
                                         pack=cfg.pack,
                                         n_devices=self.scheduler.n_devices)
        self.S = self.scheduler.wave_slots
        # static per-client step budgets + one-off (R, steps, B, ...) staging
        # over the BASE shard pool — virtual clients alias base rows through
        # ``ClientStore.row_of``, so host memory is O(base), not O(universe)
        # (identical batch sequences to the loop engine's ClientShard.batches)
        self._base_counts = sh.client_step_counts(store.base, cfg.batch_size,
                                                  cfg.local_epochs)
        self.steps_all = self._base_counts[store.row_of]
        self.x_all, self.y_all = sh.stack_client_data(
            store.base, int(self._base_counts.max()), cfg.batch_size,
            seed=cfg.seed)
        self.round_fn = sh.make_packed_baseline_round(
            self.mesh, cfg.pack, self.t_fwd, self.opt,
            prox_mu=cfg.prox_mu if self.is_prox else 0.0,
            donate=cfg.donate)
        self.stager = sh.WaveStager(self.mesh, self.x_all, self.y_all,
                                    row_maps=(store.row_of, store.row_of),
                                    capacity=self.scheduler.n_waves + 1)
        # pre-round broadcast + fresh opt init as ONE jitted program whose
        # outputs carry the packed slot sharding — that is what makes the
        # round program's donation of (p_s, s_s) usable (DESIGN.md §13)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        S, opt = self.S, self.opt
        slot_sh = NamedSharding(self.mesh, P(sh.AXIS))

        def prep(global_p):
            p_s = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (S,) + a.shape), global_p)
            s_s = jax.vmap(opt.init)(p_s)       # fresh local opt (loop too)
            return p_s, s_s

        self._prep = jax.jit(prep, out_shardings=slot_sh)
        self._take0 = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a[0], t))

    def prefetch(self, plan):
        """Overlap the NEXT round's FIRST wave staging with this round's
        compute (see ShardedClusteredKD.prefetch); later waves prefetch
        inside ``run_round``'s wave loop."""
        if plan is not None and plan.active.any():
            self.stager.prefetch(plan.wave(0))

    def _slot_keys(self, rnd, plan):
        """Per-slot training keys (sh.slot_client_keys, stable under slot
        re-assignment; the disjoint 40_000 salt keeps the stream away from
        the clustered-KD engines')."""
        return self.sh.slot_client_keys(
            jax.random.fold_in(self.key,
                               self.sh.to_device(np.uint32(40_000 + rnd))),
            plan)

    def warm_async_merge(self):
        # zero-scale fold + N=1 stacked merge on the live global tree:
        # compiles the per-leaf arrival-fold programs during warm-in so a
        # first arrival inside the guarded window reuses the cache
        g = self.global_params
        agg.add_scaled(g, g, 0.0)
        agg.staleness_weighted_average([g], [1.0], [1],
                                       decay=self.cfg.staleness_decay)

    def run_round(self, plan, rnd):
        cfg, sh = self.cfg, self.sh
        arrivals = self.arrivals
        if not plan.active.any():
            # all invitees dropped out: no-op — unless buffered updates
            # arrive, which merge host-side alone
            if arrivals:
                self.global_params = merge_arrivals_only(
                    arrivals, cfg.staleness_decay)
            return {"train_loss": 0.0}
        has_async = bool(arrivals) or bool(plan.stragglers.any())
        # the aggregation row is ALWAYS built over the FULL (L,) plan —
        # ``example_row``/``packed_async_row`` renormalise over their own
        # arrays, so per-wave slices of the global row are the partial-sum
        # weights that make ``fold_partials`` exact (DESIGN.md §15)
        if not has_async:
            row, scales = plan.example_row(self.sizes), []
        elif plan.on_time.any() or arrivals:
            # split merge over raw example counts: on-time lanes contract
            # on-mesh, arrivals fold host-side (same units as the buffered
            # entries' ``weight = num_examples``)
            safe = np.where(plan.active, plan.slot_client, 0)
            n_slot = np.where(plan.active, self.sizes[safe], 0)
            row, scales = packed_async_row(n_slot, plan.on_time, arrivals,
                                           cfg.staleness_decay)
        else:
            row, scales = np.zeros(plan.n_slots, np.float32), []
        ws = plan.wave_slots or plan.n_slots
        n_waves = plan.n_waves
        partials, losses = [], []
        for w in range(n_waves):
            wp = plan.wave(w)
            if not wp.active.any():
                continue
            with perf.span("stage"):
                with perf.span("stager"):
                    xs, ys = self.stager.stage(wp)
                with perf.span("prep"):
                    p_s, s_s = self._prep(self.global_params)
            with perf.span("compute"):
                with perf.span("dispatch"):
                    # explicit transfers (sh.to_device), legal under the guards
                    n_w = wp.steps_for(self.steps_all)
                    p_s, p_local, _s_s, loss = self.round_fn(
                        p_s, s_s, xs, ys, sh.to_device(n_w),
                        self._slot_keys(rnd, wp),
                        sh.to_device(np.ascontiguousarray(
                            row[w * ws:(w + 1) * ws])),
                        self.global_params)
                if w + 1 < n_waves:
                    self.stager.prefetch(plan.wave(w + 1))
                # the loss read blocks until the wave's program ends
                with perf.span("sync"):
                    perf.count("host_syncs")
                    loss = float(loss)
                losses.append((loss, int((n_w > 0).sum())))
            with perf.span("aggregate"):
                # every slot holds the wave's partial aggregate after the
                # (globally-weighted) contraction
                partials.append(self._take0(p_s))
            if has_async:
                for t in np.flatnonzero(wp.stragglers):
                    self.buffer.push(AsyncUpdate(
                        client=int(wp.slot_client[t]), birth=rnd,
                        arrival=rnd + int(wp.delays[t]),
                        weight=float(self.sizes[int(wp.slot_client[t])]),
                        params=sh.take_rows(p_local, jax.device_put(int(t)))))
        if len(losses) == 1:
            loss = losses[0][0]
        else:
            tot = sum(c for _, c in losses)
            loss = float(sum(lo * c for lo, c in losses) / tot) if tot else 0.0
        p0 = partials[0] if len(partials) == 1 else agg.fold_partials(partials)
        if not has_async:
            self.global_params = p0
            return {"train_loss": loss}
        if plan.on_time.any():
            acc = p0
            for u, sc in zip(arrivals, scales):
                acc = agg.add_scaled(acc, u.params, sc)
            self.global_params = acc
        elif arrivals:
            self.global_params = merge_arrivals_only(arrivals,
                                                     cfg.staleness_decay)
        # else: all-straggler round, empty buffer — params unchanged
        return {"train_loss": loss}

    def history_extras(self):
        return {"pack": self.scheduler.pack, "train_loss": []}

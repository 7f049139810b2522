"""The ``Algorithm`` strategy protocol (DESIGN.md §10).

A federated run is a fixed round skeleton parameterized by an algorithm
strategy — the framing the KD-in-FL surveys use for FL systems, and the
seam that lets every algorithm (FedSiKD, RandomCluster, FedAvg, FedProx,
FL+HC) share ONE driver (`fed/driver.py::RoundDriver`) owning participation
plans, dropout, eval/record, history, and checkpoint/resume.

Lifecycle (driven by ``RoundDriver.run``):

1. ``setup(ds, shards, cfg, key)`` — everything before round 1 that is a
   pure function of ``(dataset, config, seed)``: clustering, model/step
   construction, the ``RoundScheduler``, staged data.  Must populate
   ``scheduler`` (the participation policy the driver plans with),
   ``labels`` (cluster assignment for the run fingerprint, or None) and
   ``history_extras()``'s inputs, and stages the test set on the device
   (``stage_test_set``) for ``eval``.  Runs on resume too — it must be
   deterministic, so recomputed clustering catches silent data/config
   drift between save and resume.
2. ``warmup()`` — pre-round establishment work whose RESULT is part of the
   checkpointed state (FedSiKD's teacher warm-up).  Skipped on resume: a
   checkpoint already banks it.
3. ``run_round(plan, rnd)`` — one round of local updates + aggregation for
   the plan's participants; returns a dict of per-round metrics the driver
   appends into the history (e.g. ``teacher_loss``).  Must tolerate an
   all-idle plan (every invitee dropped out) as a no-op.
4. ``eval()`` — (accuracy, loss) of the algorithm's CURRENT global model on
   the test set; the driver records it after every round, identically for
   every algorithm (acc AND loss — no more per-algorithm reporting drift).
5. ``checkpoint_arrays()`` / ``restore_arrays(arrays)`` — the array pytree
   that crosses the round boundary (exactly what ``fedstate.FedState``
   persists) and its inverse.  The driver owns WHEN to save/restore; the
   algorithm only owns WHAT.

``setup_rounds`` (default 0) is the number of rounds consumed by ``setup``
itself: FL+HC's clustering pre-round IS its round 1, so the driver records
an eval for it and starts the plan loop at round 2.

Lifecycle hook (DESIGN.md §11): when the run has a ``ClientLifecycle`` the
driver sets ``alg.lifecycle`` BEFORE ``setup`` (so setup clusters the
initial roster only) and calls ``apply_lifecycle(event)`` at the start of
every event round — the strategy re-clusters/migrates state and rebuilds
its ``scheduler`` for the new roster, returning per-round metrics.

Semi-async hook (DESIGN.md §12): with ``cfg.async_mode`` on, the driver
sets ``alg.buffer`` (the one ``StalenessBuffer``) after setup and
``alg.arrivals`` (this round's due updates) before each ``run_round``.  A
strategy then (a) excludes the plan's straggler participants from the
round's merge, pushing their trained updates into the buffer with their
birth-round base weight, and (b) merges on-time updates together with the
arrivals under the staleness-decayed weights — via ``staleness_merge`` on
the loop engines, or ``packed_async_row``'s split (on-mesh contraction row
+ host-side ``add_scaled`` factors) on the packed engines.  With no
stragglers and no arrivals the strategies take their synchronous fast
path, bit-identical to ``async_mode=False``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.data.pipeline import ClientShard
from repro.fed.client import stage_test_set
from repro.fed.lifecycle import ClientLifecycle, LifecycleEvent
from repro.fed.schedule import RoundPlan, RoundScheduler


class Algorithm:
    """Base strategy: one subclass per (algorithm family, engine)."""

    name: str = "?"
    engine: str = "loop"
    setup_rounds: int = 0
    # populated by setup():
    scheduler: RoundScheduler
    labels: Optional[np.ndarray] = None
    mesh = None              # the packed engines' client mesh
    test_set: tuple = ()     # stage_test_set's [n_batches, batch, ...] pair
    # set by the driver before setup():
    progress: bool = False
    lifecycle: Optional[ClientLifecycle] = None
    # semi-async (driver-set; None/() when cfg.async_mode is off):
    buffer = None            # the driver's StalenessBuffer
    arrivals: tuple = ()     # AsyncUpdates merging this round

    def setup(self, ds, shards: list[ClientShard], cfg, key) -> None:
        raise NotImplementedError

    def stage_test_set(self, ds) -> None:
        """Put ``ds``'s test set on the device once for every ``eval``
        (``client.stage_test_set``), replicated over the strategy's
        ``mesh`` where it has one."""
        self.test_set = stage_test_set(ds.x_test, ds.y_test, self.mesh)

    def warmup(self) -> None:
        """Pre-round establishment (checkpointed state; skipped on resume)."""

    def apply_lifecycle(self, event: LifecycleEvent) -> dict:
        """React to a roster change / re-cluster cadence hit: re-cluster the
        active clients, migrate cross-round state, rebuild ``scheduler``.
        Returns per-round metrics (driver keeps them round-aligned)."""
        raise NotImplementedError(
            f"algorithm {self.name!r} does not support the client lifecycle")

    # --------------------------------------------------- lifecycle helpers
    def initial_active(self, cfg) -> np.ndarray:
        """(total_clients,) bool roster before round 1 — spans the virtual
        universe when ``cfg.universe`` is set (lifecycle excludes it)."""
        if self.lifecycle is None:
            return np.ones(cfg.total_clients, bool)
        return self.lifecycle.initial_active()

    def clamped_clients_per_round(self, cfg, labels) -> Optional[int]:
        """``clients_per_round`` clamped to the current roster size (a
        shrinking roster must not make the scheduler unsatisfiable)."""
        if cfg.participation == "full" or cfg.clients_per_round is None:
            return None
        return min(cfg.clients_per_round, int((np.asarray(labels) >= 0).sum()))

    def forced_devices(self, cfg) -> Optional[int]:
        """Mesh size pinned independently of the current roster.

        ``cfg.n_devices`` (the wave-scheduling knob, DESIGN.md §15) wins
        when set.  Otherwise a lifecycle pins the mesh to the largest
        roster any join can produce, so re-clustering never changes the
        compiled programs' slot count."""
        if cfg.n_devices is not None:
            return cfg.n_devices
        if self.lifecycle is None:
            return None
        from repro.launch.mesh import fed_mesh_layout
        cap = cfg.clients_per_round or cfg.num_clients
        return fed_mesh_layout(cap, pack=cfg.pack)[0]

    def prefetch(self, plan: RoundPlan) -> None:
        """Optional overlap hook: begin staging ``plan``'s data while the
        CURRENT round computes (the driver hands in the next round's plan
        before ``run_round``; plans are pure functions of (seed, round), so
        peeking ahead is side-effect free).  Default: no-op — only the
        packed engines double-buffer their slot staging."""

    def run_round(self, plan: RoundPlan, rnd: int) -> dict:
        raise NotImplementedError

    def eval(self) -> tuple[float, float]:
        raise NotImplementedError

    def checkpoint_arrays(self) -> dict:
        raise NotImplementedError

    def restore_arrays(self, arrays: dict) -> None:
        raise NotImplementedError

    def history_extras(self) -> dict:
        """Algorithm-specific history fields (scalars, or [] lists that
        ``run_round`` metrics append into)."""
        return {}

    def warm_async_merge(self) -> None:
        """Pre-compile the host-side arrival-fold programs.

        The packed engines fold buffered stale updates eagerly
        (``aggregation.add_scaled`` per arrival, ``_merge_stacked`` on
        all-straggler rounds), so the per-leaf mul/add programs compile
        on the FIRST round that actually merges an arrival — which under
        ``FedConfig.guards`` may fall inside the sentinel window and read
        as a steady-state recompile.  The driver calls this once during
        warm-in; overrides run the fold on the live global tree with a
        zero scale and discard the result.  Default: nothing to warm."""


# -------------------------------------------------- shared semi-async helpers
def staleness_merge(on_params, on_weights, arrivals, decay: float):
    """One round's merged global model on a LOOP engine: the on-time updates
    (staleness 0) and the buffered ``arrivals`` combined under the decayed,
    renormalised weights of ``aggregation.staleness_weights``.  The caller
    guarantees the merge set is non-empty."""
    params = list(on_params) + [u.params for u in arrivals]
    base = list(on_weights) + [float(u.weight) for u in arrivals]
    stale = [0] * len(on_params) + [u.staleness for u in arrivals]
    return agg.staleness_weighted_average(params, base, stale, decay=decay)


def packed_async_row(w_slot, on_time, arrivals, decay: float):
    """The PACKED engines' split of the same merge: ``(row, scales)`` where
    ``row`` is the (S,) on-mesh contraction row (on-time slots' base weights
    over the grand total) and ``scales`` are the per-arrival host-side
    ``aggregation.add_scaled`` factors (decayed weight over the same total).
    Works because ``cluster_collectives.packed_weighted_mean`` computes the
    UNNORMALISED sum ``sum_i row_i x_i`` — the program contracts the on-time
    lanes, the host folds the arrivals, and together they reproduce
    ``staleness_weights`` exactly (stale lanes are zero-weighted, so the
    fixed-shape program never recompiles)."""
    w = np.where(np.asarray(on_time), np.asarray(w_slot, np.float64), 0.0)
    f = agg.staleness_factor([u.staleness for u in arrivals], decay)
    total = w.sum() + sum(float(u.weight) * float(fi)
                          for u, fi in zip(arrivals, f))
    scales = [float(u.weight) * float(fi) / total
              for u, fi in zip(arrivals, f)]
    return (w / total).astype(np.float32), scales


def merge_arrivals_only(arrivals, decay: float):
    """A round with arrivals but NO on-time participant (every invitee a
    straggler or a dropout): the merge is the arrivals alone."""
    return staleness_merge([], [], arrivals, decay)


# ------------------------------------------------ shared loop-engine helpers
def local_epochs(shard: ClientShard, params, opt_state, key, cfg,
                 *, step_fn, extra=()):
    """``cfg.local_epochs`` of sequential local steps on one client's shard
    (the loop engines' unit of client work)."""
    for epoch in range(cfg.local_epochs):
        for x, y in shard.batches(cfg.batch_size, epoch=epoch, seed=cfg.seed):
            key, sub = jax.random.split(key)
            params, opt_state, _ = step_fn(params, opt_state,
                                           {"x": x, "y": y}, sub, *extra)
    return params, opt_state


def cluster_epochs(members: list[ClientShard], params, opt_state, key, cfg,
                   *, step_fn, epochs: int):
    """Teacher pass over the union of cluster members' shards (Alg.1 l.12).

    The cluster data is POOLED and shuffled globally — visiting member shards
    sequentially causes catastrophic interference under label skew (each
    shard's classes overwrite the previous one's; measured in EXPERIMENTS.md
    calibration: loss diverges 2.5 -> 2.9).  A single-member "union"
    (teacher_data="leader") is the member itself — keeping its client_id
    keeps the batch shuffle identical to the sharded engine's teacher feed,
    which is what makes loop/sharded parity tight."""
    if len(members) == 1:
        pooled = members[0]
    else:
        pooled = ClientShard(
            client_id=-1,
            x=np.concatenate([sh.x for sh in members]),
            y=np.concatenate([sh.y for sh in members]))
    for epoch in range(epochs):
        for x, y in pooled.batches(cfg.batch_size, epoch=epoch, seed=cfg.seed):
            key, sub = jax.random.split(key)
            params, opt_state, _ = step_fn(params, opt_state,
                                           {"x": x, "y": y}, sub)
    return params, opt_state


def tree_copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)

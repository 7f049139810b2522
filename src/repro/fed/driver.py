"""RoundDriver: the ONE federated round skeleton (DESIGN.md §10, §11).

Every algorithm runs through this driver, which owns exactly the things
that used to be triplicated across the clustered-KD, fedavg/fedprox, and
sharded paths of the old ``rounds.py`` monolith:

- the per-round ``RoundPlan`` (participation sampling + client dropout) —
  pulled from the strategy's ``RoundScheduler``;
- the client lifecycle (``fed/lifecycle.py``): deterministic join/leave
  events and the re-clustering cadence.  On an event round the driver hands
  the strategy the new roster (``Algorithm.apply_lifecycle``) BEFORE
  planning, and records the evolving cluster assignment in the history's
  ``labels_history`` (one ``[round, labels]`` entry per re-clustering);
- eval/record: after every round, acc AND loss on the test set, printed
  identically for every algorithm under ``progress=True``;
- the one-round lead (DESIGN.md §13): a round's metrics, acc and loss may
  come back as device values; the driver reads them (``_settle``: one
  ``jax.device_get``) only after it has dispatched the NEXT round, so the
  host plans and stages round r+1 while the device runs round r.  At most
  one round is ever unread when ``run_round`` is entered; a round that
  saves a checkpoint, and the last round, are read at once;
- the running history (one schema for all algorithms/engines, plus the
  strategy's ``history_extras`` and per-round ``run_round`` metrics).
  Per-round metric lists stay ROUND-ALIGNED even when a strategy emits a
  metric only in some rounds (e.g. re-cluster metrics): rounds without the
  metric get an explicit ``None`` entry;
- checkpoint/save/resume (`fed/fedstate.py`, DESIGN.md §9): the SINGLE
  copy of the save-cadence, restore, fingerprint-validation and
  skip-warmup-on-resume logic.  Resumed runs are bit-identical to
  uninterrupted ones for every checkpointable algorithm — including across
  a re-clustering boundary, because lifecycle events replay from (seed,
  round) and the evolved labels/centroids ride the checkpoint arrays
  (tests/test_fault_tolerance.py, tests/test_lifecycle.py);
- the bounded-staleness buffer (semi-async rounds, DESIGN.md §12): with
  ``cfg.async_mode`` on, the schedule's speed model marks some participants
  as stragglers whose updates land ``d >= 1`` rounds late
  (``RoundPlan.slot_delay``).  The driver owns the ONE ``StalenessBuffer``
  holding those in-flight updates: before each round it pops the updates
  arriving this round — merged by the strategy under the staleness-decayed
  weights of ``core.aggregation.staleness_weights`` if their staleness
  ``s <= cfg.max_staleness``, dropped and counted otherwise — and after the
  round it accounts stragglers/merges/drops/occupancy in the history.
  Buffer contents ride the checkpoint (entry params as a ``_async_buffer``
  sibling of the algorithm's arrays, entry metadata in the meta JSON), so
  kill-and-resume is bit-identical even mid-buffer
  (tests/test_async_rounds.py).

The driver is engine-agnostic: strategies hide whether a round is a Python
loop over clients or one jitted collective program on the packed mesh.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, NamedTuple

import jax
import numpy as np

from repro import guards, perf
from repro.data.pipeline import ClientStore, make_client_shards
from repro.fed import fedstate
from repro.fed.lifecycle import ClientLifecycle

# History keys the driver appends itself (or that are not one-entry-per-
# round); everything else list-valued is a per-round metric and must stay
# round-aligned by _append_metrics.
_NON_METRIC_KEYS = frozenset({"acc", "loss", "round", "participants",
                              "labels_history"})

# Bumped whenever the fingerprint schema changes meaning: v2 added ``pack``,
# ``k_range`` and the lifecycle knobs — a v1 checkpoint resuming under code
# that would silently run a different slot layout must refuse instead.
# v3 added the semi-async knobs (and the buffer riding the checkpoint).
# v4 added the wave-scheduling knobs (``universe``/``n_devices``/``waves``,
# DESIGN.md §15): the universe changes the client population, the mesh knobs
# change the per-wave collective numerics.
FINGERPRINT_VERSION = 4

# FedConfig fields that are deliberately NOT part of the resume identity:
# execution knobs whose change leaves the numerical run unchanged.  Every
# FedConfig field must be either fingerprinted below or listed here —
# enforced statically by fedlint FL002 and at runtime by
# tests/test_config_surface.py.  ``rounds`` is execution-only because
# resuming with a higher target is the point of resume; the checkpoint
# cadence/layout knobs and the donation/prefetch/async/guards toggles are
# pure execution strategy (tier-1 proves donate/prefetch/async_ckpt runs
# bit-identical to the eager path).
EXECUTION_ONLY = frozenset({
    "rounds", "ckpt_dir", "ckpt_every", "ckpt_keep", "resume",
    "donate", "prefetch", "async_ckpt", "guards",
})


class _Unread(NamedTuple):
    """One dispatched round whose values the driver has not read yet:
    ``token`` is its ``perf.round_token()`` (the read counts in its own
    round's bucket), ``metrics`` None for a setup round (no
    ``run_round``)."""

    rnd: int
    token: int
    metrics: dict | None
    participants: int
    acc: Any
    loss: Any


@dataclasses.dataclass
class AsyncUpdate:
    """One client update in flight between rounds: computed against round
    ``birth``'s global model, reaching the server's merge at ``arrival``
    (= birth + the speed model's delay).  ``weight`` is the update's
    BIRTH-round base weight (the plan weight for clustered-KD strategies,
    the client's example count for the baselines); the merge round decays it
    by ``(1 + staleness)^-cfg.staleness_decay`` (core/aggregation.py).
    ``params is None`` marks a tombstone: an update already known to exceed
    ``max_staleness`` at arrival — its params are never stored, but the
    entry still rides the buffer so the arrival round counts the drop (and a
    resumed run counts it identically)."""

    client: int
    birth: int
    arrival: int
    weight: float
    params: Any = None

    @property
    def staleness(self) -> int:
        return self.arrival - self.birth


class StalenessBuffer:
    """The driver's bounded-staleness buffer: every straggler update a
    strategy produces is ``push``-ed here at its birth round, and
    ``pop_due`` hands back the updates whose arrival round has come —
    split into mergeable arrivals and the count of dropped-too-stale ones.
    Entries with ``staleness > max_staleness`` are tombstoned at push time
    (params discarded immediately) so the buffer never holds model copies
    it will not merge."""

    def __init__(self, max_staleness: int):
        if max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {max_staleness}")
        self.max_staleness = max_staleness
        self.entries: list[AsyncUpdate] = []

    def __len__(self) -> int:
        return len(self.entries)

    def push(self, update: AsyncUpdate) -> None:
        if update.staleness > self.max_staleness:
            update = dataclasses.replace(update, params=None)
        self.entries.append(update)

    def pop_due(self, round_index: int) -> tuple[list[AsyncUpdate], int]:
        """(arrivals to merge this round, number dropped as too stale)."""
        due = [u for u in self.entries if u.arrival <= round_index]
        self.entries = [u for u in self.entries if u.arrival > round_index]
        arrivals = [u for u in due if u.params is not None]
        return arrivals, len(due) - len(arrivals)

    # ------------------------------------------------- checkpoint plumbing
    def meta(self) -> list[dict]:
        """JSON-safe entry metadata, in buffer order (fedstate meta JSON)."""
        return [{"client": int(u.client), "birth": int(u.birth),
                 "arrival": int(u.arrival), "weight": float(u.weight),
                 "has_params": u.params is not None}
                for u in self.entries]

    def params_list(self) -> list:
        """Param pytrees of the NON-tombstone entries, in buffer order
        (the ``_async_buffer`` array pytree of the checkpoint)."""
        return [u.params for u in self.entries if u.params is not None]

    def load(self, meta: list[dict], params: list) -> None:
        """Rebuild the buffer from a checkpoint's (meta, params) pair."""
        it = iter(params)
        self.entries = [
            AsyncUpdate(client=int(e["client"]), birth=int(e["birth"]),
                        arrival=int(e["arrival"]), weight=float(e["weight"]),
                        params=next(it) if e["has_params"] else None)
            for e in meta]


def fingerprint(cfg, labels=None) -> dict:
    """Run identity stored with every checkpoint and re-validated on resume
    (fedstate.restore_run): every config field whose change would make the
    resumed tail a DIFFERENT run — sampling identity, data/model identity,
    and training hyperparameters.  Deliberately absent: ``rounds`` (resuming
    with a higher target is the point) and ``ckpt_every``/``ckpt_keep``
    (cadence is not identity).  ``labels`` (the INITIAL cluster assignment)
    is recomputed deterministically at startup, so comparing it also catches
    silent data/config drift between save and resume; labels evolved by
    lifecycle re-clustering live in the checkpoint ARRAYS instead."""
    fp = {"fingerprint_version": FINGERPRINT_VERSION,
          "algorithm": cfg.algorithm, "engine": cfg.engine,
          "seed": cfg.seed, "num_clients": cfg.num_clients,
          "alpha": cfg.alpha, "num_clusters": cfg.num_clusters,
          "participation": cfg.participation,
          "clients_per_round": cfg.clients_per_round,
          "dropout_rate": cfg.dropout_rate,
          # pack/n_devices/waves change the packed-mesh wave layout (and
          # with it the collective numerics): a pack=4 checkpoint silently
          # resuming under pack=1 is a different run, and so is a 4-wave
          # checkpoint resuming single-wave.  ``universe`` changes the
          # virtual client population itself.
          "pack": cfg.pack, "universe": cfg.universe,
          "n_devices": cfg.n_devices, "waves": cfg.waves,
          "join_schedule": cfg.join_schedule, "leave_rate": cfg.leave_rate,
          "recluster_every": cfg.recluster_every,
          "local_epochs": cfg.local_epochs, "batch_size": cfg.batch_size,
          "lr": cfg.lr, "student_lr": cfg.student_lr,
          "kd_temperature": cfg.kd_temperature, "kd_alpha": cfg.kd_alpha,
          "kd_impl": cfg.kd_impl, "prox_mu": cfg.prox_mu,
          "teacher_warmup_epochs": cfg.teacher_warmup_epochs,
          "teacher_data": cfg.teacher_data,
          "cluster_weighting": cfg.cluster_weighting,
          "dp_noise": cfg.dp_noise,
          # semi-async identity: the speed model reshapes every plan and the
          # buffer's merge math — a sync checkpoint must not resume async
          "async_mode": cfg.async_mode, "max_staleness": cfg.max_staleness,
          "staleness_decay": cfg.staleness_decay,
          "round_deadline": cfg.round_deadline,
          "straggler_frac": cfg.straggler_frac,
          "latency_dist": cfg.latency_dist}
    if cfg.num_clusters is None:
        # with metric-voted K the sweep bounds decide the cluster count
        fp["k_range"] = cfg.k_range
    if labels is not None:
        fp["labels"] = [int(l) for l in labels]
    return fp


class RoundDriver:
    """Runs ``cfg.rounds`` federated rounds of one Algorithm strategy."""

    def __init__(self, ds, cfg, algorithm, *, progress: bool = False):
        self.ds, self.cfg, self.alg = ds, cfg, algorithm
        self.progress = progress
        self.buffer: StalenessBuffer | None = None
        self.writer: fedstate.AsyncCheckpointWriter | None = None

    def run(self) -> dict:
        ds, cfg, alg = self.ds, self.cfg, self.alg
        alg.progress = self.progress
        # the BASE shard pool is O(num_clients); a virtual universe
        # (cfg.universe, DESIGN.md §15) aliases it host-side — the store is
        # rebuilt deterministically from (seed, num_clients, universe), so
        # it never rides a checkpoint
        shards = ClientStore(
            make_client_shards(ds, cfg.num_clients, cfg.alpha,
                               seed=cfg.seed),
            universe=cfg.universe)
        lc = ClientLifecycle.from_config(cfg)
        alg.lifecycle = lc
        alg.setup(ds, shards, cfg, jax.random.PRNGKey(cfg.seed))
        if cfg.async_mode:
            self.buffer = StalenessBuffer(cfg.max_staleness)
        alg.buffer = self.buffer
        fp = fingerprint(cfg, labels=alg.labels)

        history = {"acc": [], "loss": [], "round": [], "participants": [],
                   "algorithm": cfg.algorithm, "engine": cfg.engine,
                   "participation": cfg.participation,
                   "dropout_rate": cfg.dropout_rate}
        if lc is not None and alg.labels is not None:
            history["labels_history"] = [[0, [int(l) for l in alg.labels]]]
        history.update(alg.history_extras())

        # ---- resume-or-warmup: a checkpoint's state already includes the
        # establishment work (warm-up / pre-round), so a resumed run skips it
        start_round = 0
        resumed = False
        if (cfg.resume and cfg.ckpt_dir
                and fedstate.latest_round(cfg.ckpt_dir) is not None):
            like = alg.checkpoint_arrays()
            if self.buffer is not None:
                # the buffer's param count is variable, so the restore
                # template comes from the checkpoint's OWN entry metadata
                # (each live entry is structurally a global-student copy)
                n_live = sum(
                    1 for e in fedstate.latest_meta(cfg.ckpt_dir).get(
                        "buffer", []) if e.get("has_params"))
                like["_async_buffer"] = [like["student"]] * n_live
            st = fedstate.restore_run(cfg.ckpt_dir, like, expect_meta=fp)
            buf_params = st.arrays.pop("_async_buffer", [])
            alg.restore_arrays(st.arrays)
            if self.buffer is not None:
                self.buffer.load(st.buffer_meta, buf_params)
            history.update(st.history)
            start_round = st.round_index
            resumed = True
            if self.progress:
                print(f"  resumed from round {start_round} ({cfg.ckpt_dir})")
        if not resumed:
            alg.warmup()
            # rounds consumed by setup itself (FL+HC's clustering pre-round
            # trains every client and IS the run's round 1)
            for rnd in range(1, min(alg.setup_rounds, cfg.rounds) + 1):
                self._settle(history, _Unread(
                    rnd, perf.round_token(), None, cfg.num_clients,
                    *alg.eval()), lag=0)
                self._save(history, fp, rnd)
            start_round = min(alg.setup_rounds, cfg.rounds)

        if cfg.ckpt_dir and cfg.async_ckpt:
            self.writer = fedstate.AsyncCheckpointWriter(
                cfg.ckpt_dir, keep_last=cfg.ckpt_keep)
        # Runtime sanitizers (guards.py, DESIGN.md §14).  The first rounds
        # are warm-in: round-program compiles, the first eval, the first
        # lifecycle re-cluster at the new roster size all legitimately
        # compile there.  From ``guard_from`` on, every round must (a) run
        # its plan/stage/compute path without a single implicit
        # host->device transfer and (b) finish — eval, checkpoint, and any
        # semi-async merge included — with zero new compilations.
        guard_from = None
        if cfg.guards:
            guards.install()
            guard_from = start_round + 3
            if cfg.guards == "jitter":
                # race harness (DESIGN.md §16): deterministic seeded sleeps
                # at every thread-handoff point — prefetch workers, wave
                # LRU eviction, async checkpoint submit/drain — stretch
                # the interleavings; the history must not change by a bit
                guards.enable_jitter(cfg.seed)
        unread = None                   # the round dispatched last
        try:
            for rnd in range(start_round + 1, cfg.rounds + 1):
                guarded = guard_from is not None and rnd >= guard_from
                compile_base = guards.compile_count() if guarded else 0
                with perf.span("round_total"):
                    metrics = {}
                    if lc is not None:
                        ev = lc.event(rnd)
                        if ev.recluster:
                            metrics.update(alg.apply_lifecycle(ev) or {})
                            if alg.labels is not None:
                                history["labels_history"].append(
                                    [rnd, [int(l) for l in alg.labels]])
                            if self.progress and ev.changed:
                                print(f"  round {rnd:3d}  lifecycle: "
                                      f"+{len(ev.joins)} joined, "
                                      f"-{len(ev.leaves)} left, "
                                      f"{int(ev.active.sum())} active")
                    hot = (guards.no_implicit_transfers() if guarded
                           else contextlib.nullcontext())
                    with hot:
                        with perf.span("plan"):
                            plan = alg.scheduler.plan(rnd)
                        if cfg.prefetch and rnd < cfg.rounds \
                                and (lc is None
                                     or not lc.event(rnd + 1).recluster):
                            # double-buffer: start staging round N+1's slot
                            # data while round N computes (plans are pure
                            # functions of (seed, round); a lifecycle event
                            # round is skipped — its plan only exists after
                            # apply_lifecycle rebuilds the scheduler)
                            with perf.span("prefetch"):
                                alg.prefetch(alg.scheduler.plan(rnd + 1))
                        if self.buffer is not None:
                            arrivals, dropped = self.buffer.pop_due(rnd)
                            alg.arrivals = tuple(arrivals)
                            metrics.update(alg.run_round(plan, rnd))
                            alg.arrivals = ()
                            metrics["stragglers"] = int(plan.stragglers.sum())
                            metrics["stale_merged"] = len(arrivals)
                            metrics["stale_dropped"] = dropped
                            metrics["buffered"] = len(self.buffer)
                        else:
                            metrics.update(alg.run_round(plan, rnd))
                with perf.span("eval"):
                    acc, loss = alg.eval()
                # round rnd is dispatched: now read round rnd-1
                done, unread = unread, _Unread(
                    rnd, perf.round_token(), metrics, int(plan.active.sum()),
                    acc, loss)
                if done is not None:
                    self._settle(history, done, lag=1)
                if rnd == cfg.rounds or self._saves(rnd):
                    # a checkpoint holds its own round's history, and the
                    # run returns with every round read
                    self._settle(history, unread, lag=0)
                    unread = None
                with perf.span("checkpoint"):
                    self._save(history, fp, rnd)
                perf.end_round()
                if guard_from is not None and self.buffer is not None \
                        and rnd == start_round + 1:
                    # warm-in: pre-compile the host-side arrival-fold
                    # programs on the post-round global tree (its sharding
                    # matches what real arrivals fold into), so the first
                    # arrival inside the guarded window is cache-hit only
                    alg.warm_async_merge()
                if guarded:
                    guards.assert_no_new_compiles(
                        compile_base, f"round {rnd}")
        finally:
            if cfg.guards == "jitter":
                guards.disable_jitter()
            if self.writer is not None:
                # drain pending writes (and surface any writer error) even
                # on an exception: a killed run must still leave only
                # complete, atomically-published checkpoints behind
                writer, self.writer = self.writer, None
                writer.close()
        return history

    # ------------------------------------------------------------ internals
    def _append_metrics(self, history, metrics):
        """Append this round's metrics, keeping every per-round metric list
        the same length: a metric a strategy emits only in SOME rounds (a
        re-cluster metric, say) gets explicit ``None`` entries for the
        others, instead of silently compacting against earlier rounds."""
        # run_round records so far = recorded rounds minus setup's own
        # evals (FL+HC's clustering pre-round never calls run_round)
        n_prev = max(0, len(history["round"])
                     - min(self.alg.setup_rounds, self.cfg.rounds))
        keys = set(metrics) | {k for k, v in history.items()
                               if k not in _NON_METRIC_KEYS
                               and isinstance(v, list)}
        for k in sorted(keys):
            lst = history.setdefault(k, [])
            if len(lst) < n_prev:
                lst.extend([None] * (n_prev - len(lst)))
            lst.append(metrics.get(k))

    def _settle(self, history, unread, lag):
        """Read one round's values and append them to ``history`` in round
        order.  Device values come back in one ``jax.device_get``
        (``perf``: ``settle`` > ``sync``, one ``host_syncs``, and
        ``read_lag``, the rounds dispatched after this one, all in that
        round's own bucket); host floats pass through unread."""
        values = (unread.metrics, unread.acc, unread.loss)
        if any(isinstance(v, jax.Array)
               for v in jax.tree_util.tree_leaves(values)):
            tok = unread.token
            with perf.span("settle", round_id=tok), \
                    perf.span("sync", round_id=tok):
                perf.count("host_syncs", round_id=tok)
                perf.count("read_lag", lag, round_id=tok)
                values = jax.tree_util.tree_map(
                    lambda v: v.item() if isinstance(v, np.ndarray)
                    and not v.ndim else v,
                    jax.device_get(values))
        metrics, acc, loss = values
        if metrics is not None:
            self._append_metrics(history, metrics)
        history["participants"].append(unread.participants)
        history["acc"].append(acc)
        history["loss"].append(loss)
        history["round"].append(unread.rnd)
        if self.progress:
            print(f"  round {unread.rnd:3d}  acc={acc:.4f}  loss={loss:.4f}  "
                  f"clients={unread.participants}")

    def _saves(self, rnd):
        cfg = self.cfg
        return bool(cfg.ckpt_dir) and (rnd % cfg.ckpt_every == 0
                                       or rnd == cfg.rounds)

    def _save(self, history, fp, rnd):
        cfg = self.cfg
        if self._saves(rnd):
            arrays = self.alg.checkpoint_arrays()
            buffer_meta = []
            if self.buffer is not None:
                # in-flight updates cross the round boundary too: their
                # params ride the array pytree, their (client, birth,
                # arrival, weight) metadata the meta JSON
                arrays["_async_buffer"] = self.buffer.params_list()
                buffer_meta = self.buffer.meta()
            state = fedstate.FedState(
                round_index=rnd, arrays=arrays, history=history, meta=fp,
                buffer_meta=buffer_meta)
            if self.writer is not None:
                # device-to-host copy + npz write happen on the writer
                # thread; submit only snapshots the mutable JSON members
                # (the array pytrees are immutable and never donated)
                self.writer.submit(state)
            else:
                fedstate.save_round(cfg.ckpt_dir, state,
                                    keep_last=cfg.ckpt_keep)

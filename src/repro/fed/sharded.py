"""Client-packed federated runtime on a device mesh: the jitted collective
PROGRAMS and staging helpers the sharded algorithm strategies call
(`fed/algorithms/`, DESIGN.md §10).

Each device on the 1-D ``"clients"`` mesh axis hosts a ``(pack,)`` block of
client lanes, so ``C = devices x pack`` clients run in ONE jitted program —
the clients==devices coupling of the original runtime is gone.  Local steps
are ``vmap``-ed over the lane axis inside ``shard_map``; aggregation is a
grouped weighted-gather contraction whose operators are RUNTIME arrays
built from a per-round ``RoundPlan`` (fed/schedule.py), so partial
participation (sampled client subsets) re-uses the compiled program across
rounds (DESIGN.md §3, §8).

One mesh entry point per algorithm family:

- ``make_leader_kd_round``       — the full FedSiKD round (Alg. 1) with
  ``teacher_data="leader"``: each cluster's teacher refreshed ONCE, on its
  leader's batches (a ``vmap`` over the canonical ``(K, ...)`` teacher
  rows), then student DISTILLATION steps against each lane's cluster
  teacher that call the fused Pallas ``kd_distillation_loss`` kernel inside
  the ``jax.lax.scan`` step loop, and the grouped student aggregation — all
  masked by the plan's step budgets (idle slots and absent clusters
  freeze).  ``make_leader_teacher_phase`` is its pre-round
  KD-establishment (teacher warm-up).
- ``make_packed_kd_round``       — the same round with
  ``teacher_data="cluster"``: per-cluster TEACHER REPLICAS on every
  participating slot, each stepping on its own member's shard, reconciled
  by the intra-cluster teacher sync (``cluster_collectives.
  packed_teacher_sync``) — data-parallel teacher training.
  ``make_packed_teacher_phase`` is its warm-up.
- ``make_packed_baseline_round`` — FedAvg / FedProx: plain-CE (or proximal
  CE against the broadcast round-start global params) local steps, then ONE
  all-clients example-weighted grouped mean (no cluster structure — a
  single group spanning every active slot).

Per-slot step masking: every slot is padded to the same static number of
scan steps (shorter clients' extra steps are frozen via ``jnp.where``, idle
slots run zero), so the packed engine performs exactly the same number of
REAL updates per participating client as the sequential loop engine — that
is what makes loop/packed parity tight, on full AND sampled rounds
(tests/test_sharded_kd.py, tests/test_schedule.py,
tests/test_baseline_parity.py).

Round-to-round state handling (slot gather/scatter of canonical per-cluster
state) lives with the strategies in ``fed/algorithms/``; checkpoint/resume
lives with the driver in ``fed/driver.py``.

This runtime drives the paper's CNNs (or any pure fwd fn).  Tests and
examples run it on CPU placeholder devices
(``--xla_force_host_platform_device_count``); ``chip_smoke.py`` runs it on
the TPU.  Every program is ``jax.shard_map(..., check_vma=False)``: the
Pallas ``pallas_call`` inside the KD step has no replication rule.
"""
from __future__ import annotations

import functools
import math
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import guards, perf
from repro.core import cluster_collectives as cc
from repro.core.distill import distillation_loss, softmax_cross_entropy
from repro.fed.schedule import RoundPlan
from repro.kernels import ops
from repro.launch.mesh import CLIENT_AXIS, make_fed_client_mesh
from repro.optim import Optimizer, apply_updates, fedprox_penalty

AXIS = CLIENT_AXIS


def make_client_mesh(n_devices: int):
    """1-D client mesh over the first ``n_devices`` devices (pack=1 layout;
    the packed engine sizes its mesh via ``launch.mesh.make_fed_client_mesh``)."""
    return make_fed_client_mesh(n_devices, pack=1)


# ------------------------------------------------------------ data staging
def stack_client_data(shards, steps_per_round: int, batch_size: int, *,
                      seed: int = 0):
    """(C, steps, B, ...) arrays — every client padded to the same number of
    steps per round (shorter clients repeat batches cyclically; pair with
    ``client_step_counts`` to mask the repeats out).  The packed engine
    stages ALL clients once and row-gathers each round's participants onto
    mesh slots (``RoundPlan.slot_client``)."""
    xs, ys = [], []
    for sh in shards:
        bx, by = [], []
        epoch = 0
        while len(bx) < steps_per_round:
            for x, y in sh.batches(batch_size, epoch=epoch, seed=seed):
                bx.append(x)
                by.append(y)
                if len(bx) == steps_per_round:
                    break
            epoch += 1
        xs.append(np.stack(bx))
        ys.append(np.stack(by))
    return np.stack(xs), np.stack(ys)


def client_step_counts(shards, batch_size: int, epochs: int) -> np.ndarray:
    """Number of REAL optimizer steps per client for ``epochs`` local epochs
    (matches the loop engine's per-client batch count)."""
    return np.asarray([math.ceil(sh.num_examples / batch_size) * epochs
                       for sh in shards], np.int32)


def to_device(x):
    """``jax.device_put`` of a host array (an explicit transfer, legal under
    the guards), its bytes counted as ``perf``'s ``h2d_bytes``."""
    perf.count_bytes("h2d_bytes", x)
    return jax.device_put(x)


def replicate(mesh, *arrays):
    """``jax.device_put`` of host arrays replicated over ``mesh`` (every
    device holds each whole array), their bytes counted as ``perf``'s
    ``h2d_bytes``."""
    perf.count_bytes("h2d_bytes", *arrays)
    return jax.device_put(arrays, NamedSharding(mesh, P()))


def stage_on_slots(mesh, plan: RoundPlan, *arrays, row_maps=None,
                   round_id=None):
    """Row-gather this round's participants onto mesh slots and place the
    (S, ...) stacks with the packed client-axis sharding (idle slots carry
    row 0; they run zero steps).

    The row-gather stays on the HOST (``arrays`` are the (C, ...) numpy
    stacks built once at setup by ``stack_client_data``): one fancy index
    plus one ``device_put`` per array, no intermediate default-device copy —
    this is the only host->device transfer on the per-round path.

    ``row_maps`` (optional, one entry per array, ``None`` = identity)
    translates the plan's CLIENT ids into each array's row space — how a
    100k-virtual-client universe stages through base stacks that only
    materialise the data pool (``data.pipeline.ClientStore.row_of``), and
    how the KD teacher feed maps a slot to its cluster LEADER's rows.

    ``perf`` records a ``gather`` span and the stacks' ``h2d_bytes``, into
    round ``round_id`` when given (a prefetch thread's submission token)."""
    with perf.span("gather", round_id=round_id):
        cid = np.where(plan.active, plan.slot_client, 0)
        maps = (None,) * len(arrays) if row_maps is None else row_maps
        stacks = tuple(
            np.ascontiguousarray(
                np.asarray(a)[cid if m is None else np.asarray(m)[cid]])
            for a, m in zip(arrays, maps))
        perf.count_bytes("h2d_bytes", *stacks, round_id=round_id)
        # the leading (S,) slot axis over the client axis, the rest whole
        return jax.device_put(stacks, tuple(
            NamedSharding(mesh, P(AXIS, *([None] * (a.ndim - 1))))
            for a in stacks))


class WaveStager:
    """The one stager of the packed runtime (DESIGN.md §15): an LRU cache
    of staged wave assignments plus a DICT of in-flight prefetches, so wave
    ``w+1``'s host gather + ``device_put`` runs on a background thread
    while wave ``w`` computes, and the next round's wave-0 prefetch
    coexists with this round's in-flight waves.

    ``capacity`` bounds the staged cache — size it ``n_waves + 1`` so a
    whole round's waves plus the next round's wave-0 prefetch fit; a full
    cache evicts least-recently-used (repeat assignments across rounds,
    e.g. ``participation="full"`` single-wave, then never re-upload: one
    upload total).

    ``perf``'s ``stage_wait`` records the seconds ``stage`` blocks: the
    join on a prefetched wave, or a cold wave's whole synchronous gather."""

    def __init__(self, mesh, *arrays,
                 row_maps: Optional[Sequence] = None, capacity: int = 2):
        self.mesh, self.arrays = mesh, arrays
        self.row_maps = row_maps
        self.capacity = max(2, int(capacity))
        self._staged: dict[bytes, tuple] = {}    # insertion-ordered LRU
        self._pending: dict[bytes, tuple] = {}   # key -> (thread, box)

    def _gather(self, plan: RoundPlan, round_id=None):
        return stage_on_slots(self.mesh, plan, *self.arrays,
                              row_maps=self.row_maps, round_id=round_id)

    def _put(self, key: bytes, staged):
        self._staged[key] = staged
        while len(self._staged) > self.capacity:
            self._staged.pop(next(iter(self._staged)))

    def stage(self, plan: RoundPlan):
        key = plan.slot_client.tobytes()
        hit = self._staged.pop(key, None)
        if hit is not None:
            self._put(key, hit)                  # LRU refresh
            return hit
        pend = self._pending.pop(key, None)
        if pend is not None:
            th, box = pend
            guards.jitter_point("wave-stage")
            t0 = time.perf_counter()
            th.join()
            wait = time.perf_counter() - t0
            staged = box.get("staged")
            if staged is not None:
                perf.add("stage_wait", wait)
                self._put(key, staged)
                return staged
            # background gather failed: fall through and raise synchronously
        t0 = time.perf_counter()
        staged = self._gather(plan)
        perf.add("stage_wait", time.perf_counter() - t0)
        self._put(key, staged)
        return staged

    def prefetch(self, plan: RoundPlan):
        """Begin staging ``plan``'s slot assignment on a background thread
        (no-op if already staged or already in flight).  Mispredictions are
        harmless: an unadopted prefetch just finishes and is GC'd when its
        key is evicted from the pending dict by a later prefetch storm —
        prefetch is an overlap optimisation, never a source of truth."""
        key = plan.slot_client.tobytes()
        if key in self._staged or key in self._pending:
            return
        box: dict = {}
        token = perf.round_token()

        def work():
            guards.jitter_point("wave-prefetch")
            try:
                box["staged"] = self._gather(plan, round_id=token)
            except Exception as e:  # pragma: no cover - raised on sync retry
                box["error"] = e

        th = threading.Thread(target=work, daemon=True, name="wave-prefetch")
        th.start()
        self._pending[key] = (th, box)
        # Pending-dict eviction is main-thread-only: the evicted entry's
        # worker keeps running against ITS OWN box and is never adopted —
        # stage() for that key falls back to a synchronous gather.  The
        # jitter point lets the race harness stretch this window
        # (tests/test_race_harness.py eviction regression).
        guards.jitter_point("wave-evict")
        while len(self._pending) > self.capacity:
            self._pending.pop(next(iter(self._pending)))


# Batched per-slot key derivation: ONE vmapped fold_in program instead of a
# Python loop of eager fold_in dispatches (bitwise identical to the loop —
# fold_in folds each uint32 datum independently).
_fold_keys = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))


def slot_client_keys(base, plan: RoundPlan, *, offset: int = 0):
    """One PRNG key per slot, folded by ``offset +`` the hosted CLIENT id —
    key streams stay stable under slot re-assignment across rounds (idle
    slots fold client 0; they never train)."""
    cid = np.where(plan.active, plan.slot_client, 0)
    # to_device (a device_put), not jnp.asarray: the EXPLICIT transfer
    # stays legal under guards.no_implicit_transfers() (same uint32
    # wrap-around semantics)
    return _fold_keys(base, to_device(
        (offset + cid.astype(np.int64)).astype(np.uint32)))


def cluster_keys(base, cluster_idx):
    """One PRNG key per teacher row, folded by the compact CLUSTER index
    hosting it (``RoundPlan.slot_cluster``'s id space): a cluster's teacher
    steps on the same key whichever slots its members hold."""
    return _fold_keys(base, to_device(
        np.asarray(cluster_idx).astype(np.uint32)))


@functools.partial(jax.jit, static_argnums=1)
def _replicate(params, n: int):
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n,) + a.shape), params)


def replicate_params(params, n: int):
    """Stack identical replicas on a leading slot axis (one jitted broadcast
    program, not an eager broadcast+copy per leaf)."""
    return _replicate(params, n)


@jax.jit
def take_rows(tree, idx):
    """Gather row ``idx`` from every (S, ...) leaf as ONE jitted program —
    the eager per-leaf ``a[i]`` chain costs ~30ms/op on sharded arrays
    (straggler-lane extraction, sync-path slot-0 reads)."""
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _masked_scan_steps(step_fn, carry, xs, ys, n_steps):
    """Run ``step_fn(carry, (x, y, step_index))`` over (xs, ys) freezing the
    carry once the per-slot step budget ``n_steps`` is spent (shorter
    clients stop early, idle slots — ``n_steps == 0`` — never move, exactly
    as in the sequential loop engine)."""
    idx = jnp.arange(xs.shape[0])

    def step(carry, batch):
        x, y, i = batch
        new_carry, loss = step_fn(carry, (x, y, i))
        live = i < n_steps
        with jax.named_scope("masked_carry"):
            carry = jax.tree_util.tree_map(
                lambda new, old: jnp.where(live, new, old), new_carry, carry)
        return carry, jnp.where(live, loss, 0.0)

    carry, losses = jax.lax.scan(step, carry, (xs, ys, idx))
    mean_loss = jnp.sum(losses) / jnp.maximum(n_steps.astype(jnp.float32), 1.0)
    return carry, mean_loss


def _make_teacher_step(t_fwd: Callable, t_opt: Optimizer, rng):
    """One masked-scan teacher CE step (Alg. 1 line 12), shared by the
    warm-up phase and the in-round teacher refresh."""

    def t_step(carry, batch):
        p, s = carry
        x, y, i = batch
        k = jax.random.fold_in(rng, i)

        def loss_fn(p):
            return softmax_cross_entropy(t_fwd(p, x, train=True, key=k), y)

        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = t_opt.update(g, s, p)
        return (apply_updates(p, u), s), loss

    return t_step


def _active_mean(loss, n_steps, axis_name):
    """Mean of per-lane losses over the ACTIVE slots of the whole mesh."""
    num = jax.lax.psum(jnp.sum(jnp.where(n_steps > 0, loss, 0.0)), axis_name)
    den = jax.lax.psum(jnp.sum((n_steps > 0).astype(jnp.float32)), axis_name)
    return num / jnp.maximum(den, 1.0)


# ----------------------------------------- FedSiKD packed KD round engine
def _teacher_scan(t_fwd: Callable, t_opt: Optimizer, tp, ts, xs, ys, n_steps,
                  rng):
    """The masked teacher CE scan, ``vmap``-ed over the leading axis of its
    operands: one lane per slot replica (``teacher_data="cluster"``) or one
    per cluster row (``"leader"``).  Returns ``((tp, ts), loss)``."""

    def lane(tp, ts, xs, ys, n, rng):
        step = _make_teacher_step(t_fwd, t_opt, rng)
        return _masked_scan_steps(step, (tp, ts), xs, ys, n)

    with jax.named_scope("teacher_phase"):
        return jax.vmap(lane)(tp, ts, xs, ys, n_steps, rng)


def _make_student_kd(t_fwd: Callable, s_fwd: Callable, s_opt: Optimizer,
                     pack: int, kd_temperature: float, kd_alpha: float,
                     kd_impl: str):
    """The student half of the KD round, shared by both teacher layouts:
    distillation steps on every lane against that lane's teacher ``tp``
    (Alg. 1 lines 13-14), then the grouped plan-weighted aggregation (lines
    16-18).  Returns ``student(sp, ss, sx, sy, s_n, s_rng, tp, agg_row) ->
    (sp, sp_local, ss, s_loss)`` with per-lane losses."""
    if kd_impl not in ("fused", "reference"):
        raise ValueError(
            f"kd_impl must be 'fused' or 'reference', got {kd_impl!r}")

    def s_lane(sp, ss, xs, ys, n, rng, tp):
        def s_step(carry, batch):
            p, s = carry
            x, y, i = batch
            k = jax.random.fold_in(rng, i)
            t_logits = t_fwd(tp, x, train=False, key=None)

            def loss_fn(p):
                s_logits = s_fwd(p, x, train=True, key=k)
                if kd_impl == "fused":
                    return ops.kd_distillation_loss_batched(
                        s_logits, t_logits, y,
                        tau=kd_temperature, alpha=kd_alpha)
                return distillation_loss(s_logits, t_logits, y,
                                         temperature=kd_temperature,
                                         alpha=kd_alpha)[0]

            loss, g = jax.value_and_grad(loss_fn)(p)
            u, s = s_opt.update(g, s, p)
            return (apply_updates(p, u), s), loss

        return _masked_scan_steps(s_step, (sp, ss), xs, ys, n)

    def student(sp, ss, sx, sy, s_n, s_rng, tp, agg_row):
        with jax.named_scope("student_kd"):
            (sp, ss), s_loss = jax.vmap(s_lane)(sp, ss, sx, sy, s_n, s_rng,
                                                tp)
        # the pre-aggregation per-slot students ride along so straggler
        # lanes can be buffered host-side without a second program
        sp_local = sp
        with jax.named_scope("cross_lane"):
            sp = cc.packed_weighted_mean(sp, AXIS, agg_row, pack=pack)
        return sp, sp_local, ss, s_loss

    return student


def make_packed_teacher_phase(mesh, pack: int, t_fwd: Callable,
                              t_opt: Optimizer, *, donate: bool = True):
    """``teacher_data="cluster"``'s warm-up (Alg. 1's KD establishment) as
    a jitted collective program on the packed mesh: CE steps on every
    slot's own shard (vmap over the ``pack`` lane axis), then the
    intra-cluster teacher sync with the plan's runtime (S, S) operator —
    data-parallel training of each cluster's teacher over its members.

    ``rng`` is one PRNG key per slot (training mode is on, so dropout models
    get a fresh per-step key, as in the loop engine)."""

    def phase(tp, ts, xs, ys, n_steps, rng, sync_mat):
        (tp, ts), loss = _teacher_scan(t_fwd, t_opt, tp, ts, xs, ys, n_steps,
                                       rng)
        with jax.named_scope("cross_lane"):
            tp = cc.packed_teacher_sync(tp, AXIS, sync_mat, pack=pack)
            ts = cc.packed_teacher_sync(ts, AXIS, sync_mat, pack=pack)
        return tp, ts, _active_mean(loss, n_steps, AXIS)

    return jax.jit(jax.shard_map(
        phase, mesh=mesh,
        in_specs=(P(AXIS),) * 6 + (P(),),
        out_specs=(P(AXIS), P(AXIS), P()), check_vma=False,
    ), donate_argnums=(0, 1) if donate else ())


def make_leader_teacher_phase(mesh, t_fwd: Callable, t_opt: Optimizer):
    """``teacher_data="leader"``'s warm-up: the teacher CE scan once per
    cluster ROW (``vmap`` over the canonical ``(K, ...)`` stacks) on each
    leader's feed, replicated over the mesh.  Rows with a zero step budget
    come back unchanged.

    Returns phase(tp, ts, xs, ys, n_steps, rng) -> (tp, ts, loss): ``xs``/
    ``ys`` are the (K, steps, B, ...) leader feed, ``n_steps`` and ``rng``
    one budget and one key per row, ``loss`` the mean over the rows that
    trained.  Nothing is donated: the inputs are the canonical stacks."""

    def phase(tp, ts, xs, ys, n_steps, rng):
        (tp, ts), loss = _teacher_scan(t_fwd, t_opt, tp, ts, xs, ys, n_steps,
                                       rng)
        live = n_steps > 0
        return tp, ts, (jnp.sum(jnp.where(live, loss, 0.0))
                        / jnp.maximum(jnp.sum(live), 1))

    return jax.jit(jax.shard_map(
        phase, mesh=mesh, in_specs=(P(),) * 6, out_specs=(P(),) * 3,
        check_vma=False))


def make_packed_kd_round(mesh, pack: int, t_fwd: Callable, s_fwd: Callable,
                         t_opt: Optimizer, s_opt: Optimizer, *,
                         kd_temperature: float = 2.0, kd_alpha: float = 0.5,
                         kd_impl: str = "fused", donate: bool = True):
    """The FedSiKD round (Alg. 1 lines 10-18) for ``teacher_data=
    "cluster"`` as ONE jitted collective program over the packed client
    mesh:

      1. teacher CE steps on every slot's replica of its cluster's teacher,
         each on the slot's own shard                           (line 12)
      2. intra-cluster teacher sync (grouped all-reduce over
         (device, lane) slots, runtime operator): data-parallel training
         of each teacher over its members
      3. student distillation steps vs the synced teacher — the loss is the
         fused Pallas ``kd_distillation_loss`` kernel (``kd_impl="fused"``)
         or the pure-jnp reference (``kd_impl="reference"``)    (line 13-14)
      4. grouped student aggregation with the plan's weight row: unbiased
         two-level mean collapsed into one contraction          (lines 16-18)

    Returns round_fn(tp, ts, sp, ss, tx, ty, t_n, sx, sy, s_n, t_rng, s_rng,
    sync_mat, agg_row) -> (tp, ts, sp, sp_local, ss, teacher_loss,
    student_loss); all params/opt-state pytrees carry a leading (S,) slot
    axis (S = devices x pack).  ``sp_local`` is each slot's student AFTER
    its local steps but BEFORE aggregation — the semi-async path pulls
    straggler lanes from it into the host-side staleness buffer while the
    program itself stays fixed-shape (stale lanes are merely zero-weighted
    in ``agg_row``, never recompiled; DESIGN.md §12).  ``sync_mat`` (S, S)
    and ``agg_row`` (S,) come from the round's ``RoundPlan`` — they are
    traced inputs, so sampled participation never recompiles.  ``t_rng`` /
    ``s_rng`` are one PRNG key per slot.

    With ``donate=True`` the per-round SLOT temporaries (tp, ts, sp, ss —
    args 0-3) are donated: XLA updates them in place instead of allocating
    a second copy of every param/opt-state stack each round.  Callers must
    treat those inputs as consumed after the call (the strategies rebuild
    them from canonical state every round, so nothing else holds them; see
    DESIGN.md §13 for the donation contract)."""
    student = _make_student_kd(t_fwd, s_fwd, s_opt, pack, kd_temperature,
                               kd_alpha, kd_impl)

    def kd_round(tp, ts, sp, ss, tx, ty, t_n, sx, sy, s_n, t_rng, s_rng,
                 sync_mat, agg_row):
        (tp, ts), t_loss = _teacher_scan(t_fwd, t_opt, tp, ts, tx, ty, t_n,
                                         t_rng)
        with jax.named_scope("cross_lane"):
            tp = cc.packed_teacher_sync(tp, AXIS, sync_mat, pack=pack)
            ts = cc.packed_teacher_sync(ts, AXIS, sync_mat, pack=pack)
        sp, sp_local, ss, s_loss = student(sp, ss, sx, sy, s_n, s_rng, tp,
                                           agg_row)
        return (tp, ts, sp, sp_local, ss,
                _active_mean(t_loss, t_n, AXIS),
                _active_mean(s_loss, s_n, AXIS))

    return jax.jit(jax.shard_map(
        kd_round, mesh=mesh,
        in_specs=(P(AXIS),) * 12 + (P(), P()),
        out_specs=(P(AXIS),) * 5 + (P(), P()), check_vma=False,
    ), donate_argnums=(0, 1, 2, 3) if donate else ())


def make_leader_kd_round(mesh, pack: int, t_fwd: Callable, s_fwd: Callable,
                         t_opt: Optimizer, s_opt: Optimizer, *,
                         kd_temperature: float = 2.0, kd_alpha: float = 0.5,
                         kd_impl: str = "fused", donate: bool = True):
    """The FedSiKD round for ``teacher_data="leader"`` as ONE jitted
    program over the packed client mesh.  Every member of a cluster would
    train the same teacher on the same leader batches with the same key, so
    the teacher refresh runs once per cluster ROW, not once per slot:

      1. teacher CE steps on each (K,) row's leader feed, ``vmap``-ed over
         the rows and replicated on every device; a row with a zero budget
         (no active slot of its cluster in this wave) comes back unchanged
      2. each lane reads its cluster's refreshed teacher through the (S,)
         slot->row index ``t_row`` (-1 = idle slot), gathered in-program
      3. student distillation and grouped aggregation, exactly as in
         ``make_packed_kd_round``

    Returns round_fn(tp, ts, sp, ss, tx, ty, t_n, sx, sy, s_n, t_rng, s_rng,
    t_row, agg_row) -> (tp, ts, sp, sp_local, ss, teacher_loss,
    student_loss).  ``tp``/``ts`` are the canonical (K, ...) teacher stacks
    in and the refreshed ones out; ``tx``/``ty`` the (K, steps, B, ...)
    leader feed, ``t_n`` and ``t_rng`` one budget and one key per row — all
    replicated (``P()``).  The student operands carry the (S,) slot axis as
    in ``make_packed_kd_round``.  ``teacher_loss`` is the mean over the
    active slots of their cluster's teacher loss.

    With ``donate=True`` only the student slot temporaries (sp, ss — args
    2-3) are donated: the teacher stacks are the round-start snapshot every
    wave reads, and the checkpoint writer may hold them (DESIGN.md §13)."""
    student = _make_student_kd(t_fwd, s_fwd, s_opt, pack, kd_temperature,
                               kd_alpha, kd_impl)

    def kd_round(tp, ts, sp, ss, tx, ty, t_n, sx, sy, s_n, t_rng, s_rng,
                 t_row, agg_row):
        (tp, ts), t_loss = _teacher_scan(t_fwd, t_opt, tp, ts, tx, ty, t_n,
                                         t_rng)
        row = jnp.maximum(t_row, 0)
        tp_lane = jax.tree_util.tree_map(lambda a: a[row], tp)
        sp, sp_local, ss, s_loss = student(sp, ss, sx, sy, s_n, s_rng,
                                           tp_lane, agg_row)
        lane_n = jnp.where(t_row >= 0, t_n[row], 0)
        return (tp, ts, sp, sp_local, ss,
                _active_mean(t_loss[row], lane_n, AXIS),
                _active_mean(s_loss, s_n, AXIS))

    return jax.jit(jax.shard_map(
        kd_round, mesh=mesh,
        in_specs=(P(), P(), P(AXIS), P(AXIS), P(), P(), P(), P(AXIS),
                  P(AXIS), P(AXIS), P(), P(AXIS), P(AXIS), P()),
        out_specs=(P(), P()) + (P(AXIS),) * 3 + (P(), P()),
        check_vma=False,
    ), donate_argnums=(2, 3) if donate else ())


# -------------------------------------------- FedAvg/FedProx packed engine
def make_packed_baseline_round(mesh, pack: int, fwd: Callable,
                               opt: Optimizer, *, prox_mu: float = 0.0,
                               donate: bool = True):
    """One FedAvg (``prox_mu=0``) or FedProx round as ONE jitted collective
    program over the packed client mesh:

      1. plain-CE local steps on every participating slot's batches, with
         FedProx's proximal term ``(mu/2)||w - w_g||^2`` computed against
         the broadcast ROUND-START global params (replicated input, P()
         spec) — per slot, masked like every other step quantity (idle
         slots' frozen carries never contribute);
      2. one all-clients grouped aggregation: the runtime (S,) example-
         weighted row (``RoundPlan.example_row``) contracted by
         ``cluster_collectives.packed_weighted_mean`` — a single group
         spanning every active slot, mirroring the loop engine's
         ``aggregation.fedavg(locals, sizes)``.

    Returns round_fn(p, s, xs, ys, n_steps, rng, agg_row, global_p) ->
    (p, p_local, s, train_loss); params/opt-state carry a leading (S,) slot
    axis, batch stacks are (S, steps, B, ...).  ``p_local`` is each slot's
    params after local steps but before aggregation (straggler-lane capture
    for the semi-async buffer, as in ``make_packed_kd_round``).
    ``agg_row`` is a traced input, so sampled participation and dropout
    never recompile.  After the call every slot holds the aggregated global
    model."""

    def baseline_round(p, s, xs, ys, n_steps, rng, agg_row, global_p):
        def lane(p, s, xs, ys, n, rng):
            def step(carry, batch):
                p, s = carry
                x, y, i = batch
                k = jax.random.fold_in(rng, i)

                def loss_fn(p):
                    loss = softmax_cross_entropy(
                        fwd(p, x, train=True, key=k), y)
                    if prox_mu:
                        loss = loss + fedprox_penalty(p, global_p, prox_mu)
                    return loss

                loss, g = jax.value_and_grad(loss_fn)(p)
                u, s = opt.update(g, s, p)
                return (apply_updates(p, u), s), loss

            return _masked_scan_steps(step, (p, s), xs, ys, n)

        (p, s), loss = jax.vmap(lane)(p, s, xs, ys, n_steps, rng)
        p_local = p
        with jax.named_scope("cross_lane"):
            p = cc.packed_weighted_mean(p, AXIS, agg_row, pack=pack)
        return p, p_local, s, _active_mean(loss, n_steps, AXIS)

    return jax.jit(jax.shard_map(
        baseline_round, mesh=mesh,
        in_specs=(P(AXIS),) * 6 + (P(), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P()), check_vma=False,
    ), donate_argnums=(0, 1) if donate else ())

"""Compile-surface certifier for the packed federated runtime.

The wave redesign's load-bearing promise (DESIGN.md §15) is that the
compiled round programs are shaped by ``wave_slots = n_devices * pack``
ALONE: the cohort — and the virtual client universe behind it — streams
through a fixed mesh, so membership growth never recompiles.  That
promise is enforced dynamically by ``guards.no_new_compiles`` in CI
smokes, but a shape regression only trips the sentinel on the config the
smoke happens to run.  This module certifies it STATICALLY:

  1. ``build_grid()`` enumerates real ``FedConfig`` instances over the
     engines x algorithms x (universe, waves) x async x guards axes —
     construction runs the full ``__post_init__`` validation, so the
     grid can never drift from what the runtime accepts.
  2. ``certify_config`` derives each config's slot-program input avals
     from the same layout math the strategies use (``fed_wave_layout``
     + ``jax.eval_shape`` over the model/optimizer inits) and abstractly
     evaluates the REAL round-program factories
     (``make_leader_kd_round`` / ``make_leader_teacher_phase`` for
     ``teacher_data="leader"``, ``make_packed_kd_round`` /
     ``make_packed_teacher_phase`` for ``"cluster"``,
     ``make_packed_baseline_round``) on a real host-device mesh.  No
     datasets are loaded and nothing is compiled or executed.
  3. ``check_invariants`` groups the report by everything that IS
     allowed to shape a program — (algorithm, engine, teacher_data, pack,
     wave_slots, steps, batch, kd_impl, donate) — and fails if two entries
     in one group (i.e. differing only in cohort / universe / waves /
     async / guards) disagree on any program's input or output shapes.

CI commits the canonical JSON as ``SHAPES.json`` and diffs every PR
against it (``python -m tools.shapecert --check SHAPES.json``): a change
that widens the compile surface or couples it to the cohort fails the
build before it can fail a profile.

Three modelling constants, all deliberately cohort-independent in the
real runtime and therefore safe to pin here: the scan length ``STEPS``
(derives from the BASE data pool and batch size, never the universe —
``stack_client_data`` pads every client to one cap), the teacher count
``CLUSTERS`` (fixed at setup by clustering the data pool, and kept
through every re-clustering) and the single certification dataset
(mnist; the model only changes leaf shapes, not which dimensions exist).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

import jax
import jax.numpy as jnp

from repro.fed import sharded as sh
from repro.fed.rounds import FedConfig
from repro.launch.mesh import fed_wave_layout, make_fed_client_mesh
from repro.models.cnn import make_model
from repro.optim import adamw

# Scan length of the certified programs.  The runtime cap is
# max(client_step_counts(base_pool)) — a function of the materialised
# data pool, NOT the cohort — so any fixed value certifies the same
# coupling structure.  Small keeps abstract tracing fast.
STEPS = 2
# Teacher rows K of the leader-mode programs: set by clustering the data
# pool at setup and fixed for the run, never by the cohort.
CLUSTERS = 2

KD_ALGORITHMS = ("fedsikd", "random")


# --------------------------------------------------------------- the grid
def build_grid() -> list[FedConfig]:
    """Real, validated ``FedConfig`` instances spanning the certification
    axes.  Per sharded algorithm: the legacy single-wave layout, two
    wave-scheduled universes that share one mesh (16 and 64 virtual
    clients through the same 4 slots — the pair the invariant check
    bites on), plus async and jitter-guard variants.  Loop-engine rows
    ride along with an empty program set: the loop engine jits per-client
    step functions, not cohort-shaped round programs, and recording that
    explicitly keeps the engine axis honest."""
    grid: list[FedConfig] = []
    base = dict(engine="sharded", num_clients=4, pack=2, n_devices=2,
                batch_size=8, local_epochs=1)
    for algorithm in ("fedsikd", "random", "fedavg", "fedprox"):
        grid += [
            # legacy: mesh sized for the whole (4-client) cohort, 1 wave
            FedConfig(algorithm=algorithm, **base),
            # same mesh, 16- and 64-client universes streamed in waves
            FedConfig(algorithm=algorithm, universe=16, waves=4, **base),
            FedConfig(algorithm=algorithm, universe=64, waves=16, **base),
            # execution-strategy knobs: must not touch the compile surface
            FedConfig(algorithm=algorithm, universe=16, waves=4,
                      async_mode=True, straggler_frac=0.5, guards=True,
                      **base),
            FedConfig(algorithm=algorithm, universe=16, waves=4,
                      guards="jitter", **base),
        ]
    # cluster-pooled teachers (per-slot replicas + sync) need the whole
    # cohort on the mesh at once: single-wave rows only
    for algorithm in KD_ALGORITHMS:
        grid += [
            FedConfig(algorithm=algorithm, teacher_data="cluster", **base),
            FedConfig(algorithm=algorithm, teacher_data="cluster",
                      async_mode=True, straggler_frac=0.5, guards=True,
                      **base),
        ]
    for algorithm in ("fedsikd", "random", "fedavg", "fedprox", "flhc"):
        grid.append(FedConfig(algorithm=algorithm, engine="loop",
                              num_clients=4, batch_size=8))
    return grid


# ------------------------------------------------------- aval derivation
def _spec(aval) -> str:
    return f"{jnp.dtype(aval.dtype).name}[{','.join(map(str, aval.shape))}]"


def _spec_tree(tree):
    """Pytree of avals -> JSON-serializable tree of 'dtype[dims]' leaves."""
    return jax.tree_util.tree_map(_spec, tree)


def _stack(avals, n: int):
    """Give every leaf a leading (n,) slot axis."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype), avals)


def _model_avals(dataset: str, *, student: bool):
    init, fwd = make_model(dataset, student=student)
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0))), fwd


def _opt_state_avals(opt, stacked_params):
    return jax.eval_shape(lambda p: jax.vmap(opt.init)(p), stacked_params)


def _data_avals(dataset: str, S: int, batch: int):
    """(S, STEPS, B, ...) batch stacks as staged by ``stack_client_data``
    + ``stage_on_slots`` (features float32, integer class labels)."""
    feat = {"mnist": (28, 28, 1), "har": (561, 1)}[dataset]
    xs = jax.ShapeDtypeStruct((S, STEPS, batch) + feat, jnp.float32)
    ys = jax.ShapeDtypeStruct((S, STEPS, batch), jnp.int32)
    return xs, ys


def _record(fn, *avals):
    """eval_shape ``fn`` on ``avals`` -> {inputs, outputs} spec trees."""
    out = jax.eval_shape(fn, *avals)
    return {"inputs": [_spec_tree(a) for a in avals],
            "outputs": [_spec_tree(o) for o in
                        (out if isinstance(out, tuple) else (out,))]}


# -------------------------------------------------------- per-config cert
def certify_config(cfg: FedConfig, *, dataset: str = "mnist",
                   extra_programs=None) -> dict:
    """One report entry: the config's identity, its wave layout, and the
    eval_shape'd record of every compiled round program it would build.

    ``extra_programs(cfg, layout, mesh) -> {name: (fn, avals)}`` lets
    tests inject a deliberately cohort-shaped program and watch
    ``check_invariants`` reject it."""
    entry = {
        "config": {
            "algorithm": cfg.algorithm, "engine": cfg.engine,
            "pack": cfg.pack, "n_devices": cfg.n_devices,
            "waves": cfg.waves, "universe": cfg.universe,
            "num_clients": cfg.num_clients, "async_mode": cfg.async_mode,
            "guards": cfg.guards, "batch_size": cfg.batch_size,
            "kd_impl": cfg.kd_impl, "donate": cfg.donate,
            "dataset": dataset, "steps": STEPS,
        },
        "programs": {},
    }
    if cfg.algorithm in KD_ALGORITHMS:
        entry["config"]["teacher_data"] = cfg.teacher_data
    if cfg.engine != "sharded":
        entry["layout"] = None      # no packed mesh, no compiled surface
        return entry

    cohort = cfg.clients_per_round or cfg.total_clients
    n_devices, S, n_waves = fed_wave_layout(
        cohort, pack=cfg.pack, n_devices=cfg.n_devices, waves=cfg.waves)
    entry["layout"] = {"cohort": cohort, "n_devices": n_devices,
                      "wave_slots": S, "n_waves": n_waves}
    mesh = make_fed_client_mesh(S, pack=cfg.pack, n_devices=n_devices)

    xs, ys = _data_avals(dataset, S, cfg.batch_size)
    n_steps = jax.ShapeDtypeStruct((S,), jnp.int32)
    rng = jax.ShapeDtypeStruct((S, 2), jnp.uint32)
    sync_mat = jax.ShapeDtypeStruct((S, S), jnp.float32)
    agg_row = jax.ShapeDtypeStruct((S,), jnp.float32)
    programs = entry["programs"]

    if cfg.algorithm in KD_ALGORITHMS:
        tp1, t_fwd = _model_avals(dataset, student=False)
        sp1, s_fwd = _model_avals(dataset, student=True)
        t_opt, s_opt = adamw(cfg.lr), adamw(cfg.student_lr)
        sp = _stack(sp1, S)
        ss = _opt_state_avals(s_opt, sp)
        kd = dict(kd_temperature=cfg.kd_temperature, kd_alpha=cfg.kd_alpha,
                  kd_impl=cfg.kd_impl, donate=cfg.donate)
        if cfg.teacher_data == "leader":
            # one teacher per cluster row: (K, ...) stacks, leader feed,
            # budgets and keys; each slot's row index
            tp = _stack(tp1, CLUSTERS)
            ts = _opt_state_avals(t_opt, tp)
            txs, tys = _data_avals(dataset, CLUSTERS, cfg.batch_size)
            t_n = jax.ShapeDtypeStruct((CLUSTERS,), jnp.int32)
            t_rng = jax.ShapeDtypeStruct((CLUSTERS, 2), jnp.uint32)
            t_row = jax.ShapeDtypeStruct((S,), jnp.int32)
            kd_round = sh.make_leader_kd_round(
                mesh, cfg.pack, t_fwd, s_fwd, t_opt, s_opt, **kd)
            programs["kd_round"] = _record(
                kd_round, tp, ts, sp, ss, txs, tys, t_n, xs, ys, n_steps,
                t_rng, rng, t_row, agg_row)
            phase = sh.make_leader_teacher_phase(mesh, t_fwd, t_opt)
            programs["teacher_phase"] = _record(
                phase, tp, ts, txs, tys, t_n, t_rng)
        else:
            # a teacher replica on every slot, synced per cluster
            tp = _stack(tp1, S)
            ts = _opt_state_avals(t_opt, tp)
            kd_round = sh.make_packed_kd_round(
                mesh, cfg.pack, t_fwd, s_fwd, t_opt, s_opt, **kd)
            programs["kd_round"] = _record(
                kd_round, tp, ts, sp, ss, xs, ys, n_steps, xs, ys, n_steps,
                rng, rng, sync_mat, agg_row)
            phase = sh.make_packed_teacher_phase(
                mesh, cfg.pack, t_fwd, t_opt, donate=cfg.donate)
            programs["teacher_phase"] = _record(
                phase, tp, ts, xs, ys, n_steps, rng, sync_mat)
    else:                                   # fedavg | fedprox
        p1, fwd = _model_avals(dataset, student=False)
        opt = adamw(cfg.lr)
        p = _stack(p1, S)
        s = _opt_state_avals(opt, p)
        round_fn = sh.make_packed_baseline_round(
            mesh, cfg.pack, fwd, opt,
            prox_mu=cfg.prox_mu if cfg.algorithm == "fedprox" else 0.0,
            donate=cfg.donate)
        programs["baseline_round"] = _record(
            round_fn, p, s, xs, ys, n_steps, rng, agg_row, p1)

    if extra_programs is not None:
        for name, (fn, avals) in extra_programs(
                cfg, entry["layout"], mesh).items():
            programs[name] = _record(fn, *avals)
    return entry


def certify(grid=None, *, dataset: str = "mnist",
            extra_programs=None) -> dict:
    grid = build_grid() if grid is None else grid
    report = {
        "shapecert_version": 1,
        "dataset": dataset,
        "steps": STEPS,
        "entries": [certify_config(c, dataset=dataset,
                                   extra_programs=extra_programs)
                    for c in grid],
    }
    # normalise tuple-structured pytree specs to JSON lists so a fresh
    # report compares equal to a committed-then-reloaded one
    return json.loads(json.dumps(report))


# ------------------------------------------------------------- invariants
def _surface_key(entry) -> tuple:
    """Everything ALLOWED to shape a compiled program.  Cohort, universe,
    waves, async and guards are deliberately absent: entries differing
    only in those must certify identical surfaces."""
    c, lay = entry["config"], entry["layout"]
    return (c["algorithm"], c["engine"], c.get("teacher_data"), c["pack"],
            lay["wave_slots"], c["batch_size"], c["steps"], c["kd_impl"],
            c["donate"], c["dataset"])


def check_invariants(report: dict) -> list[str]:
    """Wave-invariance violations in ``report`` (empty = certified).  Any
    two sharded entries with the same surface key must record the same
    programs with bit-identical input/output specs."""
    errors: list[str] = []
    groups: dict[tuple, tuple[dict, dict]] = {}
    for entry in report["entries"]:
        if entry["layout"] is None:
            if entry["programs"]:
                errors.append(
                    f"{entry['config']['engine']}/"
                    f"{entry['config']['algorithm']}: loop-engine entry "
                    "records compiled programs")
            continue
        key = _surface_key(entry)
        if key not in groups:
            groups[key] = (entry, entry["programs"])
            continue
        ref_entry, ref_programs = groups[key]
        if entry["programs"] != ref_programs:
            ref_c, c = ref_entry["config"], entry["config"]
            changed = sorted(
                name for name in
                set(ref_programs) | set(entry["programs"])
                if ref_programs.get(name) != entry["programs"].get(name))
            errors.append(
                f"{c['algorithm']}/{c['engine']} wave_slots="
                f"{entry['layout']['wave_slots']}: programs "
                f"{changed} change shape between cohort="
                f"{ref_entry['layout']['cohort']} (universe="
                f"{ref_c['universe']}, waves={ref_c['waves']}, async="
                f"{ref_c['async_mode']}, guards={ref_c['guards']!r}) and "
                f"cohort={entry['layout']['cohort']} (universe="
                f"{c['universe']}, waves={c['waves']}, async="
                f"{c['async_mode']}, guards={c['guards']!r}) — the "
                "compile surface must depend on wave_slots alone")
    return errors


# ------------------------------------------------------------ JSON + diff
def canonical_json(report: dict) -> str:
    return json.dumps(report, indent=2, sort_keys=True) + "\n"


def diff_reports(committed: dict, fresh: dict) -> list[str]:
    """Human-readable differences between the committed certificate and a
    freshly generated one (empty = in sync)."""
    diffs: list[str] = []
    a = {json.dumps(e["config"], sort_keys=True): e
         for e in committed.get("entries", [])}
    b = {json.dumps(e["config"], sort_keys=True): e
         for e in fresh.get("entries", [])}
    for k in sorted(a.keys() - b.keys()):
        diffs.append(f"entry removed from the grid: {k}")
    for k in sorted(b.keys() - a.keys()):
        diffs.append(f"entry missing from the committed report: {k}")
    for k in sorted(a.keys() & b.keys()):
        if a[k] != b[k]:
            c = b[k]["config"]
            changed = sorted(
                name for name in
                set(a[k]["programs"]) | set(b[k]["programs"])
                if a[k]["programs"].get(name) != b[k]["programs"].get(name))
            what = f"programs {changed}" if changed else "layout"
            diffs.append(
                f"{c['algorithm']}/{c['engine']} (universe={c['universe']},"
                f" waves={c['waves']}): {what} changed — regenerate with "
                "`python -m tools.shapecert --out SHAPES.json` and review "
                "the diff")
    return diffs

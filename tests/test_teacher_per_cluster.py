"""Leader-mode teachers refreshed once per cluster row, not once per slot.

With ``teacher_data="leader"`` every member slot of a cluster used to train
its own replica of the cluster's teacher on the leader's batches with the
cluster's key, and the round program then averaged the identical replicas
(``packed_teacher_sync``).  ``sh.make_leader_kd_round`` and
``sh.make_leader_teacher_phase`` run that refresh once per (K,) row.  The
per-slot form is kept here as the reference: a teacher replica gathered
onto every active slot, the leader feed staged per slot, the slot-indexed
program with the sync operator, and the scatter back from each cluster's
first active slot.  Both run on the 8-device CPU mesh from the same state;
teachers, students and losses must agree to 1e-5 (relative, per leaf),
and a cluster with no active slot keeps its teacher bit for bit.
"""
import textwrap

import pytest

from _subproc import run_script

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro import perf
    from repro.core import aggregation as agg
    from repro.data.pipeline import ClientStore, make_client_shards
    from repro.data.synthetic import load_dataset
    from repro.fed.algorithms import make_algorithm
    from repro.fed.lifecycle import ClientLifecycle
    from repro.fed.rounds import FedConfig

    SCENARIOS = {
        # 8 clients on 4 devices x 2 lanes, every client every round
        "full": dict(num_clients=8, pack=2),
        # 4 of 8 clients a round over 3 clusters, in a round where one
        # cluster has no active slot
        "sampled": dict(num_clients=8, pack=2, participation="uniform",
                        clients_per_round=4, num_clusters=3),
        # 8 clients over 3 clusters through a 4-slot mesh in 2 waves; the
        # last wave lacks a cluster the first refreshed
        "waves": dict(num_clients=8, pack=1, n_devices=4, waves=2,
                      num_clusters=3),
    }
    kw = dict(algorithm="fedsikd", engine="sharded", alpha=0.5,
              local_epochs=1, teacher_warmup_epochs=1, batch_size=32,
              num_clusters=2, seed=3, donate=True)
    kw.update(SCENARIOS[SCENARIO])
    cfg = FedConfig(**kw)
    ds = load_dataset("mnist", small=True)
    alg = make_algorithm(cfg)
    alg.lifecycle = ClientLifecycle.from_config(cfg)
    alg.setup(ds, ClientStore(make_client_shards(
        ds, cfg.num_clients, cfg.alpha, seed=cfg.seed)), cfg,
        jax.random.PRNGKey(cfg.seed))
    sh, tmap = alg.sh, jax.tree_util.tree_map
    assert alg.leader_mode

    # ---- the per-slot reference
    cidx = alg.scheduler.cluster_idx
    leaders = np.asarray(alg.leaders, np.int64)
    feed_of = np.where(cidx >= 0, leaders[np.maximum(cidx, 0)],
                       np.arange(len(cidx)))
    t_map = alg.store.row_of[feed_of]        # each client's leader feed row
    t_steps = alg._base_counts[t_map]

    def slot_keys(salt, wp):
        base = jax.random.fold_in(alg.key, np.uint32(salt))
        return sh.cluster_keys(base, np.where(wp.active, wp.slot_cluster, 0))

    def gather(tree, wp):
        kidx = jnp.asarray(alg._teacher_row(wp))
        return tmap(lambda a: a[kidx], tree)

    def scatter(acc, new, wp):
        row = alg._teacher_row(wp)
        for k in np.unique(row[wp.active]):
            s = int(np.flatnonzero(wp.active & (row == k))[0])
            acc = tmap(lambda a, n: a.at[k].set(n[s]), acc, new)
        return acc

    def ref_warmup(tp, ts):
        phase = sh.make_packed_teacher_phase(alg.mesh, cfg.pack,
                                             alg.t_model[1], alg.opt,
                                             donate=False)
        planw = alg.scheduler.warmup_plan()
        w_steps = (t_steps // cfg.local_epochs) * cfg.teacher_warmup_epochs
        wx_all, wy_all = sh.stack_client_data(
            alg.store.base, int(w_steps.max()), cfg.batch_size,
            seed=cfg.seed)
        acc = (tp, ts)
        for w in range(planw.n_waves):
            wp = planw.wave(w)
            if not wp.active.any():
                continue
            wx, wy = sh.stage_on_slots(alg.mesh, wp, wx_all, wy_all,
                                       row_maps=(t_map, t_map))
            tp_s, ts_s, _ = phase(
                gather(tp, wp), gather(ts, wp), wx, wy,
                jnp.asarray(wp.steps_for(w_steps)), slot_keys(9001, wp),
                jnp.asarray(wp.sync_matrix()))
            acc = scatter(acc, (tp_s, ts_s), wp)
        return acc

    def ref_round(plan, rnd, tp, ts, sp):
        round_fn = sh.make_packed_kd_round(
            alg.mesh, cfg.pack, alg.t_model[1], alg.s_model[1], alg.opt,
            alg.s_opt, kd_temperature=cfg.kd_temperature,
            kd_alpha=cfg.kd_alpha, kd_impl=cfg.kd_impl, donate=False)
        tx_all, ty_all = sh.stack_client_data(
            alg.store.base, int(t_steps.max()), cfg.batch_size,
            seed=cfg.seed)
        row_of = alg.store.row_of
        agg_row = plan.agg_row()
        ws = plan.wave_slots or plan.n_slots
        acc, partials, losses = (tp, ts), [], []
        for w in range(plan.n_waves):
            wp = plan.wave(w)
            if not wp.active.any():
                continue
            sp_s = tmap(lambda a: jnp.broadcast_to(a, (ws,) + a.shape), sp)
            tx, ty = sh.stage_on_slots(alg.mesh, wp, tx_all, ty_all,
                                       row_maps=(t_map, t_map))
            sx, sy = sh.stage_on_slots(alg.mesh, wp, alg.sx_all, alg.sy_all,
                                       row_maps=(row_of, row_of))
            t_n, s_n = wp.steps_for(t_steps), wp.steps_for(alg.s_steps_all)
            tp_s, ts_s, sp_s, _, _, tl, sl = round_fn(
                gather(tp, wp), gather(ts, wp), sp_s,
                jax.vmap(alg.s_opt.init)(sp_s), tx, ty, jnp.asarray(t_n),
                sx, sy, jnp.asarray(s_n), slot_keys(2 * rnd, wp),
                alg._student_keys(2 * rnd + 1, wp),
                jnp.asarray(wp.sync_matrix()),
                jnp.asarray(agg_row[w * ws:(w + 1) * ws]))
            acc = scatter(acc, (tp_s, ts_s), wp)
            partials.append(tmap(lambda a: a[0], sp_s))
            losses.append((float(tl), (t_n > 0).sum(), float(sl),
                           (s_n > 0).sum()))
        sp_new = (partials[0] if len(partials) == 1
                  else agg.fold_partials(partials))
        return acc + (sp_new,) + fold(losses)

    def fold(per_wave):
        # cohort means of per-wave active-slot means, weighted by counts
        def one(vals):
            vals = [(lo, c) for lo, c in vals if c > 0]
            tot = sum(c for _, c in vals)
            return sum(lo * c for lo, c in vals) / tot if tot else 0.0
        return (one([(tl, tc) for tl, tc, _, _ in per_wave]),
                one([(sl, sc) for _, _, sl, sc in per_wave]))

    def close(a, b, what):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            if not np.issubdtype(x.dtype, np.floating):
                assert np.array_equal(x, y), what
                continue
            x, y = x.astype(np.float64), y.astype(np.float64)
            gap = np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30)
            assert gap <= 1e-5, (what, gap)

    # ---- warm-up
    tp0, ts0 = alg.tp_k, alg.ts_k
    want = ref_warmup(tp0, ts0)
    alg.warmup()
    close((alg.tp_k, alg.ts_k), want, "warm-up teachers")

    # ---- one round, with the teacher_lanes counter on
    tp1, ts1, sp1 = alg.tp_k, alg.ts_k, alg.sp_global
    for rnd in range(1, 100):
        plan = alg.scheduler.plan(rnd)
        held = np.unique(alg._teacher_row(plan)[plan.active])
        if SCENARIO != "sampled" or len(held) < alg.K:
            break
    waves = sum(plan.wave(w).active.any() for w in range(plan.n_waves))
    want = ref_round(plan, rnd, tp1, ts1, sp1)
    perf.enable()
    out = alg.run_round(plan, rnd)
    perf.end_round()
    counts = perf.export()["counts"]
    perf.disable()
    close((alg.tp_k, alg.ts_k), want[:2], "teachers")
    close(alg.sp_global, want[2], "student")
    close((out["teacher_loss"], out["student_loss"]), want[3:], "losses")
    assert counts[0]["teacher_lanes"] == alg.K * waves, (counts, waves)
    absent = np.setdiff1d(np.arange(alg.K), held)
    if SCENARIO == "sampled":
        assert len(absent) > 0, held
    if SCENARIO == "waves":
        last = plan.wave(plan.n_waves - 1)
        assert len(np.unique(alg._teacher_row(last)[last.active])) \
            < len(held), held
    for k in absent:                   # no active slot: teacher untouched
        for x, y in zip(jax.tree_util.tree_leaves((alg.tp_k, alg.ts_k)),
                        jax.tree_util.tree_leaves((tp1, ts1))):
            assert np.array_equal(np.asarray(x)[k], np.asarray(y)[k]), k
    print("PER-CLUSTER-OK", SCENARIO, rnd, alg.K, held.tolist(), waves)
""")


@pytest.mark.parametrize("scenario", ["full", "sampled", "waves"])
def test_leader_teachers_match_the_per_slot_replicas(scenario):
    r = run_script(f"SCENARIO = {scenario!r}\n" + _SCRIPT)
    assert "PER-CLUSTER-OK" in r.stdout, r.stdout + r.stderr

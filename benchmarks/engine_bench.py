"""Loop vs packed-sharded round-engine benchmark (8 host devices).

Runs the SAME configuration through both round engines — FedSiKD (Alg. 1:
teacher warm-up, per-round teacher refresh, KD local steps, hierarchical
aggregation) AND the paper's baselines (FedAvg/FedProx, which since the
algorithm-strategy layer run on the packed mesh too) — sweeping the client
count and the ``pack`` factor (client lanes per device) for the mesh
engine — and reports per-round wall-clock split by phase plus final acc:

  loop    — sequential per-client Python loop (reference engine)
  sharded — pack clients per device (C = devices x pack); fused Pallas KD
            steps inside lax.scan, grouped plan-weighted aggregation
            (fed/sharded.py, DESIGN.md §8, §13)

Each row runs ONE ``run_federated`` invocation under the ``repro.perf``
phase timer and splits it honestly:

  steady_s_per_round — mean per-round wall clock over rounds 1+ (round 0
                       carries jit compilation and is EXCLUDED)
  compile_s          — round 0's excess over the steady rate: the one-off
                       trace+compile cost of the round programs
  phases             — steady-state mean seconds per round in each phase
                       (stage / compute / aggregate from the packed
                       strategies; eval / checkpoint from the driver)

On CPU the sharded engine pays the Pallas-interpreter tax inside every
student step, so the CPU wall-clock favours the loop engine — the number
that matters for the scalable path is rounds/sec AT fixed per-device work
as the client count grows (the loop engine is O(clients) per round, the
sharded engine O(pack) given enough devices).  Emits a machine-readable
JSON artifact so CI records the trajectory:

  # the CPU is a stated choice; on a TPU host drop JAX_PLATFORMS=cpu
  JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/engine_bench.py  # sweep
  JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/engine_bench.py \\
      --quick --out BENCH_engines.json                             # CI smoke
  JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/engine_bench.py \\
      --hotpath --out BENCH_hotpath.json  # §13 hot-path gate
  JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/engine_bench.py \\
      --waves --out BENCH_waves.json  # §15 wave-scaling gate: same cohort
                                      # on the same mesh at a 100x larger
                                      # universe must hold steady round time
"""
import argparse
import json
import os
import platform

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax

from repro import perf
from repro.data.synthetic import load_dataset
from repro.fed.rounds import FedConfig, run_federated

PHASES = ("stage", "compute", "aggregate", "eval", "checkpoint")

# Steady-state s/round at PR 6 (commit 29d67c8) for the hot-path config
# (sharded, C=8, pack=2, alpha=1.0, batch=32, clusters=3, warmup=1,
# rounds=4), measured as inter-eval wall clock over rounds 2+ — the closest
# pre-instrumentation proxy for steady_s_per_round.  The --hotpath gate
# reports speedup against these numbers.
PR6_STEADY_BASELINE = {
    "method": "inter-eval wall clock, rounds 2+ of 4 (pre-perf-timer proxy "
              "for steady_s_per_round), commit 29d67c8",
    "fedsikd_s_per_round": 21.19,   # mean of [20.352, 22.029]
    "fedavg_s_per_round": 25.99,    # mean of [27.396, 24.581]
}


def _round_total(bucket: dict) -> float:
    """One perf bucket -> that round's wall clock.  ``round_total`` wraps
    plan/stage/compute/aggregate; eval and checkpoint are driver-side
    siblings (stage/compute/aggregate are NESTED inside round_total and
    must not be double-counted)."""
    return (bucket.get("round_total", 0.0) + bucket.get("eval", 0.0)
            + bucket.get("checkpoint", 0.0))


def bench_engine(ds, engine: str, *, algorithm: str = "fedsikd",
                 clients: int = 8, pack: int = 1,
                 universe=None, n_devices=None, waves=None,
                 kd_impl: str = "fused", rounds: int = 3,
                 participation: str = "full",
                 clients_per_round=None, dropout_rate: float = 0.0,
                 join_schedule=None, recluster_every: int = 0,
                 async_mode: bool = False, straggler_frac: float = 0.0,
                 max_staleness: int = 2, donate: bool = True,
                 prefetch: bool = True, guards: bool = False) -> dict:
    cfg = FedConfig(algorithm=algorithm, engine=engine, kd_impl=kd_impl,
                    num_clients=clients, pack=pack, alpha=1.0, rounds=rounds,
                    universe=universe, n_devices=n_devices, waves=waves,
                    local_epochs=1, teacher_warmup_epochs=1, batch_size=32,
                    num_clusters=3, participation=participation,
                    clients_per_round=clients_per_round,
                    dropout_rate=dropout_rate,
                    join_schedule=join_schedule,
                    recluster_every=recluster_every,
                    async_mode=async_mode, straggler_frac=straggler_frac,
                    max_staleness=max_staleness, seed=0,
                    donate=donate, prefetch=prefetch, guards=guards)
    perf.enable()
    t0 = time.perf_counter()
    h = run_federated(ds, cfg)
    total = time.perf_counter() - t0
    buckets = perf.snapshot()
    perf.disable()

    totals = [_round_total(b) for b in buckets]
    if len(totals) >= 2:
        steady = sum(totals[1:]) / len(totals[1:])
        compile_s = max(totals[0] - steady, 0.0)
        phases = {k: round(sum(b.get(k, 0.0) for b in buckets[1:])
                           / len(totals[1:]), 4) for k in PHASES}
    else:   # single round: no steady split possible
        steady = totals[0] if totals else total
        compile_s = None
        phases = {k: round(buckets[0].get(k, 0.0), 4) for k in PHASES} \
            if buckets else {}

    # wave-staging overlap accounting (DESIGN.md §15): of all the host
    # gather + device_put work the WaveStager did in steady-state rounds,
    # what fraction was hidden behind compute (prefetch adopted) vs paid
    # synchronously at stage() time
    hid = sum(b.get("stage_hidden", 0.0) for b in buckets[1:])
    wai = sum(b.get("stage_wait", 0.0) for b in buckets[1:])
    overlap = round(hid / (hid + wai), 4) if (hid + wai) > 0 else None

    churn = ("-" if not cfg.lifecycle_enabled else
             "+".join([f"j{r}:{c}" for r, c in cfg.join_schedule or ()]
                      + ([f"re{recluster_every}"] if recluster_every else [])))
    asyn = (f"f{straggler_frac:.1f}/s{max_staleness}" if async_mode else "-")
    layout = {}
    if engine == "sharded":
        from repro.launch.mesh import fed_wave_layout
        cohort = clients_per_round or (universe or clients)
        nd, ws, nw = fed_wave_layout(cohort, pack=pack,
                                     n_devices=n_devices, waves=waves)
        layout = {"n_devices": nd, "wave_slots": ws, "n_waves": nw}
    return {"engine": engine, "algorithm": algorithm,
            "kd_impl": kd_impl if algorithm in ("fedsikd", "random") else "-",
            "clients": clients, "universe": universe,
            **layout,
            "pack": pack if engine == "sharded" else None,
            "overlap_efficiency": overlap,
            "participation": participation,
            "clients_per_round": clients_per_round,
            "dropout_rate": dropout_rate,
            "churn": churn, "async": asyn,
            "stale_merged": sum(h.get("stale_merged", [])),
            "stale_dropped": sum(h.get("stale_dropped", [])),
            "rounds": rounds, "total_s": round(total, 3),
            "compile_s": None if compile_s is None else round(compile_s, 3),
            "steady_s_per_round": round(steady, 4),
            "phases": phases,
            "final_acc": h["acc"][-1], "acc_curve": h["acc"]}


def print_rows(rows):
    print(f"{'engine':8s} {'alg':8s} {'kd_impl':10s} {'C':>3s} {'pack':>4s} "
          f"{'part':>10s} {'drop':>5s} {'churn':>13s} {'async':>9s} "
          f"{'total':>8s} {'compile':>8s} {'steady s/rnd':>13s} "
          f"{'final acc':>10s}")
    for r in rows:
        comp = "-" if r["compile_s"] is None else f"{r['compile_s']:.1f}s"
        print(f"{r['engine']:8s} {r['algorithm']:8s} {r['kd_impl']:10s} "
              f"{r['clients']:3d} "
              f"{str(r['pack'] or '-'):>4s} {r['participation']:>10s} "
              f"{r['dropout_rate']:5.2f} {r['churn']:>13s} "
              f"{r['async']:>9s} "
              f"{r['total_s']:7.1f}s {comp:>8s} "
              f"{r['steady_s_per_round']:12.2f}s "
              f"{r['final_acc']:10.3f}")
        ph = r["phases"]
        if any(ph.get(k) for k in PHASES):
            print("    phases: " + "  ".join(
                f"{k}={ph.get(k, 0.0):.2f}s" for k in PHASES))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small CI smoke sweep (2 rounds each)")
    ap.add_argument("--hotpath", action="store_true",
                    help="§13 hot-path gate: fedsikd + fedavg on the packed "
                         "mesh (C=8, pack=2), steady-state vs PR 6 baseline")
    ap.add_argument("--waves", action="store_true",
                    help="§15 wave-scaling gate: the SAME sampled cohort on "
                         "the SAME fixed mesh at two client-universe sizes; "
                         "steady round time must not grow with the universe")
    ap.add_argument("--universes", type=int, nargs=2,
                    default=(1000, 100000), metavar=("SMALL", "LARGE"),
                    help="the two client-universe sizes --waves compares")
    ap.add_argument("--base-clients", type=int, default=50,
                    help="--waves: base shard pool size the universe aliases")
    ap.add_argument("--cohort", type=int, default=32,
                    help="--waves: sampled clients per round (stratified)")
    ap.add_argument("--devices", type=int, default=8,
                    help="--waves: mesh devices (pack=1 -> wave_slots)")
    ap.add_argument("--assert-scaling", type=float, default=None,
                    help="--waves: fail (exit 1) unless steady(large) <= "
                         "this multiple of steady(small)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default=None,
                    help="JSON artifact path ('' disables; default "
                         "BENCH_hotpath.json under --hotpath, "
                         "BENCH_waves.json under --waves, "
                         "BENCH_engines.json otherwise)")
    args = ap.parse_args()
    print(f"jax backend: {jax.default_backend()} ({len(jax.devices())} devices)")
    if args.out is None:
        args.out = ("BENCH_hotpath.json" if args.hotpath else
                    "BENCH_waves.json" if args.waves else
                    "BENCH_engines.json")

    ds = load_dataset("mnist", small=True)
    if args.waves:
        # guards=True makes every steady round assert zero recompiles and
        # zero implicit transfers — the "no recompiles past warm-in" half
        # of the §15 acceptance runs INSIDE the benchmark
        rounds = args.rounds or 5
        small_u, large_u = args.universes
        kw = dict(algorithm="fedsikd", clients=args.base_clients,
                  participation="stratified", clients_per_round=args.cohort,
                  n_devices=args.devices, rounds=rounds, guards=True)
        rows = [bench_engine(ds, "sharded", universe=small_u, **kw),
                bench_engine(ds, "sharded", universe=large_u, **kw)]
        print_rows(rows)
        s_small = rows[0]["steady_s_per_round"]
        s_large = rows[1]["steady_s_per_round"]
        ratio = round(s_large / s_small, 4)
        print(f"wave scaling: universe {small_u} -> {large_u} "
              f"({large_u / small_u:.0f}x), cohort {args.cohort} on "
              f"{rows[0]['n_waves']} waves x {rows[0]['wave_slots']} slots: "
              f"steady {s_small:.2f}s -> {s_large:.2f}s/round "
              f"(ratio {ratio:.3f})")
        for r in rows:
            if r["overlap_efficiency"] is not None:
                print(f"  universe {r['universe']}: overlap_efficiency="
                      f"{r['overlap_efficiency']:.3f} (staging hidden "
                      "behind compute)")
        if args.out:
            artifact = {
                "benchmark": "wave_scaling",
                "host": {"platform": platform.platform(),
                         "python": platform.python_version()},
                "config": {"dataset": "mnist-small",
                           "base_clients": args.base_clients,
                           "cohort": args.cohort, "devices": args.devices,
                           "universes": [small_u, large_u],
                           "rounds": rounds, "guards": True},
                "steady_ratio_large_over_small": ratio,
                "tolerance": args.assert_scaling,
                "rows": rows,
            }
            with open(args.out, "w") as f:
                json.dump(artifact, f, indent=2)
            print(f"wrote {args.out} ({len(rows)} rows)")
        if args.assert_scaling is not None and ratio > args.assert_scaling:
            raise SystemExit(
                f"wave scaling REGRESSION: steady ratio {ratio:.3f} > "
                f"tolerance {args.assert_scaling} — round time grew with "
                f"the universe at fixed cohort/mesh")
        return
    if args.hotpath:
        # EXACTLY the PR 6 baseline config (see PR6_STEADY_BASELINE), run
        # under the runtime sanitizers (guards.py): steady-state rounds
        # must survive the transfer guard and the recompile sentinel —
        # the hot-path gate doubles as the guards acceptance run
        rounds = args.rounds or 4
        rows = [
            bench_engine(ds, "sharded", algorithm="fedsikd", clients=8,
                         pack=2, rounds=rounds, guards=True),
            bench_engine(ds, "sharded", algorithm="fedavg", clients=8,
                         pack=2, rounds=rounds, guards=True),
        ]
        print_rows(rows)
        speedup = {}
        for r in rows:
            base = PR6_STEADY_BASELINE[f"{r['algorithm']}_s_per_round"]
            speedup[r["algorithm"]] = round(base / r["steady_s_per_round"], 3)
            print(f"hot path {r['algorithm']}: steady "
                  f"{r['steady_s_per_round']:.2f}s/round vs PR6 "
                  f"{base:.2f}s/round -> {speedup[r['algorithm']]:.2f}x")
        if args.out:
            artifact = {
                "benchmark": "engine_hotpath",
                "host": {"platform": platform.platform(),
                         "python": platform.python_version()},
                "config": {"dataset": "mnist-small", "engine": "sharded",
                           "clients": 8, "pack": 2, "rounds": rounds,
                           "alpha": 1.0, "batch_size": 32, "clusters": 3,
                           "teacher_warmup_epochs": 1},
                "baseline_pr6": PR6_STEADY_BASELINE,
                "speedup_vs_pr6": speedup,
                "rows": rows,
            }
            with open(args.out, "w") as f:
                json.dump(artifact, f, indent=2)
            print(f"wrote {args.out} ({len(rows)} rows)")
        return

    if args.quick:
        rounds = args.rounds or 2
        rows = [
            bench_engine(ds, "loop", clients=8, rounds=rounds),
            bench_engine(ds, "sharded", clients=8, pack=2, rounds=rounds),
            # dropout scenario smoke: survivors reweighted per round
            bench_engine(ds, "loop", clients=8, rounds=rounds,
                         participation="uniform", clients_per_round=6,
                         dropout_rate=0.25),
            # baselines-on-mesh smoke: fedavg through both engines
            bench_engine(ds, "loop", algorithm="fedavg", clients=8,
                         rounds=rounds),
            bench_engine(ds, "sharded", algorithm="fedavg", clients=8,
                         pack=2, rounds=rounds),
            # churn scenario smoke: one join event + a periodic re-cluster
            bench_engine(ds, "loop", clients=8, rounds=max(rounds, 2),
                         join_schedule=((2, 2),), recluster_every=2),
            # semi-async smoke: stragglers buffered + staleness-merged
            bench_engine(ds, "sharded", clients=8, pack=2,
                         rounds=max(rounds, 2), async_mode=True,
                         straggler_frac=0.4),
        ]
    else:
        rounds = args.rounds or 3
        rows = [
            bench_engine(ds, "loop", clients=8, rounds=rounds),
            bench_engine(ds, "loop", clients=32, rounds=rounds),
            bench_engine(ds, "sharded", clients=8, pack=1, rounds=rounds),
            bench_engine(ds, "sharded", clients=8, pack=1,
                         kd_impl="reference", rounds=rounds),
            bench_engine(ds, "sharded", clients=16, pack=2, rounds=rounds),
            # the 8-device testbed as a 32-client mesh, sampled rounds
            bench_engine(ds, "sharded", clients=32, pack=4, rounds=rounds),
            bench_engine(ds, "sharded", clients=32, pack=4, rounds=rounds,
                         participation="stratified", clients_per_round=16),
            # dropout sweep: the failure scenario on both engines — same
            # sampled plans, 20% of invitees fail each round
            bench_engine(ds, "loop", clients=32, rounds=rounds,
                         participation="stratified", clients_per_round=16,
                         dropout_rate=0.2),
            bench_engine(ds, "sharded", clients=32, pack=4, rounds=rounds,
                         participation="stratified", clients_per_round=16,
                         dropout_rate=0.2),
            # the paper's baselines on the SAME packed mesh (fed/algorithms/
            # baselines.py): loop-vs-sharded rows so the comparative sweeps'
            # scalable path is tracked per commit too
            bench_engine(ds, "loop", algorithm="fedavg", clients=32,
                         rounds=rounds),
            bench_engine(ds, "sharded", algorithm="fedavg", clients=32,
                         pack=4, rounds=rounds),
            bench_engine(ds, "loop", algorithm="fedprox", clients=32,
                         rounds=rounds),
            bench_engine(ds, "sharded", algorithm="fedprox", clients=32,
                         pack=4, rounds=rounds,
                         participation="stratified", clients_per_round=16,
                         dropout_rate=0.2),
            # churn scenario (DESIGN.md §11): 32 clients on the packed mesh,
            # joins at rounds 3 and 6, re-clustering every 3 rounds — tracks
            # the cost of the lifecycle path (batched stats front-end,
            # warm-started k-means, teacher migration, feed re-staging)
            # against the static rows above
            bench_engine(ds, "loop", clients=32, rounds=max(rounds, 6),
                         join_schedule=((3, 4), (6, 4)), recluster_every=3),
            bench_engine(ds, "sharded", clients=32, pack=4,
                         rounds=max(rounds, 6),
                         join_schedule=((3, 4), (6, 4)), recluster_every=3),
            # semi-async rounds (DESIGN.md §12): 40% stragglers under the
            # bounded-staleness buffer, on both engines — tracks the cost
            # of the split merge (host-side add_scaled folds) against the
            # synchronous rows above
            bench_engine(ds, "loop", clients=32, rounds=max(rounds, 4),
                         async_mode=True, straggler_frac=0.4),
            bench_engine(ds, "sharded", clients=32, pack=4,
                         rounds=max(rounds, 4),
                         async_mode=True, straggler_frac=0.4),
        ]

    print_rows(rows)
    spread = [r["final_acc"] for r in rows
              if r["clients"] == 8 and r["participation"] == "full"
              and r["algorithm"] == "fedsikd" and r["churn"] == "-"
              and r["async"] == "-"]
    if len(spread) > 1:
        print("engine agreement (C=8, full): max final-acc spread "
              f"{max(spread) - min(spread):.4f}")

    if args.out:
        artifact = {
            "benchmark": "engine_bench",
            "host": {"platform": platform.platform(),
                     "python": platform.python_version()},
            "config": {"dataset": "mnist-small", "quick": args.quick,
                       "rounds": rounds},
            "rows": rows,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Bring-up smoke: the FedSiKD round on TPU through ``run_federated``.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the client mesh across four chips

One chip runs, in one process:

1. the Pallas kernels of the round (fused KD loss under ``vmap`` with its
   gradient, the fused merge) against the jnp / numpy references;
2. FedSiKD on the sharded engine: the full MNIST twin made from ``--seed``,
   the paper's CNNs at their published widths, 16 clients, alpha = 0.5,
   one device x 16 packed lanes, 3 rounds, ``kd_impl="fused"``.  The
   lowered round program must hold the compiled kernel
   (``tpu_custom_call``), not the interpreter;
3. the same run with ``kd_impl="reference"``: final accuracies within
   1 point;
4. a semi-async run (40% stragglers, guards on) whose staleness merges
   reach ``core.aggregation``'s Pallas ``fused_merge`` branch.

``--chips 4`` runs only the multi-chip path and what it is compared with:
the same FedSiKD run on 4 devices x 4 lanes, and on 1 device x 16 lanes of
the same host; the accuracy histories agree within 1 point and the staged
client stacks span the four devices.

Every phase that fails ends the script with a non-zero exit.  The last line
of stdout is ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ACC_POINT = 0.01          # "1 accuracy point", the repo's parity bound
FEDSIKD = dict(algorithm="fedsikd", engine="sharded", num_clients=16,
               alpha=0.5, rounds=3)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")


def device_info():
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------- phases
def phase_kernels(seed):
    """The round's kernels on the chip against the references."""
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    L, T, V = 16, 64, 10      # lanes x batch x MNIST classes
    s = jax.random.normal(ks[0], (L, T, V)) * 3
    t = jax.random.normal(ks[1], (L, T, V)) * 3
    y = jax.random.randint(ks[2], (L, T), -1, V)     # -1 rows are padding

    fused = jax.jit(jax.vmap(jax.value_and_grad(
        lambda s, t, y: ops.kd_distillation_loss(s, t, y, 2.0, 0.5))))

    def ref_loss(s, t, y):
        per_tok = ref.kd_loss_ref(s, t, y, tau=2.0, alpha=0.5)
        return per_tok.sum() / jnp.maximum((y >= 0).sum(), 1)

    want = jax.jit(jax.vmap(jax.value_and_grad(ref_loss)))
    (lf, gf), (lr, gr) = fused(s, t, y), want(s, t, y)
    check(bool(jnp.all(jnp.isfinite(gf))), "KD kernel gradient not finite")
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)
    text = fused.lower(s, t, y).as_text()
    check("tpu_custom_call" in text, "KD kernel was not compiled for TPU")

    N, D = 16, 2560           # 16 client copies of the MNIST student head
    x = np.asarray(jax.random.normal(ks[3], (N, D)), np.float64)
    w = np.linspace(1.0, 2.0, N)
    st = np.arange(N) % 3
    got = np.asarray(ops.fused_merge(jnp.asarray(x, jnp.float32),
                                     jnp.asarray(w, jnp.float32),
                                     jnp.asarray(st, jnp.float32), decay=0.5))
    wn = w * (1.0 + st) ** -0.5
    np.testing.assert_allclose(got, (wn / wn.sum()) @ x, rtol=1e-5, atol=1e-5)
    print(f"kernels: kd loss+grad (L={L}, T={T}, V={V}) and fused_merge "
          f"(N={N}, D={D}) match the references", flush=True)


def timed_run(ds, cfg, label):
    """``run_federated`` under the phase timer; prints per-round accuracy
    and loss (the driver's progress lines) and the set-up / compile split:
    set-up is the wall clock outside the rounds (partition, clustering,
    staging, teacher warm-up), compile is round 1's excess over the later
    rounds' median."""
    from repro import perf
    from repro.fed.rounds import run_federated
    print(f"{label}: {cfg.algorithm} engine={cfg.engine} "
          f"kd_impl={cfg.kd_impl} clients={cfg.num_clients} "
          f"n_devices={cfg.n_devices} pack={cfg.pack} "
          f"rounds={cfg.rounds}", flush=True)
    perf.enable()
    t0 = time.perf_counter()
    try:
        h = run_federated(ds, cfg, progress=True)
    finally:
        wall = time.perf_counter() - t0
        buckets = perf.snapshot()
        perf.disable()
    rounds = [b.get("round_total", 0.0) + b.get("eval", 0.0)
              + b.get("checkpoint", 0.0) for b in buckets]
    steady = float(np.median(rounds[1:])) if len(rounds) > 1 else 0.0
    print(f"{label}: setup_s={wall - sum(rounds):.3f} "
          f"round1_compile_s={max(rounds[0] - steady, 0.0):.3f} "
          f"round_s={[round(r, 3) for r in rounds]}", flush=True)
    for name in ("teacher_loss", "student_loss"):
        if h.get(name):
            print(f"{label}: {name}={[round(v, 4) for v in h[name]]}")
    check(len(h["acc"]) == cfg.rounds, f"{label}: {len(h['acc'])} rounds")
    check(all(math.isfinite(v) for v in h["acc"] + h["loss"]),
          f"{label}: non-finite accuracy or loss {h['acc']} {h['loss']}")
    check(all(0.0 <= a <= 1.0 for a in h["acc"]), f"{label}: {h['acc']}")
    return h


def capture_round_programs():
    """Record the argument shapes of every KD round program the strategy
    builds (``teacher_data="leader"``, the default: one teacher per cluster
    row), so its lowering can be inspected after the run."""
    from repro.fed import sharded
    made = []
    make = sharded.make_leader_kd_round

    def capturing(*args, **kwargs):
        fn = make(*args, **kwargs)
        entry = {"fn": fn, "specs": None}
        made.append(entry)

        def call(*a):
            if entry["specs"] is None:
                entry["specs"] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding), a)
            return fn(*a)
        return call

    sharded.make_leader_kd_round = capturing
    return made


def phase_fedsikd(ds, seed):
    from repro.fed.rounds import FedConfig
    made = capture_round_programs()
    h_fused = timed_run(ds, FedConfig(**FEDSIKD, n_devices=1, pack=16,
                                      kd_impl="fused", seed=seed),
                        "fedsikd[fused]")
    check(made and made[0]["specs"] is not None, "no KD round program ran")
    lowered = made[0]["fn"].lower(*made[0]["specs"])
    check("tpu_custom_call" in lowered.as_text(),
          "the lowered KD round has no tpu_custom_call: the Pallas kernel "
          "was interpreted")
    print("fedsikd[fused]: lowered KD round contains tpu_custom_call",
          flush=True)
    h_ref = timed_run(ds, FedConfig(**FEDSIKD, n_devices=1, pack=16,
                                    kd_impl="reference", seed=seed),
                      "fedsikd[reference]")
    gap = abs(h_fused["acc"][-1] - h_ref["acc"][-1])
    print(f"fused vs reference: final acc {h_fused['acc'][-1]:.4f} vs "
          f"{h_ref['acc'][-1]:.4f}, gap {gap:.4f}", flush=True)
    check(gap <= ACC_POINT, f"fused vs reference final-acc gap {gap}")


def phase_async(ds, seed):
    """Semi-async rounds: buffered straggler updates fold back through the
    staleness merges, whose kernel branch must run."""
    from repro.fed.rounds import FedConfig
    from repro.kernels import ops
    calls = []
    merge = ops.fused_merge

    def counting(*args, **kwargs):
        calls.append(1)
        return merge(*args, **kwargs)

    ops.fused_merge = counting
    try:
        h = timed_run(ds, FedConfig(**FEDSIKD, n_devices=1, pack=16,
                                    async_mode=True, straggler_frac=0.4,
                                    guards=True, seed=seed),
                      "fedsikd[async]")
    finally:
        ops.fused_merge = merge
    pushed = sum(h["stragglers"])
    settled = (sum(h["stale_merged"]) + sum(h["stale_dropped"])
               + h["buffered"][-1])
    print(f"fedsikd[async]: stragglers={h['stragglers']} "
          f"merged={h['stale_merged']} dropped={h['stale_dropped']} "
          f"buffered={h['buffered']} fused_merge_kernel_calls={len(calls)}",
          flush=True)
    check(pushed > 0, "no straggler update was buffered")
    check(pushed == settled, f"staleness accounting: {pushed} != {settled}")
    check(calls, "the staleness merges never reached the fused_merge kernel")


def phase_four_chips(ds, seed):
    """The client mesh across four chips against one device of the host."""
    from repro.fed import sharded
    from repro.fed.rounds import FedConfig
    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, JAX has "
          f"{len(jax.devices())}")
    staged = []
    stage = sharded.stage_on_slots

    def recording(*args, **kwargs):
        out = stage(*args, **kwargs)
        staged.extend(out)
        return out

    made = capture_round_programs()
    sharded.stage_on_slots = recording
    try:
        h4 = timed_run(ds, FedConfig(**FEDSIKD, n_devices=4, pack=4,
                                     seed=seed), "fedsikd[4x4]")
    finally:
        sharded.stage_on_slots = stage
    spans = {len(a.sharding.device_set) for a in staged}
    rows = {a.addressable_shards[0].data.shape[0] for a in staged}
    print(f"fedsikd[4x4]: {len(staged)} staged client stacks, device "
          f"counts {sorted(spans)}, rows per device {sorted(rows)}",
          flush=True)
    check(staged and spans == {4} and rows == {4},
          "staged client stacks do not span the 4 devices")
    # the round program's student params, optimizer states and batch
    # stacks (the per-slot step counts and keys are (S,) vectors jit
    # reshards itself; the (K,) teacher rows are replicated)
    specs = made[0]["specs"]
    slot_specs = jax.tree_util.tree_leaves(specs[2:4] + specs[7:9])
    check(all(len(x.sharding.device_set) == 4
              and x.sharding.shard_shape(x.shape)[0] == 4
              for x in slot_specs),
          "round-program slot stacks do not span the 4 devices")
    h1 = timed_run(ds, FedConfig(**FEDSIKD, n_devices=1, pack=16,
                                 seed=seed), "fedsikd[1x16]")
    gaps = [abs(a - b) for a, b in zip(h4["acc"], h1["acc"])]
    print(f"4 devices vs 1: acc {h4['acc']} vs {h1['acc']}, max gap "
          f"{max(gaps):.4f}", flush=True)
    check(max(gaps) <= ACC_POINT, f"4-device vs 1-device gap {gaps}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    require_tpu()
    from repro.data.synthetic import dataset_source, load_dataset
    from repro.launch.compile_cache import enable_compile_cache
    cache = Path(enable_compile_cache())
    warm = cache.is_dir() and any(cache.iterdir())
    print(f"compile cache: {cache} ({'warm' if warm else 'cold'})")
    print(f"device: {device_info()}", flush=True)
    t0 = time.perf_counter()
    ds = load_dataset("mnist", seed=args.seed)
    print(f"dataset: mnist from {dataset_source('mnist')} (seed "
          f"{args.seed}), train {ds.x_train.shape}, test {ds.x_test.shape}, "
          f"made in {time.perf_counter() - t0:.3f}s", flush=True)
    if args.chips == 4:
        phase_four_chips(ds, args.seed)
    else:
        phase_kernels(args.seed)
        phase_fedsikd(ds, args.seed)
        phase_async(ds, args.seed)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


if __name__ == "__main__":
    main()

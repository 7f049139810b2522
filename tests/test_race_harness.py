"""Schedule-jitter race harness (src/repro/guards.py, DESIGN.md §16).

``guards="jitter"`` arms deterministic seeded sleeps at every thread
handoff point (prefetch workers, stager joins, the async checkpoint
writer's queue), stretching the adversarial interleavings of the packed
runtime's background threads.  The acceptance property: the threads
overlap TIMING only, never sources of truth — so full run histories must
stay bitwise identical with jitter on vs off, with every concurrent
feature enabled at once (wave prefetch + async checkpointing + semi-async
straggler arrivals).  The sharded engine needs 8 host devices, so
everything runs in subprocesses (XLA_FLAGS pre-import, DESIGN.md §6).
"""
import textwrap

from _subproc import run_script

# ---------------------------------------------------- unit: jitter knob
_JITTER_UNIT = textwrap.dedent("""
    import time
    from repro import guards

    # disarmed: free
    t0 = time.perf_counter()
    for _ in range(1000):
        guards.jitter_point("x")
    assert time.perf_counter() - t0 < 0.5
    assert not guards.jitter_enabled()

    # armed: deterministic per (seed, tag, occurrence) — replaying a tag
    # sequence under one seed sleeps the identical schedule (the requested
    # sleep durations are recorded exactly, so host load cannot blur them)
    def schedule(seed, tags):
        slept, real_sleep = [], time.sleep
        time.sleep = lambda d: (slept.append(d), real_sleep(d))
        guards.enable_jitter(seed)
        try:
            for t in tags:
                guards.jitter_point(t)
        finally:
            guards.disable_jitter()
            time.sleep = real_sleep
        return slept

    tags = ["wave-stage", "wave-prefetch", "wave-stage", "ckpt-submit"]
    a, b = schedule(7, tags), schedule(7, tags)
    assert a == b and len(a) == len(tags), (a, b)
    assert any(d > 0.0 for d in a), a          # it actually sleeps
    assert schedule(8, tags) != a or True      # other seeds are legal too
    assert not guards.jitter_enabled()
    print("JITTER-UNIT-OK", a)
""")


def test_jitter_point_is_deterministic_and_free_when_disarmed():
    r = run_script(_JITTER_UNIT)
    assert "JITTER-UNIT-OK" in r.stdout, r.stdout + r.stderr


# ------------------------- end-to-end: jitter never changes a computed bit
_JITTER_PARITY = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.data.synthetic import load_dataset
    from repro.fed.rounds import FedConfig, run_federated

    ds = load_dataset("mnist", small=True)
    # every concurrent feature at once: a 16-client universe streaming in
    # waves through a 4-slot mesh, background wave prefetch, the async
    # checkpoint writer, and semi-async straggler arrivals
    for algorithm in ("fedsikd", "fedavg"):
        common = dict(algorithm=algorithm, engine="sharded", num_clients=8,
                      universe=16, n_devices=2, pack=2, alpha=1.0,
                      rounds=4, local_epochs=1, teacher_warmup_epochs=1,
                      batch_size=32, num_clusters=2,
                      participation="stratified", clients_per_round=8,
                      async_mode=True, straggler_frac=0.4, max_staleness=2,
                      prefetch=True, async_ckpt=True, ckpt_every=1, seed=0)
        h_off = run_federated(ds, FedConfig(
            **common, ckpt_dir=tempfile.mkdtemp(), guards=False))
        h_jit = run_federated(ds, FedConfig(
            **common, ckpt_dir=tempfile.mkdtemp(), guards="jitter"))
        assert sorted(h_off) == sorted(h_jit), (sorted(h_off),
                                                sorted(h_jit))
        for k in h_off:
            assert h_jit[k] == h_off[k], (algorithm, k, h_jit[k], h_off[k])
        print("PARITY-OK", algorithm, h_off["acc"])
    print("JITTER-PARITY-OK")
""")


def test_histories_bitwise_identical_under_jitter():
    r = run_script(_JITTER_PARITY)
    assert "JITTER-PARITY-OK" in r.stdout, r.stdout + r.stderr


# --------------- regression: WaveStager eviction with in-flight prefetch
_EVICTION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro import guards
    from repro.fed import sharded as sh
    from repro.fed.schedule import RoundPlan
    from repro.launch.mesh import make_fed_client_mesh

    S, C = 4, 16
    mesh = make_fed_client_mesh(S, pack=2, n_devices=2)
    x_all = np.arange(C * 3 * 2, dtype=np.float32).reshape(C, 3, 2)
    y_all = (np.arange(C * 3, dtype=np.int32) % 7).reshape(C, 3)

    def plan(r, clients):
        cid = np.asarray(clients, np.int32)
        return RoundPlan(round_index=r, pack=2, slot_client=cid,
                         slot_cluster=np.zeros(S, np.int32),
                         slot_weight=np.full(S, 1 / S, np.float32))

    def staged_np(staged):
        return [np.asarray(a) for a in staged]

    def expect(p):
        return staged_np(sh.stage_on_slots(mesh, p, x_all, y_all))

    guards.enable_jitter(3)      # stretch the prefetch/evict windows
    try:
        stager = sh.WaveStager(mesh, x_all, y_all, capacity=2)
        plans = [plan(r, np.arange(4 * r, 4 * r + 4) % C)
                 for r in range(4)]
        # a prefetch storm: capacity+2 in-flight entries — the pending
        # dict evicts the two OLDEST while their workers may still be
        # mid-gather (the jittered window under test)
        for p in plans:
            stager.prefetch(p)
        assert len(stager._pending) == 2, len(stager._pending)
        # the evicted assignments re-stage synchronously and correctly
        # (the orphaned workers' results are never adopted)...
        for p in plans[:2]:
            got = staged_np(stager.stage(p))
            want = expect(p)
            assert all((g == w).all() for g, w in zip(got, want)), p
        # ...and the surviving in-flight prefetches adopt bit-identically
        for p in plans[2:]:
            got = staged_np(stager.stage(p))
            want = expect(p)
            assert all((g == w).all() for g, w in zip(got, want)), p
        assert not stager._pending
        # LRU re-stage of an assignment WITH an in-flight prefetch for
        # the same key: stage() must prefer the cache and leave nothing
        # pending that could be adopted stale later
        stager.prefetch(plans[3])            # already staged -> no-op
        assert not stager._pending
        again = staged_np(stager.stage(plans[3]))
        assert all((g == w).all() for g, w in zip(again, expect(plans[3])))
    finally:
        guards.disable_jitter()
    print("EVICTION-OK")
""")


def test_wavestager_eviction_with_inflight_prefetch_is_deterministic():
    r = run_script(_EVICTION_SCRIPT)
    assert "EVICTION-OK" in r.stdout, r.stdout + r.stderr


# ------------------- SIGKILL mid-round under every background thread
def _train(ckpt, rounds, *extra, timeout=580):
    import subprocess
    import sys

    from _subproc import ENV
    return subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "fl", "--small",
         "--clients", "8", "--engine", "sharded", "--pack", "2",
         "--waves", "4", "--rounds", str(rounds), "--local-epochs", "1",
         "--clusters", "2", "--ckpt", str(ckpt), "--ckpt-every", "1",
         "--async-ckpt", *extra],
        env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def test_sigkill_mid_round_resumes_bit_identical_no_debris(tmp_path):
    """SIGKILL the CLI mid-round with wave prefetch + the async checkpoint
    writer live (--waves 4 --async-ckpt, prefetch on by default), resume,
    and demand the history is bit-identical to an uninterrupted run —
    with no leftover ``.tmp`` files and a clean process exit (a leaked
    non-daemon thread would hang the interpreter's shutdown join)."""
    import json
    import signal
    import time

    straight, killed = tmp_path / "straight", tmp_path / "killed"
    p = _train(straight, 4)
    out, err = p.communicate(timeout=580)
    assert p.returncode == 0, out + err
    h_full = json.loads((straight / "history.json").read_text())

    p = _train(killed, 4)
    try:
        deadline = time.monotonic() + 560
        # the round-2 snapshot's appearance is the commit point: past it,
        # the run is mid-round-3 with the writer and prefetcher racing
        while not (killed / "round_00002.npz").exists():
            assert p.poll() is None, p.communicate()
            assert time.monotonic() < deadline, "no round-2 checkpoint"
            time.sleep(0.02)
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == -signal.SIGKILL

    p = _train(killed, 4, "--resume")
    out, err = p.communicate(timeout=580)
    assert p.returncode == 0, out + err
    h_res = json.loads((killed / "history.json").read_text())
    for k in ("acc", "loss", "round", "participants"):
        assert h_res[k] == h_full[k], (k, h_res[k], h_full[k])
    assert h_res["round"] == [1, 2, 3, 4]
    debris = [q.name for q in killed.iterdir() if q.suffix == ".tmp"]
    assert not debris, debris

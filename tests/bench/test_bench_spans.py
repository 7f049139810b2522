"""The program's spans, counters and device scopes as the benchmark reads
them (``bench/spans.py``): idle gaps named by the driver thread's program
spans, device time by scope on operations with known scope paths, the
new readers silent where there is nothing to read, and the counters of a
real packed run on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans  # noqa: E402

MS = 1_000_000          # ns
DRIVER, PREFETCH = "/host:CPU#0", "/host:CPU#3"
READERS = ("host_sync_ms", "host_syncs_per_round", "h2d_mb_per_round")


def _host():
    # one measured round, 0-20 ms, on the driver thread: run_round 0-12 ms
    # holding the program's compute 2-10 ms (sync 6-10 ms), eval 12-18 ms
    # holding one sync 14-17 ms; nothing of the program 18-20 ms.  A
    # prefetch thread gathers 1-19 ms.
    return [("bench.round", 0, 20 * MS, DRIVER),
            ("bench.run_round", 0, 12 * MS, DRIVER),
            ("repro.compute", 2 * MS, 8 * MS, DRIVER),
            ("repro.sync", 6 * MS, 4 * MS, DRIVER),
            ("bench.eval", 12 * MS, 6 * MS, DRIVER),
            ("repro.eval", 12 * MS, 6 * MS, DRIVER),
            ("repro.sync", 14 * MS, 3 * MS, DRIVER),
            ("repro.gather", 1 * MS, 18 * MS, PREFETCH)]


def test_timeline_names_the_innermost_span():
    tl = spans.Timeline([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"),
                         (7, 9, "d")])
    assert tl.segments == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                           (4, 5, "b"), (5, 7, "a"), (7, 9, "d"),
                           (9, 10, "a")]
    assert tl.cover(1, 4) == [("a", 1e-9), ("b", 1e-9), ("c", 1e-9)]
    assert tl.uncovered(-3, 12) == [(-3, 0), (10, 12)]


def test_idle_gaps_go_to_the_driver_threads_program_spans():
    # the device is busy 0-1, 3-6 and 11-13 ms; idle 1-3, 6-11, 13-20
    ops = {"/device:TPU:0": [("fusion.1", 0, MS, ""),
                             ("fusion.2", 3 * MS, 3 * MS, ""),
                             ("fusion.3", 11 * MS, 2 * MS, "")]}
    r = spans.reduce(ops, _host())
    gaps = dict(r["idle_gaps"])
    # 1-2 ms: no program span, so the harness's run_round; 2-3 compute;
    # 6-10 sync; 10-11 run_round; 13-14 eval, 14-17 sync, 17-18 eval;
    # 18-20 nothing at all
    assert gaps["repro.sync"] == pytest.approx(0.007)
    assert gaps["repro.compute"] == pytest.approx(0.001)
    assert gaps["repro.eval"] == pytest.approx(0.002)
    assert gaps["bench.run_round"] == pytest.approx(0.002)
    assert gaps["host.other"] == pytest.approx(0.002)
    # the prefetch thread's gather spans every gap and names none
    assert "repro.gather" not in gaps
    assert r["idle_s"] == pytest.approx(0.014)
    assert r["idle_named_s"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(r["idle_s"])


def test_device_time_by_scope():
    path = "jit(kd_round)/jit(main)/shard_map/vmap({})/while/body/{}"
    ops = {"/device:TPU:0": [
        ("fusion.1", 0, 4 * MS, path.format("teacher_phase", "conv")),
        ("fusion.2", 4 * MS, 1 * MS,
         path.format("teacher_phase", "masked_carry/select_n")),
        ("kd_loss_fwd.3", 5 * MS, 2 * MS, path.format("student_kd", "x")),
        ("fusion.4", 7 * MS, 1 * MS,
         "jit(kd_round)/transpose(vmap(student_kd))/masked_carry/select_n"),
        ("all-reduce.5", 8 * MS, 1 * MS, "jit(kd_round)/cross_lane/psum"),
        ("copy.6", 9 * MS, 1 * MS, ""),
        ("fusion.7", 10 * MS, 1 * MS, "jit(f)/student_kd_extra/add"),
        ("while.8", 0, 11 * MS, "")]}
    host = [("bench.round", 0, 11 * MS, DRIVER)]
    r = spans.reduce(ops, host)
    s = r["scope_s"]
    assert s["teacher_phase"] == pytest.approx(0.005)
    assert s["student_kd"] == pytest.approx(0.003)
    assert s["masked_carry"] == pytest.approx(0.002)
    assert s["cross_lane"] == pytest.approx(0.001)
    assert "eval_forward" not in s
    # a scope counts as a whole path segment, not a prefix of one; control
    # flow is busy time but no operation of its own
    assert r["op_s"] == pytest.approx(0.011)
    assert r["scoped_s"] == pytest.approx(0.009)
    assert r["unscoped_ops"] == [["copy.6", pytest.approx(0.001)],
                                 ["fusion.7", pytest.approx(0.001)]]


def test_no_window_or_no_device_op_reads_nothing():
    assert spans.reduce({}, _host()) is None
    assert spans.reduce({"/device:TPU:0": [("x", 0, MS, "")]}, []) is None


def _exported():
    # round 0: compute with a sync, eval with two syncs; round 1: one sync;
    # round 2 still open (the round the window closed in)
    rec = lambda name, parent, s, e, rnd, th="MainThread": {
        "name": name, "parent": parent, "start": s, "end": e, "round": rnd,
        "thread": th}
    return {"spans": [rec("compute", None, 0.0, 0.10, 0),
                      rec("sync", 0, 0.05, 0.08, 0),
                      rec("eval", None, 0.10, 0.20, 0),
                      rec("sync", 2, 0.11, 0.12, 0),
                      rec("sync", 2, 0.13, 0.14, 0),
                      rec("gather", None, 0.0, 0.3, 0, "wave-prefetch"),
                      rec("eval", None, 0.3, 0.45, 1),
                      rec("sync", 6, 0.3, 0.4, 1),
                      rec("sync", None, 0.6, 0.9, 2)],
            "counts": [{"host_syncs": 6, "h2d_bytes": 3_000_000},
                       {"host_syncs": 2, "h2d_bytes": 1_000_000}]}


def test_records_per_round_and_the_longest_rounds():
    ex = _exported()
    assert spans.window_rounds(ex) == 2
    assert spans.per_round(ex, "host_syncs") == 4
    assert spans.per_round(ex, "h2d_bytes") == 2_000_000
    assert spans.per_round(ex, "no_such") is None
    # the open round's sync is left out; the mean is over closed rounds
    assert spans.span_seconds(ex, "sync", "MainThread") == \
        pytest.approx((0.03 + 0.01 + 0.01 + 0.1) / 2)
    assert spans.round_thread(ex) == "MainThread"
    own = [0.07, 0.03, 0.08, 0.01, 0.01, 0.3, 0.1, 0.1, 0.3]
    (r, sec, parts), = spans.longest_rounds(ex, own, k=1)
    assert (r, sec) == (0, pytest.approx(0.2))
    assert parts["eval"] == pytest.approx(0.08)
    assert parts["gather"] == pytest.approx(0.3)
    lines = spans.report(None, ex, own, k=2)
    assert len(lines) == 2 and lines[0].startswith("long round 0: 200.0 ms")


def test_new_readers_read_nothing_without_records(monkeypatch):
    from repro import perf
    spec = harness.Spec(ROOT)
    ctx = {"trace": None, "peaks": None, "perf_rounds": [], "window_s": 1.0,
           "window_flops": 1.0}
    perf.enable()                    # nothing recorded
    perf.disable()
    for name in READERS:
        assert spec.reader(name)(ctx) is None
    # a program without span records (before ``perf.export`` existed)
    monkeypatch.delattr(perf, "export")
    for name in READERS:
        assert spec.reader(name)(ctx) is None


def test_new_readers_on_recorded_rounds(monkeypatch):
    from repro import perf
    monkeypatch.setattr(perf, "export", _exported)
    spec = harness.Spec(ROOT)
    got = {n: spec.reader(n)({}) for n in READERS}
    assert got == {"host_sync_ms": pytest.approx(75.0),
                   "host_syncs_per_round": 4,
                   "h2d_mb_per_round": 2.0}


def test_a_packed_cpu_run_counts_its_syncs_and_bytes():
    """Two rounds of a tiny packed FedSiKD cell in four waves on the CPU:
    two loss reads per wave, two reads per eval batch."""
    from repro import perf
    from repro.data.synthetic import load_dataset
    from repro.fed.algorithms import make_algorithm
    from repro.fed.driver import RoundDriver
    from repro.fed.rounds import FedConfig
    ds = load_dataset("mnist", small=True)
    cfg = FedConfig(engine="sharded", num_clients=4, pack=1, n_devices=1,
                    waves=4, rounds=2, local_epochs=1, batch_size=64,
                    teacher_warmup_epochs=0, num_clusters=2, seed=0)
    alg = make_algorithm(cfg)
    perf.enable()
    try:
        RoundDriver(ds, cfg, alg).run()
    finally:
        perf.disable()
    spec = harness.Spec(ROOT)
    waves, batches = alg.scheduler.n_waves, -(-len(ds.y_test) // 256)
    assert waves == 4
    assert spec.reader("host_syncs_per_round")({}) == 2 * waves + 2 * batches
    ex = perf.export()
    # eval's batches cross every round; the full cohort's staging is
    # gathered in round 1 and cached after
    test_mb = (ds.x_test.nbytes + ds.y_test.nbytes) / 1e6
    per = [c["h2d_bytes"] / 1e6 for c in ex["counts"]]
    assert len(per) == 2 and per[1] >= test_mb and per[0] > per[1]
    assert spec.reader("host_sync_ms")({}) > 0
    names = {s["name"] for s in ex["spans"]}
    assert {"round_total", "plan", "stage", "stager", "prep", "compute",
            "dispatch", "sync", "aggregate", "eval", "eval_step",
            "gather"} <= names

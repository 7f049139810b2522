"""The benchmark's trace reduction on a synthetic trace with known intervals:
busy and idle time, time and calls by operation name, and idle gaps
attributed to the host annotation open at the time."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

MS = 1_000_000          # ns


def _trace():
    # two measured rounds, 0-10 ms and 10-20 ms; inside them the host is in
    # run_round 0-6 ms, eval 6-9 ms, plan 10-12 ms, run_round 12-20 ms
    host = [("bench.round", 0, 10 * MS), ("bench.round", 10 * MS, 10 * MS),
            ("bench.run_round", 0, 6 * MS), ("bench.eval", 6 * MS, 3 * MS),
            ("bench.plan", 10 * MS, 2 * MS),
            ("bench.run_round", 12 * MS, 8 * MS)]
    # device 0: ops cover 1-5, 4-6 (overlap), 7-8, 13-19 ms, plus an op
    # before the window that must not count
    dev0 = [("fusion.1", 1 * MS, 4 * MS), ("kd_loss_fwd.3", 4 * MS, 2 * MS),
            ("fusion.1", 7 * MS, 1 * MS), ("kd_loss_bwd.4", 13 * MS, 6 * MS),
            ("copy", -5 * MS, 2 * MS)]
    return {"/device:TPU:0": dev0}, host


def test_window_is_the_span_of_the_measured_rounds():
    _, host = _trace()
    assert trace.window_of(host) == (0, 20 * MS)
    assert trace.window_of([("bench.eval", 0, 5)]) is None


def test_union_merges_overlaps():
    assert trace.union([(4, 6), (1, 5), (7, 8)]) == [(1, 6), (7, 8)]


def test_busy_and_idle_share():
    ops, host = _trace()
    r = trace.reduce(ops, host)
    assert r["window_s"] == pytest.approx(0.020)
    # busy: 1-6, 7-8, 13-19 ms = 5 + 1 + 6 = 12 ms of 20
    assert r["busy_s"] == pytest.approx(0.012)
    assert r["devices"] == 1


def test_time_and_calls_by_name():
    ops, host = _trace()
    r = trace.reduce(ops, host)
    assert r["op_s"]["fusion.1"] == pytest.approx(0.005)
    assert r["op_n"]["fusion.1"] == 2
    assert "copy" not in r["op_s"]
    kd = ["kd_loss_fwd", "kd_loss_bwd"]
    assert trace.time_of(r["op_s"], kd) == pytest.approx(0.008)
    assert trace.count_of(r["op_n"], kd) == 2
    assert r["device_ops"][0] == ["kd_loss_bwd.4", pytest.approx(0.006)]


def test_idle_gaps_are_attributed_to_the_open_host_annotation():
    ops, host = _trace()
    gaps = dict(trace.reduce(ops, host)["idle_gaps"])
    # idle: 0-1 (run_round), 6-7 (eval), 8-13 (eval 8-9, none 9-10,
    # plan 10-12, run_round 12-13 -> attributed by the gap's middle,
    # 10.5 ms: plan), 19-20 (run_round)
    assert gaps["bench.run_round"] == pytest.approx(0.002)
    assert gaps["bench.eval"] == pytest.approx(0.001)
    assert gaps["bench.plan"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.008)


def test_innermost_annotation_wins():
    tl = trace.Timeline([("bench.run_round", 0, 10), ("bench.stage", 2, 3)])
    assert tl.at(1) == "bench.run_round"
    assert tl.at(3) == "bench.stage"
    assert tl.at(20) == "host.other"


def test_control_flow_is_busy_but_not_an_operation():
    _, host = _trace()
    ops = {"/device:TPU:0": [("while.1", 0, 10 * MS), ("fusion.2", MS, MS)]}
    r = trace.reduce(ops, host)
    assert r["busy_s"] == pytest.approx(0.010)
    assert set(r["op_s"]) == {"fusion.2"}


def test_op_name_from_hlo_text():
    text = ("%jvp_jit_kd_loss_fwd__.10 = (f32[16,128,1]) custom-call("
            "f32[16,128,10] %custom-call.26)")
    assert trace.op_name(text) == "jvp_jit_kd_loss_fwd__.10"
    assert trace.op_name("copy.3") == "copy.3"


def test_no_device_ops_in_window_reads_nothing():
    _, host = _trace()
    assert trace.reduce({"/device:TPU:0": [("x", -9 * MS, MS)]}, host) is None
    assert trace.reduce({}, []) is None

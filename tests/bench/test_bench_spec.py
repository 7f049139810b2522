"""The benchmark's files: every cell, configuration, traffic mix, limit and
per-layer reader is found by its name in ``BENCHMARK.json``; the file keeps
to the rules for that file; the harness refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (ROOT / SPEC["command"][1]).is_file()


def test_names_units_and_bounds():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_from_files(wl):
    spec = harness.Spec(ROOT)
    assert spec.workload(wl["name"]) == wl
    config = spec.config(wl["config"])
    assert config["name"] == wl["config"]
    traffic = spec.traffic(wl["traffic"])
    assert "fed" in traffic
    limits = spec.cell(wl["name"])["limits"]
    assert limits["cluster_mismatch"] == 0
    assert set(limits) <= {"cluster_mismatch", "teacher_loss_gap",
                           "student_loss_gap", "eval_loss_gap",
                           "first_change_gap", "student_change_gap",
                           "teacher_change_gap"}
    for m in spec.metrics("per_layer", wl["name"]):
        assert callable(spec.reader(m["name"]))


def test_config_entries_point_at_their_files():
    for c in SPEC["configs"]:
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]


def test_a_cell_of_files_alone_is_found_with_its_own_reader():
    spec = harness.Spec(FIXTURE)
    wl = spec.workload("tiny.fedsikd")
    assert spec.config(wl["config"])["dataset"]["n_train"] == 1200
    assert spec.traffic(wl["traffic"])["fed"]["num_clients"] == 4
    own = spec.reader("rounds_seen")
    assert own({"n_rounds": 7}) == 7
    shared = spec.reader("eval_ms")          # from the harness's metrics/
    assert shared({"perf_rounds": [{"eval": 0.5}, {"eval": 1.5}]}) == 1000
    assert shared({"perf_rounds": []}) is None
    with pytest.raises(KeyError):
        spec.workload("no.such.cell")


def test_readers_find_nothing_without_a_trace():
    spec = harness.Spec(ROOT)
    ctx = {"trace": None, "peaks": None, "perf_rounds": [], "window_s": 1.0,
           "window_flops": 1.0}
    for name in ("kd_kernel_roofline", "kd_kernel_share",
                 "device_idle_share", "round_mfu"):
        assert spec.reader(name)(ctx) is None


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    wl = SPEC["workloads"][0]["name"]
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", wl,
         "--seed", "2147483650", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert r.returncode == 3, r.stderr
    assert "needs a TPU" in r.stderr
    assert "{" not in r.stdout

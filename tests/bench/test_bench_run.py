"""The harness end to end on the CPU, on a cell made of files alone
(``fixture/``): the check passes a sound run and fails each planted fault
and the control.  The runs share one subprocess (``bench_cpu_runs.py``)
and one compile cache in a temporary directory."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("jc")))
    r = subprocess.run([sys.executable, str(HERE / "bench_cpu_runs.py")],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(runs):
    s = runs["sound"]
    assert s["correct"], s["checks"]
    assert s["attempted"] >= 1 and s["failed"] == 0
    assert s["metrics"] == ["round_p90_ms", "samples_per_s", "setup_s"]
    assert s["device"]["platform"] == "cpu" and s["device"]["count"] >= 1
    assert s["keys"][:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert s["keys"][-1] == "checks"


def test_traced_run_reports_per_layer_metrics(runs):
    t = runs["traced"]
    assert t["correct"], t["checks"]
    # the fixture's own reader and a shared one; the trace readers find no
    # TPU plane on the CPU and stay silent
    assert t["metrics"] == ["eval_ms", "rounds_seen"]


@pytest.mark.parametrize("fault", ["unchanged", "half_cohort",
                                   "half_batch", "no_exchange", "altered"])
def test_planted_fault_is_not_correct(runs, fault):
    f = runs[fault]
    assert not f["correct"], f["checks"]
    over = [k for k, c in f["checks"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert over


def test_control_in_bfloat16_is_not_correct(runs):
    c = runs["control"]
    assert not c["correct"], c["checks"]

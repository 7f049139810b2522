"""tools/shapecert: the compile-surface certifier (DESIGN.md §16).

The certified property is the wave redesign's core promise: compiled
round-program shapes depend on ``wave_slots`` alone, never on the cohort
or the virtual client universe streamed through it.  The certifier needs
a multi-device host mesh (XLA_FLAGS pre-import), so the eval_shape work
runs in a subprocess; the pure-python report plumbing (invariant checker,
drift differ) is unit-tested in-process against crafted reports.
"""
import copy
import json
import sys
import textwrap
from pathlib import Path

from _subproc import run_script

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

SHAPES = REPO / "SHAPES.json"


# --------------------------------------------------- eval_shape end-to-end
_CERT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, ".")
    import jax
    import jax.numpy as jnp
    from repro.fed.rounds import FedConfig
    from tools.shapecert.cert import certify, check_invariants

    # two cohorts through the SAME 4-slot mesh, per program family — the
    # exact pair the invariant check must bite on
    base = dict(engine="sharded", num_clients=4, pack=2, n_devices=2,
                batch_size=8, local_epochs=1)
    grid = [FedConfig(algorithm=a, universe=u, waves=w, **base)
            for a in ("fedsikd", "fedavg")
            for u, w in ((None, None), (16, 4))]

    report = certify(grid)
    errors = check_invariants(report)
    assert errors == [], errors

    # the subset regenerated here must match the committed certificate
    # bit for bit (the full-grid diff runs as `--check` in CI lint)
    committed = {json.dumps(e["config"], sort_keys=True): e
                 for e in json.load(open("SHAPES.json"))["entries"]}
    for entry in report["entries"]:
        key = json.dumps(entry["config"], sort_keys=True)
        assert key in committed, f"not in SHAPES.json: {key}"
        assert entry == committed[key], f"stale SHAPES.json entry: {key}"

    # a deliberately cohort-shaped program must FAIL certification: its
    # input carries the (cohort,) axis, so the 4- and 16-client entries
    # of one surface group disagree
    def cohort_shaped(cfg, layout, mesh):
        aval = jax.ShapeDtypeStruct(
            (layout["cohort"], cfg.batch_size), jnp.float32)
        return {"bad_cohort_program": (lambda z: z * 2.0, (aval,))}

    bad = certify(grid, extra_programs=cohort_shaped)
    bad_errors = check_invariants(bad)
    assert bad_errors, "cohort-shaped program passed certification"
    assert any("bad_cohort_program" in e for e in bad_errors), bad_errors
    print("SHAPECERT-OK", len(report["entries"]), len(bad_errors))
""")


def test_certifier_passes_real_factories_and_rejects_cohort_shapes():
    r = run_script(_CERT_SCRIPT)
    assert "SHAPECERT-OK" in r.stdout, r.stdout + r.stderr


# ------------------------------------------------- report plumbing (pure)
def _report():
    return json.loads(SHAPES.read_text())


def test_committed_certificate_has_the_full_grid():
    report = _report()
    entries = report["entries"]
    sharded = [e for e in entries if e["config"]["engine"] == "sharded"]
    loop = [e for e in entries if e["config"]["engine"] == "loop"]
    assert {e["config"]["algorithm"] for e in sharded} == \
        {"fedsikd", "random", "fedavg", "fedprox"}
    assert {e["config"]["algorithm"] for e in loop} == \
        {"fedsikd", "random", "fedavg", "fedprox", "flhc"}
    # every sharded family covers >= 2 cohorts on one mesh, plus async
    # and jitter variants; loop entries record no compiled surface
    for alg in ("fedsikd", "fedavg"):
        rows = [e for e in sharded if e["config"]["algorithm"] == alg]
        assert len({e["layout"]["cohort"] for e in rows}) >= 3
        assert len({e["layout"]["wave_slots"] for e in rows}) == 1
        assert any(e["config"]["async_mode"] for e in rows)
        assert any(e["config"]["guards"] == "jitter" for e in rows)
    assert all(e["programs"] == {} and e["layout"] is None for e in loop)
    # the fedsikd surface is the KD round + the warmup/refresh phase
    kd = next(e for e in sharded if e["config"]["algorithm"] == "fedsikd")
    assert set(kd["programs"]) == {"kd_round", "teacher_phase"}
    assert len(kd["programs"]["kd_round"]["inputs"]) == 14
    assert len(kd["programs"]["kd_round"]["outputs"]) == 7


def test_teacher_layouts_certify_their_own_surfaces():
    """Leader-mode KD programs take the (K, ...) teacher rows and a (S,)
    slot->row index; cluster-mode ones a teacher replica per slot and the
    (S, S) sync operator, single-wave only."""
    from tools.shapecert.cert import CLUSTERS
    kd_rows = [e for e in _report()["entries"]
               if e["config"]["engine"] == "sharded"
               and "teacher_data" in e["config"]]
    assert {e["config"]["teacher_data"] for e in kd_rows} == \
        {"leader", "cluster"}
    for e in kd_rows:
        S, leader = (e["layout"]["wave_slots"],
                     e["config"]["teacher_data"] == "leader")
        kd, phase = e["programs"]["kd_round"], e["programs"]["teacher_phase"]
        lanes = CLUSTERS if leader else S
        for prog in (kd, phase):
            assert prog["inputs"][0]["head"]["w"].startswith(
                f"float32[{lanes},"), (e["config"], prog["inputs"][0])
        assert kd["inputs"][12] == (f"int32[{S}]" if leader
                                    else f"float32[{S},{S}]")
        assert len(phase["inputs"]) == (6 if leader else 7)
        if not leader:
            assert e["layout"]["n_waves"] == 1


def test_check_invariants_flags_cohort_dependence():
    from tools.shapecert.cert import check_invariants
    report = _report()
    assert check_invariants(report) == []
    bad = copy.deepcopy(report)
    victim = next(e for e in bad["entries"]
                  if e["config"]["engine"] == "sharded"
                  and e["config"]["universe"] == 64)
    prog = next(iter(victim["programs"]))
    victim["programs"][prog]["inputs"].append(
        f"float32[{victim['layout']['cohort']}]")
    errors = check_invariants(bad)
    assert errors and any(prog in e and "wave_slots alone" in e
                          for e in errors), errors


def test_diff_reports_flags_drift_and_grid_changes():
    from tools.shapecert.cert import diff_reports
    report = _report()
    assert diff_reports(report, report) == []
    # a shape change in one program is named
    drifted = copy.deepcopy(report)
    entry = next(e for e in drifted["entries"]
                 if e["config"]["engine"] == "sharded")
    prog = next(iter(entry["programs"]))
    entry["programs"][prog]["outputs"].append("float32[1]")
    msgs = diff_reports(report, drifted)
    assert any(prog in m for m in msgs), msgs
    # a grid change (entry added/removed) is named too
    shrunk = copy.deepcopy(report)
    shrunk["entries"].pop()
    assert any("removed" in m for m in diff_reports(report, shrunk))
    assert any("missing" in m for m in diff_reports(shrunk, report))

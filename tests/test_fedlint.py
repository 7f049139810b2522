"""tools/fedlint: each rule catches its seeded fixture violation (with
file:line and rule ID), the pragma allowlist suppresses, and the shipped
``src/repro`` tree is clean (the static half of DESIGN.md §14)."""
import json
import subprocess
import sys
from pathlib import Path

from _subproc import ENV

REPO =Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:           # `tools` lives at the repo root
    sys.path.insert(0, str(REPO))

from tools.fedlint.core import Project, run_rules           # noqa: E402
from tools.fedlint.rules import RULE_DOCS, RULES            # noqa: E402

FIXTURES = Path(__file__).parent / "fedlint_fixtures"


def findings_for(sub: str):
    return run_rules(Project.load(FIXTURES / sub), RULES)


def violation_lines(path: Path) -> list[int]:
    """1-based lines of the fixture carrying a ``VIOLATION`` marker."""
    return [i for i, text in enumerate(path.read_text().splitlines(), 1)
            if "VIOLATION" in text]


def assert_seeded_violations_caught(sub: str, rule: str, rel: str):
    """Every marked fixture line is reported with file:line + rule ID."""
    found = findings_for(sub)
    assert found, f"{sub}: no findings at all"
    assert {f.rule for f in found} == {rule}
    got = {(f.path, f.line) for f in found}
    want = {(rel, ln) for ln in violation_lines(FIXTURES / sub / rel)}
    assert want, f"fixture {rel} has no VIOLATION markers"
    assert got == want, f"want {sorted(want)}, got {sorted(got)}"
    for f in found:
        # the formatted finding is the CI-facing contract: path:line + ID
        assert f.format().startswith(f"{f.path}:{f.line}: {rule} ")


def test_fl000_catches_bare_pragmas_with_exact_lines():
    assert_seeded_violations_caught("fl000", "FL000", "pragmas.py")


def test_fl000_cannot_be_self_allowlisted():
    # the fixture's line-12 pragma is `allow=all` WITH no reason: the
    # wildcard would suppress any other rule, but FL000 bypasses the
    # allowlist in run_rules — a pragma cannot vouch for itself
    found = findings_for("fl000")
    allow_all_lines = [
        i for i, text in enumerate(
            (FIXTURES / "fl000" / "pragmas.py").read_text().splitlines(), 1)
        if "allow=all" in text]
    assert allow_all_lines
    assert all(any(f.line == ln and f.rule == "FL000" for f in found)
               for ln in allow_all_lines)


def test_fl001_catches_unsalted_magic_dup_and_shape_drift():
    assert_seeded_violations_caught("fl001", "FL001", "bad_streams.py")


def test_fl001_pragma_allowlists_the_legacy_stream():
    assert not [f for f in findings_for("fl001")
                if f.path == "allowed.py"]


def test_fl002_catches_missing_double_booked_and_stale_fields():
    assert_seeded_violations_caught("fl002", "FL002", "config.py")


def test_fl003_catches_read_after_donate_and_canonical_donation():
    assert_seeded_violations_caught("fl003", "FL003", "donate.py")


def test_fl003_rebinding_to_the_result_is_clean():
    found = findings_for("fl003")
    lines = violation_lines(FIXTURES / "fl003" / "donate.py")
    safe = [f for f in found if f.line not in lines]
    assert not safe, [f.format() for f in safe]


def test_fl003_interprocedural_helper_reads_and_forwarded_donation():
    # the call-graph pass must flag: donation THROUGH a forwarding helper,
    # a helper that reads self.params after its caller donated it, and the
    # same one call deeper — while the rebound SafeTrainer stays clean.
    # (lines are pinned by the VIOLATION markers via the exact-set test
    # above; this asserts the interprocedural messages specifically)
    found = findings_for("fl003")
    helper_reads = [f for f in found if "read inside" in f.message]
    assert {m.split("read inside '")[1].split("'")[0]
            for m in (f.message for f in helper_reads)} == \
        {"_norm", "_outer"}, [f.format() for f in helper_reads]
    # the forwarding-helper donation surfaces as a plain read-after-donate
    # at the caller — the summary is what marks the argument consumed
    src = (FIXTURES / "fl003" / "donate.py").read_text().splitlines()
    fwd_line = next(i for i, t in enumerate(src, 1)
                    if "donated through the helper" in t)
    assert any(f.line == fwd_line and "donated to a jitted callee"
               in f.message for f in found)


def test_fl004_catches_branch_concretize_and_host_numpy():
    assert_seeded_violations_caught("fl004", "FL004", "fed/traced.py")


def test_fl004_interprocedural_escape_through_helpers():
    found = [f for f in findings_for("fl004") if "escapes through" in f.message]
    helpers = {f.message.split("helper '")[1].split("'")[0] for f in found}
    assert helpers == {"leak", "deep_leak"}, [f.format() for f in found]


def test_fl005_catches_tobytes_key_and_comprehension_shape():
    assert_seeded_violations_caught("fl005", "FL005", "fed/recompile.py")


def test_fl005_blesses_the_stager():
    # the fixture's WaveStager body keys on .tobytes() with no VIOLATION
    # marker — assert_seeded_violations_caught above proves it is NOT
    # flagged; this pins the blessed set itself
    from tools.fedlint.rules import BLESSED_STAGERS
    assert BLESSED_STAGERS == frozenset({"WaveStager"})


def test_fl006_catches_unlocked_thread_shared_writes():
    # RacyStager (Thread target) + SubmitStager (executor submit) violate;
    # LockedStager's lock-held writes and queue handoffs stay clean — the
    # exact-line contract proves both directions at once
    assert_seeded_violations_caught("fl006", "FL006", "racy.py")


def test_fl006_blesses_queue_and_lock_handoffs():
    from tools.fedlint.rules import LOCK_TYPES, THREAD_SAFE_TYPES
    assert "Queue" in THREAD_SAFE_TYPES and "Event" in THREAD_SAFE_TYPES
    assert LOCK_TYPES <= THREAD_SAFE_TYPES


def test_fl007_catches_blocking_calls_in_hot_spans():
    # syncs/blocking puts/sleeps/unbounded joins inside stage|compute|
    # aggregate spans — including open() inside a helper CALLED from a hot
    # span — while the checkpoint span, perf.* calls, bounded joins,
    # non-blocking puts, and attribute-boundary entry points stay clean
    assert_seeded_violations_caught("fl007", "FL007", "fed/hotpath.py")


def test_rule_registry_is_complete():
    assert [rid for rid, _ in RULES] == sorted(RULE_DOCS) == [
        "FL000", "FL001", "FL002", "FL003", "FL004", "FL005",
        "FL006", "FL007"]


def test_shipped_tree_is_clean():
    found = run_rules(Project.load(REPO / "src" / "repro"), RULES)
    assert not found, "\n".join(f.format() for f in found)


def test_cli_exit_codes_and_json_report(tmp_path):
    env = ENV
    clean = subprocess.run(
        [sys.executable, "-m", "tools.fedlint", "src/repro",
         "--json", str(tmp_path / "report.json")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["findings"] == [] and report["modules_scanned"] > 0

    dirty = subprocess.run(
        [sys.executable, "-m", "tools.fedlint",
         str(FIXTURES / "fl001" / "bad_streams.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert dirty.returncode == 1
    assert "FL001" in dirty.stdout and "bad_streams.py:" in dirty.stdout

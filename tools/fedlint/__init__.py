"""fedlint: repo-invariant static analysis for the federated runtime.

Generic linters see syntax; this one sees the repo's invariants — the bug
classes that each cost a real outage before a point regression test pinned
them down (DESIGN.md §14):

  FL001  PRNG stream discipline   every ``SeedSequence`` entropy list must
                                  carry a registered ``SALT_*`` constant at
                                  the canonical index 2, one tuple shape per
                                  salt (the PR 6 collision class).
  FL002  fingerprint completeness every ``FedConfig`` field must be in the
                                  resume fingerprint or in the explicit
                                  ``EXECUTION_ONLY`` exclusion set (the PR 5
                                  silent-omission class).
  FL003  donation safety          a Python binding passed in a donated
                                  position of a jitted callee must not be
                                  read afterwards, and canonical state must
                                  never sit in a donated position (the PR 7
                                  donated-buffer-read class).
  FL004  tracer safety            no ``if``/``float()``/``.item()``/host
                                  ``np.*`` on traced values inside jitted /
                                  ``shard_map``-ped / Pallas code.
  FL005  recompile safety         no ``.tobytes()``-keyed structures outside
                                  the blessed staging class
                                  (``WaveStager``), no Python-value-dependent
                                  array shapes (comprehension-shaped
                                  constructors) feeding jitted programs.

Findings can be allowlisted in place with ``# fedlint: allow=FL00N`` on (or
inside the statement spanning) the offending line — every pragma should say
WHY in an adjacent comment.  Usage:

    python -m tools.fedlint src/repro            # exit 1 on findings
    python -m tools.fedlint src/repro --json fedlint-report.json
"""
from tools.fedlint.core import Finding, Project, run_rules
from tools.fedlint.rules import RULES

__all__ = ["Finding", "Project", "run_rules", "RULES"]

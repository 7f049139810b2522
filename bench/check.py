"""The comparison that decides ``correct``: the program's first three rounds
against the reference's (``reference.py``).

The numbers; a cell compares those its file gives a limit:

- ``cluster_mismatch``: clients whose cluster label differs.  Exact, so
  its limit is 0.
- ``teacher_loss_gap``, ``student_loss_gap``, ``eval_loss_gap``: the
  largest gap, over rounds 1 to 3, of the teacher's loss, the clients'
  mean KD loss and the global student's test loss, each against the
  largest of the reference's three readings of it.
- ``first_change_gap``: the global student's change in round 1 (the
  update the server applies, which plays the first gradient's part), by
  its worst leaf.
- ``student_change_gap``, ``teacher_change_gap``: the global student's
  change over the three rounds, and each cluster teacher's from its
  initial weights through warm-up and the three rounds, by the worst leaf.

A leaf's gap is | |prog change| - |ref change| | over the larger of its own
reference norm and the median leaf's: the gap between the norms, not the
norm of the difference, since two sound runs part ways step by step.  A
leaf whose reference change is under a thousandth of the median leaf's is
left out: it moves by round-off alone.
"""
from __future__ import annotations

import numpy as np
from jax.tree_util import keystr, tree_flatten_with_path

NEGLIGIBLE = 1e-3


def _leaves(tree):
    return {keystr(p): np.asarray(v, np.float64)
            for p, v in tree_flatten_with_path(tree)[0]}


def _norms(after, before):
    a, b = _leaves(after), _leaves(before)
    return {k: float(np.linalg.norm(a[k] - b[k])) for k in b}


def leaf_gap(prog_after, prog_before, ref_after, ref_before) -> float:
    """Worst-leaf gap between the norms of the two runs' changes."""
    ref = _norms(ref_after, ref_before)
    prog = _norms(prog_after, prog_before)
    if set(ref) != set(prog):
        raise ValueError(f"leaf paths differ: {sorted(set(ref) ^ set(prog))}")
    med = float(np.median(list(ref.values())))
    worst = 0.0
    for k, r in ref.items():
        if r < NEGLIGIBLE * med:
            continue
        worst = max(worst, abs(prog[k] - r) / max(r, med))
    return worst


def loss_gap(prog_series, ref_series) -> float:
    """Largest gap between the program's and the reference's reading of one
    loss over the rounds, against the largest of the reference's readings:
    a loss that falls towards zero (a teacher on its own shard) would make
    a gap relative to each round's reading meaningless."""
    scale = max(abs(r) for r in ref_series)
    return max(abs(p - r) for p, r in zip(prog_series, ref_series,
                                          strict=True)) / max(scale, 1e-12)


def numbers(prog: dict, ref: dict) -> dict:
    """Every number the check can compare, for one run; ``prog`` and
    ``ref`` have the keys ``Reference.run`` returns.  A cell compares
    those its limits name."""
    labels_p, labels_r = np.asarray(prog["labels"]), np.asarray(ref["labels"])
    mismatch = (int(np.sum(labels_p != labels_r))
                if labels_p.shape == labels_r.shape else len(labels_r))
    out = {"cluster_mismatch": mismatch}
    for key in ("teacher_loss", "student_loss", "eval_loss"):
        out[f"{key}_gap"] = loss_gap(prog[key], ref[key])
    out["first_change_gap"] = leaf_gap(
        prog["after1"]["student"], prog["init"]["student"],
        ref["after1"]["student"], ref["init"]["student"])
    out["student_change_gap"] = leaf_gap(
        prog["after"]["student"], prog["init"]["student"],
        ref["after"]["student"], ref["init"]["student"])
    # teachers of different clusters are not comparable
    out["teacher_change_gap"] = float("inf") if mismatch else max(
        leaf_gap(pa, pb, ra, rb) for pa, pb, ra, rb in zip(
            prog["after"]["teachers"], prog["init"]["teachers"],
            ref["after"]["teachers"], ref["init"]["teachers"], strict=True))
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number that is not finite fails."""
    checks = {k: {"value": float(values[k]), "limit": float(limits[k])}
              for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks

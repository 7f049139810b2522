"""Mean per measured round of ``repro.perf``'s ``teacher_lanes`` counter:
the lanes the KD round programs' teacher scans run on, summed over the
round's dispatched waves (one per cluster row with leader teachers, one
per slot where every slot trains a teacher replica)."""
from bench import spans


def read(ctx):
    ex = spans.exported()
    return None if ex is None else spans.per_round(ex, "teacher_lanes")

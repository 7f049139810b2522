"""Mean per measured round of ``repro.perf``'s ``read_lag`` counter: how
many rounds the driver had dispatched after a round when it read that
round's losses and eval (1 in steady state, 0 where a save or the end of
the run reads at once)."""
from bench import spans


def read(ctx):
    ex = spans.exported()
    return None if ex is None else spans.per_round(ex, "read_lag")

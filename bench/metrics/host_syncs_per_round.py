"""Mean per measured round of ``repro.perf``'s ``host_syncs`` counter:
blocking device-to-host reads (two per wave, two per eval batch)."""
from bench import spans


def read(ctx):
    ex = spans.exported()
    return None if ex is None else spans.per_round(ex, "host_syncs")

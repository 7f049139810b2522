"""Mean per measured round of ``repro.perf``'s ``h2d_bytes`` counter, in
MB (1e6 bytes): host arrays handed to the device by the stagers' gathers,
the per-wave operands and eval's batches."""
from bench import spans


def read(ctx):
    ex = spans.exported()
    if ex is None:
        return None
    n = spans.per_round(ex, "h2d_bytes")
    return None if n is None else n / 1e6

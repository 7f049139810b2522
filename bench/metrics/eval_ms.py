"""Mean per measured round of the driver's ``eval`` span (``repro.perf``):
the global student on the test set, batch by batch."""


def read(ctx):
    rounds = ctx["perf_rounds"]
    if not rounds:
        return None
    return 1e3 * sum(r.get("eval", 0.0) for r in rounds) / len(rounds)

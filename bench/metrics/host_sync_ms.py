"""Mean per measured round of the ``sync`` spans on the round thread
(``repro.perf``): the blocking reads of each wave's losses and of eval's
two numbers per batch."""
from bench import spans


def read(ctx):
    ex = spans.exported()
    if ex is None:
        return None
    sec = spans.span_seconds(ex, "sync", spans.round_thread(ex))
    return None if sec is None else 1e3 * sec

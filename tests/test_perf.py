"""perf.py thread-aware attribution: spans recorded on a background thread
with a submission-round token land in the submitting round's bucket, even
after that round closed — the AsyncCheckpointWriter regression (ISSUE 8
satellite: checkpoint spans used to fall into whatever round was open when
the writer got around to the write)."""
import threading
import time

import numpy as np

from repro import perf
from repro.fed import fedstate


def teardown_function(_fn):
    perf.disable()


def test_span_without_token_lands_in_open_round():
    perf.enable()
    with perf.span("work"):
        pass
    perf.end_round()
    snap = perf.snapshot()
    assert len(snap) == 1 and "work" in snap[0]


def test_token_span_patches_a_closed_round():
    perf.enable()
    tok = perf.round_token()
    perf.end_round()                 # round 0 closes before the span runs
    perf.end_round()                 # round 1 is also closed
    with perf.span("checkpoint", round_id=tok):
        time.sleep(0.01)
    snap = perf.snapshot()
    assert snap[0].get("checkpoint", 0.0) >= 0.01
    assert "checkpoint" not in snap[1]


def test_token_span_from_background_thread():
    perf.enable()
    tok = perf.round_token()

    def worker():
        with perf.span("checkpoint", round_id=tok):
            time.sleep(0.01)

    th = threading.Thread(target=worker)
    perf.end_round()                 # the round closes while work is queued
    th.start()
    th.join()
    perf.end_round()
    snap = perf.snapshot()
    assert snap[0].get("checkpoint", 0.0) >= 0.01
    assert "checkpoint" not in snap[1]


def test_async_writer_attributes_by_submission_round(monkeypatch, tmp_path):
    """The writer's save runs rounds later than the submit; its checkpoint
    span must still land in the SUBMISSION round's bucket."""
    release = threading.Event()
    saved = []

    def slow_save(ckpt_dir, state, keep_last=None):
        release.wait(timeout=30)
        saved.append(state.round_index)

    monkeypatch.setattr(fedstate, "save_round", slow_save)
    perf.enable()
    writer = fedstate.AsyncCheckpointWriter(str(tmp_path))
    state = fedstate.FedState(round_index=1,
                              arrays={"w": np.zeros(2, np.float32)},
                              history={}, meta={})
    writer.submit(state)             # submitted during round 0
    perf.end_round()                 # rounds advance past the pending write
    perf.end_round()
    release.set()
    writer.close()
    perf.end_round()
    assert saved == [1]
    snap = perf.snapshot()
    assert snap[0].get("checkpoint", 0.0) > 0.0, snap
    assert all("checkpoint" not in b for b in snap[1:]), snap


# ------------------------------------------- records, counters, annotations
def _by_name(spans):
    return {s["name"]: i for i, s in enumerate(spans)}


def test_records_carry_the_parent_on_their_own_thread():
    perf.enable()
    box = {}

    def worker():
        with perf.span("gather", round_id=box["tok"]):
            pass

    with perf.span("round_total"):
        with perf.span("stage"):
            with perf.span("stager"):
                box["tok"] = perf.round_token()
                th = threading.Thread(target=worker, name="wave-prefetch")
                th.start()
                th.join()
        with perf.span("compute"):
            pass
    perf.end_round()
    spans = perf.export()["spans"]
    at = _by_name(spans)
    assert spans[at["round_total"]]["parent"] is None
    assert spans[at["stage"]]["parent"] == at["round_total"]
    assert spans[at["stager"]]["parent"] == at["stage"]
    assert spans[at["compute"]]["parent"] == at["round_total"]
    # another thread's span never nests under the driver's open spans
    assert spans[at["gather"]]["parent"] is None
    assert spans[at["gather"]]["thread"] == "wave-prefetch"
    assert spans[at["stage"]]["thread"] == threading.current_thread().name
    assert all(s["round"] == 0 and s["end"] >= s["start"] for s in spans)


def test_self_time_is_the_span_less_its_children():
    perf.enable()
    with perf.span("compute"):
        time.sleep(0.01)
        with perf.span("dispatch"):
            time.sleep(0.02)
        with perf.span("sync"):
            time.sleep(0.01)
    spans = perf.export()["spans"]
    own = perf.self_times(spans)
    at = _by_name(spans)
    whole = spans[at["compute"]]["end"] - spans[at["compute"]]["start"]
    kids = sum(spans[at[k]]["end"] - spans[at[k]]["start"]
               for k in ("dispatch", "sync"))
    assert own[at["compute"]] == whole - kids
    assert own[at["dispatch"]] >= 0.02 and own[at["compute"]] >= 0.01
    assert sum(own) == whole


def test_count_lands_in_the_open_round_and_a_tokens_round():
    perf.enable()
    perf.count("host_syncs", 2)
    tok = perf.round_token()
    perf.end_round()
    perf.count("host_syncs")
    perf.count("h2d_bytes", 8, round_id=tok)     # late, from round 0
    perf.count_bytes("h2d_bytes", np.zeros(4, np.float32))
    perf.end_round()
    counts = perf.export()["counts"]
    assert counts == [{"host_syncs": 2, "h2d_bytes": 8},
                      {"host_syncs": 1, "h2d_bytes": 16}]
    # counts stay out of the seconds buckets
    assert perf.snapshot() == [{}, {}]


class _Bare:
    """A context manager that does nothing: the cost of a ``with`` alone."""

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


def _peak_bytes(body, n=100):
    """Peak bytes traced while ``body`` runs ``n`` times, over the start."""
    import tracemalloc
    body()
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        for _ in range(n):
            body()
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_disabled_perf_records_nothing_and_stays_out_of_jax(monkeypatch):
    def no_jax(name):
        raise AssertionError("a disabled span reached the profiler")

    monkeypatch.setattr(perf, "TraceAnnotation", no_jax)
    perf.enable()
    perf.disable()
    a, bare = np.zeros(3), _Bare()

    def instrumented():
        with perf.span("sync"):
            perf.count("host_syncs", 2)
            perf.count_bytes("h2d_bytes", a)
            perf.add("stage_wait", 0.1)

    def alone():
        with bare:
            pass

    # no more than the ``with`` statement itself costs: no record, no
    # generator, no bucket
    assert _peak_bytes(instrumented) <= _peak_bytes(alone)
    perf.end_round()
    assert perf.export() == {"spans": [], "counts": []}
    assert perf.snapshot() == []


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    perf.enable()
    with jax.profiler.trace(str(tmp_path)):
        with perf.span("compute"):
            with perf.span("dispatch"):
                jax.numpy.ones(4).block_until_ready()
            with perf.span("sync"):
                pass
    spans = perf.export()["spans"]
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro.")]
    got = {n: (s, e) for n, s, e in events}
    assert sorted(got) == ["repro.compute", "repro.dispatch", "repro.sync"]
    # nested as in the records: each child inside its parent's event
    for s in spans:
        if s["parent"] is not None:
            ps, pe = got["repro." + spans[s["parent"]]["name"]]
            cs, ce = got["repro." + s["name"]]
            assert ps <= cs and ce <= pe

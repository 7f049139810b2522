"""The round path's own tracing: per-round seconds, span records with
parents, per-round counters, and the same spans on the profiler's clock.

The driver, the packed strategies, the stagers and ``client.evaluate`` are
instrumented with it; nothing is collected until ``enable()``, and while
disabled every call costs one attribute read and allocates nothing of its
own, so production runs pay nothing.

Usage:

    from repro import perf
    perf.enable()
    run_federated(ds, cfg)
    rounds = perf.snapshot()     # [{"stage": s, "compute": s, ...}, ...]
    spans = perf.export()        # {"spans": [...], "counts": [...]}
    perf.disable()

On the device timeline (operator's note): enable perf and wrap the run in
the profiler, ``jax.profiler.trace(log_dir)`` around ``RoundDriver.run``.
Every span then also appears on the trace's host plane as a
``repro.<name>`` event on the thread that ran it, on the clock the device
operations are stamped with, so each idle gap of the chip lies under the
host span that caused it.

Contract:

- ``span(name)`` accumulates wall-clock into the CURRENT round's seconds
  bucket; nested/repeated spans of the same name add up.  When enabled it
  also appends a record (``export()["spans"]``): name, the enclosing span
  on the same thread as ``parent`` (an index into the records, or None),
  ``start``/``end`` in ``time.perf_counter`` seconds, the ``round`` it
  landed in and the ``thread`` name; and it opens
  ``jax.profiler.TraceAnnotation("repro." + name)``.
- ``count(name, n)`` adds ``n`` to a per-round counter, kept apart from
  the seconds buckets (``export()["counts"]``), so a reader that sums a
  bucket never meets a count.  The round path counts ``host_syncs``
  (blocking device-to-host reads), ``read_lag`` (rounds the driver had
  dispatched after a round when it read it) and ``h2d_bytes`` (host
  arrays handed to the device: stager gathers, per-wave operands, the
  test set).
- ``end_round()`` closes the current round — the driver calls it once per
  completed round (warm-up/setup time lands in the round that follows it,
  i.e. the first bucket; steady-state consumers should skip bucket 0,
  which also carries jit compilation).
- Thread attribution: work that RUNS on a background thread or in a later
  round but BELONGS to a specific round — a stager's prefetch gather, the
  async checkpoint writer's device-to-host copy and npz write, the
  driver's read of a round's values one round late — is recorded with
  ``span(name, round_id=token)`` (and ``count(..., round_id=token)``)
  where the token was captured on the submitting thread via
  ``round_token()``.  Such a span lands in its submission round even when
  that round has already been closed by ``end_round()`` (the bucket is
  patched in place under a lock).  Without a token a span always means
  "the round currently open on the driver thread", which is wrong from
  any other thread.
- Timings NEVER enter the run history or the checkpoint: resume
  bit-identity is about model state, and an instrument must not perturb it.

Spans measure dispatch-side wall-clock: jax dispatch is asynchronous, so a
phase that merely enqueues device work attributes the wait to whichever
later span blocks — the ``sync`` span of the driver's read of a round's
losses and eval.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_enabled = False
_gen = 0                                  # bumped by enable(): stale spans
_current: dict[str, float] = {}
_rounds: list[dict[str, float]] = []
_counts: dict[str, int] = {}
_count_rounds: list[dict[str, int]] = []
_records: list[list] = []                 # [name, parent, start, end, round,
_open = threading.local()                 #  thread]; per-thread open spans


class _Null:
    """The disabled span: enter and exit allocate nothing."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL = _Null()


def enable() -> None:
    """Start collecting (clears any previous collection)."""
    global _enabled, _gen
    with _lock:
        _enabled = True
        _gen += 1
        _current.clear()
        _rounds.clear()
        _counts.clear()
        _count_rounds.clear()
        _records.clear()


def disable() -> None:
    """Stop collecting; what was collected stays readable until the next
    ``enable()``."""
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def round_token() -> int:
    """Token naming the round bucket currently open on the caller's thread.

    Capture it where the work is SUBMITTED, pass it to ``span(...,
    round_id=token)`` where the work RUNS: the span then lands in this
    bucket no matter which thread executes it or how many rounds have
    closed in between."""
    with _lock:
        return len(_rounds)


def _round_of(round_id: int | None) -> int:
    """The round a record or count lands in (caller holds ``_lock``)."""
    if round_id is None or round_id >= len(_rounds):
        return len(_rounds)
    return round_id


def _bucket(buckets, current, rnd):
    return current if rnd == len(buckets) else buckets[rnd]


class _Span:
    __slots__ = ("name", "round_id", "gen", "index", "t0", "note")

    def __init__(self, name, round_id):
        self.name, self.round_id = name, round_id

    def __enter__(self):
        self.t0 = time.perf_counter()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        parent = stack[-1] if stack else None
        with _lock:
            self.gen = _gen
            self.index = len(_records)
            _records.append([
                self.name,
                parent.index if parent is not None
                and parent.gen == self.gen else None,
                self.t0, None, None, threading.current_thread().name])
        stack.append(self)
        self.note = TraceAnnotation("repro." + self.name)
        self.note.__enter__()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.note.__exit__(*exc)
        _open.stack.pop()
        with _lock:
            if self.gen != _gen:          # enable() ran inside the span
                return False
            rnd = _round_of(self.round_id)
            rec = _records[self.index]
            rec[3], rec[4] = t1, rnd
            bucket = _bucket(_rounds, _current, rnd)
            bucket[self.name] = bucket.get(self.name, 0.0) + (t1 - self.t0)
        return False


def span(name: str, round_id: int | None = None):
    """Time the block under ``name`` and record it (see the contract).

    Without ``round_id``: into the round open at EXIT time (the
    driver-thread pattern).  With ``round_id`` (a ``round_token()``
    capture): into that specific round, open or closed."""
    if not _enabled:
        return _NULL
    return _Span(name, round_id)


def add(name: str, dt: float, round_id: int | None = None) -> None:
    """Accumulate a pre-measured duration under ``name`` — the non-context
    form of ``span`` for durations measured elsewhere (e.g. the WaveStager's
    background gather time, measured on the feeder thread but ATTRIBUTED at
    adoption time on the driver thread).  Bucket selection matches ``span``;
    no record is made."""
    if not _enabled:
        return
    with _lock:
        bucket = _bucket(_rounds, _current, _round_of(round_id))
        bucket[name] = bucket.get(name, 0.0) + float(dt)


def count(name: str, n: int = 1, round_id: int | None = None) -> None:
    """Add ``n`` to the per-round counter ``name`` (bucket as ``span``)."""
    if not _enabled:
        return
    with _lock:
        bucket = _bucket(_count_rounds, _counts, _round_of(round_id))
        bucket[name] = bucket.get(name, 0) + n


def count_bytes(name: str, *arrays, round_id: int | None = None) -> None:
    """``count(name, total nbytes of arrays)``; the sizes are read only
    while enabled."""
    if not _enabled:
        return
    count(name, sum(int(a.nbytes) for a in arrays), round_id)


def end_round() -> None:
    """Close the current round's bucket (driver: once per completed round)."""
    if not _enabled:
        return
    with _lock:
        _rounds.append(dict(_current))
        _current.clear()
        _count_rounds.append(dict(_counts))
        _counts.clear()


def snapshot() -> list[dict[str, float]]:
    """Per-round phase buckets collected since ``enable()`` (a copy).

    Late token-attributed spans (an async checkpoint still in flight)
    patch the live buckets, not this copy — flush background writers
    before snapshotting."""
    with _lock:
        return [dict(r) for r in _rounds]


def export() -> dict:
    """The span records and the closed rounds' counters (copies), for
    export when a run ends: ``{"spans": [{"name", "parent", "start",
    "end", "round", "thread"}, ...], "counts": [{name: n}, ...]}``.  A
    span still open has ``end`` and ``round`` None."""
    keys = ("name", "parent", "start", "end", "round", "thread")
    with _lock:
        return {"spans": [dict(zip(keys, r)) for r in _records],
                "counts": [dict(c) for c in _count_rounds]}


def self_times(spans: list[dict]) -> list[float]:
    """Each record's seconds less its children's (``export()["spans"]``)."""
    out = [0.0 if s["end"] is None else s["end"] - s["start"] for s in spans]
    for s in spans:
        if s["parent"] is not None and s["end"] is not None:
            out[s["parent"]] -= s["end"] - s["start"]
    return out

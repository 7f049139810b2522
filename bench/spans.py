"""The program's own spans, counters and device scopes, read for the bench.

``repro.perf`` records a span for each layer of the round path and opens
the same span on the profiler's host plane as ``repro.<name>``; the round
programs put ``jax.named_scope``s on their layers, in each device
operation's framework name.  This module reads both:

- ``load(log_dir)``: the device operations of each chip with their scope
  path, and the host events of the program (``repro.*``) and of the
  harness (``bench.*``), each with the thread that ran it.
- ``reduce(...)``: the idle gaps named by the innermost program span open
  on the driver thread (the thread that holds the ``bench.round``
  annotations), a harness annotation only where no program span is open;
  and the device seconds by scope, with the unscoped remainder by
  operation.
- ``window_rounds``, ``per_round``, ``longest_rounds``: the program's span
  records and counters over the measured rounds (``repro.perf.export``).

Everything takes plain lists, so a test can check it on intervals it
knows.  The harness's own reduction is ``trace.py``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

from bench import trace

PROGRAM = "repro."                # host spans the program writes
HARNESS = trace.PREFIX            # host annotations the harness writes
WINDOW = trace.WINDOW             # one per measured round
# the round programs' named scopes (fed/sharded.py, fed/client.py); a
# device operation counts toward every one on its path (masked_carry lies
# inside teacher_phase or student_kd)
SCOPES = ("teacher_phase", "student_kd", "cross_lane", "masked_carry",
          "eval_forward")
# the event stat that would hold an operation's framework name; a TPU v5e
# trace read through ``ProfileData`` carries none (only device offsets and
# durations), so scope paths there must come from the compiled programs'
# HLO metadata instead
SCOPE_STAT = "tf_op"


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def load(log_dir: str):
    """(device_ops, host_events): ``device_ops`` maps each TPU plane to its
    ``(name, start_ns, dur_ns, scope)`` operations (``scope`` the
    framework name path, "" where the event has none); ``host_events``
    lists the ``repro.*`` and ``bench.*`` events as ``(name, start_ns,
    dur_ns, thread)``, ``thread`` a key of the host line that ran it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return {}, []
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device_ops, host_events = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (trace.op_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns),
                         str(_stats(ev).get(SCOPE_STAT, "")))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith((PROGRAM, HARNESS)):
                        host_events.append(
                            (ev.name, float(ev.start_ns),
                             float(ev.duration_ns), f"{plane.name}#{i}"))
    return device_ops, host_events


class Timeline:
    """The innermost of a set of properly nested spans (one thread's) open
    at each time: ``segments`` are sorted ``(start, end, name)``."""

    def __init__(self, spans):
        segs, stack, t = [], [], 0.0
        # a parent sorts before a child that starts with it
        for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
            while stack and stack[-1][0] <= s:
                end, name = stack.pop()
                if end > t:
                    segs.append((t, end, name))
                    t = end
            if stack and s > t:
                segs.append((t, s, stack[-1][1]))
            t = s
            stack.append((e, n))
        while stack:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end
        self.segments = segs
        self.starts = [g[0] for g in segs]

    def _from(self, lo):
        return self.segments[max(bisect.bisect_right(self.starts, lo) - 1,
                                 0):]

    def cover(self, lo, hi):
        """``(name, seconds)`` of the pieces of ``[lo, hi)`` (ns) under a
        span."""
        out = []
        for s, e, n in self._from(lo):
            if s >= hi:
                break
            a, b = max(s, lo), min(e, hi)
            if b > a:
                out.append((n, (b - a) * 1e-9))
        return out

    def uncovered(self, lo, hi):
        """The ``(start, end)`` pieces of ``[lo, hi)`` under no span."""
        out, t = [], lo
        for s, e, _ in self._from(lo):
            if s >= hi:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < hi:
            out.append((t, hi))
        return out


def driver_thread(host_events):
    """The thread that holds the ``bench.round`` annotations, or None."""
    for name, _, _, thread in host_events:
        if name == WINDOW:
            return thread
    return None


def gap_names(host_events):
    """(program, harness) timelines of the driver thread: its ``repro.*``
    spans, and the harness's annotations other than the round itself."""
    driver = driver_thread(host_events)
    mine = [(s, s + d, n) for n, s, d, th in host_events if th == driver]
    return (Timeline([x for x in mine if x[2].startswith(PROGRAM)]),
            Timeline([x for x in mine if x[2].startswith(HARNESS)
                      and x[2] != WINDOW]))


def _has_scope(path: str, scope: str) -> bool:
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)", path) is not None


def reduce(device_ops, host_events, window=None, top=10):
    """Idle gaps named by program spans, and device seconds by scope, over
    the measured window.

    Returns ``None`` when there is no window or no device operation in it,
    else a dict with ``window_s``, ``busy_s`` (mean over the devices),
    ``idle_s`` (mean), ``devices``; ``idle_gaps``: idle seconds by the
    innermost program span open on the driver thread, else the innermost
    harness annotation, else ``host.other`` (mean over the devices, the
    ``top`` largest as ``[name, seconds]``); ``idle_named_s``: the idle
    seconds under a program span; ``scope_s``: device seconds by scope
    (``SCOPES``; summed over devices, control flow left out);
    ``scoped_s`` and ``op_s``: the seconds under any scope, and all
    seconds, summed over devices; ``unscoped_ops``: the ``top`` largest
    operations under no scope, as ``[name, seconds]``."""
    triples = [(n, s, d) for n, s, d, _ in host_events]
    window = window or trace.window_of(triples)
    if window is None:
        return None
    lo, hi = window
    program, harness = gap_names(host_events)
    gaps = defaultdict(float)
    scope_s, unscoped = defaultdict(float), defaultdict(float)
    busy, scoped, total, n_dev = [], 0.0, 0.0, 0
    for ops in device_ops.values():
        inside = [o for o in ops if o[1] + o[2] > lo and o[1] < hi]
        if not inside:
            continue
        n_dev += 1
        for n, s, d, path in inside:
            if n.split(".")[0] in trace.CONTAINERS:
                continue
            sec = (min(s + d, hi) - max(s, lo)) * 1e-9
            total += sec
            hit = [sc for sc in SCOPES if _has_scope(path, sc)]
            for sc in hit:
                scope_s[sc] += sec
            if hit:
                scoped += sec
            else:
                unscoped[n] += sec
        merged = trace.union(trace._clip(
            [(s, s + d) for _, s, d, _ in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            for name, sec in program.cover(a, b):
                gaps[name] += sec
            for u0, u1 in program.uncovered(a, b):
                left = (u1 - u0) * 1e-9
                for name, sec in harness.cover(u0, u1):
                    gaps[name] += sec
                    left -= sec
                gaps["host.other"] += max(left, 0.0)
    if not n_dev:
        return None
    ranked = lambda d, k=1.0: [[n, v / k] for n, v in
                               sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    busy_s = sum(busy) / n_dev
    window_s = (hi - lo) * 1e-9
    named = sum(v for n, v in gaps.items() if n.startswith(PROGRAM))
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_s": window_s - busy_s, "devices": n_dev,
            "idle_gaps": ranked(gaps, n_dev), "idle_named_s": named / n_dev,
            "scope_s": dict(scope_s), "scoped_s": scoped, "op_s": total,
            "unscoped_ops": ranked(unscoped)}


# ------------------------------------------------- the program's records
def window_rounds(exported: dict) -> int:
    """The number of closed rounds in ``repro.perf.export()``."""
    return len(exported["counts"])


def per_round(exported: dict, counter: str) -> float | None:
    """Mean per closed round of a ``repro.perf`` counter, or None when no
    round counted it."""
    counts = exported["counts"]
    if not counts or not any(counter in c for c in counts):
        return None
    return sum(c.get(counter, 0) for c in counts) / len(counts)


def span_seconds(exported: dict, name: str, thread: str | None = None):
    """Seconds per closed round of the records named ``name`` (on
    ``thread`` alone when given), or None when there is none."""
    n = window_rounds(exported)
    recs = [s for s in exported["spans"] if s["name"] == name
            and s["end"] is not None and s["round"] < n
            and (thread is None or s["thread"] == thread)]
    if not n or not recs:
        return None
    return sum(s["end"] - s["start"] for s in recs) / n


def round_thread(exported: dict) -> str | None:
    """The thread that closed the rounds: the one that ran ``eval``."""
    for s in exported["spans"]:
        if s["name"] == "eval":
            return s["thread"]
    return None


def longest_rounds(exported: dict, self_times, k: int = 3):
    """The ``k`` closed rounds with the most span time on the round thread,
    each as ``(round, seconds, {name: self seconds})`` over all threads."""
    n = window_rounds(exported)
    spans = exported["spans"]
    driver = round_thread(exported)
    tops = defaultdict(float)
    per = defaultdict(lambda: defaultdict(float))
    for s, own in zip(spans, self_times):
        if s["end"] is None or s["round"] >= n:
            continue
        if s["parent"] is None and s["thread"] == driver:
            tops[s["round"]] += s["end"] - s["start"]
        per[s["round"]][s["name"]] += own
    ranked = sorted(tops.items(), key=lambda kv: -kv[1])[:k]
    return [(r, sec, dict(per[r])) for r, sec in ranked]


def exported() -> dict | None:
    """The run's ``repro.perf.export()``, read where the harness left it
    (perf is enabled for a traced window and disabled after it, which
    keeps what it collected); None where the program has no span records
    or recorded no round."""
    from repro import perf
    export = getattr(perf, "export", None)
    if export is None:
        return None
    out = export()
    return out if out["counts"] else None


def report(reduced, exported_, self_times, k: int = 3) -> list[str]:
    """Lines for a traced run's log: the program spans' self times in the
    ``k`` longest rounds, and the device's busy time by scope with the
    unscoped remainder by operation."""
    lines = []
    if exported_ is not None:
        for r, sec, own in longest_rounds(exported_, self_times, k):
            parts = " ".join(f"{n} {1e3 * v:.2f}" for n, v in
                             sorted(own.items(), key=lambda kv: -kv[1]))
            lines.append(f"long round {r}: {1e3 * sec:.1f} ms of spans; "
                         f"self ms: {parts}")
    if reduced is not None:
        total = max(reduced["op_s"], 1e-12)
        lines.append("device time by scope: " + " ".join(
            f"{n} {v:.4f}s ({100 * v / total:.1f}%)"
            for n, v in sorted(reduced["scope_s"].items(),
                               key=lambda kv: -kv[1]))
            + f"; under a scope {100 * reduced['scoped_s'] / total:.1f}%")
        lines.append("unscoped ops: " + " ".join(
            f"{n} {v:.4f}s" for n, v in reduced["unscoped_ops"]))
        lines.append(f"idle {reduced['idle_s']:.4f}s, under a program "
                     f"span {reduced['idle_named_s']:.4f}s; gaps: "
                     + " ".join(f"{n} {v:.4f}s"
                                for n, v in reduced["idle_gaps"]))
    return lines

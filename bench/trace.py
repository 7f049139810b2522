"""From a profiler trace to the numbers the per-layer readers use.

``load(log_dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
returns plain lists: the device operations of each chip, and the host
spans the harness annotated (``bench.*``).  ``reduce(...)`` turns those
lists into busy time, idle gaps and their attribution, and time by
operation name.  The reduction takes plain lists so that a test can check
it on intervals it knows.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

PREFIX = "bench."                 # host annotations the harness writes
WINDOW = PREFIX + "round"         # one per measured round
# control flow whose event spans the operations it runs: busy, but not
# an operation of its own in the time by name
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """The HLO instruction's name out of the trace's ``%name = ...`` text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str):
    """(device_ops, host_spans): ``device_ops`` maps each TPU plane to its
    ``(name, start_ns, dur_ns)`` operations; ``host_spans`` lists the
    harness's annotations as ``(name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return {}, []
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops[plane.name] = [
                        (op_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host_spans.append((ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
    return device_ops, host_spans


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(host_spans):
    """(start_ns, end_ns) covered by the measured rounds, or None."""
    rounds = [(s, s + d) for n, s, d in host_spans if n == WINDOW]
    if not rounds:
        return None
    return min(s for s, _ in rounds), max(e for _, e in rounds)


class Timeline:
    """Which harness annotation (other than the round itself) is innermost
    open at a given time; ``"host.other"`` where none is."""

    def __init__(self, host_spans):
        spans = [(s, s + d, n) for n, s, d in host_spans if n != WINDOW]
        cuts = sorted({t for s, e, _ in spans for t in (s, e)})
        self.cuts, self.names = cuts, []
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            open_ = [(s, n) for s, e, n in spans if s <= mid < e]
            self.names.append(max(open_)[1] if open_ else "host.other")

    def at(self, t):
        i = bisect.bisect_right(self.cuts, t) - 1
        if i < 0 or i >= len(self.names):
            return "host.other"
        return self.names[i]


def reduce(device_ops, host_spans, window=None, top=10):
    """Busy and idle time of the devices over the measured window.

    Returns ``None`` when there is no window or no device operation in it,
    else a dict with ``window_s``, ``busy_s`` (mean over the devices),
    ``op_s`` and ``op_n`` (device seconds and event counts by operation
    name, summed over devices; control flow left out),
    ``device_ops`` and ``idle_gaps`` (each the ``top`` largest, as
    ``[name, seconds]``; gaps are summed by the host activity open at the
    gap's middle, per device, then averaged over the devices)."""
    window = window or window_of(host_spans)
    if window is None:
        return None
    lo, hi = window
    op_s, op_n = defaultdict(float), defaultdict(int)
    gaps, busy = defaultdict(float), []
    timeline = Timeline(host_spans)
    n_dev = 0
    for ops in device_ops.values():
        inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
        if not inside:
            continue
        n_dev += 1
        for n, s, d in inside:
            if n.split(".")[0] in CONTAINERS:
                continue
            op_s[n] += (min(s + d, hi) - max(s, lo)) * 1e-9
            op_n[n] += 1
        merged = union(_clip([(s, s + d) for _, s, d in inside], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[timeline.at((a + b) / 2)] += (b - a) * 1e-9
    if not n_dev:
        return None
    ranked = lambda d: [[k, v] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / n_dev,
            "devices": n_dev, "op_s": dict(op_s), "op_n": dict(op_n),
            "device_ops": ranked(op_s),
            "idle_gaps": ranked({k: v / n_dev for k, v in gaps.items()})}


def time_of(op_s: dict, patterns) -> float:
    """Device seconds of the operations whose name holds any pattern."""
    return sum(v for k, v in op_s.items() if any(p in k for p in patterns))


def count_of(op_n: dict, patterns) -> int:
    """Events of the operations whose name holds any pattern."""
    return sum(v for k, v in op_n.items() if any(p in k for p in patterns))

"""Host spans of the harness and the reduction of a profiler trace.

The harness wraps each call it makes into the program in a
``jax.profiler.TraceAnnotation`` (``bench.submit``, ``bench.step``,
``bench.idle_wait``) and the traced stretch in ``bench.window``, so that
they land in the profiler's trace on the device's clock.  ``bench.stages``
reads an ``.xplane.pb`` into plain tuples; everything here is arithmetic on
intervals, checked on small recorded traces in the tests.

A reduction is defined over the chips the cell used: the device planes that
ran any operation in the trace (``used_chips``).  A time is each chip's,
averaged over those chips; an idle gap is a stretch in which none of them
ran anything.  On one chip each reads what that chip alone reads.
"""
from __future__ import annotations

import bisect
import contextlib
import itertools
from dataclasses import dataclass, field

#: the device plane's line that holds one event per executed operation
OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    """Device operations and harness spans of one traced stretch; times in
    nanoseconds on the trace's clock."""

    ops: dict = field(default_factory=dict)     # device -> [(name, t0, t1)]
    spans: list = field(default_factory=list)   # [(name, t0, t1, stats)]

    def window(self):
        """(t0, t1) of the ``bench.window`` span."""
        w = [s for s in self.spans if s[0] == "bench.window"]
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} bench.window spans")
        return w[0][1], w[0][2]

    def step_spans(self):
        """``bench.step`` spans that lie wholly inside the window."""
        w0, w1 = self.window()
        return [s for s in self.spans
                if s[0] == "bench.step" and s[1] >= w0 and s[2] <= w1]


def span(name: str, on: bool, **stats):
    """A harness span when tracing is on; nothing otherwise."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


def op_name(event_name: str) -> str:
    """``fusion.20`` from the trace's ``%fusion.20 = f32[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted (t0, t1) intervals, clipped to [lo, hi] when given."""
    out = []
    for a, b in sorted((a, b) for _, a, b in intervals):
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(intervals, lo=None, hi=None) -> int:
    """Length of the union of ``intervals`` (name, t0, t1) in [lo, hi]."""
    return sum(b - a for a, b in union(intervals, lo, hi))


def per_span(events, spans) -> list:
    """[the events (label, t0, t1) that overlap each span (name, t0, t1,
    ...) of ``spans``], each list sorted by start, the longer first at a
    tie, as ``self_times`` orders them.  Found by bisection, so a reduction
    per step does not walk the whole trace once a step."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    starts = [e[1] for e in ev]
    reach = list(itertools.accumulate((e[2] for e in ev), max))
    out = []
    for s in spans:
        lo, hi = s[1], s[2]
        first = bisect.bisect_right(reach, lo)     # all before end by lo
        stop = bisect.bisect_left(starts, hi)      # all after start at hi
        out.append([e for e in ev[first:stop] if e[2] > lo])
    return out


def used_chips(tr: Trace) -> dict:
    """{device: its operations} of each device that ran any operation in
    the trace: the chips the cell used, in the trace's order."""
    return {dev: ev for dev, ev in tr.ops.items() if ev}


def device_busy_s(tr: Trace) -> float:
    """Busy seconds in the window, averaged over the chips the cell used."""
    w0, w1 = tr.window()
    used = used_chips(tr).values()
    if not used:
        raise RuntimeError("trace holds no device operations")
    return sum(busy_ns(ev, w0, w1) for ev in used) / len(used) / 1e9


def window_s(tr: Trace) -> float:
    w0, w1 = tr.window()
    return (w1 - w0) / 1e9


def step_busy_ms(tr: Trace) -> list:
    """Device busy milliseconds inside each whole ``bench.step`` span of
    the window: each chip's union of its operations, mean over the chips
    the cell used.  Empty where no chip ran anything."""
    used = used_chips(tr).values()
    if not used:
        return []
    steps = tr.step_spans()
    per_chip = [per_span(ev, steps) for ev in used]
    return [sum(busy_ns(sl[i], s[1], s[2]) for sl in per_chip) / len(used)
            / 1e6 for i, s in enumerate(steps)]


def matching(events, names) -> list:
    """Events whose name starts with one of ``names``."""
    names = tuple(names)
    return [e for e in events if e[0].startswith(names)]


def self_times(events, lo, hi) -> dict:
    """Nanoseconds per operation name in [lo, hi], each event counted
    without the events nested inside it (a ``while`` holds its body's
    operations on the same line)."""
    tot: dict = {}
    stack: list = []          # [name, t0, t1, child_ns]

    def close(top):
        own = max(0, min(top[2], hi) - max(top[1], lo)) - top[3]
        tot[top[0]] = tot.get(top[0], 0) + own

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += max(0, min(b, hi) - max(a, lo))
        stack.append([name, a, b, 0])
    while stack:
        close(stack.pop())
    return tot


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the device operations that took the most time
    in the window by their own time: summed by name over the chips the cell
    used, divided by their count."""
    w0, w1 = tr.window()
    used = used_chips(tr).values()
    tot: dict = {}
    for ev in used:
        for name, ns in self_times(ev, w0, w1).items():
            tot[name] = tot.get(name, 0) + ns
    best = sorted(((k, v) for k, v in tot.items() if v > 0),
                  key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(used) / 1e9] for name, ns in best]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """[[span, seconds]] of the longest stretches of the window in which
    none of the chips the cell used ran anything, each named by the
    innermost span that held its mid point (``"none"`` where no span did)."""
    w0, w1 = tr.window()
    busy = union([e for ev in used_chips(tr).values()
                  for e in ev], w0, w1)
    gaps, t = [], w0
    for a, b in busy + [[w1, w1]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    inner = [s for s in tr.spans if s[0] != "bench.window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        held = [s for s in inner if s[1] <= mid <= s[2]]
        name = min(held, key=lambda s: s[2] - s[1])[0] if held else "none"
        out.append([name, (b - a) / 1e9])
    return out

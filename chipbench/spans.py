"""The program's own spans and the device's programs, from a profiler
trace, as plain lists.

The program opens host spans named `repro.*` at its layer boundaries
(`repro.obs`, docs/tracing.md), on the clock of the device planes.
`read` turns the `.xplane.pb` of a traced window into a `Timeline` of
the `bench.window` span (`window` finds the one `run.py` just wrote):

* `spans`: each `repro.*` host span that starts in the window, as
  (start, end, name) in ns, sorted;
* `programs`: per device `/device:TPU:<i>`, each program its
  `XLA Modules` line ran, as (start, end, name), the name with its
  fingerprint (`jit_gather(5662682381896479847)`), so two programs of
  one name stay apart;
* `busy`: per device, the union of its `XLA Ops` intervals clipped to
  the window, busy as `trace.py` counts it.

The functions after `read` are pure over those lists.  Where spans of
several threads overlap, the innermost span at a time is the one opened
last among those open then.  On a TPU v5e the device events sit up to
about 0.5 ms early against the host spans, so a program dispatched as a
span opens can start, by the trace's clock, just before it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import heapq
import os
import re

from chipbench import run, trace

PREFIX = "repro."
NO_SPAN = "(no repro span)"


@dataclasses.dataclass
class Timeline:
    """A traced window as lists (see the module doc)."""

    window: tuple          # (start, end) of bench.window, ns
    spans: list            # [(start, end, name)]
    programs: list         # per device: [(start, end, name)]
    busy: list             # per device: [[start, end]], disjoint

    @property
    def window_s(self) -> float:
        """The window's seconds."""
        return (self.window[1] - self.window[0]) / 1e9


@functools.lru_cache(maxsize=1)
def read(path: str, chips: int | None = None) -> Timeline:
    """The `Timeline` of the trace at `path` over the devices
    `/device:TPU:0` to `chips - 1` (all, where `chips` is None)."""
    spans, windows, programs, busy = [], [], [], []
    for plane in trace.load(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    if ev.name == trace.WINDOW_SPAN:
                        windows.append(s)
                    elif ev.name.startswith(PREFIX):
                        spans.append(s)
            continue
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m or (chips is not None and int(m.group(1)) >= chips):
            continue
        progs, ops = [], []
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events]
            if line.name == "XLA Modules":
                progs += evs
            elif line.name == "XLA Ops":
                ops += evs
        programs.append(progs)
        busy.append(ops)
    if not windows:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0][0], windows[0][1]
    return Timeline(
        window=(lo, hi),
        spans=sorted(s for s in spans if lo <= s[0] < hi),
        programs=[sorted(p for p in progs if lo <= p[0] < hi)
                  for progs in programs],
        busy=[trace._union([(max(s, lo), min(e, hi)) for s, e, _ in ops
                            if min(e, hi) > max(s, lo)]) for ops in busy])


def window(ctx) -> Timeline:
    """The `Timeline` of the window `run.py` traced last: the
    `.xplane.pb` under its trace directory, over the cell's chips."""
    path = glob.glob(os.path.join(run.TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return read(path, ctx["chips"])


def durations(spans, name: str) -> list:
    """Seconds of each span named `name`."""
    return [(e - s) / 1e9 for s, e, n in spans if n == name]


def segments(spans) -> list:
    """Time cut into disjoint (start, end, name) pieces, each named by
    the innermost span open throughout it; time under no span is left
    out."""
    spans = sorted(spans)
    cuts = sorted({t for s in spans for t in s[:2]})
    out, heap, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            s, e, name = spans[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2]))
    return out


def self_seconds(segs) -> dict:
    """Each span name's self time: the seconds in which it was the
    innermost span."""
    out: collections.Counter = collections.Counter()
    for s, e, name in segs:
        out[name] += (e - s) / 1e9
    return dict(out)


def programs_in(spans, programs, name: str) -> list:
    """The `programs` that start inside a span named `name`."""
    inside = sorted((s, e) for s, e, n in spans if n == name)
    starts = [s for s, _ in inside]
    out = []
    for p in programs:
        k = bisect.bisect_right(starts, p[0]) - 1
        if k >= 0 and p[0] < inside[k][1]:
            out.append(p)
    return out


def _name_at(segs, starts, t) -> str:
    k = bisect.bisect_right(starts, t) - 1
    return segs[k][2] if k >= 0 and t < segs[k][1] else NO_SPAN


def program_seconds(segs, programs) -> dict:
    """Device seconds by (innermost span at the program's start,
    program)."""
    starts = [s for s, _, _ in segs]
    out: collections.Counter = collections.Counter()
    for s, e, name in programs:
        out[(_name_at(segs, starts, s), name)] += (e - s) / 1e9
    return dict(out)


def idle_seconds(segs, busy, window) -> dict:
    """The window's device idle time (not `busy`) by the innermost span
    the host was in, `NO_SPAN` for idle under none."""
    out: collections.Counter = collections.Counter()
    j = 0
    for s, e in trace._subtract([list(window)], busy):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < e:
            a, b = max(s, segs[k][0]), min(e, segs[k][1])
            out[segs[k][2]] += (b - a) / 1e9
            covered += b - a
            k += 1
        if e - s > covered:
            out[NO_SPAN] += (e - s - covered) / 1e9
    return dict(out)

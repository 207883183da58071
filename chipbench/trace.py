"""Reduce a profiler trace of the measured window to device numbers.

The trace is JAX's `.xplane.pb`.  On a TPU each chip is a plane
`/device:TPU:<i>` whose line `XLA Ops` holds one event per operation
that ran, on the host's clock.  The benchmark marks its window and its
calls into the program with host spans named `bench.*`
(`jax.profiler.TraceAnnotation`), on the same clock.

From these `reduce_trace` computes, per device and clipped to the
`bench.window` span:

* busy seconds: the union of the intervals in which an operation ran;
* kernel seconds: the summed durations of each Pallas kernel's events,
  keyed by the kernel's name as the op's HLO text gives it;
* collective seconds, and the part of them during which no other
  operation ran on that device (exposed);
* idle gaps: the complement of busy, each named by the innermost
  `bench.*` span the host was in at the gap's midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINES = ("XLA Ops", "Async XLA Ops")
_PALLAS = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute"
    r"|send|recv")


@dataclasses.dataclass
class DeviceReading:
    name: str
    busy_s: float
    kernel_s: dict
    collective_s: float
    collective_exposed_s: float
    op_s: dict


@dataclasses.dataclass
class TraceReading:
    window_s: float
    devices: list
    idle_by_span: dict    # span name -> idle seconds, summed over devices

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def kernel_s(self, kernel: str) -> float:
        """A kernel's device seconds averaged over the devices."""
        return sum(d.kernel_s.get(kernel, 0.0)
                   for d in self.devices) / len(self.devices)

    def breakdown(self, top: int = 10) -> dict:
        """The ten longest device operations (seconds per device) and the
        idle time by what the host was doing (seconds per device)."""
        ops: collections.Counter = collections.Counter()
        for d in self.devices:
            ops.update(d.op_s)
        k = len(self.devices)
        return {
            "device_ops": [[n, s / k] for n, s in ops.most_common(top)],
            "idle_gaps": [[n, s / k] for n, s in sorted(
                self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]],
        }


def op_name(hlo_text: str) -> str:
    """The op's short name: `fusion.12`, or a kernel's name."""
    return kernel_name(hlo_text) or hlo_text.split(" = ", 1)[0].lstrip(
        "%").strip()


def kernel_name(hlo_text: str):
    """A Pallas call's kernel name, `sdca_sparse_bucket_kernel` for
    `%vmap_jit_sdca_sparse_bucket_kernel__.1 = ... custom_call_target=
    "tpu_custom_call" ...`; None for any other op."""
    if _PALLAS not in hlo_text:
        return None
    head = hlo_text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"[_.\d]+$", "", head).split("jit_")[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _measure(iv) -> float:
    return float(sum(e - s for s, e in iv))


def _subtract(a, b):
    """Intervals of union `a` not covered by union `b` (both sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def load(path):
    from jax.profiler import ProfileData
    if isinstance(path, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(path))
    return ProfileData.from_file(str(path))


def reduce_trace(profile, chips: int | None = None) -> TraceReading:
    """Reduce a `ProfileData` (see the module doc) over the devices
    `/device:TPU:0` to `chips - 1` (all, where `chips` is None)."""
    spans = []
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        else:
            m = re.match(r"^/device:TPU:(\d+)$", plane.name)
            if m and (chips is None or int(m.group(1)) < chips):
                devices.append(plane)
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0][0], win[0][1]
    inner = sorted((s for s in spans if s[2] != WINDOW_SPAN),
                   key=lambda s: s[0])
    if not devices:
        raise ValueError("no /device:TPU:<i> plane in the trace")

    readings = []
    idle_by_span: collections.Counter = collections.Counter()
    for plane in devices:
        sync, coll, other = [], [], []
        kern: collections.Counter = collections.Counter()
        ops: collections.Counter = collections.Counter()
        for line in plane.lines:
            if line.name not in OPS_LINES:
                continue
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             lo, hi)
                if e <= s:
                    continue
                name = op_name(ev.name)
                is_coll = bool(_COLLECTIVE.match(name))
                if is_coll:
                    coll.append((s, e))
                if line.name == "XLA Ops":
                    sync.append((s, e))
                    if not is_coll:
                        other.append((s, e))
                    ops[name] += (e - s) / 1e9
                    k = kernel_name(ev.name)
                    if k:
                        kern[k] += (e - s) / 1e9
        busy = _union(sync)
        coll_u = _union(coll)
        exposed = _subtract(coll_u, _union(other))
        gaps = _subtract([[lo, hi]], busy)
        names = _spans_at(inner, [(g0 + g1) / 2 for g0, g1 in gaps])
        for (g0, g1), name in zip(gaps, names):
            idle_by_span[name] += (g1 - g0) / 1e9
        readings.append(DeviceReading(
            name=plane.name, busy_s=_measure(busy) / 1e9,
            kernel_s=dict(kern), collective_s=_measure(coll_u) / 1e9,
            collective_exposed_s=_measure(exposed) / 1e9,
            op_s=dict(ops)))
    return TraceReading(window_s=(hi - lo) / 1e9, devices=readings,
                        idle_by_span=dict(idle_by_span))


def _spans_at(spans, times) -> list:
    """For each of the sorted `times`, the innermost bench span holding
    it.  The bench's spans nest, so a stack swept in time order holds
    the open ones, innermost on top."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else WINDOW_SPAN)
    return out

"""Share of the traced window in which a collective ran on a chip and no
other operation did: the re-deal's all-to-all, the lane sum's all-reduce
and the certificate's reductions, where nothing hides them.  Averaged
over the cell's chips.  Layer: the collectives.

A collective is an op whose HLO text names a collective opcode
(`all-to-all`, `all-reduce`, `all-gather`, `reduce-scatter`,
`collective-permute`, or their `-start`/`-done` halves).  `trace.py`
keys ops by instruction name, and the shard_map epoch names its
collectives `all_to_all.<k>` and `psum.<k>`, so its
`collective_exposed_s` sees only the certificate's; the opcode is what
this reader matches."""
import glob
import os
import re

from chipbench import run, trace

_COLLECTIVE = re.compile(
    r" (all-to-all|all-reduce|all-gather|reduce-scatter"
    r"|collective-permute)(-start|-done)?\(")


def read(ctx):
    path = glob.glob(os.path.join(run.TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return exposed_share(trace.load(path), ctx["chips"])


def exposed_share(profile, chips: int) -> float:
    """The share in % over `/device:TPU:0` to `chips - 1` of `profile`
    (a `ProfileData`, or anything with its planes, lines and events)."""
    window, devices = None, []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace.WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            continue
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < chips:
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {trace.WINDOW_SPAN!r} span in the trace")
    shares = []
    for plane in devices:
        coll, other = [], []
        for line in plane.lines:
            if line.name not in trace.OPS_LINES:
                continue
            for ev in line.events:
                s, e = trace._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                   *window)
                if e <= s:
                    continue
                if _COLLECTIVE.search(ev.name):
                    coll.append((s, e))
                elif line.name == "XLA Ops":
                    other.append((s, e))
        exposed = trace._subtract(trace._union(coll), trace._union(other))
        shares.append(trace._measure(exposed) / (window[1] - window[0]))
    return 100.0 * sum(shares) / len(shares)

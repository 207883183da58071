"""Device programs a gap check launches: the `XLA Modules` events that
start inside the program's `repro.gap` spans, averaged over the devices,
over the number of those spans.  Layer: the gap certificate.  Silent
where the program opens no such span.

Before the result it prints the window's attributed breakdown: host
self seconds by `repro.*` span, and per device idle seconds by the
innermost span the host was in, the share of idle under none, and
device seconds by (span, program)."""
from chipbench import spans

TOP = 30


def _report(tl) -> None:
    segs = spans.segments(tl.spans)
    k = len(tl.programs)
    idle, progs = {}, {}
    for busy, programs in zip(tl.busy, tl.programs):
        for key, s in spans.idle_seconds(segs, busy, tl.window).items():
            idle[key] = idle.get(key, 0.0) + s / k
        for key, s in spans.program_seconds(segs, programs).items():
            progs[key] = progs.get(key, 0.0) + s / k
    print("spans: host self s: " + ", ".join(
        f"{n} {s!r}" for n, s in sorted(spans.self_seconds(segs).items(),
                                        key=lambda kv: -kv[1])), flush=True)
    total = sum(idle.values())
    print(f"spans: device idle {total!r} s by innermost span: " + ", ".join(
        f"{n} {s!r}" for n, s in sorted(idle.items(), key=lambda kv: -kv[1])),
        flush=True)
    none = idle.get(spans.NO_SPAN, 0.0)
    print(f"spans: idle under no repro span {none!r} s, "
          f"{100.0 * none / total if total else 0.0!r} % of idle", flush=True)
    for (span, prog), s in sorted(progs.items(), key=lambda kv: -kv[1])[:TOP]:
        print(f"spans: device s {s!r} in {span}: {prog}", flush=True)


def read(ctx):
    """Programs a check, or None without `repro.gap` spans."""
    tl = spans.window(ctx)
    checks = len(spans.durations(tl.spans, "repro.gap"))
    if not checks:
        return None
    _report(tl)
    started = sum(len(spans.programs_in(tl.spans, p, "repro.gap"))
                  for p in tl.programs)
    return started / len(tl.programs) / checks

"""Share of the traced window inside the program's gap checks: the
seconds of its `repro.gap` spans (`Session.gap`) over the window.  The
in-program twin of `gap_check_share`, which also holds `fit`'s vmax
read and the callbacks.  Layer: the gap certificate.  Silent where the
program opens no such span."""
from chipbench import spans


def read(ctx):
    """The share in %, or None without `repro.gap` spans."""
    tl = spans.window(ctx)
    gaps = spans.durations(tl.spans, "repro.gap")
    return 100.0 * sum(gaps) / tl.window_s if gaps else None

"""Examples a chip processed a second inside the window's epochs: n over
the cell's chips, times the epochs, over the seconds of the program's
`repro.epoch` spans.  Per-chip speed, to set beside a one-chip cell's
`epoch_span_examples_per_s`.  Layer: the epoch program.  Silent where
the program opens no such span."""
from chipbench import spans


def read(ctx):
    """Examples a second a chip, or None without `repro.epoch` spans."""
    epochs = spans.durations(spans.window(ctx).spans, "repro.epoch")
    if not epochs:
        return None
    return ctx["n"] / ctx["chips"] * len(epochs) / sum(epochs)

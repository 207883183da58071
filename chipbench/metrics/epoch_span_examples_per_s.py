"""Examples processed by the window's epochs over the seconds of their
`repro.epoch` spans (`Session.epoch`, ending on its rel_change read).
The in-program twin of `examples_per_s`.  Layer: the epoch program.
Silent where the program opens no such span."""
from chipbench import spans


def read(ctx):
    """Examples a second, or None without `repro.epoch` spans."""
    epochs = spans.durations(spans.window(ctx).spans, "repro.epoch")
    return ctx["n"] * len(epochs) / sum(epochs) if epochs else None

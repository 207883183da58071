"""Device-to-host reads per epoch in the window: the program's
`repro.read.*` spans over its `repro.epoch` spans.  Each read goes
through `repro.obs.read`, which opens one such span and counts one
`host_reads`, so this is the window's difference of that counter over
`epochs`: the reads of `Session.fit`, its epochs and its gap checks.
Layer: the front door.  Silent where the program opens no epoch span."""
from chipbench import spans


def read(ctx):
    """Reads an epoch, or None without `repro.epoch` spans."""
    names = [n for _, _, n in spans.window(ctx).spans]
    epochs = names.count("repro.epoch")
    reads = sum(n.startswith("repro.read.") for n in names)
    return reads / epochs if epochs else None

"""Share of the window spent in gap checks: from the end of each epoch,
which `fit`'s record `t` gives, to the bench callback that follows the
check.  Layer: the gap certificate (`Session.gap`)."""


def read(ctx):
    gap_s = sum(e["gap_s"] for s in ctx["solves"] for e in s["records"])
    return 100.0 * gap_s / ctx["window_s"]

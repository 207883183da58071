"""Examples processed by epochs over the seconds inside those epochs
(each epoch ends on `Session.epoch`'s sync).  Layer: the epoch program."""


def read(ctx):
    recs = [e for s in ctx["solves"] for e in s["records"]]
    return ctx["n"] * len(recs) / sum(e["epoch_s"] for e in recs)

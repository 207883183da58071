"""The whole solve's share of the chips' peak: the required work of
every epoch and gap check in the window (`chipbench/work.py`) over the
window's seconds times the chips, by the larger of the FLOP and the
byte bound, which it names on standard output."""


def read(ctx):
    work = ctx["work"]
    epochs = sum(len(s["records"]) for s in ctx["solves"])
    cfg, n = ctx["config"], ctx["n"]
    total = (work.epoch_work(cfg, n, chunks=ctx["chunks"],
                             lanes=ctx["lanes"])
             + work.gap_work(cfg, n)) * epochs
    t, bound = work.roofline_seconds(total, work.peak(ctx["device_kind"]))
    print(f"solve_mfu: bound by {bound}", flush=True)
    return 100.0 * t / (ctx["window_s"] * ctx["chips"])

"""Epochs each solve of the window took to reach the target gap with its
lanes on a mesh, averaged over solves (`FitResult.epochs`).  The
four-lane twin of `epochs_to_gap`.  Layer: the solver algorithm."""


def read(ctx):
    epochs = [s["epochs"] for s in ctx["solves"]]
    return sum(epochs) / len(epochs)

"""Epochs each solve of the window took to reach the target gap, averaged
over solves (`FitResult.epochs`).  Layer: the solver algorithm."""


def read(ctx):
    epochs = [s["epochs"] for s in ctx["solves"]]
    return sum(epochs) / len(epochs)

"""Required work of a solve, from the shapes alone.

These functions count what the algorithm needs, whatever implements it:
the Pallas kernels and the XLA scans of one cell get the same count.

Per epoch, every example is visited once:

* its row is read once: 8 bytes a nonzero (int32 id + float32 value)
  for sparse rows, 4·d bytes for dense ones;
* its label is read (4 bytes) and its dual variable read and written
  (8 bytes);
* the shared vector v (4·d bytes) goes in and out once per local-solver
  call, and there is one call per chunk on every lane;
* about 4 FLOPs per entry: the margin's multiply-add and v's update.

A gap check reads every row, label and dual variable once and v once,
and needs 2 FLOPs per entry for the margins.  The per-example losses and
conjugates are transcendental and are not counted.

An entry is a real nonzero of a sparse row (the configuration's `nnz`;
the zero-valued entries that pad a stored row to the kernel's multiple
are the implementation's, not the algorithm's), or one of the d values
of a dense row.
"""
from __future__ import annotations

import dataclasses
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def row_width(config: dict) -> int:
    """Entries of one row: its nonzeros, or d."""
    return config["nnz"] if config["kind"] == "sparse" else config["d"]


def row_bytes(config: dict) -> int:
    """Bytes of one row."""
    return (8 if config["kind"] == "sparse" else 4) * row_width(config)


def epoch_work(config: dict, n: int, *, chunks: int, lanes: int) -> Work:
    """Work of one epoch over all chips."""
    w = row_width(config)
    per_example = Work(4.0 * w, row_bytes(config) + 4 + 8)
    # one local-solver call a chunk on every lane
    v_traffic = Work(0.0, 2.0 * 4 * config["d"]) * (chunks * lanes)
    return per_example * n + v_traffic


def gap_work(config: dict, n: int) -> Work:
    """Work of one duality-gap check over all chips."""
    w = row_width(config)
    return Work(2.0 * w * n, n * (row_bytes(config) + 8) + 4 * config["d"])


def peak(device_kind: str) -> dict:
    """{'flops': FLOP/s, 'bytes': B/s} of one chip; a device missing from
    the table is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; add a row with its source")
    row = table[device_kind]
    return {"flops": float(row["flops_per_s"]),
            "bytes": float(row["hbm_bytes_per_s"])}


def roofline_seconds(work: Work, pk: dict) -> tuple[float, str]:
    """Least time one chip could take for `work`, and the bound."""
    t_flops = work.flops / pk["flops"]
    t_bytes = work.bytes / pk["bytes"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def kernel_roofline(ctx: dict, kernel: str):
    """A kernel's share of its roofline (%) over the traced window: the
    required work of the window's epochs on one chip over the kernel's
    device seconds on one chip.  None where the kernel did not run."""
    kernel_s = ctx["trace"].kernel_s(kernel)
    if kernel_s <= 0:
        return None
    epochs = sum(len(s["records"]) for s in ctx["solves"])
    w = epoch_work(ctx["config"], ctx["n"], chunks=ctx["chunks"],
                   lanes=ctx["lanes"]) * (epochs / ctx["chips"])
    t, _ = roofline_seconds(w, peak(ctx["device_kind"]))
    return 100.0 * t / kernel_s

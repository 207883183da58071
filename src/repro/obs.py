"""Spans and counters at the program's layer boundaries (docs/tracing.md).

A span is a `jax.profiler` trace annotation named ``repro.<name>``.  It
records only while a profiler session runs, and then lands in the
trace's host plane on the same clock as the device planes, so every
device program and idle gap can be put down to the innermost
``repro.*`` span the host was in.  Its keyword arguments (``epoch=``,
``chunk=``) become the event's stats: they tie a span to its cause.

The counters are one process-wide registry of running totals: `add`
bumps one, `counters` returns a copy, and a reader takes the difference
of two copies around the work it measures.  `read` is the one door of
the training loop's device-to-host reads: it spans, counts and copies.

Nothing here is optional: with no profiler session a span costs a
context manager and a counter one locked dict add.
"""
from __future__ import annotations

import collections
import contextlib
import threading

import jax
import numpy as np

PREFIX = "repro."
# fired once per program lowered to MLIR: a compile, or a fetch from the
# persistent compile cache; never on the dispatch of a compiled program
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_counts: collections.Counter = collections.Counter()
_lock = threading.Lock()
_local = threading.local()     # .open: this thread's open span names


def add(name: str, k: float = 1) -> None:
    """Add `k` to counter `name`."""
    with _lock:
        _counts[name] += k


def counters() -> dict:
    """A copy of every counter."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    """Zero every counter (tests)."""
    with _lock:
        _counts.clear()


def _stack() -> list:
    stack = getattr(_local, "open", None)
    if stack is None:
        stack = _local.open = []
    return stack


@contextlib.contextmanager
def span(name: str, **args):
    """Span ``repro.<name>`` with `args` as its stats.  A ``step_num``
    argument makes it a step (`jax.profiler.StepTraceAnnotation`), which
    the profiler's step view lists."""
    full = PREFIX + name
    ann = (jax.profiler.StepTraceAnnotation if "step_num" in args
           else jax.profiler.TraceAnnotation)
    stack = _stack()
    stack.append(full)
    try:
        with ann(full, **args):
            yield
    finally:
        stack.pop()


def read(name: str, x, **args):
    """Copy `x` to the host inside span ``repro.read.<name>``, counting
    ``host_reads`` and ``host_read_bytes``; a float for a scalar, else a
    host array.  `x` is a device array, or a function of no arguments
    that computes it, so that its dispatch falls inside the span too."""
    with span("read." + name, **args):
        out = np.asarray(x() if callable(x) else x)
    with _lock:
        _counts["host_reads"] += 1
        _counts["host_read_bytes"] += out.nbytes
    return float(out) if out.ndim == 0 else out


def _on_event(event: str, _secs: float, **_kw) -> None:
    if event == LOWERING_EVENT:
        stack = _stack()
        add("compiles." + (stack[-1] if stack else "outside"))


jax.monitoring.register_event_duration_secs_listener(_on_event)

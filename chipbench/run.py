#!/usr/bin/env python3
"""Time to a duality gap on the chip: the benchmark's one entry point.

    python3 chipbench/run.py --workload criteo-1chip --seed 7 \\
        --seconds 30 --trace 0

The cell is found by name in `BENCHMARK.json`; its configuration
(`chipbench/configs/<config>.json`), traffic
(`chipbench/traffic/<traffic>.json`) and cell file
(`chipbench/workloads/<name>.json`: target gap, epoch cap, engine knobs,
limits) are data.  One run:

1. set-up: draws the data from `--seed` at the configuration's shapes,
   builds the `Session`, runs a warm-up solve's first epoch and gap
   check through `Session.fit`, and resets the state;
2. the window: whole solves back to back, each from a zero state
   (`load_state_dict`), each `Session.fit(max_epochs=cap, tol=0.0)`
   stopped by `EarlyStopping(monitor="gap", threshold=target)`; once
   `--seconds` have passed the solve in progress finishes and the window
   ends;
3. the check: every solve's answer against the float64 reference
   (`chipbench/reference.py`), each number beside its limit.

`--trace 0` reports the end-to-end metrics, `--trace 1` the per-layer
ones, each read by its own file `chipbench/metrics/<name>.py` from the
window's records and the profiler trace.  The last line of standard
output is the result as JSON.  Without a TPU, or with fewer chips than
the cell needs, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell `name` with its configuration, traffic and cell file."""
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])
    cell = _json(os.path.join(HERE, "workloads", f"{name}.json"))
    return {
        "name": name, "chips": entry["chips"], "spec": spec,
        "config": _json(os.path.join(ROOT, config["file"])),
        "traffic": _json(os.path.join(HERE, "traffic",
                                      f"{entry['traffic']}.json")),
        "cell": cell,
    }


def find_devices(chips: int, require_tpu: bool = True):
    # libtpu's logs default to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def _enable_compile_cache() -> None:
    """JAX's compile cache at the program's default, `.jax_cache/` in the
    checkout, whatever `$JAX_COMPILATION_CACHE_DIR` says (the program's
    `enable_compile_cache` defers to that variable, which may name a
    directory outside the checkout), holding every program however fast
    it compiled."""
    import jax
    from repro.compile_cache import DEFAULT_DIR
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts lowerings to MLIR: one per program traced and compiled (or
    fetched from the persistent cache) in this process."""

    def __init__(self):
        from jax._src import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == LOWERING_EVENT:
            self.count += 1


class Recorder:
    """`Session.fit` callback: splits each epoch of a solve into the
    epoch itself and the gap check that follows it.  `fit` stamps its
    record's `t` at the end of the epoch; this callback runs after the
    gap check."""

    needs_gap = False

    def __init__(self):
        self.epochs: list[dict] = []

    def bind(self, session) -> None:
        self.t0 = self.last = time.perf_counter()

    def on_epoch_end(self, rec: dict) -> bool:
        now = time.perf_counter()
        end = self.t0 + rec["t"]
        self.epochs.append({"epoch_s": end - self.last, "gap_s": now - end})
        self.last = now
        return False


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _traced(fn, name: str):
    def call(*a, **kw):
        with _span(name):
            return fn(*a, **kw)
    return call


def build_session(cell: dict, data: dict):
    """The `Session` the cell's configuration and traffic describe."""
    from repro.api import Session
    from repro.core import EngineConfig
    cfgd = cell["config"]
    kw = dict(objective=cfgd["objective"], lam=cfgd["lam"],
              cfg=EngineConfig.make(**cell["cell"]["engine"]),
              streamed=bool(cell["traffic"]["streamed"]))
    if "idx" in data:
        s = Session((data["idx"], data["val"]), data["y"], d=cfgd["d"], **kw)
    else:
        s = Session(data["X"], data["y"], **kw)
    if s.n != data["y"].shape[0]:
        raise ValueError(f"the session padded n={data['y'].shape[0]} to "
                         f"{s.n}; choose n as a multiple of its layout")
    return s


def _solve(session, cap: int, target: float):
    from repro.api.callbacks import EarlyStopping
    rec = Recorder()
    with _span("bench.solve"):
        res = session.fit(max_epochs=cap, tol=0.0, callbacks=[
            EarlyStopping(monitor="gap", threshold=target), rec])
    return res, rec


def _reset(session) -> None:
    import numpy as np
    with _span("bench.reset"):
        session.load_state_dict({
            "alpha": np.zeros(session.n, np.float32),
            "v": np.zeros(session.d, np.float32), "epoch": 0})


def _start_trace():
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)


def _stop_trace(chips: int):
    import jax
    from chipbench import trace
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return trace.reduce_trace(trace.load(path), chips)


def _load_metric(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: dict, ctx: dict) -> dict:
    """Every per-layer metric of this cell whose reader finds something."""
    out = {}
    for m in cell["spec"]["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = _load_metric(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, flush=True)


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, fault=None,
             n: int | None = None) -> dict:
    """One run of `cell` (see the module doc); returns the result dict.

    `fault` and `n` are for the harness's own tests: a `faults.Fault`
    planted under the timed path, and a smaller example count."""
    devices = find_devices(cell["chips"], require_tpu)
    log(f"devices: {len(devices)} x {devices[0].device_kind}, found at "
        f"{time.perf_counter() - t_start:.3f}s")
    os.environ.setdefault("REPRO_CACHE_DIR", os.path.join(ROOT, ".repro_cache"))
    if require_tpu:
        _enable_compile_cache()
    import jax
    from chipbench import gen, reference, work

    cfgd, c = cell["config"], cell["cell"]
    n = n or cfgd["n"]
    target, cap = float(c["target_gap"]), int(c["max_epochs"])
    counter = CompileCounter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        data = gen.make_data(cfgd, n, seed)
        log(f"data: n={n} d={cfgd['d']} seed={seed} "
            f"drawn in {time.perf_counter() - t0:.3f}s")
        t0 = time.perf_counter()
        session = build_session(cell, data)
        log(f"session built in {time.perf_counter() - t0:.3f}s")
        if fault is not None:
            fault.wrap(session)
        session.epoch = _traced(session.epoch, "bench.epoch")
        session.gap = _traced(session.gap, "bench.gap")
        plan = session.solver_plan
        log(f"route: {plan.route if plan else None} "
            f"solver={session.spec.algo.local_solver} plan={plan}")
        # warm-up: an epoch and its gap check, the programs the window
        # runs
        t0 = time.perf_counter()
        _solve(session, 1, target)
        _reset(session)
        jax.block_until_ready((session.alpha, session.v))
        setup_s = time.perf_counter() - t_start
        log(f"warm-up took {time.perf_counter() - t0:.3f}s; "
            f"setup_s {setup_s:.3f}")
        before = counter.count
        if trace:
            _start_trace()
        solves = []
        with _span("bench.window"):
            t_w0 = time.perf_counter()
            while True:
                _reset(session)
                solves.append(_solve(session, cap, target))
                if time.perf_counter() - t_w0 >= seconds:
                    break
            window_s = time.perf_counter() - t_w0
        reading = _stop_trace(cell["chips"]) if trace else None
        compiles = counter.count - before
    for w in caught:
        log(f"warning: {w.category.__name__}: {w.message}")
    log(f"compilations inside the window: {compiles}")
    log(f"solves: {len(solves)} in {window_s:.3f}s, seconds each "
        f"{[round(r.wall_time, 4) for r, _ in solves]}")
    for key in ("epoch_s", "gap_s"):
        log(f"{key} per solve: "
            f"{[[round(e[key], 4) for e in rec.epochs] for _, rec in solves]}")

    used = devices[:cell["chips"]]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    first = solves[0][0]
    log(f"first solve: epochs={first.epochs} gaps per epoch="
        f"{[h.get('gap') for h in first.history]}")
    spec = session.spec
    lanes = spec.deployment.pods * spec.deployment.lanes
    chunks = spec.algo.chunks
    del session

    t0 = time.perf_counter()
    prob = reference.Problem(data, cfgd["lam"], cfgd["d"])
    readings = [reference.check_solve(prob, r.v, r.alpha) for r, _ in solves]
    worst = reference.worst(readings)
    failed = sum(1 for r, _ in solves
                 if r.diverged or not r.history[-1]["gap"] < target)
    limits = {k: float(c["limits"][k]) for k in reference.CHECKS}
    correct = failed == 0 and reference.within(worst, limits)
    log(f"reference check of {len(solves)} solves took "
        f"{time.perf_counter() - t0:.3f}s")

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(peak_mem)}
    if trace:
        ctx = {
            "config": cfgd, "n": n, "chips": cell["chips"], "lanes": lanes,
            "chunks": chunks, "window_s": window_s, "trace": reading,
            "solves": [{"epochs": r.epochs, "records": rec.epochs}
                       for r, rec in solves],
            "device_kind": devices[0].device_kind, "work": work,
        }
        metrics = per_layer(cell, ctx)
        dev["busy_s"] = reading.busy_s
        dev["window_s"] = reading.window_s
    else:
        metrics = {
            "time_to_gap_s": {"value": window_s / len(solves), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    checks = {k: {"value": worst[k], "limit": limits[k]}
              for k in reference.CHECKS}
    checks["failed"] = {"value": failed, "limit": 0}
    result = {"correct": bool(correct), "attempted": len(solves),
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = reading.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start)
    except NoChip as err:
        print(f"chipbench: {err}; the benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Chip smoke test: train the GLM solver through `Session` on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the mesh phases only

One chip runs three phases at published widths, each one warm-up epoch
then three timed epochs, with seeded synthetic data from the registry:

  (a) criteo-kaggle-sub, d=1,000,000, nnz=40, logistic, n cut to
      262,144: the replicated sparse Pallas kernel (v resident in VMEM);
  (b) higgs, d=28, n=1,048,576: the dense Pallas kernel;
  (c) (a) and (b) again with local_solver="xla" on the same data.

Four chips run a criteo-shaped data-parallel mesh-streamed `Session`
against the same run on the one-device sim path (bitwise under
deterministic=True), and a feature-sharded sparse run on the
pallas-sharded route (four model lanes) against its masked-XLA twin.

Fails when no TPU is present, when a Pallas phase's epoch program has
no `tpu_custom_call`, when the gap does not fall, when the kernel and
XLA routes' final gaps differ by more than 1e-3 relative, or when an
auto-fallback or planner-fallback warning fires.  Times printed are
smoke-test readings, not benchmark results.  The last line of stdout
is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GAP_RTOL = 1e-3
EPOCHS = 3
# example counts, cut from the published ones for run time
CRITEO_N, HIGGS_N = 262_144, 1_048_576
MESH_N, SHARDED_N = 131_072, 16_384
# published widths; the sharded phase's d is chosen so that v is over
# the replicated kernel's VMEM budget and the planner shards it
CRITEO_D, HIGGS_D, SHARDED_D, NNZ = 1_000_000, 28, 4_194_304, 40


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def _fallbacks_are_errors() -> None:
    # the engine's auto->xla misfit reroute and the planner's
    # infeasible/failed-plan fallbacks must not hide a kernel that
    # cannot run
    for pat in ("local_solver='auto'", "planner produced an infeasible",
                "solver planner failed"):
        warnings.filterwarnings("error", message=pat)


def _program_text(session) -> tuple[str, float]:
    import jax.numpy as jnp
    t0 = time.perf_counter()
    compiled = session._epoch_fn.lower(
        session.alpha, session.v, jnp.int32(session.epochs_done)).compile()
    return compiled.as_text(), time.perf_counter() - t0


def gap64(s) -> float:
    """The logistic duality gap of a resident Session, in float64 on the
    host: the device's f32 gap cannot resolve less than an ulp of the
    objective (about 6e-8 at 0.5), which near the optimum is a sizeable
    share of the gap itself."""
    import numpy as np
    v = np.asarray(s.v, np.float64)
    y = np.asarray(s.y, np.float64)
    b = np.asarray(s.alpha, np.float64) * y
    if s.sparse:
        m = np.sum(v[np.asarray(s.idx)] * np.asarray(s.val, np.float64),
                   axis=1)
    else:
        m = np.asarray(s.X, np.float64).T @ v

    def xlogx(t):
        return np.where(t > 1e-12, t * np.log(np.maximum(t, 1e-12)), 0.0)

    primal = np.mean(np.logaddexp(0.0, -y * m)) + 0.5 * s.lam * v @ v
    dual = -np.mean(xlogx(b) + xlogx(1.0 - b)) - 0.5 * s.lam * v @ v
    return float(primal - dual)


def run_phase(label: str, make_session, *, pallas: bool) -> dict:
    """Warm-up epoch + EPOCHS timed epochs through one Session."""
    import jax
    s = make_session()
    plan = s.solver_plan
    route = plan.route if plan is not None else s.spec.algo.local_solver
    text, compile_s = _program_text(s)
    kernel = "tpu_custom_call" in text
    s.epoch()                                     # warm-up
    gaps = [s.gap()]
    times = []
    for _ in range(EPOCHS):
        t0 = time.perf_counter()
        s.epoch()
        jax.block_until_ready(s.v)
        times.append(time.perf_counter() - t0)
        gaps.append(s.gap())
    log(f"[{label}] n={s.n} d={s.d} bucket={s.bplan.bucket} "
        f"route={route} tpu_custom_call={kernel} "
        f"compile_s={compile_s:.3f}")
    log(f"[{label}] smoke-test reading, not a benchmark: "
        f"s/epoch={[round(t, 4) for t in times]}")
    final = gap64(s)
    log(f"[{label}] gap after warm-up and each epoch: "
        f"{[float(f'{g:.6e}') for g in gaps]}; final in float64 "
        f"{final:.6e}")
    if pallas and not kernel:
        fail(f"{label}: no tpu_custom_call in the epoch program")
    if not gaps[-1] < gaps[0]:
        fail(f"{label}: the gap did not fall: {gaps}")
    return {"session": s, "gap": final, "route": route}


def compare_routes(label: str, kern: dict, xla: dict) -> None:
    import numpy as np
    dv = float(np.max(np.abs(np.asarray(kern["session"].v)
                             - np.asarray(xla["session"].v))))
    rel = abs(kern["gap"] - xla["gap"]) / abs(xla["gap"])
    log(f"[{label}] kernel vs xla: max|dv|={dv:.3e} "
        f"final float64 gaps {kern['gap']:.6e} vs {xla['gap']:.6e} "
        f"(rel diff {rel:.3e}, limit {GAP_RTOL})")
    if not rel <= GAP_RTOL:
        fail(f"{label}: kernel and xla final gaps differ by {rel:.3e}")


def one_chip() -> None:
    from repro.api import Session
    from repro.core import EngineConfig

    def cfg(solver):
        return EngineConfig.make(local_solver=solver)

    def criteo(solver):
        return lambda: Session("criteo-kaggle-sub", n=CRITEO_N,
                               d=CRITEO_D, bucket=8, cfg=cfg(solver))

    def higgs(solver):
        return lambda: Session("higgs", n=HIGGS_N, d=HIGGS_D, bucket=64,
                               cfg=cfg(solver))

    log(f"criteo-kaggle-sub: d={CRITEO_D} (published) and nnz={NNZ} "
        f"(the real 39, kernel-aligned); n cut from 45,840,617 to "
        f"{CRITEO_N} for run time")
    log(f"higgs: d={HIGGS_D} (published); n cut from 11,000,000 to "
        f"{HIGGS_N}")
    a = run_phase("a criteo pallas", criteo("auto"), pallas=True)
    if a["route"] != "pallas-replicated":
        fail(f"criteo ran route {a['route']}, not pallas-replicated")
    b = run_phase("b higgs pallas", higgs("auto"), pallas=True)
    ax = run_phase("c criteo xla", criteo("xla"), pallas=False)
    bx = run_phase("c higgs xla", higgs("xla"), pallas=False)
    compare_routes("criteo", a, ax)
    compare_routes("higgs", b, bx)


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api import Session
    from repro.core import EngineConfig, engine
    from repro.core.objectives import LOGISTIC
    from repro.data import make_sparse_classification, registry
    from repro.data.cache import ArrayFeed
    from repro.kernels import ops as kops
    from repro.launch import glm
    from repro.launch.mesh import make_host_mesh

    # (1) data-parallel, criteo-shaped, mesh-streamed vs one-device sim
    K, B = 4, 8
    ds = registry.get_dataset("criteo-kaggle-sub", n=MESH_N, d=CRITEO_D)
    cfg = EngineConfig.make(lanes=K, bucket=B, chunks=2,
                            partition="alltoall", deterministic=True,
                            compress_pod=False)
    mesh = make_host_mesh(data=K)
    s = Session((ds.idx, ds.val), ds.y, d=ds.d, cfg=cfg, streamed=True,
                mesh=mesh)
    scale = glm.scale_for_estimator(s, nnz=NNZ)
    sched = engine.MeshSchedule(s.n // B, pods=1, data=K, model=1,
                                seed=scale.seed)
    sim = engine.make_streamed_epoch(
        LOGISTIC, scale.engine_config(mesh), sched,
        ArrayFeed(np.asarray(ds.y), idx=ds.idx, val=ds.val, d=ds.d,
                  bucket=B), lam=s.lam)
    a_s, v_s = jnp.zeros(s.n), jnp.zeros(s.d)
    gaps = []
    for e in range(1 + EPOCHS):
        t0 = time.perf_counter()
        s.epoch()
        jax.block_until_ready(s.v)
        t_mesh = time.perf_counter() - t0
        t0 = time.perf_counter()
        a_s, v_s = sim(a_s, v_s, e)
        jax.block_until_ready(v_s)
        t_sim = time.perf_counter() - t0
        gaps.append(s.gap())
        log(f"[mesh data=4] epoch {e} smoke-test reading, not a "
            f"benchmark: mesh {t_mesh:.4f}s sim {t_sim:.4f}s "
            f"gap {gaps[-1]:.6e}")
    same_v = bool(np.array_equal(np.asarray(s.v), np.asarray(v_s)))
    same_a = bool(np.array_equal(np.asarray(s.alpha), np.asarray(a_s)))
    dv = float(np.max(np.abs(np.asarray(s.v) - np.asarray(v_s))))
    route = s.solver_plan.route if s.solver_plan else scale.local_solver
    log(f"[mesh data=4] route={route} n={s.n} d={s.d} "
        f"nnz={scale.nnz} mesh vs sim: v bitwise={same_v} "
        f"alpha bitwise={same_a} max|dv|={dv:.3e}")
    if not gaps[-1] < gaps[0]:
        fail(f"mesh data=4: the gap did not fall: {gaps}")

    # (2) feature-sharded sparse: pallas-sharded vs its masked-XLA twin
    M, n, d = 4, SHARDED_N, SHARDED_D
    (idx, val), y, _ = make_sparse_classification(n=n, d=d, nnz=NNZ,
                                                  seed=5, skew=1.1)
    mesh = make_host_mesh(data=1, model=M)
    route, why = kops.sparse_solver_plan(n, NNZ, d, B, model_lanes=M)
    if route != "pallas-sharded":
        fail(f"feature-sharded phase planned {route} ({why})")
    out = {}
    for solver in ("auto", "xla"):
        sc = glm.GLMScale("sharded", "sparse", n=n, d=d, nnz=NNZ,
                          bucket=B, chunks=1, feature_shard=True,
                          compress_pod=False, deterministic=True,
                          local_solver=solver, seed=5)
        ep = jax.jit(glm.make_sparse_epoch(sc, mesh))
        st = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
              jnp.zeros(n), jnp.zeros(d))
        t0 = time.perf_counter()
        text = ep.lower(*st, jnp.int32(0)).compile().as_text()
        compile_s = time.perf_counter() - t0
        kernel = "tpu_custom_call" in text
        times = []
        for e in range(1 + EPOCHS):
            t0 = time.perf_counter()
            st = ep(*st, jnp.int32(e))
            jax.block_until_ready(st[4])
            times.append(time.perf_counter() - t0)
        log(f"[sharded model=4 {solver}] tpu_custom_call={kernel} "
            f"compile_s={compile_s:.3f} smoke-test reading, not a "
            f"benchmark: s/epoch={[round(t, 4) for t in times[1:]]}")
        if solver == "auto" and not kernel:
            fail("feature-sharded phase: no tpu_custom_call")
        out[solver] = st
    same_v = bool(np.array_equal(np.asarray(out["auto"][4]),
                                 np.asarray(out["xla"][4])))
    dv = float(np.max(np.abs(np.asarray(out["auto"][4])
                             - np.asarray(out["xla"][4]))))
    vmax = float(np.max(np.abs(np.asarray(out["xla"][4]))))
    log(f"[sharded model=4] pallas-sharded vs masked xla: v bitwise="
        f"{same_v} max|dv|={dv:.3e} (max|v|={vmax:.3e})")
    if not dv <= GAP_RTOL * max(vmax, 1e-30):
        fail(f"feature-sharded phase: kernel and xla v differ by {dv}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
              f"this script runs on the chip only", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    _fallbacks_are_errors()
    log(f"device: {devs[0].device_kind} x{len(devs)}, jax "
        f"{jax.__version__}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

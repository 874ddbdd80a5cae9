"""Bring-up check: CP-ALS and CP-APR on a TPU through Mosaic-compiled kernels.

Drives the main path the way a user calls it — device ingest
(`alto.build_device`), the plan layer (`plan.make_plan`), and the CP-ALS /
CP-APR drivers on the Pallas backend — at the published shapes of two
FROSTT tensors (the paper's Table 1), synthesized from ``--seed``. Every
kernel result is checked against a reduction computed straight from the
COO entries (`coo_reference`: gathers and a segment sum, independent of
the ALTO stream and the oriented views), and every driver result against
the pure-jnp reference backend on the same chip:

* Phase A, CP-ALS at rank 16 on a nell-2-shaped tensor (12,092 × 9,184 ×
  28,818, 76,879,419 uniform coordinates, Gaussian values): each mode's
  MTTKRP on the plan's kernel, the recursive kernel forced on one mode,
  then fits, weights and factors after three sweeps.
* Phase B, CP-APR at rank 16 on an uber-shaped count tensor (183 × 24 ×
  1,140 × 1,717, 3,309,490 uniform coordinates, counts 1–9): each mode's
  fused Φ on the plan's kernel, the one-hot MTTKRP and the recursive and
  one-hot Φ forced on one mode, then factors, weights, KKT violations and
  log-likelihoods after three outer iterations.

``--chips 4`` runs only the row-sharded CP-ALS (`dist.cpd`) on a mesh of
four local chips, on the Phase A tensor: each mode's sharded MTTKRP
against the COO reduction, the placement of the views the driver runs
on, and its fits, weights and factors against a one-device run in the
same process.

Every failure — no TPU, a plan off the Pallas backend or in interpret
mode, an answer off the reference, an exception — exits non-zero before
the last line. On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Usage:  python chip_smoke.py [--seed N] [--chips {1,4}]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import operator
import os
import pathlib
import sys
import time

NELL2 = ((12_092, 9_184, 28_818), 76_879_419)      # FROSTT nell-2
UBER = ((183, 24, 1_140, 1_717), 3_309_490)         # FROSTT uber
RANK = 16
ALS_SWEEPS = 3
APR_OUTER = 3
# f32 sums of ~10^4 products per output row in two different orders:
# the kernels' element order against XLA's scatter-add.
MTTKRP_RTOL = 1e-4          # max |pallas - ref| / max |ref|, per mode
FIT_ATOL = 1e-3             # per sweep
ALS_RTOL = 1e-3             # CP-ALS weights and factors
APR_RTOL = 1e-3             # factors, weights, KKT, log-likelihood
COO_CHUNK = 1 << 21         # COO entries per step of the reference
EPS = 1e-10                 # Φ denominator floor (the CP-APR default)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def timed(fn):
    """(result, seconds) of ``fn()``, waiting for the device."""
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def check_close(got, want, tol: float, what: str) -> float:
    import jax.numpy as jnp
    check(bool(jnp.all(jnp.isfinite(got))), f"{what} has non-finite entries")
    err = rel_err(got, want)
    check(err <= tol, f"{what} off the reference: {err:.3e} > {tol}")
    return err


def peak_hbm_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.2f} GB"


def require_chip_plan(plan) -> None:
    """Every mode on compiled Pallas kernels: no reference, no interpret."""
    from repro.kernels import ops
    check(plan.backend == "pallas",
          f"plan backend is {plan.backend!r}, not 'pallas'")
    check(ops._auto_interpret(plan.interpret) is False,
          "Pallas kernels would run in interpret mode")
    for mp in plan.modes:
        log(f"  mode {mp.mode}: {mp.traversal.value}, r_block {mp.r_block}, "
            f"block_m {mp.block_m}, VMEM model "
            f"{mp.vmem_bytes / 2**20:.1f} MiB "
            f"(Φ {mp.phi_vmem_bytes / 2**20:.1f} MiB)")


def force_traversal(plan, mode: int, traversal):
    """``plan`` with one mode on ``traversal``, at the first tiling the
    plan layer offers for it (`candidate_mode_plans`)."""
    from repro.core import plan as plan_mod
    mp = next((c for c in plan_mod.candidate_mode_plans(
        plan.meta, mode, plan.rank) if c.traversal is traversal), None)
    check(mp is not None, f"no {traversal.value} tiling for mode {mode}")
    modes = list(plan.modes)
    modes[mode] = mp
    return dataclasses.replace(plan, modes=tuple(modes))


# ---------------------------------------------------------------------------
# The reference: straight from the COO entries
# ---------------------------------------------------------------------------

def coo_columns(x):
    """The COO tensor on the default device as lane-dense columns,
    zero-padded to whole `COO_CHUNK`s (value 0 at coordinate 0)."""
    import jax.numpy as jnp
    import numpy as np
    pad = -x.nnz % COO_CHUNK
    cols = tuple(jnp.asarray(np.pad(x.coords[:, m], (0, pad)))
                 for m in range(x.ndim))
    return cols, jnp.asarray(np.pad(x.values, (0, pad)))


@functools.cache
def _coo_reduce():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames="mode")
    def run(cols, vals, factors, B, mode):
        n_rows, R = factors[mode].shape

        def step(k, out):
            c = [jax.lax.dynamic_slice_in_dim(a, k * COO_CHUNK, COO_CHUNK)
                 for a in cols]
            v = jax.lax.dynamic_slice_in_dim(vals, k * COO_CHUNK, COO_CHUNK)
            krp = functools.reduce(operator.mul, (
                factors[m][c[m]] for m in range(len(cols)) if m != mode))
            if B is not None:
                v = v / jnp.maximum(jnp.sum(B[c[mode]] * krp, axis=1), EPS)
            return out + jax.ops.segment_sum(v[:, None] * krp, c[mode],
                                             num_segments=n_rows)

        return jax.lax.fori_loop(0, vals.shape[0] // COO_CHUNK, step,
                                 jnp.zeros((n_rows, R), factors[0].dtype))
    return run


def coo_reference(x, factors, phi: bool = False) -> dict:
    """Every mode's MTTKRP — or, with ``phi``, CP-APR's Φ with the mode's
    own factor as the model B — summed from the COO entries. The COO
    columns live on the device only while this runs."""
    import jax
    cols, vals = coo_columns(x)
    out, t = {}, time.perf_counter()
    for mode in range(x.ndim):
        out[mode] = _coo_reduce()(cols, vals, list(factors),
                                  factors[mode] if phi else None, mode=mode)
    jax.block_until_ready(out)
    log(f"  COO reference {'Φ' if phi else 'MTTKRP'}, {x.ndim} modes: "
        f"{time.perf_counter() - t:.1f} s (compile included)")
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def synthesize(shape, seed: int, count_data: bool):
    from repro.sparse import synthetic
    dims, nnz = shape
    t = time.perf_counter()
    x = synthetic.uniform_tensor(dims, nnz, seed=seed, count_data=count_data)
    log(f"  synthesized {dims}: {nnz} coordinates, {x.nnz} distinct, "
        f"{time.perf_counter() - t:.1f} s")
    return x


def ingest(x):
    from repro.core import alto
    at, s = timed(lambda: alto.build_device(x))
    reuse = tuple(round(r, 3) for r in at.meta.fiber_reuse)
    log(f"  build_device: {s:.1f} s (compile included), fiber reuse {reuse}")
    return at


def plan_and_views(at, **kw):
    from repro.core import plan as plan_mod
    t = time.perf_counter()
    plan = plan_mod.make_plan(at.meta, RANK, **kw)
    log(f"  make_plan: {time.perf_counter() - t:.2f} s, Π policy "
        f"{plan.pi_policy.value}")
    require_chip_plan(plan)
    views, s = timed(lambda: plan_mod.build_views(at, plan))
    log(f"  oriented views {sorted(views)}: {s:.1f} s")
    return plan, views


def run_mttkrp(plan, at, views, factors, mode: int, want, label: str):
    """One mode's MTTKRP on ``plan``, timed twice, checked against the
    COO reference."""
    from repro.core import plan as plan_mod
    run = functools.partial(plan_mod.execute_mttkrp, plan, at, views,
                            factors, mode)
    got, first = timed(run)
    _, again = timed(run)
    err = check_close(got, want, MTTKRP_RTOL, f"{label} MTTKRP mode {mode}")
    log(f"  {label} MTTKRP mode {mode} "
        f"({plan.modes[mode].traversal.value}): {first:.4f} s first call, "
        f"{again:.4f} s second; max rel error vs COO {err:.3e}")


def phase_a(seed: int, device) -> None:
    import jax
    from repro.core import cpals, heuristics, plan as plan_mod
    log("[A] CP-ALS, nell-2 shape, rank 16")
    x = synthesize(NELL2, seed, count_data=False)
    factors = cpals.init_factors(x.dims, RANK, seed=seed)
    want = coo_reference(x, factors)
    at = ingest(x)
    del x
    plan, views = plan_and_views(at)
    ref_plan = plan_mod.make_plan(at.meta, RANK, backend="reference")
    for mode in range(len(at.dims)):
        run_mttkrp(plan, at, views, factors, mode, want[mode], "pallas")
        # The XLA path the reference backend runs, for comparison.
        ref = jax.jit(functools.partial(plan_mod.execute_mttkrp, ref_plan,
                                        mode=mode))
        timed(lambda: ref(at, views, factors))
        _, s = timed(lambda: ref(at, views, factors))
        log(f"  reference-backend MTTKRP mode {mode}: {s:.4f} s second call")
    rec = force_traversal(plan, 2, heuristics.Traversal.RECURSIVE)
    run_mttkrp(rec, at, views, factors, 2, want[2], "forced")
    out = {}
    for name, p in (("pallas", plan), ("reference", ref_plan)):
        out[name], s = timed(lambda: cpals.cp_als(
            at, RANK, n_iters=ALS_SWEEPS, tol=0.0, seed=seed, plan=p,
            views=views))
        log(f"  cp_als {name}: {ALS_SWEEPS} sweeps {s:.1f} s (compile "
            f"included), fits {out[name].fits}")
    compare_als(out["pallas"], out["reference"], "reference")
    log(f"  peak HBM {peak_hbm_gb(device)}")


def compare_als(res, want, what: str) -> None:
    """Fits, weights and factors of two CP-ALS runs of the same sweeps."""
    check(len(res.fits) == len(want.fits) == ALS_SWEEPS, "sweep count")
    gap = max(abs(a - b) for a, b in zip(res.fits, want.fits))
    check(gap <= FIT_ATOL, f"CP-ALS fits off the {what}: {gap:.3e}")
    errs = {"lambda": check_close(res.lam, want.lam, ALS_RTOL, "lambda")}
    for n, (a, b) in enumerate(zip(res.factors, want.factors)):
        errs[f"factor {n}"] = check_close(a, b, ALS_RTOL, f"factor {n}")
    log(f"  max fit gap vs {what} {gap:.3e}; max rel error: " + ", ".join(
        f"{k} {v:.3e}" for k, v in errs.items()))


def phase_b(seed: int, device) -> None:
    import jax.numpy as jnp
    from repro.core import cpals, cpapr, heuristics, plan as plan_mod
    log("[B] CP-APR, uber shape, rank 16")
    x = synthesize(UBER, seed + 1, count_data=True)
    factors = [jnp.abs(f) + 0.1 for f in cpals.init_factors(
        x.dims, RANK, seed=seed)]
    want = coo_reference(x, factors, phi=True)
    want_mttkrp = coo_reference(x, factors)
    at = ingest(x)
    del x
    plan, views = plan_and_views(at)

    def run_phi(p, mode: int, label: str):
        run = functools.partial(plan_mod.execute_phi, p, at, views[mode],
                                factors[mode], mode, factors=factors,
                                eps=EPS)
        got, first = timed(run)
        _, again = timed(run)
        err = check_close(got, want[mode], MTTKRP_RTOL,
                          f"{label} Φ mode {mode}")
        log(f"  {label} Φ mode {mode} ({p.modes[mode].traversal.value}): "
            f"{first:.4f} s first call, {again:.4f} s second; max rel "
            f"error vs COO {err:.3e}")

    for mode in range(len(at.dims)):
        run_phi(plan, mode, "pallas")
    # The kernels the plan did not pick here, on the largest mode.
    mode = 3
    onehot = force_traversal(plan, mode, heuristics.Traversal.OUTPUT_ORIENTED)
    run_mttkrp(onehot, at, views, factors, mode, want_mttkrp[mode], "forced")
    run_phi(onehot, mode, "forced")
    run_phi(force_traversal(plan, mode, heuristics.Traversal.RECURSIVE),
            mode, "forced")

    ref_plan = plan_mod.make_plan(at.meta, RANK, backend="reference")
    params = cpapr.CpaprParams(k_max=APR_OUTER)
    out = {}
    for name, p in (("pallas", plan), ("reference", ref_plan)):
        out[name], s = timed(lambda: cpapr.cp_apr(
            at, RANK, params=params, seed=seed, plan=p, views=views,
            track_ll=True))
        log(f"  cp_apr {name}: {APR_OUTER} outer iterations {s:.1f} s "
            f"(compile included), {out[name].n_inner_total} inner, KKT "
            f"{out[name].kkt_violations}, log-likelihood "
            f"{out[name].log_likelihoods}")
    got, ref = out["pallas"], out["reference"]
    check(got.n_outer == ref.n_outer == APR_OUTER, "outer iteration count")
    errs = {f"factor {n}": rel_err(a, b)
            for n, (a, b) in enumerate(zip(got.factors, ref.factors))}
    errs["lambda"] = rel_err(got.lam, ref.lam)
    errs["kkt"] = rel_err(got.kkt_violations, ref.kkt_violations)
    errs["log-likelihood"] = rel_err(got.log_likelihoods,
                                     ref.log_likelihoods)
    log("  max rel error vs reference: " + ", ".join(
        f"{k} {v:.3e}" for k, v in errs.items())
        + f"; peak HBM {peak_hbm_gb(device)}")
    for k, v in errs.items():
        check(v <= APR_RTOL, f"CP-APR {k} off the reference: {v:.3e}")


def phase_mesh(seed: int, devices) -> None:
    import jax
    from repro.core import cpals
    from repro.dist import cpd
    log("[mesh] row-sharded CP-ALS on 4 chips, nell-2 shape, rank 16")
    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    x = synthesize(NELL2, seed, count_data=False)
    factors = cpals.init_factors(x.dims, RANK, seed=seed)
    want = coo_reference(x, factors)
    at = ingest(x)
    del x
    # Keep the plan and the placed views distributed_cp_als runs on.
    seen = {}
    place = cpd.shard_views

    def shard_views(plan, views):
        seen["plan"], seen["views"] = plan, place(plan, views)
        return seen["views"]

    cpd.shard_views = shard_views
    try:
        (lam, fs, fits), s = timed(lambda: cpd.distributed_cp_als(
            at, RANK, mesh, n_iters=ALS_SWEEPS, tol=0.0, seed=seed))
    finally:
        cpd.shard_views = place
    log(f"  distributed_cp_als: {s:.1f} s (compile included), fits {fits}")
    plan, views = seen["plan"], seen["views"]
    require_chip_plan(plan)
    for mode, v in views.items():
        for name in ("rows", "words", "values"):
            a = getattr(v, name)
            sizes = [sh.data.shape[0] for sh in a.addressable_shards]
            check(len({sh.device for sh in a.addressable_shards}) == 4
                  and len(set(sizes)) == 1,
                  f"view {mode} {name} is not split evenly over the 4 chips")
        log(f"  view {mode}: {v.rows.shape[0]} rows, {sizes} per chip")
        run_mttkrp(plan, at, views, factors, mode, want[mode], "sharded")
    del views, seen["views"]      # chip 0 is near full in the one-device run
    one, s = timed(lambda: cpals.cp_als(at, RANK, n_iters=ALS_SWEEPS,
                                        tol=0.0, seed=seed))
    log(f"  cp_als on 1 device: {s:.1f} s, fits {one.fits}")
    require_chip_plan(one.plan)
    compare_als(cpals.CpalsResult(lam=lam, factors=fs, fits=fits,
                                  n_iters=ALS_SWEEPS), one, "1-device run")
    for d in devices[:4]:
        log(f"  {d}: peak HBM {peak_hbm_gb(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the row-sharded CP-ALS on a 4-chip mesh")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no package at {src / 'repro'}; run "
                         "from a checkout of the repository")
    sys.path.insert(0, str(src))
    # Never fall back to the CPU: with the platform pinned, JAX fails at
    # start-up when it cannot reach a TPU.
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX runs on {devices[0].platform!r}, not a TPU")
    from repro import caches
    log(f"devices: {len(devices)} x {devices[0].device_kind}; jax "
        f"{jax.__version__}; compile cache {caches.use_compile_cache()}")

    t = time.perf_counter()
    if args.chips == 4:
        check(len(devices) >= 4, f"--chips 4 needs 4 chips, found "
                                 f"{len(devices)}")
        phase_mesh(args.seed, devices)
    else:
        phase_a(args.seed, devices[0])
        phase_b(args.seed, devices[0])
    log(f"all phases passed in {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

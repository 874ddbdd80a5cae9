"""Health guards: finite/monotonicity checks, rollback, plan degradation.

The drivers' numerical contract — monotone CP-ALS fit, finite factors —
holds for finite inputs, but a serving endpoint sees the other kind: a
tenant whose values carry NaN/Inf poisons every subsequent sweep, and in
a vmapped bucket its slot stays poisoned while bucket-mates keep paying
for its flops. The guards here are the detection half of the resilience
tentpole (`docs/resilience.md`); `core.faults` provides the injection
half and `launch.serve_cpd` the recovery ladders.

Two guard shapes, both opt-in (``guard=`` on `cpals.cp_als` /
`cpapr.cp_apr`, per-tenant inside `core.batched`):

* **finite guard** — one fused jitted all-finite reduction over the
  sweep's outputs (:func:`all_finite`, per-tenant
  :func:`tenants_finite`). Jitted so the check is a single tiny
  executable per pytree shape, not a host visit per array; the cost is
  one pass over the factors per sweep, which the serving benchmark pins
  at <= 5% of an unguarded sweep (`benchmarks/bench_serving.py`).
* **fit-monotonicity guard** — CP-ALS's fit sequence is monotone
  non-decreasing (PR 1 fixed the float32 cancellation that used to mask
  this); a drop beyond ``slack`` means the iterate left the admissible
  region (huge-but-finite poison, broken kernel) and the last good state
  is the answer to return. Host-side: the fit is already a host scalar.

On violation the drivers roll back to the last good (factors, lam) —
the previous iterate, retained by reference (arrays are immutable, a
rollback copies nothing) — stop, and report a :class:`HealthReport` on
the result instead of raising: a poisoned tenant gets a structured,
finite, degraded answer, not a stack trace.

:func:`degrade_plan` is the plan half of the recovery ladders: given a
plan and the exception it produced, return the next-softer plan (halve
``chunk_m`` on streaming OOM) or None when out of rungs. A kernel
failure has no softer plan: it is a defect to surface, not a fault to
heal.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod


# Divergence floor for the fit guard. The Kolda–Bader fit is <= 1 by
# construction and can dip mildly negative from a bad init, but a fit
# below this floor means an iterate left the admissible region with
# huge-but-FINITE magnitude (e.g. a ~1e30 poisoned entry: its Gram
# product overflows float32 to inf and XLA's SVD on a non-finite matrix
# can spin forever). The guard must catch that at the iteration that
# PRODUCED it — before the next sweep consumes it — so all-finite checks
# alone are not enough.
FIT_FLOOR = -1e8


@dataclasses.dataclass
class HealthReport:
    """Per-solve guard outcome, attached to CpalsResult/CpaprResult."""
    guarded: bool = True
    checks: int = 0               # guard evaluations run
    violations: int = 0           # non-finite or non-monotone events seen
    rolled_back: bool = False     # result is the last good iterate
    reason: str | None = None     # first violation, human-readable


def _inexact(arrays):
    return [jnp.asarray(a) for a in arrays
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)]


@jax.jit
def _all_finite_core(arrays):
    ok = jnp.asarray(True)
    for a in arrays:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(a)))
    return ok


def all_finite(arrays) -> bool:
    """True iff every inexact array is entirely finite (one fused jitted
    reduction; jit caches one executable per shape list)."""
    xs = _inexact(arrays)
    if not xs:
        return True
    return bool(_all_finite_core(xs))


@jax.jit
def _tenants_finite_core(arrays):
    ok = None
    for a in arrays:
        fin = jnp.all(jnp.isfinite(a.reshape(a.shape[0], -1)), axis=1)
        ok = fin if ok is None else jnp.logical_and(ok, fin)
    return ok


def tenants_finite(arrays) -> np.ndarray:
    """Per-tenant all-finite mask over stacked (cap, ...) leaves.

    The batched drivers call this once per sweep to quarantine poisoned
    slots without touching bucket-mates (vmap keeps tenants' lanes
    independent, so NaN cannot cross slots — but an unguarded bucket
    still burns ``n_iters`` full sweeps waiting for a fit that will
    never converge, and returns the poison to the caller).
    """
    xs = _inexact(arrays)
    if not xs:
        raise ValueError("tenants_finite needs at least one inexact array")
    return np.asarray(_tenants_finite_core(xs))


# ---------------------------------------------------------------------------
# Degradation ladder (plan half; the store half lives in serve_cpd)
# ---------------------------------------------------------------------------

def degrade_plan(plan: plan_mod.ExecutionPlan, exc: BaseException):
    """Next-softer plan after ``plan`` failed with ``exc``, or (None, None).

    One rung: streaming OOM → halve ``chunk_m`` (kept a multiple of the
    plan's largest block_m so chunk-parity alignment survives) and
    re-count chunks. Repeatable until one aligned chunk remains.

    A kernel that fails to build or dispatch gets no rung: swapping the
    Pallas backend for the jnp reference would serve a chip defect as a
    "degraded" answer, so the failure surfaces to the caller (a served
    request gets it as its structured error). Transient faults (I/O,
    allocator blips — `faults.is_transient`) should be *retried*, not
    degraded; callers check that first.
    """
    msg = str(exc)
    if plan.streaming is not None and "RESOURCE_EXHAUSTED" in msg:
        align = max(m.block_m for m in plan.modes)
        cm = plan.streaming.chunk_m
        new_cm = max(align, ((cm // 2) // align) * align)
        if new_cm < cm:
            streaming = dataclasses.replace(
                plan.streaming, chunk_m=new_cm,
                n_chunks=plan_mod.chunk_count(plan.meta, new_cm))
            return (dataclasses.replace(plan, streaming=streaming),
                    f"halved chunk_m {cm} -> {new_cm}")
    return None, None

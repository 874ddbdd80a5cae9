"""CP-ALS on ALTO tensors (paper Alg. 1).

The MTTKRP bottleneck (line 11) runs through the execution-plan layer
(`core.plan`): the plan resolves the paper's adaptive heuristics into a
concrete kernel per mode — pure-jnp reference traversals by default on CPU,
Pallas kernels (interpret on CPU, Mosaic on TPU) when the plan says so.
Mesh-bearing plans (``make_plan(..., mesh=)``) transparently shard the
MTTKRP over the mesh's devices (`repro.dist.cpd`); the fully distributed
driver (sharded Gram matrices too) is `dist.cpd.distributed_cp_als`.
Gram matrices, the pseudo-inverse solve, and normalization are dense JAX.
One full sweep over all modes is a single jitted function; the outer
iteration is a host loop with fit-based early stopping (as in the paper's
setup).

Fit tracking: the sweep returns the MTTKRP of its *last* mode update — the
one matrix for which ``<X, X̂> = Σ_r λ_r <A_n[:,r], M[:,r]>`` holds exactly
(every other mode's MTTKRP is stale by the end of the sweep, computed
against factors that were subsequently overwritten). The Kolda–Bader
residual identity ``||X-X̂||² = ||X||² + ||X̂||² − 2<X,X̂>`` is then
evaluated on the host in float64: near convergence the three terms agree to
~1e-5 relative, so combining them in float32 inside the jitted sweep left
cancellation noise (~1e-3 in fit units) larger than the per-iteration fit
gain and the reported fit sequence was not monotone even though the
iterates were.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import health as health_mod
from repro.core import ingest as ingest_mod
from repro.core import plan as plan_mod
from repro.core import telemetry
from repro.core.alto import AltoTensor, OrientedView
from repro.core.mttkrp import mttkrp_adaptive


@dataclasses.dataclass
class CpalsResult:
    lam: jnp.ndarray                 # (R,) component weights
    factors: list[jnp.ndarray]       # per-mode (I_n, R)
    fits: list[float]                # fit per iteration
    n_iters: int
    plan: plan_mod.ExecutionPlan | None = None
    # Guard outcome when the solve ran with guard=True (None otherwise).
    # rolled_back=True means the returned state is the last good iterate
    # before a non-finite or fit-regressing sweep (core.health).
    health: health_mod.HealthReport | None = None


def init_factors(dims: Sequence[int], rank: int, seed: int = 0,
                 dtype=jnp.float32) -> list[jnp.ndarray]:
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims))
    return [jax.random.uniform(k, (I, rank), dtype=dtype)
            for k, I in zip(keys, dims)]


def build_views(at: AltoTensor,
                plan: plan_mod.ExecutionPlan | None = None
                ) -> dict[int, OrientedView]:
    """Oriented views only for modes the plan routes that way
    (keeps the single-copy property for high-reuse tensors). Served
    from the process-wide view cache (`core.views`): device-built by
    default, one build per (tensor, mode) shared across drivers."""
    if plan is None:
        plan = plan_mod.make_plan(at.meta, rank=1)  # traversal is rank-free
    return plan_mod.build_views(at, plan)


def _sweep(plan, at: AltoTensor, views, factors, lam, gram_fn=None):
    """One CP-ALS sweep over all modes.

    Returns (factors, lam, M_last): M_last is the final mode's MTTKRP, the
    only one consistent with the returned factors — the host-side fit
    evaluation depends on it being fresh, not reused from earlier modes.

    ``gram_fn`` overrides the Gram computation (default dense AᵀA); the
    distributed driver passes `dist.cpd.sharded_gram` so Grams are
    row-sharded and psum-combined. MTTKRP placement needs no hook — a
    mesh-bearing plan already routes it through the sharded merge.
    """
    # f32 at full precision: the TPU's default matmul rounds its inputs
    # to bf16, three significant digits of every factor.
    hi = jax.lax.Precision.HIGHEST
    gram = gram_fn if gram_fn is not None else (
        lambda A: jnp.matmul(A.T, A, precision=hi))
    N = len(factors)
    grams = [gram(A) for A in factors]
    M = None
    for n in range(N):
        V = None
        for m in range(N):
            if m == n:
                continue
            V = grams[m] if V is None else V * grams[m]
        M = mttkrp_adaptive(at, views, factors, n, plan=plan)  # (I_n, R)
        A = jnp.matmul(M, jnp.linalg.pinv(V), precision=hi)
        lam = jnp.linalg.norm(A, axis=0)
        lam = jnp.where(lam > 0, lam, 1.0)
        A = A / lam[None, :]
        factors = list(factors)
        factors[n] = A
        grams[n] = gram(A)
    return factors, lam, M


def _fit_host(M_last, factors, lam, normX2: float) -> float:
    """Kolda–Bader fit from sweep-consistent state, in host float64."""
    if normX2 == 0.0:
        # All-zero (or empty) tensor: the zero model is exact. Without
        # this the fit divides by sqrt(0) and reports NaN forever.
        return 1.0
    n = len(factors) - 1
    fs = [np.asarray(A, np.float64) for A in factors]
    lam64 = np.asarray(lam, np.float64)
    M = np.asarray(M_last, np.float64)
    inner = float(((fs[n] * M).sum(axis=0) * lam64).sum())
    V = np.ones((lam64.size, lam64.size))
    for A in fs:
        V *= A.T @ A
    norm_model2 = float((np.outer(lam64, lam64) * V).sum())
    resid2 = max(normX2 + norm_model2 - 2.0 * inner, 0.0)
    return float(1.0 - np.sqrt(resid2) / np.sqrt(normX2))


def cp_als(at: AltoTensor, rank: int, n_iters: int = 50, tol: float = 1e-5,
           seed: int = 0, views: dict[int, OrientedView] | None = None,
           factors: list[jnp.ndarray] | None = None,
           plan: plan_mod.ExecutionPlan | None = None,
           gram_fn=None, tune: str = "off",
           warm_start=None, guard: bool = False,
           guard_slack: float = 1e-3) -> CpalsResult:
    """CP-ALS driver. ``tune`` ("off"|"auto"|"force"|"search") selects measured
    plans from the autotuner's persistent store — the tensor data is in
    hand here, so a store miss under "auto"/"force" runs the measured
    tuner (`core.autotune`) before the first sweep.

    ``warm_start`` seeds the sweep from a previous solve — a
    `CpalsResult`, ``(lam, factors)``, or a factor list — with rows for
    newly-grown extents filled from the seeded init
    (`ingest.grow_factors`). After `ingest.append_delta` this turns the
    per-delta cost into sweeps-from-converged instead of from-scratch.

    ``guard=True`` runs the per-sweep health guards (`core.health`): a
    jitted all-finite check over the sweep's outputs plus the host-side
    fit-monotonicity check (a drop beyond ``guard_slack``), rolling back
    to the last good (factors, λ) and stopping on violation. On finite
    inputs the guard changes nothing — the returned trajectory stays
    bitwise identical to an unguarded run.
    """
    with telemetry.span("cpals.call"):
        with telemetry.span("cpals.prepare"):
            if factors is not None and warm_start is not None:
                raise ValueError("pass factors= or warm_start=, not both")
            if warm_start is not None:
                lam_w, factors = ingest_mod.grow_factors(
                    warm_start, at.dims, rank, seed=seed,
                    dtype=at.values.dtype)
                if lam_w is not None:
                    # Fold the previous weights in so the first sweep
                    # starts at the previous MODEL, not its
                    # column-normalized shadow.
                    factors = list(factors)
                    factors[0] = factors[0] * lam_w[None, :]
            if plan is None:
                plan = plan_mod.make_plan(at.meta, rank, tune=tune, at=at)
            elif plan.rank != rank:
                raise ValueError(f"plan was built for rank {plan.rank}, "
                                 f"cp_als called with rank {rank}")
            if at.meta.nnz == 0:
                # Degenerate tenant input (a public serving endpoint WILL
                # see these): the zero model is the exact decomposition.
                # Well-defined result — zero factors, zero weights, fit
                # 1.0 — not an exception or a NaN fit trajectory.
                dtype = at.values.dtype
                return CpalsResult(lam=jnp.zeros((rank,), dtype),
                                   factors=[jnp.zeros((I, rank), dtype)
                                            for I in at.dims],
                                   fits=[1.0], n_iters=0, plan=plan)
            if factors is None:
                factors = init_factors(at.dims, rank, seed=seed,
                                       dtype=at.values.dtype)
            if views is None:
                views = plan_mod.build_views(at, plan)
            lam = jnp.ones((rank,), dtype=at.values.dtype)
            normX2 = float((np.asarray(at.values, np.float64) ** 2).sum())

            sweep_fn = functools.partial(_sweep, plan, gram_fn=gram_fn)
            # Streaming (out-of-core) plans keep the sweep a host loop:
            # the chunked executors are themselves host loops over
            # per-chunk jitted calls, and a host-resident stream is not a
            # jit operand. The dense algebra still runs the same XLA
            # kernels per op.
            if plan.streaming is not None:
                sweep = sweep_fn
            else:
                def sweep(*args):
                    with telemetry.traced("cpals.trace"):
                        return sweep_fn(*args)
                sweep = jax.jit(sweep)
        report = health_mod.HealthReport() if guard else None
        fits: list[float] = []
        prev_fit = -np.inf
        it = 0
        for it in range(1, n_iters + 1):
            good = (factors, lam)
            # Holds any trace, lowering or compile the call needs.
            with telemetry.span("cpals.dispatch", it=it):
                factors, lam, M_last = sweep(at, views, factors, lam)
            pd = faults.fire("cpals.nan")
            if pd is not None:
                # Poison the LAST factor: the next sweep's first mode
                # update consumes it through the Gram products, so an
                # unguarded run propagates the poison everywhere (the
                # realistic hazard).
                poison = pd.get("value", float("nan"))
                factors = list(factors)
                factors[-1] = factors[-1].at[0, 0].set(poison)
            # The host round trip: waits for the sweep, copies to float64.
            with telemetry.span("cpals.fit", it=it):
                fit = _fit_host(M_last, factors, lam, normX2)
            if guard:
                report.checks += 1
                reason = None
                if not np.isfinite(fit) or not health_mod.all_finite(
                        [*factors, lam, M_last]):
                    reason = f"non-finite sweep output at iteration {it}"
                elif fit < health_mod.FIT_FLOOR:
                    # Huge-but-finite iterate: must be stopped HERE — its
                    # Gram products overflow the next sweep
                    # (health.FIT_FLOOR)
                    reason = f"fit diverged to {fit:.3e} at iteration {it}"
                elif fits and fit < fits[-1] - guard_slack:
                    reason = (f"fit regressed {fits[-1]:.6f} -> {fit:.6f} "
                              f"at iteration {it}")
                if reason is not None:
                    report.violations += 1
                    report.rolled_back = True
                    report.reason = reason
                    factors, lam = good
                    it -= 1
                    break
            fits.append(fit)
            if abs(fit - prev_fit) < tol:
                break
            prev_fit = fit
        return CpalsResult(lam=lam, factors=list(factors), fits=fits,
                           n_iters=it, plan=plan, health=report)


def reconstruct_values(coords: jnp.ndarray, lam: jnp.ndarray,
                       factors: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Model values at given coordinates (for residual checks)."""
    prod = lam[None, :].astype(factors[0].dtype)
    out = jnp.broadcast_to(prod, (coords.shape[0], lam.shape[0]))
    for m, A in enumerate(factors):
        out = out * A[coords[:, m]]
    return jnp.sum(out, axis=-1)

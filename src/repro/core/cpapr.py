"""CP-APR multiplicative updates on ALTO tensors (paper Alg. 2 / Alg. 5).

Poisson tensor decomposition for non-negative count data. The Φ (model
update) kernel — >99% of runtime per the paper §5.3 — runs through the
generic ALTO row-reduction engine with the paper's two adaptive choices:

  * traversal: recursive (Temp + pull reduction) vs output-oriented
    (sorted segment reduction), per fiber reuse (§4.2);
  * Π policy: ALTO-PRE (precompute the (M, R) Khatri-Rao rows once per
    outer iteration) vs ALTO-OTF (recompute per inner iteration), per the
    memory heuristic (§4.3).

The inner multiplicative-update loop (Alg. 2 lines 7-14) is a lax.scan with
freeze-on-convergence masking so the whole mode update jits.

Mesh-bearing plans (``plan.make_plan(..., mesh=)``) shard the Φ row
reduction over the mesh via `repro.dist.cpd.sharded_phi` — same driver
code, per-device oriented segment reduction plus psum carry merge.
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
from repro.core import heuristics
from repro.core import ingest as ingest_mod
from repro.core import plan as plan_mod
from repro.core import telemetry
from repro.core.alto import AltoTensor, OrientedView, delinearize
from repro.core.mttkrp import krp_rows


@dataclasses.dataclass(frozen=True)
class CpaprParams:
    """Algorithmic parameters of Alg. 2 (defaults follow the paper / ttb)."""
    k_max: int = 50          # max outer iterations
    l_max: int = 10          # max inner iterations (paper uses 10)
    tau: float = 1e-4        # KKT convergence tolerance
    kappa: float = 1e-2      # inadmissible-zero avoidance adjustment
    kappa_tol: float = 1e-10 # potential inadmissible zero threshold
    eps_div: float = 1e-10   # minimum divisor


@dataclasses.dataclass
class CpaprResult:
    lam: jnp.ndarray
    factors: list[jnp.ndarray]
    kkt_violations: list[float]    # per outer iteration (max over modes)
    log_likelihoods: list[float]
    n_outer: int
    n_inner_total: int
    pi_policy: str
    traversals: list[str]
    plan: plan_mod.ExecutionPlan | None = None
    # Guard outcome when the solve ran with guard=True (core.health).
    health: health_mod.HealthReport | None = None


def init_factors(dims: Sequence[int], rank: int, seed: int = 0,
                 total: float = 1.0, dtype=jnp.float32):
    """Random positive factors, columns 1-normalized; λ carries the mass."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims))
    factors = []
    for k, I in zip(keys, dims):
        A = jax.random.uniform(k, (I, rank), dtype=dtype, minval=0.1,
                               maxval=1.1)
        factors.append(A / jnp.sum(A, axis=0, keepdims=True))
    lam = jnp.full((rank,), total / rank, dtype=dtype)
    return lam, factors


def _phi(rows, vals, krp, B, eps):
    """Per-nonzero Φ contribution: (v / max(<B[i],krp>, ε)) · krp."""
    denom = jnp.maximum(jnp.sum(B[rows] * krp, axis=-1), eps)
    return (vals / denom)[:, None] * krp


def _mode_update(at: AltoTensor, view: OrientedView | None, mode: int,
                 lam, factors, phi_prev, first_outer: bool,
                 pre_pi: bool, p: CpaprParams,
                 plan: plan_mod.ExecutionPlan):
    """One full Alg. 2 mode update (lines 4-15), jit-able."""
    with telemetry.traced("cpapr.trace", mode=mode,
                          first_outer=first_outer):
        A = factors[mode]
        # Line 4: inadmissible-zero adjustment (skipped on the first
        # outer iteration).
        if first_outer:
            S = jnp.zeros_like(A)
        else:
            S = jnp.where((A < p.kappa_tol) & (phi_prev > 1.0), p.kappa, 0.0)
        B0 = (A + S) * lam[None, :]                       # line 5: B = (A+S)Λ

        if pre_pi:
            # Line 6 (Π, M×R rows) in the element order the plan's traversal
            # will consume (oriented modes read the view-permuted stream).
            oriented = (view is not None
                        and heuristics.is_oriented(
                            plan.modes[mode].traversal))
            words = view.words if oriented else at.words
            coords = delinearize(at.meta.enc, words)
            pi = krp_rows(coords, factors, mode)

        def phi_of(B):                                    # lines 8-9
            return plan_mod.execute_phi(
                plan, at, view, B, mode,
                factors=None if pre_pi else factors,
                pi=pi if pre_pi else None, eps=p.eps_div)

        def inner(carry, _):
            B, done, n_inner = carry
            Phi = phi_of(B)                               # line 8
            kkt = jnp.max(jnp.abs(jnp.minimum(B, 1.0 - Phi)))  # line 9
            now_done = done | (kkt < p.tau)
            # Line 13, frozen after convergence.
            B_new = jnp.where(now_done, B, B * Phi)
            n_inner = n_inner + jnp.where(now_done, 0, 1)
            return (B_new, now_done, n_inner), (Phi, kkt)

        (B, done, n_inner), (phis, kkts) = jax.lax.scan(
            inner, (B0, jnp.asarray(False), jnp.asarray(0, jnp.int32)),
            None, length=p.l_max)
        Phi_last = phis[-1]

        lam_new = jnp.sum(B, axis=0)                      # line 15: λ = eᵀB
        lam_new = jnp.where(lam_new > 0, lam_new, 1.0)
        A_new = B / lam_new[None, :]
        # Mode converged iff no inner update was applied.
        mode_converged = n_inner == 0
        kkt_first = kkts[0]
        return A_new, lam_new, Phi_last, mode_converged, n_inner, kkt_first


def _mode_update_streaming(at: AltoTensor, view, mode: int,
                           lam, factors, phi_prev, first_outer: bool,
                           pre_pi: bool, p: CpaprParams,
                           plan: plan_mod.ExecutionPlan):
    """Out-of-core twin of `_mode_update`: host inner loop, chunked Φ.

    A streaming plan's Φ is a host loop over chunks (`kernels.ops`), so
    the jitted `lax.scan` inner loop is replaced by a python loop with
    the IDENTICAL semantics: Φ is computed from the current B, the KKT
    check freezes B on convergence, and the loop breaks where the scan
    would only recompute Φ of a frozen B (the same value — the masked
    scan runs `l_max` steps, the break just skips the no-op tail). Under
    ALTO-PRE there is no full-stream Π precompute — the chunked executor
    rebuilds each chunk's Π rows on device (`execute_phi(pre=True)`),
    elementwise-identical, so the result stays bitwise (see
    `docs/out-of-core.md` for the cost-semantics shift).
    """
    A = factors[mode]
    if first_outer:
        S = jnp.zeros_like(A)
    else:
        S = jnp.where((A < p.kappa_tol) & (phi_prev > 1.0), p.kappa, 0.0)
    B = (A + S) * lam[None, :]

    Phi = None
    n_inner = 0
    kkt_first = None
    for _ in range(p.l_max):
        Phi = plan_mod.execute_phi(plan, at, view, B, mode,
                                   factors=factors, eps=p.eps_div,
                                   pre=pre_pi)
        kkt = jnp.max(jnp.abs(jnp.minimum(B, 1.0 - Phi)))
        if kkt_first is None:
            kkt_first = kkt
        if bool(kkt < p.tau):
            break               # frozen: further steps recompute this Phi
        B = B * Phi
        n_inner += 1

    lam_new = jnp.sum(B, axis=0)
    lam_new = jnp.where(lam_new > 0, lam_new, 1.0)
    A_new = B / lam_new[None, :]
    return (A_new, lam_new, Phi, n_inner == 0,
            jnp.asarray(n_inner, jnp.int32), kkt_first)


def log_likelihood(at: AltoTensor, lam, factors, eps=1e-10):
    """Poisson log-likelihood Σ x·log(m) − Σ m (columns 1-normalized)."""
    coords = delinearize(at.meta.enc, at.words)
    prod = jnp.broadcast_to(lam[None, :], (coords.shape[0], lam.shape[0]))
    for m, A in enumerate(factors):
        prod = prod * A[coords[:, m]]
    model = jnp.maximum(jnp.sum(prod, axis=-1), eps)
    ll = jnp.sum(at.values * jnp.log(model))          # padding: v=0 rows
    return ll - jnp.sum(lam)


def cp_apr(at: AltoTensor, rank: int, params: CpaprParams | None = None,
           seed: int = 0, pi_policy: str | None = None,
           views: dict[int, OrientedView] | None = None,
           track_ll: bool = False,
           plan: plan_mod.ExecutionPlan | None = None,
           tune: str = "off", warm_start=None,
           guard: bool = False) -> CpaprResult:
    """CP-APR MU driver (Alg. 2). `pi_policy`: None=adaptive|'pre'|'otf'.

    ``warm_start`` seeds (λ, factors) from a previous solve — a
    `CpaprResult`, ``(lam, factors)``, or a factor list — clamped
    positive and column-renormalized, with rows for newly-grown extents
    filled small-positive (`ingest.grow_factors(positive=True)`); after
    `ingest.append_delta` the MU loop resumes near the converged state.

    All kernel routing (traversal per mode, Π policy, jnp vs Pallas) comes
    from ``plan``; the default plan resolves the paper heuristics with the
    reference backend on CPU and the Pallas backend on TPU. Oriented
    views come from the process-wide cache (`core.views` via
    `plan.build_views`): device-built by default, shared with CP-ALS and
    the autotuner — a tensor decomposed by both drivers materializes
    each mode's view once. ``tune``
    ("off"|"auto"|"force"|"search") swaps the analytic plan for a measured one
    from the autotuner's persistent store (`core.autotune`), timing
    candidates here if the store misses — the tensor data is in hand.
    CP-APR tunes against the fused Φ kernel (objective="phi"), its >99%
    bottleneck, under a store key distinct from CP-ALS's MTTKRP plans.
    """
    p = params or CpaprParams()
    N = len(at.dims)
    with telemetry.span("cpapr.call"):
        if at.meta.nnz == 0:
            # Degenerate tenant input: the zero model maximizes the
            # Poisson likelihood of an all-zero tensor (λ → 0). Return a
            # well-defined converged result instead of iterating on NaNs.
            dtype = at.values.dtype
            return CpaprResult(
                lam=jnp.zeros((rank,), dtype),
                factors=[jnp.zeros((I, rank), dtype) for I in at.dims],
                kkt_violations=[0.0], log_likelihoods=[], n_outer=0,
                n_inner_total=0, pi_policy=pi_policy or "otf",
                traversals=["oriented"] * N,
                plan=plan)
        with telemetry.span("cpapr.prepare"):
            total = float(jnp.sum(at.values))
            if warm_start is not None:
                lam, factors = ingest_mod.grow_factors(
                    warm_start, at.dims, rank, seed=seed,
                    dtype=at.values.dtype, positive=True)
                if lam is None:
                    lam = jnp.full((rank,), total / rank,
                                   dtype=at.values.dtype)
            else:
                lam, factors = init_factors(at.dims, rank, seed=seed,
                                            total=total,
                                            dtype=at.values.dtype)

            if plan is None:
                plan = plan_mod.make_plan(at.meta, rank, tune=tune,
                                          tune_objective="phi", at=at)
            elif plan.rank != rank:
                raise ValueError(f"plan was built for rank {plan.rank}, "
                                 f"cp_apr called with rank {rank}")
            if pi_policy is None:
                pi_policy = plan.pi_policy.value
            pre_pi = pi_policy == "pre"

            if views is None:
                views = plan_mod.build_views(at, plan)
            traversals = [plan.modes[n].traversal.value
                          if (n in views and heuristics.is_oriented(
                              plan.modes[n].traversal))
                          else "recursive" for n in range(N)]

            if plan.streaming is not None:
                # Out-of-core: the chunked Φ executor is a host loop over
                # per-chunk jitted calls, and a HostStream is not a jit
                # operand.
                update = _mode_update_streaming
            else:
                update = jax.jit(_mode_update,
                                 static_argnames=("mode", "first_outer",
                                                  "pre_pi", "p", "plan"))

        phi_prev = [jnp.zeros_like(A) for A in factors]
        report = health_mod.HealthReport() if guard else None
        kkt_hist: list[float] = []
        ll_hist: list[float] = []
        n_inner_total = 0
        outer = 0
        for outer in range(1, p.k_max + 1):
            with telemetry.span("cpapr.outer", outer=outer):
                # Last good state for the guard's rollback (references
                # only — the arrays are immutable, nothing is copied).
                good = (lam, list(factors), list(phi_prev))
                all_converged = True
                kkt_max = 0.0
                for n in range(N):
                    # Holds any trace, lowering or compile the call needs.
                    with telemetry.span("cpapr.dispatch", mode=n,
                                        first_outer=outer == 1):
                        A, lam, phi_n, conv, n_inner, kkt = update(
                            at, views.get(n), n, lam, factors, phi_prev[n],
                            first_outer=(outer == 1), pre_pi=pre_pi, p=p,
                            plan=plan)
                    pd = faults.fire("cpapr.nan")
                    if pd is not None:
                        A = A.at[0, 0].set(pd.get("value", float("nan")))
                    factors = list(factors)
                    factors[n] = A
                    phi_prev[n] = phi_n
                    # The host reads that wait for the mode's update.
                    with telemetry.span("cpapr.sync", mode=n):
                        n_inner_total += int(n_inner)
                        all_converged &= bool(conv)
                        kkt_max = max(kkt_max, float(kkt))
                if guard:
                    report.checks += 1
                    if not np.isfinite(kkt_max) or not health_mod.all_finite(
                            [lam, *factors]):
                        report.violations += 1
                        report.rolled_back = True
                        report.reason = (f"non-finite mode update at outer "
                                         f"iteration {outer}")
                        lam, factors, phi_prev = good
                        outer -= 1
                        break
                kkt_hist.append(kkt_max)
                if track_ll:
                    ll_hist.append(float(log_likelihood(at, lam, factors)))
                if all_converged:                          # lines 17-19
                    break
        return CpaprResult(lam=lam, factors=factors,
                           kkt_violations=kkt_hist,
                           log_likelihoods=ll_hist, n_outer=outer,
                           n_inner_total=n_inner_total,
                           pi_policy=pi_policy, traversals=traversals,
                           plan=plan, health=report)

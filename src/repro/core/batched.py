"""Batched CP-ALS / CP-APR: one executable sweeps a whole shape class.

Tenants that :func:`shapeclass.classify` buckets into the same class
share an `AltoEncoding`, a padded stream length, and a canonical
`AltoMeta` — so their `AltoTensor` / `OrientedView` pytrees have
identical treedefs and leaf shapes. Stacking K tenants leaf-wise gives
one pytree with a leading tenant axis, and ``jax.vmap`` of the EXISTING
single-tensor sweeps (`cpals._sweep`, `cpapr._mode_update`) runs all K
through one jitted executable. Nothing about the per-tensor math is
reimplemented here; this module only stacks, masks, and unstacks.

Per-tenant convergence: a converged tenant cannot leave the bucket (its
bucket-mates still need the executable's shapes), so its state freezes —
the batched step computes the update for every slot and applies
``jnp.where(active, new, old)`` per leaf. Frozen tenants burn flops but
never drift: their factors, λ, and (for CP-APR) Φ memory are bit-frozen
at the converged iterate while neighbours keep sweeping.

Exactness of bucketing (why a tenant's answer matches its solo run):
each tenant enters with its solo init embedded into the class dims
(`embed_factors` — extra rows are exact zeros). Padded factor rows
receive no stream contributions (pad elements carry value 0, so their
row updates add exact IEEE zeros) and a zero row of the MTTKRP stays a
zero factor row through the pinv solve; zero rows also contribute
nothing to Gram matrices, λ, or the fit. The batched trajectory is
therefore the solo trajectory with zeros appended — sliced back to real
dims on exit.

The batched sweeps run the reference (pure-jnp) backend: those
traversals are ordinary vmappable jnp programs. The Pallas kernels are
not vmap-wired (Mosaic batching rules are carry-over work; see
docs/known-issues.md) — the canonical meta's ``fiber_reuse = 1.0``
already routes every mode to the output-oriented jnp family.

Trace accounting mirrors `alto.device_ingest_traces`: `sweep_traces()`
counts actual jit traces of the batched cores, and the serving tests pin
"one trace per shape class, not per tenant" with before/after deltas.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cpals, cpapr, faults
from repro.core import health as health_mod
from repro.core import plan as plan_mod
from repro.core import telemetry
from repro.core.alto import AltoTensor, OrientedView


# Jitted batched cores, keyed on (algorithm, plan[, statics]); the
# stacked input shapes are a pure function of the plan's meta + bucket
# capacity, so one entry per key is one XLA executable. Guarded like the
# ingest cache — serving drivers hit this from worker threads.
_SWEEP_FNS: dict[tuple, object] = {}
_SWEEP_LOCK = threading.Lock()


def sweep_traces() -> dict[str, int]:
    """Trace counts of the batched cores (per algorithm). The serving
    acceptance test asserts the delta is bounded by the number of shape
    classes, never the number of tenants."""
    c = telemetry.counts()
    return {k: c.get(f"batched.{k}.trace", 0) for k in ("als", "apr")}


def sweep_cache_clear() -> None:
    with _SWEEP_LOCK:
        _SWEEP_FNS.clear()


def _cached_sweep_fn(key: tuple, build):
    with _SWEEP_LOCK:
        fn = _SWEEP_FNS.get(key)
        if fn is None:
            fn = _SWEEP_FNS[key] = build()
        return fn


def stack_tenants(items: Sequence):
    """Leaf-wise stack of same-class pytrees → one pytree, leading K axis.

    Works for `AltoTensor`, view dicts, factor lists — any pytree whose
    members agree on treedef and static aux (which same-class tenants
    do by construction: they share the canonical meta).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)


def embed_factors(factors: Sequence[jnp.ndarray],
                  class_dims: Sequence[int]) -> list[jnp.ndarray]:
    """Embed real-dims factor matrices into class dims with zero rows.

    The zero rows are the exactness anchor: they stay exactly zero
    through every CP-ALS/CP-APR update (see module docstring), so the
    embedded trajectory IS the solo trajectory.
    """
    out = []
    for A, D in zip(factors, class_dims):
        pad = int(D) - A.shape[0]
        if pad < 0:
            raise ValueError(f"factor rows {A.shape[0]} exceed class "
                             f"dim {D}")
        out.append(jnp.pad(A, ((0, pad), (0, 0))) if pad else A)
    return out


def _slice_factors(factors, dims):
    return [A[:int(I)] for A, I in zip(factors, dims)]


# ---------------------------------------------------------------------------
# Batched CP-ALS
# ---------------------------------------------------------------------------

def _als_sweep_fn(plan: plan_mod.ExecutionPlan):
    """One jitted batched ALS sweep: vmap of `cpals._sweep` + freeze mask."""
    @telemetry.traced("batched.als.trace")
    def core(at, views, factors, lam, active):
        new_factors, new_lam, M_last = jax.vmap(
            functools.partial(cpals._sweep, plan))(at, views, factors, lam)
        a3 = active[:, None, None]
        factors = [jnp.where(a3, nf, f)
                   for nf, f in zip(new_factors, factors)]
        lam = jnp.where(active[:, None], new_lam, lam)
        return factors, lam, M_last

    return _cached_sweep_fn(("als", plan), lambda: jax.jit(core))


@dataclasses.dataclass
class BatchedCpalsResult:
    results: list[cpals.CpalsResult]   # per tenant, factors at REAL dims
    n_sweeps: int                      # batched sweeps executed
    # quarantined[i]: tenant i's update went non-finite under guard=True;
    # its result is the last good iterate, frozen from that sweep on.
    quarantined: list[bool] = dataclasses.field(default_factory=list)


def batched_cp_als(ats: Sequence[AltoTensor],
                   views: Sequence[dict[int, OrientedView]],
                   real_dims: Sequence[tuple[int, ...]],
                   rank: int, *,
                   plan: plan_mod.ExecutionPlan,
                   n_iters: int = 50, tol: float = 1e-5,
                   seeds: Sequence[int] | None = None,
                   init_factors: Sequence[list[jnp.ndarray]] | None = None,
                   capacity: int | None = None,
                   guard: bool = False) -> BatchedCpalsResult:
    """CP-ALS over K same-class tenants through ONE jitted executable.

    ``ats``/``views`` are the canonicalized class members (all sharing
    ``plan.meta``); ``real_dims[i]`` are tenant i's true extents, used
    for the solo-equivalent init and to slice the answer back out.
    ``capacity`` (≥ K) fixes the stacked leading axis: short buckets are
    filled with inactive replicas of tenant 0, so every bucket of the
    class reuses one trace regardless of how full it is. Per-tenant
    convergence uses the same host-side Kolda–Bader fit and ``tol`` as
    solo `cp_als`; a converged tenant freezes while bucket-mates sweep.

    ``guard=True`` adds the per-tenant quarantine (`core.health`): after
    each sweep a jitted per-slot all-finite mask flags tenants whose
    update went non-finite (vmap keeps lanes independent, so the poison
    never crosses slots); a flagged tenant rolls back to its previous
    iterate and freezes through the SAME where-mask machinery that
    freezes converged tenants — bucket-mates keep sweeping, bitwise
    unaffected, and the offender's result carries ``quarantined=True``.
    """
    K = len(ats)
    if K == 0:
        return BatchedCpalsResult(results=[], n_sweeps=0)
    if len(views) != K or len(real_dims) != K:
        raise ValueError("ats/views/real_dims length mismatch")
    for at in ats:
        if at.meta != plan.meta:
            raise ValueError("tenant meta differs from plan meta — "
                             "canonicalize (shapeclass.canonicalize_tensor) "
                             "before batching")
    cap = K if capacity is None else int(capacity)
    if cap < K:
        raise ValueError(f"capacity {cap} < bucket size {K}")
    class_dims = plan.meta.dims
    dtype = ats[0].values.dtype
    if seeds is None:
        seeds = [0] * K
    if init_factors is None:
        init_factors = [cpals.init_factors(real_dims[i], rank,
                                           seed=int(seeds[i]), dtype=dtype)
                        for i in range(K)]
    factors_k = [embed_factors(f, class_dims) for f in init_factors]

    # Fill to capacity with inactive replicas of slot 0 (frozen from the
    # first sweep, discarded on exit) so K never perturbs trace shapes.
    fill = cap - K
    at_b = stack_tenants(list(ats) + [ats[0]] * fill)
    views_b = stack_tenants(list(views) + [views[0]] * fill)
    factors_b = stack_tenants(factors_k + [factors_k[0]] * fill)
    lam_b = jnp.ones((cap, rank), dtype=dtype)

    normX2 = [float((np.asarray(at.values, np.float64) ** 2).sum())
              for at in ats]
    active = np.zeros(cap, bool)
    active[:K] = True
    quarantined = np.zeros(cap, bool)
    fits: list[list[float]] = [[] for _ in range(K)]
    prev = np.full(K, -np.inf)
    sweep = _als_sweep_fn(plan)
    n_sweeps = 0
    for _ in range(n_iters):
        faults.inject("batched.sweep")
        good_f, good_l = factors_b, lam_b
        factors_b, lam_b, M_last = sweep(at_b, views_b, factors_b, lam_b,
                                         jnp.asarray(active))
        n_sweeps += 1
        pd = faults.fire("batched.nan")
        if pd is not None:
            t = int(pd.get("tenant", 0))
            poison = pd.get("value", float("nan"))
            factors_b = list(factors_b)
            factors_b[-1] = factors_b[-1].at[t, 0, 0].set(poison)
        if guard:
            ok = health_mod.tenants_finite([*factors_b, lam_b, M_last])
            bad = active & ~ok
        else:
            bad = np.zeros(cap, bool)
        for i in range(K):
            if not active[i] or bad[i]:
                continue
            fit = cpals._fit_host(M_last[i], [A[i] for A in factors_b],
                                  lam_b[i], normX2[i])
            if guard and (not np.isfinite(fit)
                          or fit < health_mod.FIT_FLOOR):
                # Huge-but-finite poison: this slot must be quarantined
                # NOW — its Grams overflow the next vmapped sweep and a
                # non-finite SVD can spin forever (health.FIT_FLOOR).
                bad[i] = True
                continue
            fits[i].append(fit)
            if abs(fit - prev[i]) < tol:
                active[i] = False
            prev[i] = fit
        if guard and bad.any():
            # Roll the poisoned slots back to their previous iterate
            # and freeze them — the same where-mask that freezes
            # converged tenants, so bucket-mates are untouched.
            b3 = jnp.asarray(bad)[:, None, None]
            factors_b = [jnp.where(b3, g, f)
                         for g, f in zip(good_f, factors_b)]
            lam_b = jnp.where(jnp.asarray(bad)[:, None], good_l, lam_b)
            quarantined |= bad
            active &= ~bad
        if not active[:K].any():
            break

    results = []
    for i in range(K):
        fac = _slice_factors([A[i] for A in factors_b], real_dims[i])
        results.append(cpals.CpalsResult(
            lam=lam_b[i], factors=fac, fits=fits[i],
            n_iters=len(fits[i]), plan=plan))
    return BatchedCpalsResult(results=results, n_sweeps=n_sweeps,
                              quarantined=[bool(q)
                                           for q in quarantined[:K]])


# ---------------------------------------------------------------------------
# Batched CP-APR
# ---------------------------------------------------------------------------

def _apr_update_fn(plan: plan_mod.ExecutionPlan, mode: int,
                   first_outer: bool, pre_pi: bool, p: cpapr.CpaprParams):
    """One jitted batched CP-APR mode update: vmap of `cpapr._mode_update`
    + per-tenant freeze of factors[mode], λ, and the Φ memory."""
    @telemetry.traced("batched.apr.trace")
    def core(at, view, lam, factors, phi_prev, active):
        def upd(t, v, l, f, ph):
            return cpapr._mode_update(t, v, mode, l, f, ph,
                                      first_outer=first_outer,
                                      pre_pi=pre_pi, p=p, plan=plan)
        A_new, lam_new, Phi, conv, n_inner, kkt = jax.vmap(upd)(
            at, view, lam, factors, phi_prev)
        a3 = active[:, None, None]
        A = jnp.where(a3, A_new, factors[mode])
        lam = jnp.where(active[:, None], lam_new, lam)
        Phi = jnp.where(a3, Phi, phi_prev)
        n_inner = jnp.where(active, n_inner, 0)
        return A, lam, Phi, conv, n_inner, kkt

    key = ("apr", plan, mode, bool(first_outer), bool(pre_pi), p)
    return _cached_sweep_fn(key, lambda: jax.jit(core))


@dataclasses.dataclass
class BatchedCpaprResult:
    results: list[cpapr.CpaprResult]   # per tenant, factors at REAL dims
    n_outer: int                       # batched outer iterations executed
    # Same contract as BatchedCpalsResult.quarantined (guard=True only).
    quarantined: list[bool] = dataclasses.field(default_factory=list)


def batched_cp_apr(ats: Sequence[AltoTensor],
                   views: Sequence[dict[int, OrientedView]],
                   real_dims: Sequence[tuple[int, ...]],
                   rank: int, *,
                   plan: plan_mod.ExecutionPlan,
                   params: cpapr.CpaprParams | None = None,
                   seeds: Sequence[int] | None = None,
                   capacity: int | None = None,
                   guard: bool = False) -> BatchedCpaprResult:
    """CP-APR over K same-class tenants through one executable per mode.

    Same stacking/masking contract as `batched_cp_als`. A tenant freezes
    (factors, λ, AND its Φ inadmissible-zero memory) once every mode
    reports KKT convergence, exactly the solo driver's stopping rule.
    The jit key includes the static mode/first_outer flags, so a class
    costs 2·N traces for N-mode tensors — still independent of K and of
    how many buckets the class serves.
    """
    K = len(ats)
    if K == 0:
        return BatchedCpaprResult(results=[], n_outer=0)
    for at in ats:
        if at.meta != plan.meta:
            raise ValueError("tenant meta differs from plan meta — "
                             "canonicalize before batching")
    p = params or cpapr.CpaprParams()
    cap = K if capacity is None else int(capacity)
    if cap < K:
        raise ValueError(f"capacity {cap} < bucket size {K}")
    N = len(plan.meta.dims)
    class_dims = plan.meta.dims
    dtype = ats[0].values.dtype
    pre_pi = plan.pi_policy.value == "pre"
    if seeds is None:
        seeds = [0] * K

    lam_k, factors_k = [], []
    for i in range(K):
        total = float(jnp.sum(ats[i].values))
        lam_i, fac_i = cpapr.init_factors(real_dims[i], rank,
                                          seed=int(seeds[i]), total=total,
                                          dtype=dtype)
        lam_k.append(lam_i)
        factors_k.append(embed_factors(fac_i, class_dims))

    fill = cap - K
    at_b = stack_tenants(list(ats) + [ats[0]] * fill)
    views_b = {n: stack_tenants([v[n] for v in views]
                                + [views[0][n]] * fill)
               for n in views[0]}
    factors_b = stack_tenants(factors_k + [factors_k[0]] * fill)
    lam_b = stack_tenants(lam_k + [lam_k[0]] * fill)
    phi_b = [jnp.zeros_like(A) for A in factors_b]

    active = np.zeros(cap, bool)
    active[:K] = True
    quarantined = np.zeros(cap, bool)
    kkt_hist: list[list[float]] = [[] for _ in range(K)]
    n_inner_tot = np.zeros(cap, np.int64)
    n_outer_seen = np.zeros(K, np.int32)
    n_outer = 0
    for outer in range(1, p.k_max + 1):
        faults.inject("batched.sweep")
        good = (lam_b, list(factors_b), list(phi_b))
        n_outer = outer
        conv_all = np.ones(cap, bool)
        kkt_max = np.zeros(cap)
        for n in range(N):
            fn = _apr_update_fn(plan, n, outer == 1, pre_pi, p)
            A, lam_b, Phi, conv, n_inner, kkt = fn(
                at_b, views_b.get(n), lam_b, factors_b, phi_b[n],
                jnp.asarray(active))
            pd = faults.fire("batched.nan")
            if pd is not None:
                t = int(pd.get("tenant", 0))
                A = A.at[t, 0, 0].set(pd.get("value", float("nan")))
            factors_b = list(factors_b)
            factors_b[n] = A
            phi_b[n] = Phi
            conv_all &= np.asarray(conv)
            n_inner_tot += np.asarray(n_inner, np.int64)
            kkt_max = np.maximum(kkt_max, np.asarray(kkt))
        if guard:
            ok = health_mod.tenants_finite([lam_b, *factors_b])
            ok &= np.isfinite(kkt_max)
            bad = active & ~ok
            if bad.any():
                g_lam, g_fac, g_phi = good
                b3 = jnp.asarray(bad)[:, None, None]
                factors_b = [jnp.where(b3, g, f)
                             for g, f in zip(g_fac, factors_b)]
                phi_b = [jnp.where(b3, g, f)
                         for g, f in zip(g_phi, phi_b)]
                lam_b = jnp.where(jnp.asarray(bad)[:, None], g_lam, lam_b)
                quarantined |= bad
                active &= ~bad
        for i in range(K):
            if active[i]:
                kkt_hist[i].append(float(kkt_max[i]))
                n_outer_seen[i] = outer
        newly_done = active & conv_all
        active &= ~newly_done
        if not active[:K].any():
            break

    results = []
    for i in range(K):
        fac = _slice_factors([A[i] for A in factors_b], real_dims[i])
        results.append(cpapr.CpaprResult(
            lam=lam_b[i], factors=fac, kkt_violations=kkt_hist[i],
            log_likelihoods=[], n_outer=int(n_outer_seen[i]),
            n_inner_total=int(n_inner_tot[i]),
            pi_policy=plan.pi_policy.value,
            traversals=[plan.modes[n].traversal.value for n in range(N)],
            plan=plan))
    return BatchedCpaprResult(results=results, n_outer=n_outer,
                              quarantined=[bool(q)
                                           for q in quarantined[:K]])

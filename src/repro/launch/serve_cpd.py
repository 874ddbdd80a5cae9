"""Multi-tenant decomposition serving: COO submissions → bucketed CPD.

  PYTHONPATH=src python -m repro.launch.serve_cpd --tenants 12 --rank 4

The request path the ROADMAP's production workload needs — thousands of
tenant tensors decomposed concurrently without thousands of compiles:

  submit(COO)                   thread-safe admission, classified into a
    │                           shape class (`core.shapeclass.classify`)
    ▼
  per-class queue               tenants accumulate until a bucket fills
    │                           (or `process()` flushes a partial bucket,
    ▼                           padded with inactive slots)
  pad → ingest → views          `shapeclass.pad_to_class` then the PR 5
    │                           device ingest (`alto.build_device`,
    │                           compute_reuse off — the canonical meta
    ▼                           overrides reuse anyway) and the unified
  batched sweep                 view cache (`core.views` via
    │                           `plan.build_views`); one vmapped
    ▼                           executable per class (`core.batched`)
  per-tenant result             factors sliced back to real dims, fit /
                                KKT trajectory, wall-clock latency

Zero-warmup dispatch: the class plan comes from `plan.make_class_plan`
with ``tune="auto"`` — the autotuner's persistent store is keyed on the
canonical class meta (`autotune.class_plan_key`), so a class ever tuned
by ANY process on this machine dispatches measurement-free, and the
first bucket of a class warms every later bucket, tenant, and restart.

Degenerate tenants (empty or single-nonzero COO) are first-class: they
admit, bucket, and return well-defined results (an empty tensor yields
zero factors and fit 1.0) instead of raising mid-queue.

Resilience (PR 9, `docs/resilience.md`): the service is a *runtime*,
not just a queue. A background worker loop (:meth:`CpdService.serve` /
:meth:`CpdService.shutdown`) drains the queues continuously and
survives any request's failure; every failure mode maps to a structured
:class:`CpdResponse` — never a crash, never a poisoned bucket-mate:

* transient faults (I/O blips, allocator RESOURCE_EXHAUSTED —
  `faults.is_transient`) are retried with exponential backoff;
* plan failures walk the degradation ladder (`health.degrade_plan`):
  streaming OOM halves ``chunk_m``, and a stored plan that fails at
  dispatch is evicted from the autotune store and replaced by the
  heuristic plan. A Pallas kernel that fails under the heuristic plan
  is a defect, returned as the request's error — never re-served on
  the reference backend;
* a bucket that still fails is *bisected*: each member re-runs solo,
  and an offender that fails alone too is quarantined with a
  structured error while its bucket-mates' results are unaffected;
* ``guard=True`` (default) runs the per-sweep health guards
  (`core.health`) — a tenant whose iterates go non-finite is rolled
  back to its last good state and marked quarantined in-place;
* per-request deadlines (``deadline_s``) and a deadline-aware partial-
  bucket flush (``max_wait_s``) bound tail latency.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import threading
import time
from typing import Callable, Sequence

import numpy as np

from repro import caches
from repro.core import alto, batched, faults, shapeclass
from repro.core import autotune as autotune_mod
from repro.core import cpals as cpals_mod
from repro.core import cpapr as cpapr_mod
from repro.core import health as health_mod
from repro.core import ingest as ingest_mod
from repro.core import plan as plan_mod
from repro.core import stream as stream_mod
from repro.sparse.tensor import SparseTensor


@dataclasses.dataclass
class CpdRequest:
    """One tenant's admitted submission."""
    request_id: int
    x: SparseTensor
    sc: shapeclass.ShapeClass
    seed: int
    submitted_at: float
    deadline_s: float | None = None


@dataclasses.dataclass
class DeltaRequest:
    """An incremental update against a previously served result."""
    request_id: int
    base_id: int                   # request id of the retained base result
    coords: np.ndarray
    values: np.ndarray
    policy: str
    submitted_at: float
    deadline_s: float | None = None


@dataclasses.dataclass
class CpdResponse:
    request_id: int
    sc: shapeclass.ShapeClass
    result: object                 # CpalsResult | CpaprResult | None
    latency_s: float               # submit → result wall clock
    bucket_size: int               # real tenants in the bucket served with
    # Resilience outcome. ``error`` is None on success; a quarantined or
    # deadline-expired request gets the reason here (its ``result`` may
    # still carry the last good, rolled-back iterate — degraded but
    # finite — or be None when nothing was computed). ``degraded`` marks
    # results served through a ladder rung (halved chunks, evicted
    # store plan); ``retries`` counts transient-fault
    # re-attempts absorbed on this request's behalf.
    error: str | None = None
    degraded: bool = False
    retries: int = 0

    @property
    def ok(self) -> bool:
        return self.error is None


class CpdService:
    """Request-queue front end over the shape-class batched layer.

    ``submit`` is thread-safe and cheap (classify + enqueue); the heavy
    path is ``process()``, which drains every class queue bucket-by-
    bucket. ``capacity`` fixes each bucket's stacked width — partial
    buckets are padded with inactive slots so a class compiles exactly
    once no matter how its tenants arrive (`core.batched` docstring).

    Run it caller-driven (call ``process()`` yourself) or as a runtime:
    ``serve()`` starts a daemon worker that drains continuously, and
    ``wait(request_id)`` blocks until that request's response lands.
    """

    def __init__(self, rank: int, algorithm: str = "cp_als", *,
                 capacity: int = 8, n_partitions: int | None = None,
                 n_iters: int = 25, tol: float = 1e-4,
                 tune: str = "auto", backend: str | None = None,
                 retain_results: int = 128, guard: bool = True,
                 max_wait_s: float | None = None, max_retries: int = 2,
                 retry_base_s: float = 0.02,
                 search_budget: int | None = None,
                 search_budgets: dict | None = None):
        if algorithm not in ("cp_als", "cp_apr"):
            raise ValueError(f"unknown algorithm {algorithm!r}")
        self.rank = int(rank)
        self.algorithm = algorithm
        self.capacity = int(capacity)
        self.n_partitions = (shapeclass.DEFAULT_PARTITIONS
                             if n_partitions is None else int(n_partitions))
        self.n_iters = int(n_iters)
        self.tol = float(tol)
        self.tune = tune
        self.backend = backend
        # Budgeted-search warm start (tune="search"): a class-keyed run
        # budget per ShapeClass, falling back to the flat default. High
        # -traffic classes deserve more measurements than one-off shapes;
        # None everywhere = the search engine's own default (25% of the
        # feasible space). Ignored under the other tune modes.
        self.search_budget = (None if search_budget is None
                              else int(search_budget))
        self.search_budgets = dict(search_budgets or {})
        self.guard = bool(guard)
        # Deadline-aware flush: a partial bucket whose oldest request
        # has waited this long is flushed without waiting for capacity.
        self.max_wait_s = None if max_wait_s is None else float(max_wait_s)
        self.max_retries = int(max_retries)
        self.retry_base_s = float(retry_base_s)
        self._lock = threading.Lock()
        self._queues: dict[shapeclass.ShapeClass, collections.deque] = {}
        self._plans: dict[shapeclass.ShapeClass,
                          plan_mod.ExecutionPlan] = {}
        self._next_id = 0
        self._latencies: list[float] = []
        self._tenants_done = 0
        self._buckets_run = 0
        self._busy_s = 0.0
        # rid -> (x | None, AltoTensor | None, result, sc): every served
        # result is retained (LRU-bounded) so `submit_delta` can append
        # against it and warm-start from its factors. The AltoTensor slot
        # starts None (the bucketed path pads to the class shape, which
        # the delta path does NOT want) and is filled lazily on the first
        # delta; delta responses retain their merged tensor directly, so
        # delta CHAINS run the jitted merge with no rebuild anywhere.
        self.retain_results = int(retain_results)
        self._retained: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        self._delta_queue: collections.deque = collections.deque()
        self._deltas_done = 0
        # Resilience counters (all under self._lock; see stats()).
        self._retries = 0
        self._backoff_s = 0.0
        self._quarantined_tenants = 0
        self._degraded_dispatches = 0
        self._plan_evictions = 0
        self._deadline_expired = 0
        self._errors = 0
        # Completed responses for wait(): bounded mailbox, popped on
        # delivery; notified under the service lock.
        self._responses: "collections.OrderedDict[int, CpdResponse]" = \
            collections.OrderedDict()
        self._resp_cond = threading.Condition(self._lock)
        # Worker-loop state.
        self._worker: threading.Thread | None = None
        self._stop_evt = threading.Event()
        self._worker_recoveries = 0

    # -- admission --------------------------------------------------------

    def submit(self, x: SparseTensor, seed: int = 0, *,
               deadline_s: float | None = None) -> int:
        """Admit one COO submission; returns its request id.

        Classification is pure metadata (dims/nnz rounding) — no device
        work happens under the lock, so admission never blocks on a
        bucket in flight. ``deadline_s`` bounds submit→serve wall clock:
        a request still queued past its deadline is answered with a
        structured error instead of being served late.
        """
        sc = shapeclass.classify(x, self.rank,
                                 n_partitions=self.n_partitions)
        req = CpdRequest(request_id=-1, x=x, sc=sc, seed=int(seed),
                         submitted_at=time.perf_counter(),
                         deadline_s=deadline_s)
        with self._lock:
            req.request_id = self._next_id
            self._next_id += 1
            self._queues.setdefault(sc, collections.deque()).append(req)
        return req.request_id

    def submit_delta(self, base_id: int, coords, values,
                     policy: str = "sum", *,
                     deadline_s: float | None = None) -> int:
        """Admit a COO delta against a previously served result; returns
        the new request id. The base must still be retained (see
        ``retain_results``). Deltas skip class bucketing entirely: they
        are latency-sensitive singletons whose jit cache is already warm
        (the merge core keys on the static merge meta, the sweep on the
        tensor meta), so `process()` serves them solo with
        ``warm_start=`` from the base's factors.
        """
        if policy not in ingest_mod.POLICIES:
            raise ValueError(f"policy {policy!r}: expected one of "
                             f"{ingest_mod.POLICIES}")
        coords = np.asarray(coords, dtype=np.int32)
        values = np.asarray(values)
        req = DeltaRequest(request_id=-1, base_id=int(base_id),
                           coords=coords, values=values, policy=policy,
                           submitted_at=time.perf_counter(),
                           deadline_s=deadline_s)
        with self._lock:
            if int(base_id) not in self._retained:
                raise KeyError(f"request {base_id} is not retained "
                               f"(never served, or aged out of the "
                               f"{self.retain_results}-entry LRU)")
            req.request_id = self._next_id
            self._next_id += 1
            self._delta_queue.append(req)
        return req.request_id

    def pending(self) -> int:
        with self._lock:
            return (sum(len(q) for q in self._queues.values())
                    + len(self._delta_queue))

    def shape_classes(self) -> list[shapeclass.ShapeClass]:
        with self._lock:
            return list(self._queues)

    # -- worker loop (the runtime half) -----------------------------------

    def serve(self, poll_s: float = 0.005) -> None:
        """Start the background worker: a daemon thread that drains the
        queues continuously (full buckets immediately, partial ones once
        ``max_wait_s`` is exceeded). Idempotent — a live worker is left
        alone. The loop is self-healing: an exception that escapes a
        request path is counted (``worker_recoveries``) and the loop
        keeps serving everyone else."""
        with self._lock:
            if self._worker is not None and self._worker.is_alive():
                return
            self._stop_evt = threading.Event()
            self._worker = threading.Thread(
                target=self._worker_loop, args=(float(poll_s),),
                name="cpd-serve-worker", daemon=True)
            self._worker.start()

    def _worker_loop(self, poll_s: float) -> None:
        stop = self._stop_evt
        while not stop.is_set():
            try:
                served = self.process(flush=False)
            except Exception:
                # Every request path converts failures into structured
                # responses, so anything landing here is a runtime bug —
                # survive it, count it, keep serving other tenants.
                with self._lock:
                    self._worker_recoveries += 1
                served = []
            if not served:
                stop.wait(poll_s)
        # Final drain: shutdown(wait=True) must leave no admitted
        # request unanswered, including partial buckets.
        try:
            self.process(flush=True)
        except Exception:
            with self._lock:
                self._worker_recoveries += 1

    def shutdown(self, wait: bool = True, timeout: float = 60.0) -> None:
        """Stop the worker. ``wait=True`` joins it — the worker drains
        everything still queued (flush) before exiting, so a clean
        shutdown never drops an admitted request."""
        with self._lock:
            worker = self._worker
        if worker is None:
            return
        self._stop_evt.set()
        if wait:
            worker.join(timeout)
        with self._lock:
            if self._worker is worker:
                self._worker = None

    @property
    def serving(self) -> bool:
        with self._lock:
            return self._worker is not None and self._worker.is_alive()

    def wait(self, request_id: int,
             timeout: float | None = None) -> CpdResponse:
        """Block until ``request_id``'s response lands (worker mode) and
        return it. Raises TimeoutError past ``timeout`` seconds."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._resp_cond:
            while request_id not in self._responses:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(f"request {request_id} not served "
                                       f"within {timeout}s")
                self._resp_cond.wait(remaining)
            return self._responses.pop(request_id)

    def _deliver(self, responses: Sequence[CpdResponse]) -> None:
        if not responses:
            return
        with self._resp_cond:
            for r in responses:
                self._responses[r.request_id] = r
            # Bound the mailbox: nobody waiting on very old responses.
            cap = max(64, 4 * self.retain_results)
            while len(self._responses) > cap:
                self._responses.popitem(last=False)
            self._resp_cond.notify_all()

    # -- class plan (store-backed, shared by every bucket of the class) ---

    def _class_plan(self, sc, at_canonical=None):
        with self._lock:
            plan = self._plans.get(sc)
        if plan is not None:
            return plan
        plan = plan_mod.make_class_plan(
            sc, backend=self.backend, tune=self.tune,
            tune_objective=self._objective(),
            at=at_canonical,
            search_budget=self.search_budgets.get(sc, self.search_budget))
        with self._lock:
            return self._plans.setdefault(sc, plan)

    def _objective(self) -> str:
        return "phi" if self.algorithm == "cp_apr" else "mttkrp"

    # -- the resilience ladder --------------------------------------------

    def _with_ladder(self, sc, run: Callable[[], object]):
        """Run ``run()`` under the recovery ladder; returns
        ``(out, retries, degraded)`` or raises when out of rungs.

        Rungs, in order, per failure: (1) transient fault
        (`faults.is_transient`) → retry with exponential backoff, up to
        ``max_retries``; (2) `health.degrade_plan` → swap the class plan
        (halved ``chunk_m`` on streaming OOM) and re-run; (3) a stored
        plan failing at dispatch → evict it from the autotune store,
        rebuild the heuristic plan (``tune="off"``), re-run once. ``run``
        must read the current class plan each attempt so rung swaps take
        effect.
        """
        retries = 0
        degraded = False
        evicted = False
        while True:
            try:
                return run(), retries, degraded
            except Exception as exc:  # noqa: BLE001 — ladder sorts them
                if faults.is_transient(exc) and retries < self.max_retries:
                    retries += 1
                    delay = self.retry_base_s * (2 ** (retries - 1))
                    with self._lock:
                        self._retries += 1
                        self._backoff_s += delay
                    time.sleep(delay)
                    continue
                with self._lock:
                    plan = self._plans.get(sc) if sc is not None else None
                if plan is not None:
                    new_plan, why = health_mod.degrade_plan(plan, exc)
                    if new_plan is not None:
                        with self._lock:
                            self._plans[sc] = new_plan
                            self._degraded_dispatches += 1
                        degraded = True
                        continue
                    if not evicted and self.tune != "off":
                        self._evict_class_plan(sc, plan)
                        evicted = True
                        degraded = True
                        continue
                raise

    def _evict_class_plan(self, sc, failed_plan) -> None:
        """Evict-and-retune rung: the stored (measured) plan failed at
        dispatch — drop its store entry so no later process trusts it,
        and fall back to the heuristic plan for this class."""
        key = autotune_mod.class_plan_key(sc, failed_plan.backend,
                                          objective=self._objective())
        autotune_mod.evict(key)
        fresh = plan_mod.make_class_plan(sc, backend=self.backend,
                                         tune="off")
        with self._lock:
            self._plans[sc] = fresh
            self._plan_evictions += 1

    def _error_response(self, req, sc, message: str,
                        result=None) -> CpdResponse:
        with self._lock:
            self._errors += 1
        return CpdResponse(request_id=req.request_id, sc=sc,
                           result=result,
                           latency_s=time.perf_counter() - req.submitted_at,
                           bucket_size=0, error=message)

    def _expired(self, req) -> bool:
        return (req.deadline_s is not None
                and time.perf_counter() - req.submitted_at > req.deadline_s)

    # -- the heavy path ---------------------------------------------------

    def _prepare(self, req: CpdRequest, plan):
        """pad → device ingest → canonical meta → cached views."""
        xp = shapeclass.pad_to_class(req.x, req.sc)
        # Reuse stats are data-dependent (they would fork the meta per
        # tenant) and the canonical meta pins reuse to 1.0 regardless —
        # skip the fiber count entirely.
        at = alto.build_device(xp, n_partitions=req.sc.n_partitions,
                               compute_reuse=False)
        at = shapeclass.canonicalize_tensor(at, req.sc)
        views = plan_mod.build_views(at, plan)
        return at, views

    def _run_bucket(self, sc, reqs: Sequence[CpdRequest]) -> list[CpdResponse]:
        t0 = time.perf_counter()
        # The first bucket of a never-seen class may tune (store miss
        # with tune="auto"); give the tuner a canonical representative.
        at0, views0 = None, None
        with self._lock:
            plan = self._plans.get(sc)
        if plan is None:
            xp0 = shapeclass.pad_to_class(reqs[0].x, sc)
            at0 = shapeclass.canonicalize_tensor(
                alto.build_device(xp0, n_partitions=sc.n_partitions,
                                  compute_reuse=False), sc)
            plan = self._class_plan(sc, at_canonical=at0)
            views0 = plan_mod.build_views(at0, plan)
        ats, views, rdims, seeds = [], [], [], []
        for j, req in enumerate(reqs):
            if j == 0 and at0 is not None:
                at, vs = at0, views0
            else:
                at, vs = self._prepare(req, plan)
            ats.append(at)
            views.append(vs)
            rdims.append(req.x.dims)
            seeds.append(req.seed)
        if self.algorithm == "cp_als":
            out = batched.batched_cp_als(
                ats, views, rdims, self.rank, plan=plan,
                n_iters=self.n_iters, tol=self.tol, seeds=seeds,
                capacity=self.capacity, guard=self.guard)
        else:
            out = batched.batched_cp_apr(
                ats, views, rdims, self.rank, plan=plan,
                params=cpapr_mod.CpaprParams(k_max=self.n_iters,
                                             tau=self.tol),
                seeds=seeds, capacity=self.capacity, guard=self.guard)
        done = time.perf_counter()
        quarantined = (out.quarantined if out.quarantined
                       else [False] * len(reqs))
        responses = []
        for req, result, quar in zip(reqs, out.results, quarantined):
            lat = done - req.submitted_at
            err = None
            if quar:
                # Guard quarantine: the slot went non-finite mid-solve
                # and was rolled back to its last good iterate — the
                # result is degraded but finite, and ONLY this tenant is
                # affected (vmap lanes are independent).
                err = ("quarantined: non-finite update detected; "
                       "result is the last good iterate")
            responses.append(CpdResponse(
                request_id=req.request_id, sc=sc, result=result,
                latency_s=lat, bucket_size=len(reqs), error=err,
                degraded=bool(quar)))
        with self._lock:
            self._latencies.extend(r.latency_s for r in responses)
            self._tenants_done += len(responses)
            self._buckets_run += 1
            self._busy_s += done - t0
            self._quarantined_tenants += sum(bool(q) for q in quarantined)
            self._errors += sum(bool(q) for q in quarantined)
            for req, result in zip(reqs, out.results):
                self._retain_locked(req.request_id,
                                    (req.x, None, result, sc))
        return responses

    def _serve_bucket(self, sc,
                      reqs: Sequence[CpdRequest]) -> list[CpdResponse]:
        """The resilient bucket path: deadline triage → ladder-wrapped
        bucket run → bisection to solo re-runs on bucket failure."""
        live, responses = [], []
        for req in reqs:
            if self._expired(req):
                with self._lock:
                    self._deadline_expired += 1
                responses.append(self._error_response(
                    req, sc, f"deadline expired: waited "
                             f"{time.perf_counter() - req.submitted_at:.3f}s "
                             f"of {req.deadline_s:.3f}s budget"))
            else:
                live.append(req)
        if not live:
            return responses
        try:
            served, retries, degraded = self._with_ladder(
                sc, lambda: self._run_bucket(sc, live))
            for r in served:
                r.retries += retries
                r.degraded = r.degraded or degraded
            responses.extend(served)
        except Exception as exc:  # noqa: BLE001 — bisect, don't crash
            # The whole bucket failed beyond the ladder. Bisect: each
            # member re-runs solo so one poisoned tenant cannot take
            # down its bucket-mates' answers.
            for req in live:
                responses.append(self._serve_solo(sc, req, cause=exc))
        return responses

    def _serve_solo(self, sc, req: CpdRequest,
                    cause: BaseException) -> CpdResponse:
        """Bisection rung: re-run one member of a failed bucket alone
        (through the ladder again — the failure may have been a bucket-
        mate's). A request that fails solo too is quarantined with a
        structured error carrying both failures."""
        try:
            served, retries, degraded = self._with_ladder(
                sc, lambda: self._run_bucket(sc, [req]))
        except Exception as solo_exc:  # noqa: BLE001 — quarantine
            with self._lock:
                self._quarantined_tenants += 1
            return self._error_response(
                req, sc, f"quarantined after repeated failures "
                         f"(bucket: {cause}; solo: {solo_exc})")
        resp = served[0]
        resp.retries += retries
        resp.degraded = resp.degraded or degraded
        return resp

    def _retain_locked(self, rid: int, entry: tuple) -> None:
        self._retained[rid] = entry
        while len(self._retained) > max(1, self.retain_results):
            self._retained.popitem(last=False)

    def _run_delta(self, req: DeltaRequest) -> CpdResponse:
        t0 = time.perf_counter()
        with self._lock:
            x, at, result, sc = self._retained[req.base_id]
        if at is None:
            # First delta against a bucket-served base: materialize the
            # REAL-dims tensor once (the bucketed solve ran on the
            # class-padded shape, which deltas must not inherit).
            at = alto.build_device(x, n_partitions=self.n_partitions,
                                   compute_reuse=False)
            with self._lock:
                if req.base_id in self._retained:
                    self._retained[req.base_id] = (x, at, result, sc)
        new_at = ingest_mod.append_delta(at, req.coords, req.values,
                                         policy=req.policy)
        if self.algorithm == "cp_als":
            res = cpals_mod.cp_als(new_at, self.rank, n_iters=self.n_iters,
                                   tol=self.tol, warm_start=result,
                                   guard=self.guard)
        else:
            res = cpapr_mod.cp_apr(
                new_at, self.rank,
                params=cpapr_mod.CpaprParams(k_max=self.n_iters,
                                             tau=self.tol),
                warm_start=result, guard=self.guard)
        done = time.perf_counter()
        resp = CpdResponse(request_id=req.request_id, sc=sc, result=res,
                           latency_s=done - req.submitted_at,
                           bucket_size=1)
        if res.health is not None and res.health.rolled_back:
            resp.error = f"quarantined: {res.health.reason}"
            resp.degraded = True
            with self._lock:
                self._quarantined_tenants += 1
                self._errors += 1
        with self._lock:
            self._latencies.append(resp.latency_s)
            self._deltas_done += 1
            self._busy_s += done - t0
            self._retain_locked(req.request_id, (None, new_at, res, sc))
        return resp

    def _serve_delta(self, req: DeltaRequest) -> CpdResponse:
        """Resilient delta path: deadline triage, transient retry. The
        jitted merge is functional (`ingest._append`), so a failure mid-
        delta leaves the retained base tensor fully serviceable — the
        structured error invites a clean resubmit, never torn state."""
        if self._expired(req):
            with self._lock:
                self._deadline_expired += 1
            return self._error_response(
                req, self._delta_sc(req),
                f"deadline expired: waited "
                f"{time.perf_counter() - req.submitted_at:.3f}s "
                f"of {req.deadline_s:.3f}s budget")
        try:
            resp, retries, degraded = self._with_ladder(
                None, lambda: self._run_delta(req))
        except KeyError as exc:
            return self._error_response(req, None,
                                        f"base result gone: {exc}")
        except Exception as exc:  # noqa: BLE001 — structured error
            return self._error_response(
                req, self._delta_sc(req),
                f"delta failed (base retained, resubmit is safe): {exc}")
        resp.retries += retries
        resp.degraded = resp.degraded or degraded
        return resp

    def _delta_sc(self, req: DeltaRequest):
        """Best-effort shape class for a delta's error response (the
        base may have aged out of the LRU by then)."""
        with self._lock:
            entry = self._retained.get(req.base_id)
        return entry[3] if entry is not None else None

    def process(self, flush: bool = True) -> list[CpdResponse]:
        """Drain the queues: deltas first (latency-sensitive, already
        warm — solo solves seeded from the retained base), then full
        buckets always, partial ones if ``flush`` — or, under
        ``max_wait_s``, once the bucket's oldest request has aged past
        the wait budget (the deadline-aware flush the worker loop runs
        on). Every admitted request yields exactly one response; failure
        modes come back as structured errors, not exceptions."""
        responses: list[CpdResponse] = []
        while True:
            with self._lock:
                dreq = (self._delta_queue.popleft()
                        if self._delta_queue else None)
            if dreq is None:
                break
            responses.append(self._serve_delta(dreq))
        while True:
            now = time.perf_counter()
            with self._lock:
                batch_ = None
                for sc, q in self._queues.items():
                    ready = len(q) >= self.capacity or (flush and bool(q))
                    if (not ready and q and self.max_wait_s is not None
                            and now - q[0].submitted_at >= self.max_wait_s):
                        ready = True          # deadline-aware flush
                    if ready:
                        n = min(len(q), self.capacity)
                        batch_ = (sc, [q.popleft() for _ in range(n)])
                        break
                empties = [sc for sc, q in self._queues.items() if not q]
                for sc in empties:
                    del self._queues[sc]
            if batch_ is None:
                break
            responses.extend(self._serve_bucket(*batch_))
        self._deliver(responses)
        return responses

    # -- observability ----------------------------------------------------

    def stats(self) -> dict:
        """Serving counters + the trace counters the tests pin."""
        integ = stream_mod.integrity_stats()
        with self._lock:
            lats = sorted(self._latencies)
            n = len(lats)
            done, buckets, busy = (self._tenants_done, self._buckets_run,
                                   self._busy_s)
            classes = len(self._plans)
            deltas = self._deltas_done
            resilience = {
                "retries": self._retries,
                "backoff_s": self._backoff_s,
                "quarantined_tenants": self._quarantined_tenants,
                "degraded_dispatches": self._degraded_dispatches,
                "plan_evictions": self._plan_evictions,
                "deadline_expired": self._deadline_expired,
                "errors": self._errors,
                "worker_alive": (self._worker is not None
                                 and self._worker.is_alive()),
                "worker_recoveries": self._worker_recoveries,
            }

        def pct(p):
            return lats[min(n - 1, int(p * n))] if n else 0.0

        return {
            "tenants_done": done,
            "deltas_done": deltas,
            "buckets_run": buckets,
            "shape_classes": classes,
            "tenants_per_s": (done / busy) if busy > 0 else 0.0,
            "latency_p50_s": pct(0.50),
            "latency_p99_s": pct(0.99),
            "ingest_traces": alto.device_ingest_traces(),
            "sweep_traces": batched.sweep_traces(),
            "checksum_failures": integ["checksum_failures"],
            "stream_rebuilds": integ["rebuilds"],
            **resilience,
        }


# ---------------------------------------------------------------------------
# CLI demo: synthetic tenants with deliberately scattered shapes
# ---------------------------------------------------------------------------

def main(argv=None):
    from repro.sparse.synthetic import uniform_tensor

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, default=12)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--algorithm", default="cp_als",
                    choices=["cp_als", "cp_apr"])
    ap.add_argument("--capacity", type=int, default=8)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--worker", action="store_true",
                    help="serve through the background worker loop "
                         "instead of a caller-driven process()")
    ap.add_argument("--max-wait-s", type=float, default=0.05,
                    help="deadline-aware partial-bucket flush budget "
                         "(worker mode)")
    ap.add_argument("--tune", default="auto",
                    choices=["off", "auto", "force", "search"],
                    help="plan selection: analytic, store-backed "
                         "exhaustive, or budgeted search")
    ap.add_argument("--search-budget", type=int, default=None,
                    help="timing-run budget per class under "
                         "--tune search (default: the engine's 25%% "
                         "of the feasible space)")
    args = ap.parse_args(argv)
    caches.use_compile_cache()

    svc = CpdService(args.rank, args.algorithm, capacity=args.capacity,
                     n_iters=args.iters, tune=args.tune,
                     search_budget=args.search_budget,
                     max_wait_s=(args.max_wait_s if args.worker else None))
    rng = np.random.default_rng(args.seed)
    shapes = [(9, 7, 5), (12, 6, 8), (16, 8, 8), (30, 20, 10)]
    rids = []
    if args.worker:
        svc.serve()
    for t in range(args.tenants):
        dims = shapes[t % len(shapes)]
        nnz = int(rng.integers(60, 128))
        x = uniform_tensor(dims, nnz, seed=args.seed + t,
                           count_data=(args.algorithm == "cp_apr"))
        rids.append(svc.submit(x, seed=t))
    print(f"admitted {args.tenants} tenants")
    t0 = time.perf_counter()
    if args.worker:
        responses = [svc.wait(rid, timeout=300.0) for rid in rids]
        svc.shutdown()
    else:
        responses = svc.process()
    dt = time.perf_counter() - t0
    s = svc.stats()
    print(f"served {len(responses)} tenants in {dt:.2f}s "
          f"({s['tenants_per_s']:.1f} tenants/s busy-rate), "
          f"{s['buckets_run']} buckets, {s['shape_classes']} classes")
    print(f"latency p50 {s['latency_p50_s']*1e3:.0f} ms, "
          f"p99 {s['latency_p99_s']*1e3:.0f} ms")
    print(f"jit traces: ingest {s['ingest_traces']}, "
          f"sweeps {s['sweep_traces']}")
    print(f"resilience: retries {s['retries']}, quarantined "
          f"{s['quarantined_tenants']}, degraded {s['degraded_dispatches']}, "
          f"errors {s['errors']}")
    return responses


if __name__ == "__main__":
    main()

"""Resilient serving runtime: fault injection, health guards, self-healing.

Pins the PR 9 tentpole contract (`docs/resilience.md`): every failure
the runtime claims to survive has a named fault site (`core.faults`)
threaded through the real hot path, and arming it produces a structured
error or a degraded-but-finite result for the affected request ONLY —
no crash, no poisoned bucket-mates, no torn on-disk state:

* the fault registry is deterministic, env-configurable, and zero-cost
  disabled;
* spilled streams carry content checksums: corruption is a load-time
  `StreamIntegrityError`, the respill is crash-safe (old generation
  stays byte-identical), and `load_or_rebuild` is the rebuild rung;
* the per-sweep health guards roll a poisoned solve back to its last
  good iterate (solo and per-tenant in a bucket) and change NOTHING on
  finite inputs — guarded runs stay bitwise identical to unguarded;
* the service walks the recovery ladders: transient retry with backoff,
  plan degradation (OOM -> halve chunk_m; kernel failures surface), stored
  plan eviction, bucket bisection -> solo -> quarantine; deadlines and
  the deadline-aware flush bound tail latency; the background worker
  loop survives a 16-thread submit/delta/shutdown stress.

Runs on the hermetic `tests/proptest.py` harness (no hypothesis in the
offline image).
"""
import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, settings, strategies as st

from repro.core import alto, autotune, batched, faults, health, ingest
from repro.core import cpals, cpapr, shapeclass
from repro.core import plan as plan_mod
from repro.core import stream as stream_mod
from repro.core import views as views_mod
from repro.kernels import ops
from repro.launch.serve_cpd import CpdService
from repro.sparse.synthetic import uniform_tensor

RANK = 3
DIMS = (9, 7, 5)


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with nothing armed (a leaked arm in
    one test must not fire in another) and fresh integrity counters."""
    faults.reset()
    stream_mod.integrity_stats_clear()
    yield
    faults.reset()


def _tensor(seed=0, dims=DIMS, nnz=80, count_data=False):
    return uniform_tensor(dims, nnz, seed=seed, count_data=count_data)


def _service(**kw):
    kw.setdefault("capacity", 2)
    kw.setdefault("n_iters", 4)
    kw.setdefault("tune", "off")
    kw.setdefault("retry_base_s", 1e-4)
    return CpdService(RANK, **kw)


# ---------------------------------------------------------------------------
# The fault registry
# ---------------------------------------------------------------------------

class TestFaultRegistry:

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.arm("nope.such_site")
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.configure("stream.chunk_io,typo.site:3")

    def test_deterministic_times(self):
        faults.arm("ingest.merge", times=2)
        for _ in range(2):
            with pytest.raises(faults.InjectedInterrupt):
                faults.inject("ingest.merge")
        faults.inject("ingest.merge")        # exhausted: no-op
        assert faults.fired()["ingest.merge"] == 2
        assert not faults.armed("ingest.merge")

    def test_zero_overhead_disabled(self):
        assert faults._ENABLED is False
        assert faults.fire("batched.nan") is None
        faults.inject("ops.chunk_oom")       # returns, does not raise

    def test_injected_scopes_the_arm(self):
        with faults.injected("stream.chunk_io", times=5):
            assert faults.armed("stream.chunk_io")
        assert not faults.armed("stream.chunk_io")
        assert faults._ENABLED is False

    def test_env_spec_parsing(self):
        faults.configure("stream.chunk_io:2, batched.nan")
        assert faults.armed("stream.chunk_io")
        assert faults.armed("batched.nan")
        faults.configure(None)
        assert faults._ENABLED is False

    def test_exception_classes_mimic_real_faults(self):
        assert faults.is_transient(faults.InjectedIOError("x"))
        assert faults.is_transient(
            faults.InjectedResourceExhausted("ops.chunk_oom"))
        assert not faults.is_transient(faults.InjectedDispatchError("x"))
        assert not faults.is_transient(faults.InjectedInterrupt("x"))
        assert isinstance(faults.InjectedCorruption("x"), ValueError)

    def test_after_skips_leading_hits(self):
        faults.arm("ingest.merge", times=1, after=2)
        faults.inject("ingest.merge")            # hit 1: let through
        faults.inject("ingest.merge")            # hit 2: let through
        with pytest.raises(faults.InjectedInterrupt):
            faults.inject("ingest.merge")        # hit 3: fires
        assert faults.fired()["ingest.merge"] == 1

    def test_data_rides_along(self):
        faults.arm("batched.nan", data={"tenant": 2, "value": 7.0})
        assert faults.fire("batched.nan") == {"tenant": 2, "value": 7.0}
        assert faults.fire("batched.nan") is None


# ---------------------------------------------------------------------------
# Stream integrity: checksums, crash-safe respill, rebuild rung
# ---------------------------------------------------------------------------

def _spilled(tmp_path, seed=0):
    at = alto.build(_tensor(seed=seed), n_partitions=2)
    hs = stream_mod.to_memmap(stream_mod.host_stream(at, 0), tmp_path)
    return at, hs


class TestStreamIntegrity:

    def test_checksum_roundtrip(self, tmp_path):
        at, hs = _spilled(tmp_path)
        assert hs.checksum is not None
        assert hs.checksum == stream_mod.stream_checksum(
            hs.rows, hs.words, hs.values)
        again = stream_mod.from_memmap(tmp_path, at.meta, 0)
        assert again.checksum == hs.checksum

    def test_corruption_detected_at_load(self, tmp_path):
        at, _ = _spilled(tmp_path)
        faults.arm("stream.checksum")
        with pytest.raises(stream_mod.StreamIntegrityError,
                           match="fails its checksum"):
            stream_mod.from_memmap(tmp_path, at.meta, 0)
        assert stream_mod.integrity_stats()["checksum_failures"] == 1

    def test_load_or_rebuild_recovers_corruption(self, tmp_path):
        at, hs = _spilled(tmp_path)
        faults.arm("stream.checksum")
        rebuilt = stream_mod.load_or_rebuild(tmp_path, at, 0)
        assert stream_mod.integrity_stats()["rebuilds"] == 1
        for a, b in ((rebuilt.rows, hs.rows), (rebuilt.words, hs.words),
                     (rebuilt.values, hs.values)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # the rebuilt spill verifies clean on the next load
        assert stream_mod.from_memmap(
            tmp_path, at.meta, 0).checksum == rebuilt.checksum

    def test_respill_crash_leaves_old_generation_intact(self, tmp_path):
        at, hs = _spilled(tmp_path)
        x2 = _tensor(seed=1, nnz=30)
        at2 = ingest.append_delta(at, x2.coords, x2.values)
        faults.arm("stream.respill")
        with pytest.raises(faults.InjectedInterrupt):
            stream_mod.append_stream(hs, at2)
        # crash between write and replace phases: the previous
        # generation still loads and verifies byte-identical
        old = stream_mod.from_memmap(tmp_path, at.meta, 0)
        assert old.checksum == hs.checksum
        assert np.array_equal(np.asarray(old.words), np.asarray(hs.words))
        # the retry completes and matches a from-scratch rebuild
        fresh = stream_mod.host_stream(at2, 0)
        redo = stream_mod.append_stream(hs, at2)
        assert np.array_equal(np.asarray(redo.words),
                              np.asarray(fresh.words))
        assert np.array_equal(np.asarray(redo.values),
                              np.asarray(fresh.values))

    def test_memmap_load_fault_is_transient(self, tmp_path):
        at, hs = _spilled(tmp_path)
        faults.arm("stream.memmap_load")
        with pytest.raises(OSError):
            stream_mod.from_memmap(tmp_path, at.meta, 0)
        # one retry later the same call succeeds — the definition of
        # transient the service's ladder relies on
        again = stream_mod.from_memmap(tmp_path, at.meta, 0)
        assert again.checksum == hs.checksum

@settings(max_examples=10)
@given(idx=st.integers(0, 10_000), seed=st.integers(0, 2**31 - 1))
def test_checksum_detects_any_value_flip(idx, seed):
    at = alto.build(_tensor(seed=seed, nnz=120), n_partitions=2)
    hs = stream_mod.host_stream(at, 0)
    ref = stream_mod.stream_checksum(hs.rows, hs.words, hs.values)
    values = np.array(hs.values, copy=True)
    i = idx % values.shape[0]
    values[i] = values[i] + 1.0 if np.isfinite(values[i]) else 0.0
    assert stream_mod.stream_checksum(hs.rows, hs.words, values) != ref


# ---------------------------------------------------------------------------
# Chunked-executor faults: OOM retry parity and plan degradation
# ---------------------------------------------------------------------------

class TestChunkFaults:

    def _chunked(self, hs_or_view, factors):
        return ops.mttkrp_oriented_chunked(hs_or_view, factors,
                                           chunk_m=16, block_m=8,
                                           r_block=RANK, interpret=True)

    def test_chunk_oom_retry_parity(self):
        at = alto.build(_tensor(seed=4, nnz=100), n_partitions=2)
        view = alto.oriented_view(at, 0)
        factors = cpals.init_factors(at.dims, RANK, seed=4)
        clean = self._chunked(view, factors)
        faults.arm("ops.chunk_oom")
        with pytest.raises(faults.InjectedResourceExhausted,
                           match="RESOURCE_EXHAUSTED"):
            self._chunked(view, factors)
        # allocator exhaustion is transient: the bare retry is bitwise
        retry = self._chunked(view, factors)
        assert jnp.array_equal(clean, retry)

    def test_degrade_plan_halves_chunks(self):
        at = alto.build(_tensor(seed=5, nnz=400, dims=(64, 9, 5)),
                        n_partitions=2)
        plan = plan_mod.make_plan(at.meta, RANK, device_bytes=1)
        assert plan.streaming is not None
        align = max(m.block_m for m in plan.modes)
        # give the plan halving headroom (a tiny budget may already sit
        # at the one-block minimum, where the rung correctly gives up)
        cm = 4 * align
        plan = dataclasses.replace(
            plan, streaming=dataclasses.replace(
                plan.streaming, chunk_m=cm,
                n_chunks=plan_mod.chunk_count(plan.meta, cm)))
        degraded, why = health.degrade_plan(
            plan, faults.InjectedResourceExhausted("ops.chunk_oom"))
        assert degraded is not None and "chunk_m" in why
        assert degraded.streaming.chunk_m < cm
        assert degraded.streaming.chunk_m % align == 0
        assert degraded.streaming.n_chunks == plan_mod.chunk_count(
            plan.meta, degraded.streaming.chunk_m)
        # repeatable until one aligned chunk remains, then out of rungs
        # (reference backend, in-core) -> (None, None)
        while degraded is not None:
            last = degraded
            degraded, _ = health.degrade_plan(
                last, faults.InjectedResourceExhausted("ops.chunk_oom"))
        assert last.streaming.chunk_m == align

    def test_degrade_plan_backend_rung_and_exhaustion(self):
        """A kernel failure has no softer plan: the Pallas plan is never
        swapped for the reference backend, and a served request gets
        the failure as its structured error, not a degraded answer."""
        at = alto.build(_tensor(seed=6), n_partitions=2)
        plan = plan_mod.make_plan(at.meta, RANK, backend="pallas")
        out, why = health.degrade_plan(
            plan, faults.InjectedDispatchError("kernel build failed"))
        assert out is None and why is None
        svc = _service(capacity=1, backend="pallas")
        faults.arm("plan.dispatch", times=2)      # the bucket, then solo
        rid = svc.submit(_tensor(seed=6))
        r = {r.request_id: r for r in svc.process()}[rid]
        assert not r.ok and not r.degraded
        assert "injected dispatch failure" in r.error
        assert svc.stats()["degraded_dispatches"] == 0


# ---------------------------------------------------------------------------
# Health guards: solo rollback, bitwise no-op on finite inputs
# ---------------------------------------------------------------------------

class TestGuards:

    def test_guard_is_bitwise_noop_on_finite_inputs(self):
        x = _tensor(seed=7)
        at = alto.build(x, n_partitions=2)
        a = cpals.cp_als(at, RANK, n_iters=5, seed=7, guard=False)
        b = cpals.cp_als(at, RANK, n_iters=5, seed=7, guard=True)
        assert a.fits == b.fits
        assert all(jnp.array_equal(fa, fb)
                   for fa, fb in zip(a.factors, b.factors))
        assert jnp.array_equal(a.lam, b.lam)
        assert b.health.checks > 0 and b.health.violations == 0
        assert not b.health.rolled_back

    def test_nan_poison_rolls_back_to_last_good(self):
        x = _tensor(seed=8)
        at = alto.build(x, n_partitions=2)
        faults.arm("cpals.nan")
        bad = cpals.cp_als(at, RANK, n_iters=5, seed=8, guard=False)
        assert not all(bool(jnp.all(jnp.isfinite(A)))
                       for A in bad.factors), \
            "unguarded run must expose the hazard (poison propagates)"
        faults.arm("cpals.nan")
        good = cpals.cp_als(at, RANK, n_iters=5, seed=8, guard=True)
        assert good.health.rolled_back
        assert "non-finite" in good.health.reason
        assert all(bool(jnp.all(jnp.isfinite(A))) for A in good.factors)
        assert all(np.isfinite(f) for f in good.fits)

    def test_huge_finite_poison_trips_divergence_guard(self):
        # 1e30 is FINITE, so the all-finite check alone would pass it
        # through to the next sweep, whose float32 Grams overflow and
        # whose SVD can then spin forever — the fit-floor guard must
        # stop it at the iteration that produced it.
        at = alto.build(_tensor(seed=9), n_partitions=2)
        faults.arm("cpals.nan", data={"value": 1e30})
        res = cpals.cp_als(at, RANK, n_iters=6, seed=9, guard=True)
        assert res.health.rolled_back
        assert "diverged" in res.health.reason
        assert all(bool(jnp.all(jnp.isfinite(A))) for A in res.factors)

    def test_mild_regression_trips_monotonicity_guard(self):
        # a modest poison that keeps everything finite and well-scaled,
        # landed once a fit history exists (after=2): only the
        # fit-monotonicity check can see it
        at = alto.build(_tensor(seed=16), n_partitions=2)
        faults.arm("cpals.nan", data={"value": 25.0}, after=2)
        res = cpals.cp_als(at, RANK, n_iters=8, seed=16, guard=True,
                           guard_slack=1e-6)
        assert res.health.rolled_back
        assert "regressed" in res.health.reason

    def test_cpapr_guard_rolls_back(self):
        at = alto.build(_tensor(seed=10, count_data=True), n_partitions=2)
        params = cpapr.CpaprParams(k_max=4)
        faults.arm("cpapr.nan")
        bad = cpapr.cp_apr(at, RANK, params=params, seed=10, guard=False)
        assert not all(bool(jnp.all(jnp.isfinite(A))) for A in bad.factors)
        faults.arm("cpapr.nan")
        good = cpapr.cp_apr(at, RANK, params=params, seed=10, guard=True)
        assert good.health.rolled_back
        assert all(bool(jnp.all(jnp.isfinite(A))) for A in good.factors)

    def test_guarded_apr_matches_unguarded_clean(self):
        at = alto.build(_tensor(seed=11, count_data=True), n_partitions=2)
        params = cpapr.CpaprParams(k_max=4)
        a = cpapr.cp_apr(at, RANK, params=params, seed=11, guard=False)
        b = cpapr.cp_apr(at, RANK, params=params, seed=11, guard=True)
        assert all(jnp.array_equal(fa, fb)
                   for fa, fb in zip(a.factors, b.factors))
        assert b.health.violations == 0


# ---------------------------------------------------------------------------
# Batched quarantine: one slot degrades, bucket-mates bitwise untouched
# ---------------------------------------------------------------------------

def _bucket(seeds, guard, n_iters=5):
    xs = [_tensor(seed=s) for s in seeds]
    sc = shapeclass.classify(xs[0], RANK)
    plan = plan_mod.make_class_plan(sc)
    ats, views, rdims = [], [], []
    for x in xs:
        xp = shapeclass.pad_to_class(x, sc)
        at = shapeclass.canonicalize_tensor(
            alto.build_device(xp, n_partitions=sc.n_partitions,
                              compute_reuse=False), sc)
        ats.append(at)
        views.append(plan_mod.build_views(at, plan))
        rdims.append(x.dims)
    return batched.batched_cp_als(ats, views, rdims, RANK, plan=plan,
                                  n_iters=n_iters, seeds=list(seeds),
                                  capacity=4, guard=guard)


class TestBatchedQuarantine:

    def test_poisoned_slot_quarantined_mates_bitwise_clean(self):
        clean = _bucket((0, 1, 2), guard=True)
        assert clean.quarantined == [False, False, False]
        faults.arm("batched.nan", data={"tenant": 1})
        out = _bucket((0, 1, 2), guard=True)
        assert out.quarantined == [False, True, False]
        for i in (0, 2):
            for fa, fb in zip(clean.results[i].factors,
                              out.results[i].factors):
                assert jnp.array_equal(fa, fb), \
                    f"bucket-mate {i} was perturbed by tenant 1's poison"
        assert all(bool(jnp.all(jnp.isfinite(A)))
                   for A in out.results[1].factors)

    def test_unguarded_bucket_returns_poison(self):
        faults.arm("batched.nan", data={"tenant": 1})
        out = _bucket((0, 1, 2), guard=False)
        assert not any(out.quarantined)
        assert not all(bool(jnp.all(jnp.isfinite(A)))
                       for A in out.results[1].factors)

    def test_guard_bitwise_noop_on_clean_bucket(self):
        a = _bucket((3, 4), guard=False)
        b = _bucket((3, 4), guard=True)
        for ra, rb in zip(a.results, b.results):
            assert ra.fits == rb.fits
            assert all(jnp.array_equal(fa, fb)
                       for fa, fb in zip(ra.factors, rb.factors))


# ---------------------------------------------------------------------------
# The service runtime: ladders, bisection, deadlines, worker loop
# ---------------------------------------------------------------------------

class TestServiceResilience:

    def test_poisoned_tenant_gets_structured_error_only(self):
        svc = _service(capacity=3)
        rids = [svc.submit(_tensor(seed=s), seed=s) for s in (0, 1, 2)]
        faults.arm("batched.nan", data={"tenant": 1})
        rs = {r.request_id: r for r in svc.process()}
        assert not rs[rids[1]].ok
        assert "quarantined" in rs[rids[1]].error
        assert rs[rids[1]].result is not None          # last good iterate
        assert rs[rids[0]].ok and rs[rids[2]].ok
        s = svc.stats()
        assert s["quarantined_tenants"] == 1
        assert s["errors"] == 1

    def test_transient_faults_retried_with_backoff(self):
        views_mod.cache_clear()
        faults.arm("views.build", times=2)
        svc = _service()
        rids = [svc.submit(_tensor(seed=s)) for s in (0, 1)]
        rs = svc.process()
        assert all(r.ok for r in rs)
        assert all(r.retries == 2 for r in rs)
        s = svc.stats()
        assert s["retries"] == 2 and s["backoff_s"] > 0

    def test_bucket_failure_bisects_to_solo_runs(self):
        batched.sweep_cache_clear()
        faults.arm("batched.sweep", times=1)
        svc = _service()
        rids = [svc.submit(_tensor(seed=s)) for s in (0, 1)]
        rs = svc.process()
        assert all(r.ok for r in rs)
        # the bucket run died; each member was re-served alone
        assert all(r.bucket_size == 1 for r in rs)

    def test_second_solo_failure_quarantines_offender(self):
        batched.sweep_cache_clear()
        faults.arm("batched.sweep", times=2)
        svc = _service()
        rids = [svc.submit(_tensor(seed=s)) for s in (0, 1)]
        rs = {r.request_id: r for r in svc.process()}
        # shot 1 kills the bucket, shot 2 kills the first solo re-run:
        # that request is quarantined, its bucket-mate is served clean
        assert not rs[rids[0]].ok
        assert "quarantined after repeated failures" in rs[rids[0]].error
        assert rs[rids[1]].ok
        assert svc.stats()["quarantined_tenants"] == 1

    def test_evict_and_retune_on_stored_plan_failure(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
        x = _tensor(seed=12, dims=(8, 6, 4), nnz=50)
        warm = _service(tune="auto")
        warm.submit(x)
        assert all(r.ok for r in warm.process())
        assert len(autotune.load_store()) == 1
        # fresh service trusts the store; its stored plan fails at
        # dispatch -> evicted, heuristic plan takes over, request served
        batched.sweep_cache_clear()
        faults.arm("plan.dispatch", times=1)
        svc = _service(tune="auto")
        svc.submit(x)
        rs = svc.process()
        assert all(r.ok and r.degraded for r in rs)
        assert svc.stats()["plan_evictions"] == 1
        assert len(autotune.load_store()) == 0

    def test_corrupt_plan_store_is_a_miss_not_a_crash(self, tmp_path,
                                                      monkeypatch):
        monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
        faults.arm("autotune.store")
        assert autotune.load_store() == {}
        svc = _service(tune="auto")
        svc.submit(_tensor(seed=13, dims=(8, 6, 4), nnz=50))
        assert all(r.ok for r in svc.process())

    def test_deadline_expired_request_gets_error(self):
        svc = _service()
        rid_late = svc.submit(_tensor(seed=0), deadline_s=0.0)
        rid_ok = svc.submit(_tensor(seed=1), deadline_s=3600.0)
        time.sleep(0.005)
        rs = {r.request_id: r for r in svc.process()}
        assert not rs[rid_late].ok
        assert "deadline expired" in rs[rid_late].error
        assert rs[rid_late].result is None
        assert rs[rid_ok].ok
        assert svc.stats()["deadline_expired"] == 1

    def test_deadline_aware_flush(self):
        svc = _service(capacity=4, max_wait_s=0.02)
        svc.submit(_tensor(seed=0))
        assert svc.process(flush=False) == []      # partial, still young
        time.sleep(0.03)
        rs = svc.process(flush=False)              # aged past max_wait_s
        assert len(rs) == 1 and rs[0].ok

    def test_ingest_merge_interrupt_leaves_base_serviceable(self):
        svc = _service(capacity=1)
        rid = svc.submit(_tensor(seed=14))
        base = svc.process()[0]
        assert base.ok
        x2 = _tensor(seed=15, nnz=20)
        faults.arm("ingest.merge")
        did = svc.submit_delta(rid, x2.coords, x2.values)
        r = {r.request_id: r for r in svc.process()}[did]
        assert not r.ok and "resubmit is safe" in r.error
        # the merge is functional: the retained base tensor was never
        # touched, so the clean resubmit serves normally
        did2 = svc.submit_delta(rid, x2.coords, x2.values)
        r2 = {r.request_id: r for r in svc.process()}[did2]
        assert r2.ok
        assert all(bool(jnp.all(jnp.isfinite(A)))
                   for A in r2.result.factors)


class TestWorkerLoop:

    def test_lifecycle(self):
        svc = _service(max_wait_s=0.01)
        assert not svc.serving
        svc.serve(poll_s=0.002)
        svc.serve(poll_s=0.002)                    # idempotent
        assert svc.serving
        rid = svc.submit(_tensor(seed=0))
        resp = svc.wait(rid, timeout=120)
        assert resp.ok
        svc.shutdown()
        assert not svc.serving
        svc.shutdown()                             # idempotent
        assert svc.stats()["worker_recoveries"] == 0

    def test_shutdown_drains_admitted_requests(self):
        svc = _service(capacity=8)                 # never fills a bucket
        svc.serve(poll_s=0.002)
        rids = [svc.submit(_tensor(seed=s)) for s in range(3)]
        svc.shutdown(wait=True)                    # final flush drains
        rs = [svc.wait(r, timeout=5) for r in rids]
        assert all(r.ok for r in rs)

    def test_wait_times_out(self):
        svc = _service()
        with pytest.raises(TimeoutError):
            svc.wait(999, timeout=0.02)

    def test_sixteen_thread_stress(self):
        svc = _service(capacity=4, n_iters=3, max_wait_s=0.01,
                       retain_results=256)
        svc.serve(poll_s=0.002)
        n_threads, per_thread = 16, 2
        failures: list[str] = []
        lock = threading.Lock()

        def client(t):
            try:
                rids = [svc.submit(_tensor(seed=(t * per_thread + j) % 7),
                                   seed=t) for j in range(per_thread)]
                rs = [svc.wait(r, timeout=300) for r in rids]
                for r in rs:
                    if not r.ok:
                        raise AssertionError(f"thread {t}: {r.error}")
                # half the clients chase with a delta against their base
                if t % 2 == 0:
                    x2 = _tensor(seed=t, nnz=15)
                    did = svc.submit_delta(rids[0], x2.coords, x2.values)
                    rd = svc.wait(did, timeout=300)
                    if not rd.ok:
                        raise AssertionError(f"thread {t} delta: {rd.error}")
            except Exception as exc:  # noqa: BLE001 — collected for report
                with lock:
                    failures.append(f"{type(exc).__name__}: {exc}")

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(600)
        svc.shutdown()
        assert not failures, failures
        s = svc.stats()
        assert s["tenants_done"] == n_threads * per_thread
        assert s["deltas_done"] == n_threads // 2
        assert s["worker_recoveries"] == 0
        assert s["errors"] == 0

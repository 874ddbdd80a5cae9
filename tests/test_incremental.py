"""Incremental-ingest test layer (`core.ingest` + friends).

Pins the PR's acceptance contracts:

* `ingest.append_delta` is BIT-IDENTICAL to the from-scratch host
  rebuild (`alto.merge_reference` — numpy `build` over the merged COO)
  on adversarial layouts and random property cases, under both duplicate
  policies: stream words, values, partition boxes, meta, and every
  oriented view;
* the jitted merge core has zero host callbacks and traces once per
  static merge meta;
* view invalidation is surgical — per (fingerprint, mode), with the
  `invalidated` counter; a no-op append or a re-tile drops nothing and
  keeps hitting, a real append costs at most ONE new view build per
  touched mode;
* `stream.append_stream` updates host/memmap streams in place (atomic
  respill — old maps stay readable);
* warm-start CP-ALS/CP-APR converge in fewer sweeps than cold on a
  perturbed tensor, and extent-growth warm starts match cold fits;
* a 16-thread append/read stress (mirroring `test_outofcore.py`'s cache
  stress) keeps every thread's merge bitwise and every read consistent.

Runs on the hermetic `tests/proptest.py` harness (no hypothesis in the
offline image).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, settings, strategies as st

from repro.core import alto, ingest
from repro.core import encoding as E
from repro.core import stream as stream_mod
from repro.core import views as views_mod
from repro.core.cpals import cp_als
from repro.core.cpapr import CpaprParams, cp_apr
from repro.sparse.tensor import SparseTensor

DIMS = (6, 7, 8)


@pytest.fixture(scope="module", autouse=True)
def _release_jit_footprint():
    """This file compiles O(100) small one-off executables (one per
    random merge meta); release them at module teardown so the many
    much larger compiles later in the suite don't inherit the JIT-code
    footprint."""
    yield
    views_mod.cache_clear()
    jax.clear_caches()


def _random_tensor(dims, nnz, seed=0, dup_frac=0.0, lo=0):
    rng = np.random.default_rng(seed)
    coords = np.stack([rng.integers(lo, d, nnz) for d in dims],
                      axis=1).astype(np.int32)
    if dup_frac and nnz > 4:
        k = max(1, int(nnz * dup_frac))
        coords[-k:] = coords[:k]
    values = rng.standard_normal(nnz).astype(np.float32)
    return SparseTensor(tuple(dims), coords, values)


def _delta(dims, D, seed=0, lo=0, hi=None):
    rng = np.random.default_rng(seed)
    hi = list(hi or dims)
    coords = np.stack([rng.integers(lo, h, D) for h in hi],
                      axis=1).astype(np.int32)
    values = rng.standard_normal(D).astype(np.float32)
    return coords, values


def _lowrank_tensor(dims, rank, nnz, seed=0, count_data=False):
    """Low-rank-structured values: warm starts only help when the model
    actually fits, so the regression tests need fittable tensors."""
    rng = np.random.default_rng(seed)
    fac = [rng.uniform(0.1, 1.0, (d, rank)) for d in dims]
    coords = np.stack([rng.integers(0, d, nnz) for d in dims],
                      axis=1).astype(np.int32)
    v = np.ones(nnz)
    for m, A in enumerate(fac):
        v = v * A[coords[:, m]].sum(axis=1)
    if count_data:
        v = np.maximum(1, np.round(v))
    return SparseTensor(tuple(dims), coords, v.astype(np.float32))


def _assert_tensor_bitwise(got: alto.AltoTensor, ref: alto.AltoTensor):
    assert got.meta == ref.meta
    np.testing.assert_array_equal(np.asarray(got.words),
                                  np.asarray(ref.words))
    np.testing.assert_array_equal(np.asarray(got.values),
                                  np.asarray(ref.values))
    np.testing.assert_array_equal(np.asarray(got.part_start),
                                  np.asarray(ref.part_start))
    np.testing.assert_array_equal(np.asarray(got.part_end),
                                  np.asarray(ref.part_end))


def _assert_view_bitwise(got: alto.AltoTensor, ref: alto.AltoTensor):
    for mode in range(len(ref.dims)):
        dv = alto.oriented_view_device(got, mode)
        hv = alto.oriented_view(ref, mode)
        for f in ("rows", "words", "values", "perm"):
            np.testing.assert_array_equal(np.asarray(getattr(dv, f)),
                                          np.asarray(getattr(hv, f)))


# ---------------------------------------------------------------------------
# merge parity: adversarial layouts x both policies
# ---------------------------------------------------------------------------

# Bit-interleaved keys are not lexicographic, but componentwise dominance
# is order-preserving: if every delta coordinate < every resident one per
# mode, every delta key sorts strictly before the resident stream.
ADVERSARIAL = {
    "empty_delta": dict(M=40, D=0),
    "empty_resident": dict(M=0, D=12),
    "both_empty": dict(M=0, D=0),
    "delta_entirely_before": dict(M=40, D=10, res_lo=4, d_hi=(2, 2, 2)),
    "delta_entirely_after": dict(M=40, D=10, res_hi=(2, 2, 2), d_lo=4),
    "cross_duplicates": dict(M=40, D=12, cross=5, dup_frac=0.3),
    "dup_heavy_delta": dict(M=20, D=30, cross=10, dup_frac=0.5),
    "extent_growth": dict(M=40, D=12, grow=(3, 0, 2)),
    "two_word_encoding": dict(M=60, D=20, dims=(300, 300, 300, 300)),
    "single_partition": dict(M=25, D=9, L=1),
    "more_partitions_than_nnz": dict(M=3, D=2, L=16),
}


def _adversarial_case(name, policy):
    c = ADVERSARIAL[name]
    dims = c.get("dims", DIMS)
    res_dims = c.get("res_hi", dims)
    x = _random_tensor(res_dims, c["M"], seed=hash(name) % 1000,
                       dup_frac=c.get("dup_frac", 0.0),
                       lo=c.get("res_lo", 0))
    x = SparseTensor(tuple(dims), x.coords, x.values)   # full extents
    L = c.get("L", 4)
    at = alto.build_device(x, n_partitions=L)
    grow = c.get("grow")
    d_hi = (tuple(d + g for d, g in zip(dims, grow)) if grow
            else c.get("d_hi", dims))
    coords, values = _delta(dims, c["D"], seed=hash(name) % 1000 + 7,
                            lo=c.get("d_lo", 0), hi=d_hi)
    if c.get("cross") and c["M"] and c["D"]:
        k = min(c["cross"], c["D"], c["M"])
        coords[:k] = x.coords[:k]                       # resident dups
    return at, coords, values


@pytest.mark.parametrize("policy", ingest.POLICIES)
@pytest.mark.parametrize("name", sorted(ADVERSARIAL))
def test_merge_parity_adversarial(name, policy):
    at, coords, values = _adversarial_case(name, policy)
    got = ingest.append_delta(at, coords, values, policy=policy)
    ref = alto.merge_reference(at, coords, values, policy=policy)
    _assert_tensor_bitwise(got, ref)
    _assert_view_bitwise(got, ref)


@settings(max_examples=30, deadline=None)
@given(ndim=st.integers(2, 4), side=st.integers(2, 40),
       m=st.integers(0, 60), d=st.integers(0, 25),
       grow=st.integers(0, 5), L=st.integers(1, 6),
       policy=st.sampled_from(ingest.POLICIES),
       seed=st.integers(0, 2**31 - 1))
def test_merge_parity_property(ndim, side, m, d, grow, L, policy, seed):
    rng = np.random.default_rng(seed)
    dims = tuple(int(rng.integers(2, side + 1)) for _ in range(ndim))
    x = _random_tensor(dims, m, seed=seed,
                       dup_frac=float(rng.random() * 0.4))
    at = alto.build_device(x, n_partitions=L)
    hi = tuple(dd + (int(rng.integers(0, grow + 1)) if grow else 0)
               for dd in dims)
    coords, values = _delta(dims, d, seed=seed + 1, hi=hi)
    if m and d:
        k = int(rng.integers(0, min(m, d) + 1))
        coords[:k] = x.coords[:k]
    got = ingest.append_delta(at, coords, values, policy=policy)
    ref = alto.merge_reference(at, coords, values, policy=policy)
    _assert_tensor_bitwise(got, ref)


def test_last_policy_masks_to_last_write():
    """Last-write semantics end to end: re-writing a coordinate leaves
    exactly the new value live (old occurrence masked to 0)."""
    x = _random_tensor(DIMS, 20, seed=5)
    at = alto.build_device(x, n_partitions=4)
    target = x.coords[3]
    got = ingest.append_delta(at, target[None, :], [2.5], policy="last")
    back = alto.to_sparse(got)
    match = np.all(back.coords == target, axis=1)
    vals = np.sort(back.values[match])
    assert vals[-1] == np.float32(2.5) and np.all(vals[:-1] == 0.0)


def test_append_chain_matches_single_rebuild():
    """Three chained appends == one host rebuild of all three batches."""
    x = _random_tensor(DIMS, 30, seed=9)
    at = alto.build_device(x, n_partitions=4)
    ref = at
    for i in range(3):
        coords, values = _delta(DIMS, 6, seed=20 + i)
        at = ingest.append_delta(at, coords, values)
        ref = alto.merge_reference(ref, coords, values)
    _assert_tensor_bitwise(at, ref)


def test_append_linearized_matches_append_delta():
    x = _random_tensor(DIMS, 30, seed=13)
    at = alto.build_device(x, n_partitions=4)
    coords, values = _delta(DIMS, 8, seed=14)
    enc = E.make_encoding(DIMS)
    words = E.linearize_np(enc, coords)
    got = ingest.append_linearized(at, words, values, DIMS)
    ref = ingest.append_delta(at, coords, values)
    _assert_tensor_bitwise(got, ref)


def test_dims_override_validation():
    x = _random_tensor(DIMS, 10, seed=1)
    at = alto.build_device(x, n_partitions=2)
    coords, values = _delta(DIMS, 4, seed=2)
    with pytest.raises(ValueError, match="does not cover"):
        ingest.append_delta(at, coords, values, dims=(2, 2, 2))
    with pytest.raises(ValueError, match="policy"):
        ingest.append_delta(at, coords, values, policy="first")


# ---------------------------------------------------------------------------
# jit contracts: zero host callbacks, once-per-merge-meta tracing
# ---------------------------------------------------------------------------

def test_merge_core_has_zero_host_callbacks():
    x = _random_tensor(DIMS, 40, seed=11)
    at = alto.build_device(x, n_partitions=4)
    coords, values = _delta(DIMS, 12, seed=12)
    grown = tuple(d + 2 for d in DIMS)   # growth path re-encodes in-jit
    for dims in (DIMS, grown):
        enc = E.make_encoding(dims)
        fn = ingest._merge_device_fn(
            at.meta.enc, enc, 4, at.nnz, at.words.shape[0],
            coords.shape[0], "last", jnp.float32, "coords")
        jaxpr = jax.make_jaxpr(fn)(at.words, at.values,
                                   jnp.asarray(coords),
                                   jnp.asarray(values))
        assert "callback" not in str(jaxpr)


def test_merge_traces_once_per_static_meta():
    x1 = _random_tensor(DIMS, 40, seed=21)
    x2 = _random_tensor(DIMS, 40, seed=22)
    at1 = alto.build_device(x1, n_partitions=4)
    at2 = alto.build_device(x2, n_partitions=4)
    coords, values = _delta(DIMS, 8, seed=23)
    ingest.append_delta(at1, coords, values)
    before = alto.device_ingest_traces()["merge"]
    ingest.append_delta(at2, coords, values)       # same merge meta
    assert alto.device_ingest_traces()["merge"] == before
    ingest.append_delta(at1, coords[:5], values[:5])   # new D: retrace
    assert alto.device_ingest_traces()["merge"] == before + 1


# ---------------------------------------------------------------------------
# surgical view invalidation
# ---------------------------------------------------------------------------

class TestViewInvalidation:
    def _tensor(self, seed=31, L=4):
        x = _random_tensor((10, 9, 8), 40, seed=seed)
        return alto.build_device(x, n_partitions=L), x

    def test_invalidate_single_mode_counter(self):
        views_mod.cache_clear()
        at, _ = self._tensor()
        for m in range(3):
            views_mod.get_view(at, m)
        b0 = views_mod.cache_stats()["builds"]
        assert views_mod.invalidate(at, modes=(0,)) == 1
        s = views_mod.cache_stats()
        assert s["invalidated"] == 1
        views_mod.get_view(at, 1)                  # untouched mode: hit
        assert views_mod.cache_stats()["builds"] == b0
        views_mod.get_view(at, 0)                  # dropped mode: rebuild
        assert views_mod.cache_stats()["builds"] == b0 + 1
        views_mod.cache_clear()

    def test_invalidate_all_modes_default(self):
        views_mod.cache_clear()
        at, _ = self._tensor()
        for m in range(3):
            views_mod.get_view(at, m)
        assert views_mod.invalidate(at) == 3
        assert views_mod.cache_stats()["invalidated"] == 3
        views_mod.cache_clear()

    def test_retile_keeps_views_and_rebinds_meta(self):
        """Same stream re-tiled (L=4 -> L=2, same padded length): every
        view stays cached — the per-mode fingerprint excludes the
        partitioning fields — and hits carry the new meta."""
        views_mod.cache_clear()
        at4, x = self._tensor(L=4)                 # Mp = 40 both ways
        for m in range(3):
            views_mod.get_view(at4, m)
        b0 = views_mod.cache_stats()["builds"]
        at2 = alto.build_device(x, n_partitions=2)
        assert at2.words.shape == at4.words.shape
        for m in range(3):
            v = views_mod.get_view(at2, m)
            assert v.meta == at2.meta
        assert views_mod.cache_stats()["builds"] == b0
        views_mod.cache_clear()

    def test_noop_append_drops_nothing_and_hits(self):
        views_mod.cache_clear()
        at, _ = self._tensor()
        for m in range(3):
            views_mod.get_view(at, m)
        b0 = views_mod.cache_stats()["builds"]
        new = ingest.append_delta(at, np.empty((0, 3), np.int32), [])
        for m in range(3):
            views_mod.get_view(new, m)
        s = views_mod.cache_stats()
        assert s["builds"] == b0 and s["invalidated"] == 0
        views_mod.cache_clear()

    def test_append_costs_one_build_per_touched_mode(self):
        views_mod.cache_clear()
        at, _ = self._tensor()
        for m in range(3):
            views_mod.get_view(at, m)
        b0 = views_mod.cache_stats()["builds"]
        coords, values = _delta((10, 9, 8), 6, seed=33)
        new = ingest.append_delta(at, coords, values)
        # the stale entries were invalidated eagerly (content changed)
        assert views_mod.cache_stats()["invalidated"] == 3
        for m in range(3):
            views_mod.get_view(new, m)
            views_mod.get_view(new, m)             # second get: hit
        assert views_mod.cache_stats()["builds"] == b0 + 3
        views_mod.cache_clear()


# ---------------------------------------------------------------------------
# host/memmap stream append
# ---------------------------------------------------------------------------

class TestStreamAppend:
    def _pair(self):
        x = _random_tensor(DIMS, 35, seed=41)
        at = alto.build_device(x, n_partitions=4)
        coords, values = _delta(DIMS, 9, seed=42)
        new_at = ingest.append_delta(at, coords, values)
        return at, new_at

    def test_numpy_stream_append(self):
        at, new_at = self._pair()
        hs = stream_mod.host_stream(at, 0)
        got = stream_mod.append_stream(hs, new_at)
        ref = stream_mod.host_stream(new_at, 0)
        assert got.length == ref.length
        np.testing.assert_array_equal(got.words, ref.words)
        np.testing.assert_array_equal(got.values, ref.values)
        np.testing.assert_array_equal(got.rows, ref.rows)

    def test_memmap_stream_appends_in_place(self, tmp_path):
        at, new_at = self._pair()
        mm = stream_mod.to_memmap(stream_mod.host_stream(at, 0), tmp_path)
        old_words = mm.words                       # held across the respill
        old_copy = np.array(old_words)
        got = stream_mod.append_stream(mm, new_at)
        ref = stream_mod.host_stream(new_at, 0)
        assert isinstance(got.words, np.memmap)
        assert str(got.words.filename) == str(tmp_path / "words.npy")
        np.testing.assert_array_equal(np.asarray(got.words), ref.words)
        np.testing.assert_array_equal(np.asarray(got.values), ref.values)
        # atomic replace: the pre-append map still reads the old inode
        np.testing.assert_array_equal(np.asarray(old_words), old_copy)
        # reopening from disk sees the new generation
        re = stream_mod.from_memmap(tmp_path, new_at.meta, 0)
        np.testing.assert_array_equal(np.asarray(re.words), ref.words)

    def test_memmap_backed_merge_parity(self, tmp_path):
        """Adversarial satellite case: the resident tensor's stream lives
        on disk, the append still matches the host rebuild bitwise."""
        x = _random_tensor(DIMS, 30, seed=43)
        at = alto.build_device(x, n_partitions=4)
        mm = stream_mod.to_memmap(stream_mod.host_stream(at, 1), tmp_path)
        coords, values = _delta(DIMS, 7, seed=44)
        new_at = ingest.append_delta(at, coords, values)
        ref = alto.merge_reference(at, coords, values)
        _assert_tensor_bitwise(new_at, ref)
        got = stream_mod.append_stream(mm, new_at)
        ref_hs = stream_mod.host_stream(ref, 1)
        np.testing.assert_array_equal(np.asarray(got.words), ref_hs.words)
        np.testing.assert_array_equal(np.asarray(got.values),
                                      ref_hs.values)


# ---------------------------------------------------------------------------
# warm-start regressions (tier-1)
# ---------------------------------------------------------------------------

class TestWarmStart:
    DIMS = (14, 12, 10)

    def _als_setup(self):
        x = _lowrank_tensor(self.DIMS, 3, 250, seed=0)
        at = alto.build_device(x, n_partitions=4)
        base = cp_als(at, 3, n_iters=80, tol=1e-5, seed=1)
        rng = np.random.default_rng(5)
        coords = np.stack([rng.integers(0, d, 6) for d in self.DIMS],
                          axis=1).astype(np.int32)
        values = (0.02 * rng.standard_normal(6)).astype(np.float32)
        return at, base, coords, values

    def test_cpals_warm_fewer_sweeps_than_cold(self):
        at, base, coords, values = self._als_setup()
        new = ingest.append_delta(at, coords, values)
        warm = cp_als(new, 3, n_iters=80, tol=1e-4, warm_start=base)
        cold = cp_als(new, 3, n_iters=80, tol=1e-4, seed=1)
        assert warm.n_iters < cold.n_iters
        assert warm.fits[-1] >= cold.fits[-1] - 1e-3

    def test_cpals_warm_with_extent_growth_matches_cold_fit(self):
        at, base, _, _ = self._als_setup()
        grown = ingest.append_delta(
            at, np.array([[d for d in self.DIMS]], np.int32), [0.5])
        assert grown.dims == tuple(d + 1 for d in self.DIMS)
        warm = cp_als(grown, 3, n_iters=80, tol=1e-5, warm_start=base)
        cold = cp_als(grown, 3, n_iters=80, tol=1e-5, seed=1)
        assert abs(warm.fits[-1] - cold.fits[-1]) < 0.02

    def test_cpapr_warm_fewer_iterations_than_cold(self):
        x = _lowrank_tensor((12, 10, 9), 3, 220, seed=7, count_data=True)
        at = alto.build_device(x, n_partitions=4)
        p = CpaprParams(k_max=80, tau=1e-4)
        base = cp_apr(at, 3, params=p, seed=1)
        rng = np.random.default_rng(8)
        coords = np.stack([rng.integers(0, d, 5) for d in (12, 10, 9)],
                          axis=1).astype(np.int32)
        new = ingest.append_delta(at, coords, np.ones(5, np.float32))
        warm = cp_apr(new, 3, params=p, warm_start=base)
        cold = cp_apr(new, 3, params=p, seed=1)
        assert warm.n_inner_total < cold.n_inner_total
        assert warm.n_outer <= cold.n_outer

    def test_grow_factors_validation(self):
        lam = jnp.ones((3,))
        factors = [jnp.ones((d, 3)) for d in (4, 5)]
        with pytest.raises(ValueError, match="shrank"):
            ingest.grow_factors((lam, factors), (3, 5), 3)
        with pytest.raises(ValueError, match="expected"):
            ingest.grow_factors((lam, factors), (4, 5), 2)
        with pytest.raises(ValueError, match="factors"):
            ingest.grow_factors((lam, [factors[0]]), (4, 5), 3)
        lam2, grown = ingest.grow_factors((lam, factors), (6, 5), 3,
                                          positive=True)
        assert grown[0].shape == (6, 3)
        np.testing.assert_allclose(np.asarray(grown[0]).sum(axis=0), 1.0,
                                   rtol=1e-5)

    def test_cp_als_rejects_factors_plus_warm_start(self):
        x = _random_tensor(DIMS, 20, seed=51)
        at = alto.build_device(x, n_partitions=2)
        f = [jnp.ones((d, 2)) for d in DIMS]
        with pytest.raises(ValueError, match="not both"):
            cp_als(at, 2, factors=f, warm_start=(None, f))


# ---------------------------------------------------------------------------
# 16-thread append/read stress (mirrors the out-of-core cache stress)
# ---------------------------------------------------------------------------

class TestThreadedAppendStress:
    N_THREADS = 16

    def _run_threads(self, fn, n):
        barrier = threading.Barrier(n)
        errors = []

        def wrap(i):
            try:
                barrier.wait()
                fn(i)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=wrap, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors

    def test_concurrent_appends_and_view_reads(self):
        """Even threads append a private delta to a shared base and check
        bitwise parity vs the host reference; odd threads hammer the view
        cache on the base. Appends are pure (the base tensor is never
        mutated), so every thread must see consistent data throughout."""
        views_mod.cache_clear()
        x = _random_tensor((12, 11, 10), 60, seed=61)
        base = alto.build_device(x, n_partitions=4)
        base_views = [np.asarray(views_mod.get_view(base, m).values)
                      for m in range(3)]

        def work(i):
            if i % 2 == 0:
                coords, values = _delta((12, 11, 10), 5 + (i % 3),
                                        seed=70 + i)
                policy = ingest.POLICIES[i % len(ingest.POLICIES)]
                got = ingest.append_delta(base, coords, values,
                                          policy=policy)
                ref = alto.merge_reference(base, coords, values,
                                           policy=policy)
                _assert_tensor_bitwise(got, ref)
            else:
                m = i % 3
                v = views_mod.get_view(base, m)
                np.testing.assert_array_equal(np.asarray(v.values),
                                              base_views[m])

        self._run_threads(work, self.N_THREADS)
        views_mod.cache_clear()


# ---------------------------------------------------------------------------
# distributed + serving integration
# ---------------------------------------------------------------------------

def test_sharded_append_delta_matches_local():
    from jax.sharding import Mesh
    from repro.dist import cpd as dist_cpd
    devs = np.array(jax.devices()[:1])     # 1-device mesh: same code path
    mesh = Mesh(devs, ("x",))
    x = _random_tensor(DIMS, 30, seed=71)
    at = alto.build_device(x, n_partitions=4)
    coords, values = _delta(DIMS, 7, seed=72)   # 7 % 1 == 0 pad; also odd
    got = dist_cpd.sharded_append_delta(at, coords, values, mesh,
                                        policy="last")
    ref = ingest.append_delta(at, coords, values, policy="last")
    _assert_tensor_bitwise(got, ref)
    empty = dist_cpd.sharded_append_delta(
        at, np.empty((0, 3), np.int32), [], mesh)
    _assert_tensor_bitwise(empty, at)


class TestServingDeltas:
    def _service(self, **kw):
        from repro.launch.serve_cpd import CpdService
        return CpdService(3, "cp_als", capacity=4, n_iters=15, **kw)

    def test_delta_request_roundtrip_and_chaining(self):
        svc = self._service()
        x = _lowrank_tensor((12, 10, 8), 3, 180, seed=81)
        rid = svc.submit(x, seed=0)
        svc.process()
        coords, values = _delta((12, 10, 8), 5, seed=82)
        did = svc.submit_delta(rid, coords, values)
        r1 = svc.process()
        assert len(r1) == 1 and r1[0].bucket_size == 1
        assert r1[0].request_id == did
        coords2, values2 = _delta((12, 10, 8), 4, seed=83)
        did2 = svc.submit_delta(did, coords2, values2)   # chain off delta
        r2 = svc.process()
        assert r2[0].request_id == did2
        s = svc.stats()
        assert s["deltas_done"] == 2
        # the chained result models the twice-appended tensor
        assert r2[0].result.factors[0].shape[0] == 12

    def test_delta_against_unknown_base_raises(self):
        svc = self._service()
        with pytest.raises(KeyError, match="not retained"):
            svc.submit_delta(999, np.empty((0, 3), np.int32), [])

    def test_retention_lru_bound(self):
        svc = self._service(retain_results=2)
        xs = [_random_tensor((6, 5, 4), 12, seed=90 + i) for i in range(3)]
        rids = [svc.submit(x, seed=i) for i, x in enumerate(xs)]
        svc.process()
        with pytest.raises(KeyError):          # oldest aged out of the LRU
            svc.submit_delta(rids[0], np.empty((0, 3), np.int32), [])
        did = svc.submit_delta(rids[2], np.empty((0, 3), np.int32), [])
        assert len(svc.process()) == 1
        assert svc.stats()["deltas_done"] == 1
        assert did > rids[2]

"""Device-resident ALTO ingest: host/device parity, cache, jit contracts.

Pins the acceptance conditions of the device ingest stack:

* `alto.build_device` / `alto.oriented_view_device` produce BIT-IDENTICAL
  element order to the host numpy path — duplicate-key ties included —
  on adversarial inputs (empty tensor, extent-1 modes, duplicate
  coordinates, two- and four-word encodings, all-nonzeros-one-row);
* the jitted ingest cores trace once per static meta and contain zero
  host callbacks;
* the view cache (`core.views`) builds once per (tensor, mode) per
  process and the drivers consume cached device-built views end to end.

Runs on the hermetic `tests/proptest.py` harness (no hypothesis in the
offline image).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from proptest import given, settings, strategies as st

from repro.core import alto, cpals, cpapr, encoding as E
from repro.core import plan as plan_mod
from repro.core import views as views_mod
from repro.sparse.tensor import SparseTensor


def _random_tensor(dims, nnz, seed, dup_frac=0.3):
    """COO tensor with a controlled fraction of duplicate coordinates."""
    rng = np.random.default_rng(seed)
    dims = tuple(int(d) for d in dims)
    if nnz == 0:
        return SparseTensor(dims, np.zeros((0, len(dims)), np.int32),
                            np.zeros((0,), np.float32))
    base = np.stack([rng.integers(0, I, size=nnz) for I in dims],
                    axis=1).astype(np.int32)
    n_dup = int(nnz * dup_frac)
    if n_dup and nnz > 1:
        # Overwrite a suffix with copies of earlier rows -> duplicate
        # linearized keys at distinct stream positions (tie stability).
        src = rng.integers(0, nnz - n_dup, size=n_dup)
        base[nnz - n_dup:] = base[src]
    vals = rng.random(nnz).astype(np.float32) + 0.1
    return SparseTensor(dims, base, vals)


def _assert_tensor_parity(h, d):
    assert h.meta == d.meta
    np.testing.assert_array_equal(np.asarray(h.words), np.asarray(d.words))
    np.testing.assert_array_equal(np.asarray(h.values),
                                  np.asarray(d.values))
    np.testing.assert_array_equal(np.asarray(h.part_start),
                                  np.asarray(d.part_start))
    np.testing.assert_array_equal(np.asarray(h.part_end),
                                  np.asarray(d.part_end))


def _assert_view_parity(vh, vd):
    assert vh.meta == vd.meta and vh.mode == vd.mode
    np.testing.assert_array_equal(np.asarray(vh.rows), np.asarray(vd.rows))
    np.testing.assert_array_equal(np.asarray(vh.words),
                                  np.asarray(vd.words))
    np.testing.assert_array_equal(np.asarray(vh.values),
                                  np.asarray(vd.values))
    np.testing.assert_array_equal(np.asarray(vh.perm), np.asarray(vd.perm))


# ---------------------------------------------------------------------------
# Device sort primitive vs the host packed-key argsort
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n_words=st.sampled_from([1, 2, 4]), m=st.integers(0, 200),
       seed=st.integers(0, 2**31 - 1))
def test_sort_by_key_matches_host_argsort(n_words, m, seed):
    """`encoding.sort_by_key` == stable `sort_key_np` permutation, with
    a narrow value range so duplicate full keys exercise tie stability."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 7, size=(m, n_words)).astype(np.uint32)
    order = E.sort_key_np(words)
    iota = jnp.arange(m, dtype=jnp.int32)
    sorted_words, perm = E.sort_by_key(jnp.asarray(words), iota)
    np.testing.assert_array_equal(np.asarray(perm), order.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(sorted_words), words[order])


@settings(max_examples=15, deadline=None)
@given(n_words=st.sampled_from([1, 2, 4]), m=st.integers(0, 150),
       seed=st.integers(0, 2**31 - 1))
def test_count_distinct_matches_unique(n_words, m, seed):
    """Both distinct-row counters == the np.unique(axis=0) oracle they
    replaced (the fiber_reuse_stats satellite's parity condition)."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 5, size=(m, n_words)).astype(np.uint32)
    expect = len(np.unique(words, axis=0)) if m else 0
    assert E.count_distinct_np(words) == expect
    assert int(E.count_distinct(jnp.asarray(words))) == expect


def test_extract_mode_matches_delinearize():
    """Masked bit-extract of one mode == that column of the full
    delinearize, on both numpy and jax words."""
    rng = np.random.default_rng(0)
    for dims in [(6, 4, 3), (5000, 4000, 3000), (1, 9, 1, 2**17)]:
        enc = E.make_encoding(dims)
        coords = np.stack([rng.integers(0, I, 64) for I in dims],
                          axis=1).astype(np.int32)
        words = E.linearize_np(enc, coords)
        full = E.delinearize_np(enc, words)
        for mode in range(len(dims)):
            got_np = E.extract_mode(enc, words, mode)
            got_dev = E.extract_mode(enc, jnp.asarray(words), mode)
            np.testing.assert_array_equal(got_np, full[:, mode])
            np.testing.assert_array_equal(np.asarray(got_dev),
                                          full[:, mode])
            assert got_np.dtype == np.int32


# ---------------------------------------------------------------------------
# build_device / oriented_view_device parity (adversarial + property)
# ---------------------------------------------------------------------------

ADVERSARIAL = {
    "empty": ((4, 3, 2), 0),
    "extent_1_modes": ((1, 7, 1, 13), 60),
    "duplicates_heavy": ((12, 9, 5), 160),       # dup_frac below
    "two_word": ((5000, 4000, 3000), 220),       # 36 bits -> 2 u32 words
    "four_word": ((2**17, 2**17, 2**17, 2**17), 150),  # 68 bits -> 4 words
    "single_nonzero": ((30, 20), 1),
}


@pytest.mark.parametrize("name", sorted(ADVERSARIAL))
def test_build_and_view_parity_adversarial(name):
    dims, nnz = ADVERSARIAL[name]
    dup = 0.8 if name == "duplicates_heavy" else 0.3
    x = _random_tensor(dims, nnz, seed=hash(name) % 2**31, dup_frac=dup)
    if name == "extent_1_modes":
        x.coords[:, 1] = 3          # every nonzero in one row of mode 1
    h = alto.build(x, n_partitions=4)
    d = alto.build_device(x, n_partitions=4)
    _assert_tensor_parity(h, d)
    for mode in range(x.ndim):
        _assert_view_parity(alto.oriented_view(h, mode),
                            alto.oriented_view_device(d, mode))


@settings(max_examples=12, deadline=None)
@given(dims=st.lists(st.integers(1, 64), min_size=1, max_size=4),
       nnz=st.integers(0, 250), L=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1))
def test_build_device_parity_property(dims, nnz, L, seed):
    x = _random_tensor(tuple(dims), nnz, seed)
    h = alto.build(x, n_partitions=L)
    d = alto.build_device(x, n_partitions=L)
    _assert_tensor_parity(h, d)
    mode = seed % len(dims)
    _assert_view_parity(alto.oriented_view(h, mode),
                        alto.oriented_view_device(d, mode))


def test_build_device_skips_reuse_like_host():
    x = _random_tensor((20, 15, 10), 120, seed=7)
    h = alto.build(x, compute_reuse=False)
    d = alto.build_device(x, compute_reuse=False)
    assert all(np.isnan(v) for v in d.meta.fiber_reuse)
    assert h.meta.temp_rows == d.meta.temp_rows


# ---------------------------------------------------------------------------
# jit contracts: once-per-meta tracing, zero host callbacks
# ---------------------------------------------------------------------------

def test_build_device_traces_once_per_meta():
    x = _random_tensor((25, 18, 11), 140, seed=3)
    alto.build_device(x, n_partitions=4)
    before = alto.device_ingest_traces()
    d = alto.build_device(x, n_partitions=4)    # same meta: no retrace
    alto.build_device(_random_tensor((25, 18, 11), 140, seed=99),
                      n_partitions=4)           # same meta, other data
    assert alto.device_ingest_traces()["build"] == before["build"]
    alto.oriented_view_device(d, 0)
    mid = alto.device_ingest_traces()
    alto.oriented_view_device(d, 0)             # same (meta, mode)
    assert alto.device_ingest_traces()["view"] == mid["view"]
    # a different static meta (nnz changes Mp) must trace fresh
    alto.build_device(_random_tensor((25, 18, 11), 141, seed=5),
                      n_partitions=4)
    assert alto.device_ingest_traces()["build"] == before["build"] + 1


def test_ingest_cores_have_zero_host_callbacks():
    """The jitted build/fiber/view cores must be pure device programs —
    no pure_callback/io_callback/debug.callback primitives in the jaxpr."""
    x = _random_tensor((40, 30, 20), 200, seed=11)
    enc = E.make_encoding(x.dims)
    build_fn = alto._build_device_fn(enc, 4, x.nnz, jnp.float32)
    jaxpr = jax.make_jaxpr(build_fn)(jnp.asarray(x.coords),
                                     jnp.asarray(x.values))
    assert "callback" not in str(jaxpr)
    d = alto.build_device(x, n_partitions=4)
    Mp, W = d.words.shape
    fiber_fn = alto._fiber_count_fn(x.nnz, Mp, W)
    jaxpr = jax.make_jaxpr(fiber_fn)(d.words,
                                     jnp.asarray(~enc.mode_masks()[0]))
    assert "callback" not in str(jaxpr)
    rows = alto._view_rows_fn(enc, 0, Mp)
    jaxpr = jax.make_jaxpr(rows)(d.words)
    assert "callback" not in str(jaxpr)
    view_fn = alto._view_sort_fn(Mp, W, jnp.float32)
    jaxpr = jax.make_jaxpr(view_fn)(rows(d.words), d.words, d.values)
    assert "callback" not in str(jaxpr)


def test_build_device_core_runs_under_jit():
    """The cached core composes under an outer jit (jit-compatible end
    to end — e.g. regeneration inside a larger traced program)."""
    x = _random_tensor((16, 12, 9), 90, seed=13)
    enc = E.make_encoding(x.dims)
    fn = alto._build_device_fn(enc, 4, x.nnz, jnp.float32)

    @jax.jit
    def outer(coords, values):
        words, vals, ps, pe = fn(coords, values)
        return words, vals, ps, pe

    words, *_ = outer(jnp.asarray(x.coords), jnp.asarray(x.values))
    h = alto.build(x, n_partitions=4)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(h.words))


# ---------------------------------------------------------------------------
# View cache: one build per (tensor, mode) per process, shared end to end
# ---------------------------------------------------------------------------

def test_view_cache_one_build_per_tensor_mode():
    views_mod.cache_clear()
    x = _random_tensor((40, 30, 20), 300, seed=17)
    at = alto.build_device(x)
    plan = plan_mod.make_plan(at.meta, rank=4)
    vs1 = plan_mod.build_views(at, plan)
    n = len(vs1)
    assert n > 0
    vs2 = plan_mod.build_views(at, plan)
    stats = views_mod.cache_stats()
    assert stats["builds"] == n
    assert stats["hits"] == n
    assert all(vs1[k] is vs2[k] for k in vs1)
    # same content in a distinct AltoTensor object -> same cached views
    at2 = alto.build_device(x)
    vs3 = plan_mod.build_views(at2, plan)
    assert views_mod.cache_stats()["builds"] == n
    assert all(vs1[k] is vs3[k] for k in vs1)
    # different data -> different fingerprint -> fresh builds
    at3 = alto.build_device(_random_tensor((40, 30, 20), 300, seed=18))
    plan_mod.build_views(at3, plan)
    assert views_mod.cache_stats()["builds"] == 2 * n


def test_view_cache_invalidate_and_byte_bound(monkeypatch):
    views_mod.cache_clear()
    x = _random_tensor((20, 15, 10), 150, seed=41)
    at = alto.build_device(x)
    v = views_mod.get_view(at, 0)
    assert views_mod.cache_stats()["size"] == 1
    assert views_mod.invalidate(at) == 1
    assert views_mod.cache_stats()["size"] == 0
    # a byte budget below two views LRU-evicts down to the newest one
    monkeypatch.setenv("REPRO_VIEW_CACHE_BYTES",
                       str(views_mod._view_bytes(v) + 1))
    views_mod.get_view(at, 0)
    views_mod.get_view(at, 1)
    stats = views_mod.cache_stats()
    assert stats["size"] == 1 and stats["builds"] == 3
    views_mod.cache_clear()


def test_view_cache_routes_match_bitwise():
    views_mod.cache_clear()
    x = _random_tensor((22, 14, 8), 130, seed=23)
    at = alto.build_device(x)
    dev = views_mod.get_view(at, 0, route="device")
    views_mod.cache_clear()
    host = views_mod.get_view(at, 0, route="host")
    _assert_view_parity(host, dev)
    views_mod.cache_clear()


def test_drivers_consume_cached_device_views_end_to_end():
    """CP-ALS and CP-APR run on device-built tensors + cached device
    views, matching the host-ingest path bit-for-bit (identical element
    order => identical reduction order)."""
    views_mod.cache_clear()
    x = _random_tensor((30, 20, 12), 400, seed=29)
    at_h = alto.build(x)
    at_d = alto.build_device(x)
    res_h = cpals.cp_als(at_h, rank=4, n_iters=3,
                         views={m: alto.oriented_view(at_h, m)
                                for m in range(3)})
    res_d = cpals.cp_als(at_d, rank=4, n_iters=3)
    for A_h, A_d in zip(res_h.factors, res_d.factors):
        np.testing.assert_array_equal(np.asarray(A_h), np.asarray(A_d))
    assert res_h.fits == res_d.fits
    # further driver runs on the same tensor: zero additional view builds
    # (CP-APR's plan orients the same rank-free traversal set)
    builds = views_mod.cache_stats()["builds"]
    cpals.cp_als(at_d, rank=4, n_iters=2)
    p = cpapr.CpaprParams(k_max=2, l_max=2)
    cpapr.cp_apr(at_d, rank=3, params=p)
    assert views_mod.cache_stats()["builds"] == builds


def test_resident_bytes_accounts_views():
    x = _random_tensor((26, 17, 9), 180, seed=31)
    at = alto.build_device(x)
    plan = plan_mod.make_plan(at.meta, rank=4)
    views = plan_mod.build_views(at, plan)
    base = plan_mod.resident_bytes(at)
    full = plan_mod.resident_bytes(at, views)
    Mp = at.words.shape[0]
    W = at.meta.enc.n_words
    per_view = Mp * (4 + 4 * W + at.values.dtype.itemsize + 4)
    assert base == (Mp * (4 * W + at.values.dtype.itemsize)
                    + 2 * at.part_start.size * 4)
    assert full == base + len(views) * per_view
    assert full > at.storage_bytes()    # Fig. 12 accounting undercounts


# ---------------------------------------------------------------------------
# Shard-local consumption of the device-built view (dist seam, no mesh)
# ---------------------------------------------------------------------------

def test_device_view_shards_like_host_view():
    """`dist.cpd.local_mttkrp` over contiguous slices of the
    device-built view sums to the unsharded oriented MTTKRP (the psum
    simulation the dist unit tests use, fed by device ingest)."""
    from repro.dist import cpd as dist_cpd
    from repro.core import mttkrp as core_mttkrp
    x = _random_tensor((24, 16, 10), 240, seed=37)
    at = alto.build_device(x)
    view = views_mod.get_view(at, 0)
    plan = plan_mod.make_plan(at.meta, rank=4, backend="reference")
    rng = np.random.default_rng(0)
    factors = [jnp.asarray(rng.random((I, 4)), jnp.float32)
               for I in x.dims]
    full = core_mttkrp.mttkrp_oriented(view, factors)
    Mp = view.rows.shape[0]
    cut = Mp // 2
    parts = [
        dist_cpd.local_mttkrp(plan, 0, view.rows[s], view.words[s],
                              view.values[s], factors)
        for s in (slice(0, cut), slice(cut, Mp))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(full), rtol=1e-5, atol=1e-5)

"""Out-of-core chunked execution: chunk-parity property suite.

The tentpole contract — a host-resident stream sliced into block-aligned
chunks flowing through device memory with a cross-chunk carry chain is
**bitwise-identical** to the in-core scratch-carry path at equal tiling —
pinned on the adversarial layouts where chunking can go wrong:

  * one run spanning EVERY chunk (carry threads through all boundaries);
  * chunk capacity of a single block (``chunk_m == block_m``: every
    block boundary is also a chunk boundary);
  * nnz not divisible by the chunk size (short tail chunk);
  * duplicates-heavy streams (many short runs per chunk);
  * empty and single-nonzero tensors;
  * both Π policies for the fused Φ (PRE rebuilds chunk Π rows on
    device; OTF gathers factors per chunk).

Plus the plan layer (byte budget -> StreamPlan -> routing), the modeled
chunk count vs the executed grid, memory-mapped streams, end-to-end
driver parity over-budget, and the threaded one-build/no-use-after-evict
contract of the byte-bounded stream cache.

Runs on the hermetic tests/proptest.py harness (no hypothesis offline).
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, settings, strategies as st

from repro.core import alto, heuristics, mttkrp as core_mttkrp
from repro.core import plan as plan_mod
from repro.core import stream as stream_mod
from repro.core import views as views_mod
from repro.core.cpals import cp_als
from repro.core.cpapr import CpaprParams, cp_apr
from repro.kernels import ops
from repro.sparse.tensor import SparseTensor

TOL = 1e-5
DIMS = (29, 13, 7)          # non-pow2; mode 0 is the reduction target
MODE = 0
BM = 8                      # smallest legal block: maximizes boundaries


def _stream_tensor(row_counts, seed, count_data=False):
    """SparseTensor whose mode-0 rows appear with given multiplicities."""
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(len(row_counts), dtype=np.int32),
                     row_counts)
    coords = np.stack(
        [rows] + [rng.integers(0, I, size=rows.shape[0]).astype(np.int32)
                  for I in DIMS[1:]], axis=1)
    if count_data:
        values = rng.integers(1, 5, size=rows.shape[0]).astype(np.float32)
    else:
        values = rng.standard_normal(rows.shape[0]).astype(np.float32)
    return SparseTensor(DIMS, coords, values)


def _factors(seed, R=8):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(np.abs(rng.standard_normal((I, R))
                               ).astype(np.float32) + 0.05) for I in DIMS]


def _layout_counts(layout, rng):
    """Per-row multiplicities realizing the adversarial chunk layouts."""
    I0 = DIMS[0]
    counts = np.zeros(I0, dtype=np.int64)
    if layout == "span_all_chunks":
        # one row owns the whole stream: a single run covering every
        # chunk, so the carry crosses every chunk boundary open
        counts[int(rng.integers(I0))] = 5 * BM + 3
    elif layout == "distinct":
        # every present row once: the carry flushes at every boundary
        n = min(I0, 3 * BM)
        counts[rng.choice(I0, size=n, replace=False)] = 1
    elif layout == "duplicates_heavy":
        # few rows, many repeats: several runs per chunk plus runs that
        # straddle chunk boundaries
        hot = rng.choice(I0, size=3, replace=False)
        counts[hot] = rng.integers(BM, 3 * BM, size=3)
    else:                                   # "mixed"
        counts[:] = rng.integers(0, 2 * BM, size=I0)
        if counts.sum() == 0:
            counts[0] = 1
    return counts


LAYOUTS = ["span_all_chunks", "distinct", "duplicates_heavy", "mixed"]


# ---------------------------------------------------------------------------
# Kernel-level chunk parity (the tentpole bitwise fence)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       chunk_blocks=st.sampled_from([1, 2, 3]),   # 1 = capacity one block
       r_block=st.sampled_from([4, 8]))
def test_mttkrp_chunked_bitwise(layout, seed, chunk_blocks, r_block):
    rng = np.random.default_rng(seed)
    x = _stream_tensor(_layout_counts(layout, rng), seed)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(seed)

    incore = ops.mttkrp_oriented_carry(view, factors, block_m=BM,
                                       r_block=r_block, interpret=True)
    chunked = ops.mttkrp_oriented_chunked(view, factors,
                                          chunk_m=chunk_blocks * BM,
                                          block_m=BM, r_block=r_block,
                                          interpret=True)
    assert jnp.array_equal(incore, chunked), (
        "chunked MTTKRP not bit-identical to in-core carry path")

    ref = core_mttkrp.mttkrp_oriented(view, factors)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(chunked - ref))) / scale < TOL


@pytest.mark.parametrize("layout", LAYOUTS)
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       chunk_blocks=st.sampled_from([1, 3]),
       pre=st.booleans())
def test_phi_chunked_bitwise_both_policies(layout, seed, chunk_blocks, pre):
    rng = np.random.default_rng(seed)
    x = _stream_tensor(_layout_counts(layout, rng), seed, count_data=True)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(seed)
    B = jnp.abs(factors[MODE]) + 0.1

    if pre:
        coords = alto.delinearize(at.meta.enc, view.words)
        kw = dict(pi=core_mttkrp.krp_rows(coords, factors, MODE))
    else:
        kw = dict(factors=factors)
    incore = ops.cpapr_phi_oriented_carry(view, B, block_m=BM,
                                          interpret=True, **kw)
    chunked = ops.cpapr_phi_oriented_chunked(view, B, factors, pre=pre,
                                             chunk_m=chunk_blocks * BM,
                                             block_m=BM, interpret=True)
    assert jnp.array_equal(incore, chunked), (
        f"chunked Φ (pre={pre}) not bit-identical to in-core carry path")


def test_nnz_not_divisible_by_chunk():
    """Short tail chunk: padded stream not a multiple of chunk_m."""
    x = _stream_tensor(np.full(DIMS[0], 3), seed=5)      # 87 nnz
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(5)
    incore = ops.mttkrp_oriented_carry(view, factors, block_m=BM,
                                       r_block=8, interpret=True)
    hs = stream_mod.host_stream(at, MODE)
    for chunk_m in (2 * BM, 4 * BM, 8 * BM):
        if hs.padded_len(BM) % chunk_m == 0:
            continue
        chunked = ops.mttkrp_oriented_chunked(view, factors,
                                              chunk_m=chunk_m, block_m=BM,
                                              r_block=8, interpret=True)
        assert jnp.array_equal(incore, chunked)


@pytest.mark.parametrize("nnz", [0, 1])
def test_degenerate_streams(nnz):
    """Empty and single-nonzero tensors chunk without special cases."""
    counts = np.zeros(DIMS[0], dtype=np.int64)
    if nnz:
        counts[11] = 1
    x = _stream_tensor(counts, seed=9)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(9)
    incore = ops.mttkrp_oriented_carry(view, factors, block_m=BM,
                                       r_block=8, interpret=True)
    chunked = ops.mttkrp_oriented_chunked(view, factors, chunk_m=BM,
                                          block_m=BM, r_block=8,
                                          interpret=True)
    assert jnp.array_equal(incore, chunked)


def test_memmapped_stream_parity(tmp_path):
    """A spilled (memory-mapped) stream chunks bitwise like the in-core
    path — the executor never distinguishes mmap from RAM numpy."""
    rng = np.random.default_rng(2)
    x = _stream_tensor(_layout_counts("mixed", rng), seed=2)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(2)
    hs = stream_mod.to_memmap(stream_mod.host_stream(at, MODE), tmp_path)
    assert isinstance(hs.words, np.memmap)
    incore = ops.mttkrp_oriented_carry(view, factors, block_m=BM,
                                       r_block=8, interpret=True)
    chunked = ops.mttkrp_oriented_chunked(hs, factors, chunk_m=2 * BM,
                                          block_m=BM, r_block=8,
                                          interpret=True)
    assert jnp.array_equal(incore, chunked)


def test_reference_chunked_tolerance():
    """The reference-backend chunked executors agree with the in-core
    reference traversals to float tolerance (different association)."""
    rng = np.random.default_rng(7)
    x = _stream_tensor(_layout_counts("duplicates_heavy", rng), seed=7,
                       count_data=True)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(7)
    ref = core_mttkrp.mttkrp_oriented(view, factors)
    got = ops.mttkrp_oriented_chunked_reference(view, factors, chunk_m=13)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(got - ref))) / scale < TOL

    B = jnp.abs(factors[MODE]) + 0.1
    coords = alto.delinearize(at.meta.enc, view.words)
    pi = core_mttkrp.krp_rows(coords, factors, MODE)
    ref_phi = ops.cpapr_phi_oriented_carry(view, B, pi=pi, block_m=BM,
                                           interpret=True)
    got_phi = ops.cpapr_phi_oriented_chunked_reference(
        view, B, factors, pre=True, chunk_m=13)
    scale = float(jnp.max(jnp.abs(ref_phi))) + 1e-9
    assert float(jnp.max(jnp.abs(got_phi - ref_phi))) / scale < TOL


def test_chunk_m_must_align_to_block_m():
    x = _stream_tensor(np.full(DIMS[0], 2), seed=0)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    with pytest.raises(ValueError, match="multiple of"):
        ops.mttkrp_oriented_chunked(view, _factors(0), chunk_m=BM + 1,
                                    block_m=BM, interpret=True)


def test_modeled_chunk_count_matches_executed_grid():
    """`plan.chunk_count` (the StreamPlan's n_chunks) equals the number
    of chunk executions the executor actually performs, and each chunk
    beyond the first was prefetched (double buffer)."""
    rng = np.random.default_rng(4)
    x = _stream_tensor(_layout_counts("mixed", rng), seed=4)
    at = alto.build(x, n_partitions=2)
    view = alto.oriented_view(at, MODE)
    factors = _factors(4)
    for chunk_m in (BM, 2 * BM, 4 * BM):
        before = ops.chunk_stats()
        ops.mttkrp_oriented_chunked(view, factors, chunk_m=chunk_m,
                                    block_m=BM, r_block=8, interpret=True)
        after = ops.chunk_stats()
        want = plan_mod.chunk_count(at.meta, chunk_m)
        assert after["chunks"] - before["chunks"] == want
        assert after["prefetches"] - before["prefetches"] == want - 1


# ---------------------------------------------------------------------------
# Plan layer: budget -> StreamPlan -> routing
# ---------------------------------------------------------------------------

def _tensor_and_meta(seed=0, scale=4):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, scale * 2, size=DIMS[0])
    # long enough for three chunks of two minimum blocks each
    counts[3] = scale * BM + 6 * plan_mod.MIN_BLOCK_M
    x = _stream_tensor(counts, seed, count_data=True)
    return alto.build(x, n_partitions=2)


def _streaming_plan(at, R, n_chunks_min=3):
    """A streaming plan with a genuinely multi-chunk grid: vmem_limit=0
    makes every tiling choice advisory-minimal (block_m == MIN_BLOCK_M),
    so the chunk alignment is MIN_BLOCK_M and a small budget yields
    several chunks."""
    meta = at.meta
    resident = plan_mod.streaming_resident_bytes(meta, R)
    elem = plan_mod.stream_elem_bytes(meta)
    budget = resident + 2 * elem * (2 * plan_mod.MIN_BLOCK_M)
    plan = plan_mod.make_plan(meta, R, backend="pallas", interpret=True,
                              vmem_limit=0, device_bytes=budget)
    assert plan.streaming is not None
    assert plan.streaming.n_chunks >= n_chunks_min
    return plan


class TestStreamPlan:
    def test_over_budget_goes_streaming(self):
        at = _tensor_and_meta()
        sp = _streaming_plan(at, R=4).streaming
        assert sp.chunk_m % BM == 0
        assert sp.n_chunks == plan_mod.chunk_count(at.meta, sp.chunk_m)
        assert sp.stream_bytes > sp.device_bytes

    def test_under_budget_stays_incore(self):
        at = _tensor_and_meta()
        plan = plan_mod.make_plan(at.meta, 4, device_bytes=1 << 40)
        assert plan.streaming is None

    def test_no_budget_never_streams(self, monkeypatch):
        monkeypatch.delenv("REPRO_DEVICE_BYTES", raising=False)
        at = _tensor_and_meta()
        assert plan_mod.make_plan(at.meta, 4).streaming is None

    def test_env_budget_is_picked_up(self, monkeypatch):
        at = _tensor_and_meta()
        resident = plan_mod.streaming_resident_bytes(at.meta, 4)
        monkeypatch.setenv("REPRO_DEVICE_BYTES", str(resident + 1))
        assert plan_mod.make_plan(at.meta, 4).streaming is not None

    def test_streaming_forces_carry_traversal(self):
        at = _tensor_and_meta()
        plan = _streaming_plan(at, R=4)
        assert all(m.traversal is heuristics.Traversal.ORIENTED_CARRY
                   for m in plan.modes)

    def test_streaming_rejects_mesh(self):
        at = _tensor_and_meta()
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
        with pytest.raises(ValueError, match="mesh"):
            plan_mod.make_plan(at.meta, 4, device_bytes=1, mesh=mesh)

    def test_streaming_tune_no_longer_raises(self, tmp_path, monkeypatch):
        # The PR-7 streaming+tune raise is lifted: a store miss with no
        # tensor data falls back to the STATIC streaming plan (same
        # "auto" semantics as in-core), zero timing runs.
        monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "p.json"))
        at = _tensor_and_meta()
        runs = ops.timing_runs()
        plan = plan_mod.make_plan(at.meta, 4, device_bytes=1, tune="auto")
        assert plan.streaming is not None
        assert ops.timing_runs() == runs
        assert plan == plan_mod.make_plan(at.meta, 4, device_bytes=1)

    def test_build_views_yields_host_streams(self):
        at = _tensor_and_meta()
        plan = _streaming_plan(at, R=4)
        views = plan_mod.build_views(at, plan)
        assert views and all(isinstance(v, stream_mod.HostStream)
                             for v in views.values())
        # ...and they carry zero device bytes in the residency accounting
        incore = plan_mod.build_views(
            at, dataclasses.replace(plan, streaming=None))
        assert (plan_mod.resident_bytes(at, views)
                < plan_mod.resident_bytes(at, incore))

    def test_execute_routes_through_chunked(self):
        at = _tensor_and_meta()
        R = 4
        plan = _streaming_plan(at, R)
        views = plan_mod.build_views(at, plan)
        factors = [f[:, :R] for f in _factors(1)]
        before = ops.chunk_stats()["chunks"]
        out = plan_mod.execute_mttkrp(plan, at, views, factors, MODE)
        assert ops.chunk_stats()["chunks"] - before \
            == plan.streaming.n_chunks
        incore = ops.mttkrp_oriented_carry(
            alto.oriented_view(at, MODE), factors,
            block_m=plan.modes[MODE].block_m,
            r_block=plan.modes[MODE].r_block, interpret=True)
        assert jnp.array_equal(out, incore)

    def test_streaming_phi_requires_factors(self):
        at = _tensor_and_meta()
        R = 4
        plan = _streaming_plan(at, R)
        views = plan_mod.build_views(at, plan)
        B = jnp.ones((DIMS[MODE], R), jnp.float32)
        with pytest.raises(ValueError, match="factors"):
            plan_mod.execute_phi(plan, at, views[MODE], B, MODE,
                                 pi=jnp.ones((1, R)))


# ---------------------------------------------------------------------------
# End-to-end: over-budget tensors decompose bitwise-identically
# ---------------------------------------------------------------------------

class TestEndToEndParity:
    """A tensor whose padded stream exceeds the device byte budget runs
    end-to-end through both drivers, multi-chunk, bitwise-identical to
    the in-core scratch-carry path at equal tiling (interpret mode)."""

    def _setup(self, R=4):
        at = _tensor_and_meta(seed=6)
        plan_s = _streaming_plan(at, R)
        plan_i = dataclasses.replace(plan_s, streaming=None)
        views_s = plan_mod.build_views(at, plan_s)
        views_i = plan_mod.build_views(at, plan_i)
        return at, plan_s, plan_i, views_s, views_i

    def test_cp_als_bitwise(self):
        at, plan_s, plan_i, views_s, views_i = self._setup()
        rs = cp_als(at, 4, n_iters=3, plan=plan_s, views=views_s)
        ri = cp_als(at, 4, n_iters=3, plan=plan_i, views=views_i)
        assert rs.fits == ri.fits
        assert jnp.array_equal(rs.lam, ri.lam)
        for a, b in zip(rs.factors, ri.factors):
            assert jnp.array_equal(a, b)

    @pytest.mark.parametrize("policy", ["pre", "otf"])
    def test_cp_apr_bitwise(self, policy):
        at, plan_s, plan_i, views_s, views_i = self._setup()
        p = CpaprParams(k_max=2, l_max=3)
        rs = cp_apr(at, 4, params=p, plan=plan_s, views=views_s,
                    pi_policy=policy)
        ri = cp_apr(at, 4, params=p, plan=plan_i, views=views_i,
                    pi_policy=policy)
        assert rs.kkt_violations == ri.kkt_violations
        assert rs.n_inner_total == ri.n_inner_total
        assert jnp.array_equal(rs.lam, ri.lam)
        for a, b in zip(rs.factors, ri.factors):
            assert jnp.array_equal(a, b)

    def test_runs_genuinely_chunked(self):
        at, plan_s, _, views_s, _ = self._setup()
        before = ops.chunk_stats()["chunks"]
        cp_als(at, 4, n_iters=1, plan=plan_s, views=views_s)
        executed = ops.chunk_stats()["chunks"] - before
        # one sweep = one chunked MTTKRP per mode
        assert executed == len(DIMS) * plan_s.streaming.n_chunks
        assert plan_s.streaming.n_chunks >= 3


# ---------------------------------------------------------------------------
# Threaded stream-cache regression (one build per key, no use-after-evict)
# ---------------------------------------------------------------------------

class TestThreadedStreamCache:
    N_THREADS = 16

    def _tensors(self, n=4):
        return [alto.build(_stream_tensor(
            np.random.default_rng(100 + i).integers(0, 12, size=DIMS[0]),
            seed=100 + i), n_partitions=2) for i in range(n)]

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

    def test_exactly_one_build_per_key(self, monkeypatch):
        """16 concurrent requesters over 8 (tensor, mode) keys: the
        per-key latch admits exactly one build each."""
        monkeypatch.delenv("REPRO_VIEW_CACHE_BYTES", raising=False)
        monkeypatch.delenv("REPRO_VIEW_CACHE_SIZE", raising=False)
        tensors = self._tensors(4)
        keys = [(at, m) for at in tensors for m in (0, 1)]   # 8 keys
        views_mod.cache_clear()
        before = views_mod.cache_stats()["builds"]
        got = {}

        def work(i):
            at, m = keys[i % len(keys)]
            hs = views_mod.get_stream(at, m)
            got[i] = hs

        self._run_threads(work, self.N_THREADS)
        assert views_mod.cache_stats()["builds"] - before == len(keys)
        # same key -> identical cached object
        for i in range(len(keys), self.N_THREADS):
            assert got[i] is got[i % len(keys)]
        views_mod.cache_clear()

    def test_no_use_after_evict_under_byte_bound(self, monkeypatch):
        """A byte bound so tight every insert evicts its predecessor:
        threads holding chunk slices of evicted entries must still
        compute bitwise-correct results (numpy slices keep the backing
        buffers alive past eviction)."""
        monkeypatch.setenv("REPRO_VIEW_CACHE_BYTES", "1")
        tensors = self._tensors(4)
        factors = _factors(0)
        want = {}
        for at in tensors:
            view = alto.oriented_view(at, MODE)
            want[id(at)] = ops.mttkrp_oriented_carry(
                view, factors, block_m=BM, r_block=8, interpret=True)
        views_mod.cache_clear()

        def work(i):
            at = tensors[i % len(tensors)]
            hs = views_mod.get_stream(at, MODE)   # may evict a peer's entry
            out = ops.mttkrp_oriented_chunked(hs, factors, chunk_m=2 * BM,
                                              block_m=BM, r_block=8,
                                              interpret=True)
            assert jnp.array_equal(out, want[id(at)])

        self._run_threads(work, self.N_THREADS)
        # the bound held: at most one stream entry survives
        assert views_mod.cache_stats()["size"] <= 1
        views_mod.cache_clear()

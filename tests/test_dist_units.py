"""Hermetic unit tests for the distributed seam (`repro.dist.cpd`).

The shard-local reductions are pure functions of a contiguous slice of
the row-sorted stream, so the mesh is simulated in-process: call the
local function per shard and sum on the host — arithmetically the same
combination ``lax.psum`` performs on device. That keeps these tests on
the single-device pytest host (the real 8-fake-device path is covered by
the subprocess tests in ``test_distributed.py``). Property cases run on
the hermetic ``tests/proptest.py`` harness.

Covered: boundary-run carries under adversarial row distributions (every
nonzero in one row → one run spanning all shards; nnz < shards → shards
made entirely of padding; random streams), psum'd Gram equivalence, and
mesh-aware plan resolution / hashing / caching.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import given, settings, strategies as st
from repro.core import alto, heuristics, mttkrp as cm, plan as plan_mod
from repro.dist import cpd as dist_cpd
from repro.sparse import synthetic
from repro.sparse.tensor import SparseTensor

TOL = 1e-5


def _factors(dims, R, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((I, R)).astype(np.float32))
            for I in dims]


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _simulated_sharded_mttkrp(plan, view, factors, mode, n_shards):
    """Shard-local reduce per contiguous slice + host-side sum (≡ psum)."""
    bm = plan.modes[mode].block_m if plan.backend == "pallas" else 1
    rows, words, values, _ = dist_cpd._pad_stream(
        view.rows, view.words, view.values, n_shards * bm)
    per = rows.shape[0] // n_shards
    out = None
    for s in range(n_shards):
        sl = slice(s * per, (s + 1) * per)
        part = dist_cpd.local_mttkrp(plan, mode, rows[sl], words[sl],
                                     values[sl], factors)
        out = part if out is None else out + part
    return out


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("case", ["uniform", "single_row", "tiny_nnz"])
def test_shard_boundary_carries(backend, case):
    """Sum of per-shard local reductions == unsharded oracle, including
    a single row spanning every shard and shards that are pure padding."""
    dims, R, D = (17, 9, 5), 6, 4
    if case == "uniform":
        x = synthetic.uniform_tensor(dims, 300, seed=0)
    elif case == "single_row":
        # every nonzero in mode-0 row 4: one segment run crosses all
        # shard boundaries; every shard contributes a carry to row 4
        rng = np.random.default_rng(1)
        coords = np.stack([np.full(64, 4),
                           rng.integers(0, dims[1], 64),
                           rng.integers(0, dims[2], 64)], axis=1)
        x = SparseTensor(dims, coords.astype(np.int32),
                         rng.standard_normal(64).astype(np.float32)
                         ).deduplicate()
    else:   # tiny_nnz: fewer nonzeros than shards → padding-only shards
        coords = np.array([[0, 0, 0], [16, 8, 4]], np.int32)
        x = SparseTensor(dims, coords, np.array([1.5, -2.0], np.float32))
    at = alto.build(x, n_partitions=2)
    factors = _factors(dims, R)
    plan = plan_mod.make_plan(at.meta, R, mesh=_mesh1(), backend=backend,
                              interpret=True)
    dense = x.todense()
    for mode in range(len(dims)):
        view = alto.oriented_view(at, mode)
        ref = cm.dense_mttkrp_reference(dense, factors, mode)
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        out = _simulated_sharded_mttkrp(plan, view, factors, mode, D)
        err = float(jnp.max(jnp.abs(out - ref))) / scale
        assert err < TOL, (case, backend, mode, err)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_shards=st.integers(1, 9),
       zipf=st.booleans())
def test_shard_carries_property(seed, n_shards, zipf):
    """Random streams (skewed included): sharded sum == oracle for every
    mode and any shard count, shards aligned with rows or not."""
    dims, R = (12, 8, 6), 5
    gen = synthetic.zipf_tensor if zipf else synthetic.uniform_tensor
    x = gen(dims, 150, seed=seed)
    at = alto.build(x, n_partitions=2)
    factors = _factors(dims, R, seed=seed % 100)
    plan = plan_mod.make_plan(at.meta, R, mesh=_mesh1())
    dense = x.todense()
    for mode in range(3):
        view = alto.oriented_view(at, mode)
        ref = cm.dense_mttkrp_reference(dense, factors, mode)
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        out = _simulated_sharded_mttkrp(plan, view, factors, mode, n_shards)
        assert float(jnp.max(jnp.abs(out - ref))) / scale < TOL


@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 50), rank=st.integers(1, 8),
       n_shards=st.integers(1, 7), seed=st.integers(0, 2**31 - 1))
def test_sharded_gram_equivalence(rows, rank, n_shards, seed):
    """Row-sharded AᵀA partials sum to the dense Gram (zero-row padding
    included), the combination `dist_cpd.sharded_gram` psums on device."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((rows, rank)).astype(np.float32))
    ref = A.T @ A
    pad = (-rows) % n_shards
    Ap = jnp.concatenate([A, jnp.zeros((pad, rank), A.dtype)]) if pad else A
    per = Ap.shape[0] // n_shards
    acc = sum(dist_cpd.local_gram(Ap[s * per:(s + 1) * per])
              for s in range(n_shards))
    np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sharded_gram_on_device():
    """The shard_map wrapper itself on a 1-device mesh (plumbing check)."""
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((13, 4)).astype(np.float32))
    out = dist_cpd.sharded_gram(_mesh1(), A)
    np.testing.assert_allclose(np.asarray(out), np.asarray(A.T @ A),
                               rtol=1e-5, atol=1e-5)


def test_sharded_mttkrp_on_device():
    """execute_mttkrp routes mesh-bearing plans through shard_map and
    matches the oracle on a 1-device mesh."""
    x = synthetic.uniform_tensor((11, 7, 5), 120, seed=2)
    at = alto.build(x, n_partitions=2)
    factors = _factors(x.dims, 4)
    plan = plan_mod.make_plan(at.meta, 4, mesh=_mesh1())
    views = plan_mod.build_views(at, plan)
    assert set(views) == {0, 1, 2}        # mesh plans orient every mode
    dense = x.todense()
    for mode in range(3):
        ref = cm.dense_mttkrp_reference(dense, factors, mode)
        out = plan_mod.execute_mttkrp(plan, at, views, factors, mode)
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        assert float(jnp.max(jnp.abs(out - ref))) / scale < TOL


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("pre_pi", [True, False])
def test_shard_phi_carries(backend, pre_pi):
    """Sharded CP-APR Φ: per-shard local_phi + host sum == the unsharded
    reference Φ, for both Π policies and backends (carry merge holds for
    the fused kernel too — B rows gather by global ids)."""
    dims, R, D = (14, 9, 6), 5, 4
    x = synthetic.uniform_tensor(dims, 250, seed=4, count_data=True)
    at = alto.build(x, n_partitions=2)
    mode = 0
    view = alto.oriented_view(at, mode)
    rng = np.random.default_rng(0)
    B = jnp.asarray(np.abs(rng.standard_normal((dims[mode], R))
                           ).astype(np.float32))
    factors = [jnp.asarray(np.abs(rng.standard_normal((I, R))
                                  ).astype(np.float32)) for I in dims]
    plan = plan_mod.make_plan(at.meta, R, mesh=_mesh1(), backend=backend,
                              interpret=True)
    # numpy oracle in view (row-sorted) order: Φ = scatter-add of
    # (v / max(<B[row], krp>, ε)) · krp by target row
    coords = np.asarray(alto.delinearize(at.meta.enc, view.words))
    krp_np = np.prod([np.asarray(f)[coords[:, m]]
                      for m, f in enumerate(factors) if m != mode], axis=0)
    rows_np = np.asarray(view.rows)
    denom = np.maximum((np.asarray(B)[rows_np] * krp_np).sum(-1), 1e-10)
    contrib = (np.asarray(view.values) / denom)[:, None] * krp_np
    ref = np.zeros((dims[mode], R), np.float32)
    np.add.at(ref, rows_np, contrib)
    ref = jnp.asarray(ref)
    pi_full = jnp.asarray(krp_np) if pre_pi else None
    bm = plan.modes[mode].block_m if backend == "pallas" else 1
    rows, words, values, pi = dist_cpd._pad_stream(
        view.rows, view.words, view.values, D * bm, pi=pi_full)
    per = rows.shape[0] // D
    out = None
    for s in range(D):
        sl = slice(s * per, (s + 1) * per)
        part = dist_cpd.local_phi(
            plan, mode, 1e-10, rows[sl], words[sl], values[sl], B,
            factors=None if pre_pi else factors,
            pi=pi[sl] if pre_pi else None)
        out = part if out is None else out + part
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(out - ref))) / scale < TOL


def test_sharded_phi_on_device():
    """execute_phi routes mesh-bearing plans through sharded_phi; matches
    the reference Φ on a 1-device mesh (shard_map plumbing + caching)."""
    x = synthetic.uniform_tensor((10, 8, 6), 150, seed=5, count_data=True)
    at = alto.build(x, n_partitions=2)
    R, mode = 4, 1
    view = alto.oriented_view(at, mode)
    rng = np.random.default_rng(1)
    B = jnp.asarray(np.abs(rng.standard_normal((x.dims[mode], R))
                           ).astype(np.float32))
    factors = [jnp.asarray(np.abs(rng.standard_normal((I, R))
                                  ).astype(np.float32)) for I in x.dims]
    mesh_plan = plan_mod.make_plan(at.meta, R, mesh=_mesh1())
    ref_plan = plan_mod.make_plan(at.meta, R, backend="reference")
    ref = plan_mod.execute_phi(ref_plan, at, view, B, mode, factors=factors)
    out = plan_mod.execute_phi(mesh_plan, at, view, B, mode,
                               factors=factors)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(out - ref))) / scale < TOL


def test_pipeline_params_roundtrip():
    """to_pipeline_params is losslessly inverted by from_pipeline_params
    and rejects indivisible stage counts / unsupported families."""
    from repro.configs import reduced_config
    from repro.dist import pipeline as PP
    from repro.models import model as M
    from repro.models.common import materialize

    cfg = reduced_config("glm4-9b", n_repeats=4)
    params = materialize(M.model_def(cfg), jax.random.PRNGKey(0))
    pp = PP.to_pipeline_params(cfg, params, 2)
    leaf = jax.tree.leaves(pp["blocks_0"])[0]
    assert leaf.shape[:2] == (2, 2)
    back = PP.from_pipeline_params(cfg, pp)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        PP.to_pipeline_params(cfg, params, 3)       # 4 repeats % 3 != 0
    enc_cfg = reduced_config("whisper-base")
    with pytest.raises(NotImplementedError):
        PP._forward_with_aux(enc_cfg, {}, jnp.zeros((2, 4), jnp.int32),
                             _mesh1(), 1)


def test_mesh_plan_resolution():
    """Mesh plans force the oriented *family* everywhere (one-hot merge or
    scratch carry, both shardable) and size shard-local tiles against each
    device's own VMEM: every mode's tiling is the single-device oriented
    plan's.
    """
    x = synthetic.blocked_tensor((64, 48, 32), 20_000, seed=0)
    at = alto.build(x, n_partitions=8)
    single = plan_mod.make_plan(at.meta, 16)
    meshed = plan_mod.make_plan(at.meta, 16, mesh=_mesh1())
    from repro.core import heuristics
    assert all(heuristics.is_oriented(mp.traversal) for mp in meshed.modes)
    assert meshed.n_shards == 1 and meshed.mesh_axis == "data"
    assert single.mesh is None and single.n_shards == 1
    pre = meshed.pi_policy is heuristics.PiPolicy.PRE
    for n, mp_m in enumerate(meshed.modes):
        assert mp_m == plan_mod.static_mode_plan(
            at.meta, n, 16, force_oriented=True, pre_pi=pre)


def test_mesh_plan_hashing_and_caching():
    """Mesh-bearing plans stay hashable/static: equal inputs → equal plans
    (same hash, cache hit); mesh presence changes the key."""
    x = synthetic.uniform_tensor((10, 8, 6), 100, seed=1)
    at = alto.build(x, n_partitions=4)
    m1, m2 = _mesh1(), _mesh1()
    p1 = plan_mod.make_plan(at.meta, 4, mesh=m1)
    p2 = plan_mod.make_plan(at.meta, 4, mesh=m2)
    p0 = plan_mod.make_plan(at.meta, 4)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert p1 != p0
    cache = {p1: "sharded", p0: "local"}   # executable-cache key usage
    assert cache[p2] == "sharded" and len(cache) == 2
    # static jit argument: two identical-mesh plans must not retrace
    import functools
    traces = []

    @functools.partial(jax.jit, static_argnames=("plan",))
    def fn(A, *, plan):
        traces.append(1)
        return A * plan.rank

    fn(jnp.ones((2,)), plan=p1)
    fn(jnp.ones((2,)), plan=p2)
    assert len(traces) == 1

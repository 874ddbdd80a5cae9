"""Pallas kernels (interpret mode) vs pure-jnp ref.py oracles,
swept over shapes / dtypes / partition counts / ranks."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alto, mttkrp as core_mttkrp
from repro.kernels import ops, ref
from repro.kernels.delinearize import delinearize_pallas
from repro.kernels.mttkrp import mttkrp_partials_pallas
from repro.kernels.cpapr_phi import phi_partials_pallas
from repro.sparse import synthetic
from repro.sparse.tensor import SparseTensor


def _setup(dims, nnz, L, R, seed=0, dtype=jnp.float32, count=True):
    x = synthetic.zipf_tensor(dims, nnz, seed=seed, count_data=count)
    at = alto.build(x, n_partitions=L)
    rng = np.random.default_rng(seed)
    factors = [jnp.asarray(
        np.abs(rng.standard_normal((I, R))).astype(np.float32) + 0.05
    ).astype(dtype) for I in dims]
    return x, at, factors


@pytest.mark.parametrize("dims,nnz,L,R", [
    ((48, 64, 32), 4000, 4, 16),
    ((48, 64, 32), 4000, 8, 32),
    ((16, 16, 16, 16), 3000, 4, 16),
    ((128, 8, 255), 2000, 2, 8),
    ((1000, 999, 17), 1000, 4, 16),
])
def test_mttkrp_kernel_shapes(dims, nnz, L, R):
    x, at, factors = _setup(dims, nnz, L, R)
    for mode in range(len(dims)):
        got = ops.mttkrp(at, factors, mode)
        want = core_mttkrp.mttkrp_recursive(at, factors, mode)
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        assert float(jnp.max(jnp.abs(got - want))) / scale < 1e-5


@pytest.mark.parametrize("r_block", [8, 16])
def test_mttkrp_kernel_rank_tiling(r_block):
    x, at, factors = _setup((40, 48, 24), 3000, 4, 32)
    got = ops.mttkrp(at, factors, 0, r_block=r_block)
    want = core_mttkrp.mttkrp_recursive(at, factors, 0)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    assert float(jnp.max(jnp.abs(got - want))) / scale < 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mttkrp_kernel_dtypes(dtype):
    x, at, factors = _setup((32, 48, 24), 2000, 4, 16, dtype=dtype)
    vals = at.values.astype(dtype)
    at2 = alto.AltoTensor(at.meta, at.words, vals, at.part_start,
                          at.part_end)
    got = ops.mttkrp(at2, factors, 1)
    want = core_mttkrp.mttkrp_recursive(at2, factors, 1)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-9
    diff = float(jnp.max(jnp.abs((got - want).astype(jnp.float32))))
    assert diff / scale < tol


@pytest.mark.parametrize("dims", [(64, 64), (48, 64, 32), (16, 8, 4, 2),
                                  (3, 5, 7, 11, 13)])
@pytest.mark.parametrize("block_m", [64, 256])
def test_delinearize_kernel_sweep(dims, block_m):
    x = synthetic.uniform_tensor(dims, 2048, seed=1)
    at = alto.build(x, n_partitions=4)
    got = ops.delinearize(at.meta.enc, at.words, block_m=block_m)
    want = ref.ref_delinearize(at.meta.enc, at.words)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("mode", [0, 1, 2])
@pytest.mark.parametrize("pre", [True, False])
def test_phi_kernel(mode, pre):
    x, at, factors = _setup((48, 64, 32), 4000, 4, 16)
    B = jnp.abs(factors[mode]) + 0.1
    coords = at.coords()
    pi = core_mttkrp.krp_rows(coords, factors, mode) if pre else None
    got = ops.cpapr_phi(at, B, mode,
                        factors=None if pre else factors, pi=pi)
    want = ref.ref_pull_reduction(
        ref.ref_phi_partials(at.meta.enc, mode, at.meta.temp_rows[mode],
                             1e-10, at.words, at.values, at.part_start, B,
                             factors=factors),
        at.part_start[:, mode], x.dims[mode])
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    assert float(jnp.max(jnp.abs(got - want))) / scale < 1e-5


def test_partials_match_ref_directly():
    """Kernel partials (pre-reduction) equal the ref oracle partials."""
    x, at, factors = _setup((40, 32, 24), 2000, 4, 16)
    pk = mttkrp_partials_pallas(at.meta.enc, 0, at.meta.temp_rows[0],
                                at.words, at.values, at.part_start,
                                factors)
    pr = ref.ref_mttkrp_partials(at.meta.enc, 0, at.meta.temp_rows[0],
                                 at.words, at.values, at.part_start,
                                 factors)
    scale = float(jnp.max(jnp.abs(pr))) + 1e-9
    assert float(jnp.max(jnp.abs(pk - pr))) / scale < 1e-5


def _phi_partials_pair(at, factors, mode, B, pre, eps=1e-10):
    """Recursive Φ partials (before the pull reduction) from the kernel
    and from the oracle, under ALTO-PRE or ALTO-OTF."""
    enc, T = at.meta.enc, at.meta.temp_rows[mode]
    if pre:
        kw = dict(pi=core_mttkrp.krp_rows(at.coords(), factors, mode))
    else:
        kw = dict(factors=factors)
    args = (enc, mode, T, eps, at.words, at.values, at.part_start, B)
    return phi_partials_pallas(*args, **kw), ref.ref_phi_partials(*args, **kw)


def _rel_err(got, want) -> float:
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    return float(jnp.max(jnp.abs(got - want))) / scale


@pytest.mark.parametrize("pre", [False, True], ids=["otf", "pre"])
def test_phi_partials_match_ref_directly(pre):
    x, at, factors = _setup((40, 32, 24), 2000, 4, 16)
    for mode in range(3):
        B = jnp.abs(factors[mode]) + 0.1
        got, want = _phi_partials_pair(at, factors, mode, B, pre)
        assert _rel_err(got, want) < 1e-5


def _few_rows_tensor(dims, mode, nnz, seed=0):
    """Distinct nonzeros whose ``mode`` coordinate takes three values, so
    a block's elements hit few Temp rows, mostly the previous one's."""
    rng = np.random.default_rng(seed)
    coords = np.stack([rng.integers(0, I, 3 * nnz) for I in dims], 1)
    coords[:, mode] = rng.choice([1, dims[mode] // 2, dims[mode] - 1],
                                 3 * nnz)
    coords = np.unique(coords, axis=0)
    coords = coords[rng.permutation(len(coords))[:nnz]]
    values = rng.integers(1, 10, len(coords)).astype(np.float32)
    return SparseTensor(dims, coords, values)


@pytest.mark.parametrize("pre", [False, True], ids=["otf", "pre"])
@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_phi_partials_repeated_rows(mode, pre):
    """Short 4-mode shape, consecutive elements on one Temp row: the
    scatter's read-modify-writes of a row follow one another."""
    dims, L, R = (24, 8, 40, 56), 2, 16
    at = alto.build(_few_rows_tensor(dims, mode, 2500), n_partitions=L)
    rows = np.asarray(at.coords())[:, mode].reshape(L, -1)
    assert (rows[:, 1:] == rows[:, :-1]).mean() > 0.5
    rng = np.random.default_rng(1)
    factors = [jnp.asarray(np.abs(rng.standard_normal((I, R)))
                           .astype(np.float32) + 0.05) for I in dims]
    B = factors[mode] + 0.1
    got, want = _phi_partials_pair(at, factors, mode, B, pre)
    assert _rel_err(got, want) < 1e-5


@pytest.mark.parametrize("pre", [False, True], ids=["otf", "pre"])
def test_phi_partials_eps_floor(pre):
    """Rows of B near zero put <B row, krp> under ε: those elements take
    v / ε, the others v / <B row, krp>, both as the oracle does."""
    x, at, factors = _setup((40, 32, 24), 2000, 4, 16)
    mode, eps = 0, 0.5
    B = np.abs(np.asarray(factors[mode])) + 0.1
    B[::2] *= 1e-3                         # even rows fall under ε
    B = jnp.asarray(B)
    coords = at.coords()
    dots = jnp.sum(B[coords[:, mode]]
                   * core_mttkrp.krp_rows(coords, factors, mode), axis=-1)
    floored = float(jnp.mean(dots < eps))
    assert 0.1 < floored < 0.9
    got, want = _phi_partials_pair(at, factors, mode, B, pre, eps=eps)
    assert _rel_err(got, want) < 1e-5

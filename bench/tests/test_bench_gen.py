"""The device generator: deterministic in the seed, the published nnz, and
clustered coordinates distinct within each cube with fiber reuse above
the plan's threshold of 4."""
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import gen, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
CLUSTERED = json.loads(
    (ROOT / "bench/traffic/apr.clustered.json").read_text())["coords"]
UNIFORM = {"distribution": "uniform"}
COUNTS = {"kind": "counts", "low": 1, "high": 9}
GAUSS = {"kind": "gaussian"}


def fiber_reuse(coords, dims):
    """Average nonzeros per non-empty fiber along each mode, counted from
    the distinct coordinates."""
    uniq = np.unique(coords, axis=0)
    return [len(uniq) / len(np.unique(np.delete(uniq, n, axis=1), axis=0))
            for n in range(len(dims))]


def _np(x):
    return np.asarray(x.coords), np.asarray(x.values)


@pytest.mark.parametrize("coords", [UNIFORM, CLUSTERED],
                         ids=["uniform", "clustered"])
def test_deterministic_in_seed(coords):
    dims, seed = (40, 30, 50), 2 ** 31 + 12345
    a = _np(gen.generate(dims, 5000, coords, GAUSS, seed))
    b = _np(gen.generate(dims, 5000, coords, GAUSS, seed))
    c = _np(gen.generate(dims, 5000, coords, GAUSS, seed + 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].min() >= 0 and (a[0].max(axis=0) < np.array(dims)).all()


def test_seeds_past_32_bits_do_not_alias():
    lo, hi = gen.seed_key(5), gen.seed_key(2 ** 32 + 5)
    assert not np.array_equal(np.asarray(lo), np.asarray(hi))


@pytest.mark.parametrize("name", ["uber"])
@pytest.mark.parametrize("coords", [UNIFORM, CLUSTERED],
                         ids=["uniform", "clustered"])
def test_published_nnz(name, coords):
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    fn = gen._generator(tuple(cfg["dims"]), cfg["nnz"], gen._freeze(coords),
                        gen._freeze(cfg["values"]))
    c, v = jax.eval_shape(fn, gen.seed_key(0))
    assert c.shape == (cfg["nnz"], len(cfg["dims"])) and v.shape == (cfg["nnz"],)


@pytest.mark.parametrize("dims", [(300, 200, 400), (183, 24, 114, 171)])
def test_clustered_distinct_per_cube_and_high_reuse(dims):
    edge, density = CLUSTERED["edge"], CLUSTERED["density"]
    per = round(edge ** len(dims) * density)
    nnz = 6 * per
    c, v = _np(gen.generate(dims, nnz, CLUSTERED, COUNTS, 7))
    assert c.shape == (nnz, len(dims))
    for b in range(6):
        block = c[b * per:(b + 1) * per]
        assert len(np.unique(block, axis=0)) == per
        span = block.max(axis=0) - block.min(axis=0)
        assert (span < edge).all()
    assert min(fiber_reuse(c, dims)) > 4
    assert v.min() >= 1 and v.max() <= 9


def test_cycle_walking_for_cubes_off_a_power_of_two():
    coords = {"distribution": "clustered", "edge": 12, "density": 0.5}
    dims = (40, 24, 60, 60)
    per = round(12 ** 4 * 0.5)
    c, _ = _np(gen.generate(dims, 3 * per, coords, COUNTS, 3))
    for b in range(3):
        assert len(np.unique(c[b * per:(b + 1) * per], axis=0)) == per


def test_overlapping_cubes_stay_as_summed_entries():
    # Every cube of a 16^3 tensor sits at the origin: the second cube's
    # cells repeat many of the first's.
    dims, per = (16, 16, 16), 2048
    x = gen.generate(dims, 2 * per, CLUSTERED, GAUSS, 11)
    c, v = _np(x)
    assert len(np.unique(c, axis=0)) < 2 * per
    dense = np.zeros(dims)
    np.add.at(dense, tuple(c.T), v)
    rng = np.random.default_rng(0)
    factors = [rng.random((I, 4)).astype(np.float32) for I in dims]
    want = np.einsum("ijk,jr,kr->ir", dense, factors[1], factors[2])
    cols, vals, chunk = reference._pad(x.coords, x.values)
    m = reference._reduce(cols, vals, [jax.numpy.asarray(f) for f in factors],
                          None, mode=0, eps=0.0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(m), want, rtol=1e-4, atol=1e-4)

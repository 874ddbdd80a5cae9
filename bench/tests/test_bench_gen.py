"""The device generator: deterministic in the seed, the published nnz,
clustered coordinates distinct within each cube with fiber reuse above
the plan's threshold of 4, and each coordinate law found by name."""
import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import gen, reference
from bench.files import BenchError

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c["file"] for c in SPEC["configs"]}
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


# sha256 (first 32 hex digits) of a tensor's int32 coordinates then its
# float32 values, little-endian, from the generator as it stood before the
# laws moved to bench/coords/: every seed gives the same tensor, bit for bit.
PINNED = {
    ("uniform", 3, 7): "4535016ca4c6a940e71ecad74cb138d0",
    ("uniform", 3, 2 ** 32 + 2 ** 31 + 19): "9b7b2ff01935e431d29feee11f7d6e64",
    ("uniform", 4, 7): "c65e449cbc123c487dd98623e9aa694e",
    ("uniform", 4, 2 ** 32 + 2 ** 31 + 19): "eee6e78e5207e29cb41ddf2d978de433",
    ("clustered", 3, 7): "6f00968711146b5c21f4667c2350d3bc",
    ("clustered", 3, 2 ** 32 + 2 ** 31 + 19):
        "946809a34187e3004200aa5df8844bcd",
    ("clustered", 4, 7): "4b12057400b23778bae4d87bf22e34b3",
    ("clustered", 4, 2 ** 32 + 2 ** 31 + 19):
        "f28f6f8173c691a234320bf2658f4e7c",
}
PINNED_SHAPES = {3: ((40, 30, 50), 5000), 4: ((18, 24, 40, 30), 30_000)}


@pytest.mark.parametrize("law,modes,seed", PINNED)
def test_tensor_pinned_bit_for_bit(law, modes, seed):
    coords = {"uniform": UNIFORM, "clustered": CLUSTERED}[law]
    dims, nnz = PINNED_SHAPES[modes]
    c, v = _np(gen.generate(dims, nnz, coords, GAUSS, seed))
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(c, "<i4").tobytes())
    h.update(np.ascontiguousarray(v, "<f4").tobytes())
    assert h.hexdigest()[:32] == PINNED[law, modes, seed]


def test_unknown_distribution_names_the_missing_file():
    with pytest.raises(BenchError, match="bench/coords/no_such_law.py"):
        gen.generate((8, 8, 8), 100, {"distribution": "no_such_law"},
                     GAUSS, 1)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("coords", [UNIFORM, CLUSTERED],
                         ids=["uniform", "clustered"])
def test_published_nnz(name, coords):
    cfg = json.loads((ROOT / CONFIGS[name]).read_text())
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

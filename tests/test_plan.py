"""Execution-plan layer: Pallas kernels (interpret) vs the dense oracle.

Parity on adversarial shapes — non-power-of-two dims, nnz not divisible by
the partition count, empty tensors/modes, ranks whose only divisors are
awkward, duplicate coordinates — for BOTH traversals, plus plan-resolution
and executable-cache behaviour.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alto, heuristics, mttkrp as cm, plan as plan_mod
from repro.kernels import ops
from repro.sparse import synthetic
from repro.sparse.tensor import SparseTensor

TOL = 1e-5


def _factors(dims, R, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((I, R)).astype(np.float32))
            for I in dims]


def _parity_all_modes(x, L, R, seed=0):
    """Both Pallas traversals + plan dispatch vs dense einsum, all modes."""
    at = alto.build(x, n_partitions=L)
    factors = _factors(x.dims, R, seed=seed)
    dense = x.todense()
    plan = plan_mod.make_plan(at.meta, R, backend="pallas", interpret=True)
    views = {m: alto.oriented_view(at, m) for m in range(x.ndim)}
    for mode in range(x.ndim):
        mp = plan.modes[mode]
        assert R % mp.r_block == 0          # plan only picks divisors
        ref = cm.dense_mttkrp_reference(dense, factors, mode)
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        rec = ops.mttkrp(at, factors, mode, r_block=mp.r_block,
                         interpret=True)
        ori = ops.mttkrp_oriented(views[mode], factors,
                                  block_m=mp.block_m, r_block=mp.r_block,
                                  interpret=True)
        via_plan = plan_mod.execute_mttkrp(plan, at, views, factors, mode)
        for name, out in (("recursive", rec), ("oriented", ori),
                          ("plan", via_plan)):
            err = float(jnp.max(jnp.abs(out - ref))) / scale
            assert err < TOL, (name, mode, err)


@pytest.mark.parametrize("dims,nnz,L,R", [
    ((13, 7, 5), 97, 4, 6),        # non-pow2 dims, nnz % L != 0
    ((37, 18, 11, 3), 451, 8, 7),  # 4-D, prime-ish rank (r_block in {1,7})
    ((20, 1, 12), 150, 4, 16),     # length-1 mode (zero index bits)
    ((257, 255, 2), 1000, 16, 12), # dims straddling powers of two
])
def test_plan_parity_adversarial_shapes(dims, nnz, L, R):
    x = synthetic.uniform_tensor(dims, nnz, seed=3)
    _parity_all_modes(x, L, R)


def test_plan_parity_empty_tensor():
    """nnz=0: every kernel must return exact zeros of the right shape."""
    x = SparseTensor((9, 6, 4), np.zeros((0, 3), np.int32),
                     np.zeros((0,), np.float32))
    at = alto.build(x, n_partitions=4)
    factors = _factors(x.dims, 5)
    plan = plan_mod.make_plan(at.meta, 5, backend="pallas", interpret=True)
    views = {m: alto.oriented_view(at, m) for m in range(3)}
    for mode in range(3):
        out = plan_mod.execute_mttkrp(plan, at, views, factors, mode)
        assert out.shape == (x.dims[mode], 5)
        assert float(jnp.max(jnp.abs(out))) == 0.0


def test_plan_parity_duplicate_coordinates():
    """Duplicate nonzeros must sum, matching the dense scatter-add oracle."""
    rng = np.random.default_rng(7)
    base = np.stack([rng.integers(0, I, size=60) for I in (11, 9, 7)],
                    axis=1).astype(np.int32)
    coords = np.concatenate([base, base[:25], base[:10]], axis=0)
    values = rng.standard_normal(coords.shape[0]).astype(np.float32)
    x = SparseTensor((11, 9, 7), coords, values)   # NOT deduplicated
    _parity_all_modes(x, L=4, R=8)


def test_plan_parity_rank_not_multiple_of_default_tile():
    """Odd ranks: the plan must fall back to a dividing r_block and the
    kernels must reject a non-dividing override."""
    x = synthetic.uniform_tensor((24, 18, 10), 400, seed=1)
    at = alto.build(x, n_partitions=4)
    for R in (1, 7, 13):
        plan = plan_mod.make_plan(at.meta, R, backend="pallas",
                                  interpret=True)
        for mp in plan.modes:
            assert R % mp.r_block == 0
    factors = _factors(x.dims, 13)
    with pytest.raises(ValueError):
        ops.mttkrp(at, factors, 0, r_block=8, interpret=True)


def test_oriented_blocks_smaller_than_block_m():
    """Streams shorter than one block are padded, not rejected."""
    x = synthetic.uniform_tensor((6, 5, 4), 17, seed=2)
    at = alto.build(x, n_partitions=2)
    factors = _factors(x.dims, 4)
    view = alto.oriented_view(at, 0)
    got = ops.mttkrp_oriented(view, factors, block_m=256, interpret=True)
    ref = cm.dense_mttkrp_reference(x.todense(), factors, 0)
    scale = float(jnp.max(jnp.abs(ref))) + 1e-9
    assert float(jnp.max(jnp.abs(got - ref))) / scale < TOL


def test_phi_oriented_vs_reference_both_policies():
    """Oriented Φ kernel (PRE and OTF) vs the reference-backend Φ."""
    x = synthetic.zipf_tensor((19, 23, 11), 700, seed=4, count_data=True)
    at = alto.build(x, n_partitions=4)
    rng = np.random.default_rng(0)
    R = 6
    factors = [jnp.asarray(np.abs(rng.standard_normal((I, R))
                                  ).astype(np.float32) + 0.05)
               for I in x.dims]
    pallas = plan_mod.make_plan(at.meta, R, backend="pallas",
                                interpret=True)
    ref = plan_mod.make_plan(at.meta, R, backend="reference")
    for mode in range(x.ndim):
        B = jnp.abs(factors[mode]) + 0.1
        view = alto.oriented_view(at, mode)
        coords = alto.delinearize(at.meta.enc, view.words)
        pi = cm.krp_rows(coords, factors, mode)
        want = plan_mod.execute_phi(ref, at, view, B, mode, factors=factors)
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        otf = plan_mod.execute_phi(pallas, at, view, B, mode,
                                   factors=factors)
        pre = plan_mod.execute_phi(pallas, at, view, B, mode, pi=pi)
        assert float(jnp.max(jnp.abs(otf - want))) / scale < TOL
        assert float(jnp.max(jnp.abs(pre - want))) / scale < TOL


def test_vmem_budgeting_scales_blocks_down():
    """Tighter budgets must shrink r_block/block_m, never break divisors."""
    x = synthetic.uniform_tensor((64, 48, 32), 5000, seed=0)
    at = alto.build(x, n_partitions=4)
    R = 32
    roomy = plan_mod.make_plan(at.meta, R, vmem_limit=plan_mod.VMEM_BYTES)
    tight = plan_mod.make_plan(at.meta, R, vmem_limit=64 * 1024)
    for big, small in zip(roomy.modes, tight.modes):
        assert small.r_block <= big.r_block
        assert small.block_m <= big.block_m
        assert R % small.r_block == 0
        assert small.block_m >= plan_mod.MIN_BLOCK_M
    # the budget estimate itself must be monotone in the block sizes
    assert (plan_mod.oriented_vmem_bytes(at.meta, 0, 256, 8)
            < plan_mod.oriented_vmem_bytes(at.meta, 0, 512, 8))
    # (rank tiles below 128 lanes pad to a full vreg row, so compare
    # lane-aligned tiles)
    assert (plan_mod.recursive_vmem_bytes(at.meta, 0, 128)
            < plan_mod.recursive_vmem_bytes(at.meta, 0, 256))


def test_executable_cache_reuses_compilations():
    """Two calls with identical static meta must share one executable."""
    x = synthetic.uniform_tensor((30, 20, 10), 500, seed=0)
    at = alto.build(x, n_partitions=4)
    factors = _factors(x.dims, 8)
    ops.cache_clear()
    ops.mttkrp(at, factors, 0, interpret=True)
    n1 = ops.cache_size()
    ops.mttkrp(at, factors, 0, interpret=True)   # hit
    assert ops.cache_size() == n1
    ops.mttkrp(at, factors, 1, interpret=True)   # new mode -> new entry
    assert ops.cache_size() == n1 + 1
    # same shape but different meta (different nnz) -> new entry
    y = synthetic.uniform_tensor((30, 20, 10), 400, seed=1)
    ops.mttkrp(alto.build(y, n_partitions=4), factors, 0, interpret=True)
    assert ops.cache_size() == n1 + 2


def test_plan_is_static_and_hashable():
    """Plans must be usable as static jit arguments / cache keys."""
    x = synthetic.uniform_tensor((16, 12, 8), 200, seed=0)
    at = alto.build(x, n_partitions=2)
    a = plan_mod.make_plan(at.meta, 4, backend="reference")
    b = plan_mod.make_plan(at.meta, 4, backend="reference")
    assert a == b and hash(a) == hash(b)
    assert a != plan_mod.make_plan(at.meta, 8, backend="reference")


def test_drivers_reject_mismatched_plan_rank():
    from repro.core import cpals, cpapr
    x = synthetic.uniform_tensor((10, 8, 6), 100, seed=0)
    at = alto.build(x, n_partitions=2)
    plan = plan_mod.make_plan(at.meta, 4)
    with pytest.raises(ValueError, match="rank"):
        cpals.cp_als(at, rank=6, n_iters=1, plan=plan)
    with pytest.raises(ValueError, match="rank"):
        cpapr.cp_apr(at, rank=6, plan=plan)


def test_plan_routes_per_forced_traversal(monkeypatch):
    """The plan layer must dispatch to the kernel its traversal names.

    Low-reuse modes go output-oriented; on this small tensor the stream
    dwarfs the mode dim, so the traffic refinement picks the scratch-carry
    kernel. Capping the VMEM budget below the carry's resident-output
    floor must fall back to the one-hot merge kernel.
    """
    x = synthetic.uniform_tensor((16, 12, 8), 300, seed=0)
    at = alto.build(x, n_partitions=2)
    factors = _factors(x.dims, 4)
    calls = []
    real = {"rec": ops.mttkrp, "ori": ops.mttkrp_oriented,
            "carry": ops.mttkrp_oriented_carry}
    for tag, fn in real.items():
        monkeypatch.setattr(
            ops, {"rec": "mttkrp", "ori": "mttkrp_oriented",
                  "carry": "mttkrp_oriented_carry"}[tag],
            lambda *a, _tag=tag, _fn=fn, **k: calls.append(_tag)
            or _fn(*a, **k))
    # budget below the carry floor for mode 0 (but roomy for one-hot)
    tight = plan_mod.oriented_carry_vmem_bytes(
        at.meta, 0, plan_mod.MIN_BLOCK_M, 1) - 1
    cases = ((10.0, dict(), "rec"),
             (1.5, dict(), "carry"),
             (1.5, dict(vmem_limit=tight), "ori"))
    for reuse, kw, expect in cases:
        meta = dataclasses.replace(at.meta, fiber_reuse=(reuse,) * 3)
        at2 = alto.AltoTensor(meta, at.words, at.values, at.part_start,
                              at.part_end)
        plan = plan_mod.make_plan(meta, 4, backend="pallas",
                                  interpret=True, **kw)
        views = plan_mod.build_views(at2, plan)
        calls.clear()
        plan_mod.execute_mttkrp(plan, at2, views, factors, 0)
        assert calls == [expect], (reuse, kw, calls)

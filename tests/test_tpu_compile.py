"""Mosaic compiles of the main-path kernels for a TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described but not attached (`topologies.get_topology_desc`). Interpret
mode accepts constructs Mosaic refuses (in-kernel gathers, scatters,
prefix scans, blocks off the (8, 128) tiling), so these tests lower every
traversal's kernel at nell-2 widths — FROSTT's 12,092 × 9,184 × 28,818,
rank 16, the plan's tiling — with a few thousand blocks, and check that
the compiled program fits one v5e's 16 GB of HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.core import alto, heuristics, plan as plan_mod
from repro.core import views as views_mod
from repro.core.encoding import make_encoding
from repro.kernels import cpapr_phi, mttkrp, mttkrp_oriented

DIMS = (12_092, 9_184, 28_818)          # nell-2 (FROSTT)
RANK = 16
NNZ = 3 * 2 ** 21                       # 3,072 blocks of 2,048 nonzeros
HBM_BYTES = 16 * 10 ** 9                # one v5e
Traversal = heuristics.Traversal


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # Compiles for a described chip cannot be read back from the
    # persistent cache; keep it out of the way.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _meta(reuse: float) -> alto.AltoMeta:
    """nell-2 metadata; every partition spans every mode (worst Temp)."""
    return alto.AltoMeta(enc=make_encoding(DIMS), nnz=NNZ, n_partitions=8,
                         temp_rows=DIMS, fiber_reuse=(reuse,) * 3)


def _mode_plan(meta, mode, traversal) -> plan_mod.ModePlan:
    """The plan's preferred tiling for ``traversal`` on this mode."""
    for mp in plan_mod.candidate_mode_plans(meta, mode, RANK):
        if mp.traversal is traversal:
            return mp
    raise AssertionError(f"plan offers no {traversal} tiling")


def _shapes(sharding, meta, mode=None, B=False):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    stream = dict(rows=sds((NNZ,), jnp.int32),
                  words=sds((NNZ, meta.enc.n_words), jnp.uint32),
                  values=sds((NNZ,), jnp.float32))
    factors = [sds((I, RANK), jnp.float32) for I in DIMS]
    return stream, factors, (sds((DIMS[mode], RANK), jnp.float32)
                             if B else None), sds


def _compile_fits(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return ma


def test_recursive_mttkrp_compiles(one_chip):
    meta = _meta(reuse=100.0)
    mode = 2                                    # the largest Temp
    mp = _mode_plan(meta, mode, Traversal.RECURSIVE)
    s, factors, _, sds = _shapes(one_chip, meta)
    fn = functools.partial(mttkrp.mttkrp_partials_pallas, meta.enc, mode,
                           meta.temp_rows[mode], r_block=mp.r_block,
                           interpret=False)
    _compile_fits(fn, s["words"], s["values"],
                  sds((meta.n_partitions, 3), jnp.int32), factors)


def test_onehot_oriented_mttkrp_compiles(one_chip):
    meta = _meta(reuse=1.0)
    mp = _mode_plan(meta, 0, Traversal.OUTPUT_ORIENTED)
    s, factors, _, _ = _shapes(one_chip, meta)
    fn = functools.partial(mttkrp_oriented.mttkrp_oriented_partials_pallas,
                           meta.enc, 0, block_m=mp.block_m,
                           r_block=mp.r_block, interpret=False)
    _compile_fits(fn, s["rows"], s["words"], s["values"], factors)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_carry_mttkrp_compiles(one_chip, mode):
    meta = _meta(reuse=1.0)
    mp = plan_mod.static_mode_plan(meta, mode, RANK)
    assert mp.traversal is Traversal.ORIENTED_CARRY    # nell-2's choice
    assert mp.block_m % 1024 == 0
    s, factors, _, _ = _shapes(one_chip, meta)
    fn = functools.partial(mttkrp_oriented.mttkrp_oriented_carry_pallas,
                           meta.enc, mode, block_m=mp.block_m,
                           r_block=mp.r_block, interpret=False)
    _compile_fits(fn, s["rows"], s["words"], s["values"], factors)


@pytest.mark.parametrize("pre", [False, True])
def test_carry_phi_compiles(one_chip, pre):
    meta = _meta(reuse=1.0)
    mode = 2
    mp = _mode_plan(meta, mode, Traversal.ORIENTED_CARRY)
    s, factors, B, sds = _shapes(one_chip, meta, mode=mode, B=True)
    kw = dict(block_m=mp.block_m, interpret=False)
    fn = functools.partial(mttkrp_oriented.phi_oriented_carry_pallas,
                           meta.enc, mode, 1e-10)
    if pre:
        pi = sds((NNZ, RANK), jnp.float32)
        _compile_fits(lambda r, w, v, b, p: fn(r, w, v, b, pi=p, **kw),
                      s["rows"], s["words"], s["values"], B, pi)
    else:
        _compile_fits(lambda r, w, v, b, f: fn(r, w, v, b, factors=f, **kw),
                      s["rows"], s["words"], s["values"], B, factors)


def _compile_phi_within_model(monkeypatch, meta, mode, pre, args):
    """Compile the recursive Φ kernel with Mosaic's scoped-VMEM limit set
    to `plan.phi_recursive_vmem_bytes`, so scratch the model leaves out
    fails the compile."""
    budget = plan_mod.phi_recursive_vmem_bytes(meta, mode, RANK,
                                               pre_pi=pre)
    monkeypatch.setattr(cpapr_phi, "compiler_params",
                        lambda *sem: pltpu.CompilerParams(
                            dimension_semantics=sem,
                            vmem_limit_bytes=budget))
    fn = functools.partial(cpapr_phi.phi_partials_pallas, meta.enc, mode,
                           meta.temp_rows[mode], 1e-10, interpret=False)
    if pre:
        _compile_fits(lambda w, v, ps, b, p: fn(w, v, ps, b, pi=p), *args)
    else:
        _compile_fits(lambda w, v, ps, b, f: fn(w, v, ps, b, factors=f),
                      *args)


def test_recursive_phi_compiles(one_chip, monkeypatch):
    meta = _meta(reuse=100.0)
    mode = 0
    mp = _mode_plan(meta, mode, Traversal.RECURSIVE)
    assert mp.block_m == plan_mod.MIN_BLOCK_M      # the kernel's fixed block
    s, factors, B, sds = _shapes(one_chip, meta, mode=mode, B=True)
    _compile_phi_within_model(
        monkeypatch, meta, mode, False,
        (s["words"], s["values"], sds((meta.n_partitions, 3), jnp.int32), B,
         factors))


UBER = (183, 24, 1_140, 1_717)          # FROSTT uber
UBER_NNZ = 3_309_496                    # 3,309,490 padded to 8 partitions


@pytest.mark.parametrize("pre", [False, True], ids=["otf", "pre"])
@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_recursive_phi_compiles_at_uber_shape(one_chip, monkeypatch, mode,
                                              pre):
    """CP-APR's benchmark tensor: four modes, two index words, every
    partition spanning every mode."""
    meta = alto.AltoMeta(enc=make_encoding(UBER), nnz=UBER_NNZ,
                         n_partitions=8, temp_rows=UBER,
                         fiber_reuse=(8.0,) * 4)
    assert meta.enc.n_words == 2

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    operand = (sds((UBER_NNZ, RANK), jnp.float32) if pre
               else [sds((I, RANK), jnp.float32) for I in UBER])
    _compile_phi_within_model(
        monkeypatch, meta, mode, pre,
        (sds((UBER_NNZ, 2), jnp.uint32), sds((UBER_NNZ,), jnp.float32),
         sds((8, 4), jnp.int32), sds((UBER[mode], RANK), jnp.float32),
         operand))


def test_plan_tilings_are_chip_legal():
    """Every tiling the plan can emit has a 1024-multiple block and a rank
    tile that is the rank or a multiple of 128."""
    meta = _meta(reuse=1.0)
    for rank in (7, 16, 256, 384):
        for mode in range(3):
            for mp in plan_mod.candidate_mode_plans(meta, mode, rank):
                assert mp.block_m % 1024 == 0
                assert mp.r_block == rank or mp.r_block % 128 == 0


def test_stream_checksum_stays_lane_dense(one_chip):
    """The view cache's stream checksum must not relayout the (M, 2)
    word array into 128-lane tiles: at nell-2's 76.9M nonzeros an eager
    ``ravel`` of it asked for 39 GB on the chip."""
    words = jax.ShapeDtypeStruct((76_879_424, 2), jnp.uint32,
                                 sharding=one_chip)
    compiled = views_mod._u32_mix.lower(words, 0x85EBCA6B).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < words.size * 4


def test_sharded_sweep_compiles_on_four_chips(topo):
    """The row-sharded CP-ALS sweep (`dist.cpd`) on a 2x2 mesh built the
    way `jax.make_mesh` builds it, with explicit axes: the TPU's SVD
    behind ``pinv`` refused their sharding-in-types until plans
    normalized the mesh."""
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.core import cpals
    from repro.dist import cpd
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("data",),
                             axis_types=(AxisType.Explicit,))
    meta = _meta(reuse=1.0)
    plan = plan_mod.make_plan(meta, RANK, mesh=mesh, backend="pallas",
                              interpret=False)
    rep = NamedSharding(plan.mesh, P())
    shd = NamedSharding(plan.mesh, P("data"))
    W = meta.enc.n_words

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    at = alto.AltoTensor(meta, sds((NNZ, W), jnp.uint32),
                         sds((NNZ,), jnp.float32),
                         sds((meta.n_partitions, 3), jnp.int32),
                         sds((meta.n_partitions, 3), jnp.int32))
    views = {m: alto.OrientedView(meta, m, sds((NNZ,), jnp.int32, shd),
                                  sds((NNZ, W), jnp.uint32, shd),
                                  sds((NNZ,), jnp.float32, shd),
                                  sds((NNZ,), jnp.int32, shd))
             for m in range(3)}
    factors = [sds((I, RANK), jnp.float32) for I in DIMS]
    sweep = functools.partial(
        cpals._sweep, plan,
        gram_fn=functools.partial(cpd.sharded_gram, plan.mesh))
    _compile_fits(sweep, at, views, factors, sds((RANK,), jnp.float32))

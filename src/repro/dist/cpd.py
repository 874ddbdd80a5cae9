"""Distributed CP-ALS / CP-APR over row-range shards (paper §4.1/§4.2).

ALTO's linearized nonzero stream is "streamed from memory and amenable to
parallel execution"; this module is that claim made literal on a device
mesh. The oriented view — device-built and process-cached by default
(`core.views`, backed by `core.alto.oriented_view_device`) — sorts
nonzeros by the target-mode row, and the sharding is the simplest one that
preserves every single-device invariant: cut the sorted stream into
per-device **contiguous, equal-size slices** (`shard_map` over the mesh's
first axis). The shard-local row-range slices are carved by `shard_map`'s
input specs from the device-resident view arrays, so from COO ingest to
psum merge nothing round-trips through the host: build_device → cached
view → in-jit padding → per-device slice. Each device runs the *existing* single-device oriented segment
reduction on its slice — reference jnp `segment_sum` or the Pallas kernel
plus `kernels.ops.segment_merge`, exactly as the plan dictates — into a
full-width dense ``(I_n, R)`` output, and the outputs are combined with
``psum``.

Invariants (the carry-merge correctness condition):

* the stream stays **row-sorted**; a shard is a contiguous slice, so each
  device's rows are a sorted run and `segment_sum(indices_are_sorted)` /
  the kernel's run-rank scan stay valid;
* row ids are **global**, so a row whose run spans a shard boundary
  yields one partial sum per adjacent device and the ``psum`` adds them —
  the cross-device analogue of the in-block boundary carry that
  `ops.segment_merge` resolves, and of the paper's "atomics only at
  partition boundaries";
* plans are **static and hashable** (mesh included), so the sharded
  executables cache and jit exactly like the single-device ones;
* padding replicates the last element with zero values, contributing
  nothing while keeping shard shapes equal (perfect workload balance, the
  §4.1 property, inherited by construction from the equal-size cut).

`distributed_cp_als` is the driver: it *is* `core.cpals.cp_als` run under
a mesh-bearing plan (MTTKRP placement comes from the plan routing) with
`sharded_gram` injected as the sweep's Gram hook — one sweep
implementation, so its fit sequence matches the single-device one to
float32 reduction-order noise (≪ 1e-3).

The shard-local reductions are pure functions of their slice, so the unit
tests simulate the mesh by calling them per shard and summing on the host
— bit-identical to what ``psum`` computes on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import alto
from repro.core import cpals
from repro.core import ingest as ingest_mod
from repro.core.encoding import make_encoding
from repro.core import heuristics
from repro.core import plan as plan_mod
from repro.core.alto import AltoTensor, OrientedView
from repro.core.mttkrp import krp_rows
from repro.kernels import mttkrp_oriented as _oriented
from repro.kernels import ops
from repro.sparse.tensor import SparseTensor


# The padding rule is part of the carry-merge correctness condition;
# there is exactly one implementation (shared with the kernel wrappers).
_pad_stream = ops.pad_sorted_stream


def _shard_mult(plan: plan_mod.ExecutionPlan, mode: int) -> int:
    """Global padding multiple: per-shard length must divide block_m on
    the Pallas path (the kernel's grid is exact, no partial blocks)."""
    bm = plan.modes[mode].block_m if plan.backend == "pallas" else 1
    return plan.n_shards * bm


# ---------------------------------------------------------------------------
# Shard-local reductions (pure — unit-testable without a mesh)
# ---------------------------------------------------------------------------

def local_mttkrp(plan: plan_mod.ExecutionPlan, mode: int, rows, words,
                 values, factors) -> jnp.ndarray:
    """One device's oriented MTTKRP over its slice: full-width (I_n, R).

    Exactly the single-device oriented reduction (plan-selected backend);
    summing this over all slices of a sorted stream equals the unsharded
    result because `ops.segment_merge` / `segment_sum` scatter to global
    rows (see module docstring).
    """
    meta = plan.meta
    I_n = meta.dims[mode]
    if plan.backend == "pallas":
        mp = plan.modes[mode]
        if mp.traversal is heuristics.Traversal.ORIENTED_CARRY:
            # Shard-local scratch-carry scan: the final (I_n, R) rows come
            # straight out of the kernel — boundary-run carries survive
            # only at shard boundaries, where the psum merges them.
            return _oriented.mttkrp_oriented_carry_pallas(
                meta.enc, mode, rows, words, values, list(factors),
                block_m=mp.block_m, r_block=mp.r_block,
                interpret=ops._auto_interpret(plan.interpret))
        partials = _oriented.mttkrp_oriented_partials_pallas(
            meta.enc, mode, rows, words, values, list(factors),
            block_m=mp.block_m, r_block=mp.r_block,
            interpret=ops._auto_interpret(plan.interpret))
        return ops.segment_merge(partials, rows, I_n)
    coords = alto.delinearize(meta.enc, words)
    contrib = values[:, None] * krp_rows(coords, factors, mode)
    return jax.ops.segment_sum(contrib, rows, num_segments=I_n,
                               indices_are_sorted=True)


def local_phi(plan: plan_mod.ExecutionPlan, mode: int, eps: float, rows,
              words, values, B, factors=None, pi=None) -> jnp.ndarray:
    """One device's fused CP-APR Φ over its slice: full-width (I_n, R).

    ``B`` is replicated (the Φ denominator needs the full-rank row
    ``B[i_n, :]``, available locally because rows are global ids); the Π
    rows (ALTO-PRE) travel with the stream shard.
    """
    meta = plan.meta
    I_n = meta.dims[mode]
    if plan.backend == "pallas":
        mp = plan.modes[mode]
        if mp.traversal is heuristics.Traversal.ORIENTED_CARRY:
            return _oriented.phi_oriented_carry_pallas(
                meta.enc, mode, eps, rows, words, values, B,
                factors=list(factors) if factors is not None else None,
                pi=pi, block_m=mp.block_m,
                interpret=ops._auto_interpret(plan.interpret))
        partials = _oriented.phi_oriented_partials_pallas(
            meta.enc, mode, eps, rows, words, values, B,
            factors=list(factors) if factors is not None else None, pi=pi,
            block_m=mp.block_m,
            interpret=ops._auto_interpret(plan.interpret))
        return ops.segment_merge(partials, rows, I_n)
    if pi is None:
        coords = alto.delinearize(meta.enc, words)
        pi = krp_rows(coords, factors, mode)
    denom = jnp.maximum(
        jnp.sum(jnp.take(B, rows, axis=0) * pi, axis=-1), eps)
    contrib = (values / denom)[:, None] * pi
    return jax.ops.segment_sum(contrib, rows, num_segments=I_n,
                               indices_are_sorted=True)


def local_gram(A_shard: jnp.ndarray) -> jnp.ndarray:
    """One device's Gram contribution over its row slice: AᵀA is a sum of
    rank-1 outer products, so row shards combine by plain addition."""
    return jnp.matmul(A_shard.T, A_shard,
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# shard_map wrappers (the mesh-visible primitives)
# ---------------------------------------------------------------------------

def sharded_mttkrp(plan: plan_mod.ExecutionPlan, at: AltoTensor,
                   views: dict[int, OrientedView] | None, factors,
                   mode: int) -> jnp.ndarray:
    """MTTKRP for one mode with the stream row-range-sharded over the mesh.

    Entry point `core.plan.execute_mttkrp` routes mesh-bearing plans to.
    """
    if plan.mesh is None:
        raise ValueError("sharded_mttkrp needs a mesh-bearing plan")
    if not views or mode not in views:
        raise ValueError(
            "mesh-bearing plans orient every mode; build views with "
            "repro.core.plan.build_views(at, plan)")
    view = views[mode]
    ax = plan.mesh_axis
    local = functools.partial(local_mttkrp, plan, mode)

    def build():
        @functools.partial(jax.shard_map, mesh=plan.mesh,
                           in_specs=(P(ax), P(ax), P(ax), P()),
                           out_specs=P(),
                           check_vma=False)  # pallas_call has no rep rule
        def sharded(rows, words, values, factors):
            return jax.lax.psum(local(rows, words, values, factors), ax)

        def run(rows, words, values, factors):
            rows, words, values, _ = _pad_stream(rows, words, values,
                                                 _shard_mult(plan, mode))
            return sharded(rows, words, values, factors)

        return jax.jit(run)

    fn = ops._cached_executable(("dist_mttkrp", plan, mode), build)
    return fn(view.rows, view.words, view.values, list(factors))


def sharded_phi(plan: plan_mod.ExecutionPlan, at: AltoTensor,
                view: OrientedView | None, B: jnp.ndarray, mode: int,
                factors=None, pi: jnp.ndarray | None = None,
                eps: float = 1e-10) -> jnp.ndarray:
    """CP-APR Φ row reduction, row-range-sharded (`execute_phi` routing)."""
    if plan.mesh is None:
        raise ValueError("sharded_phi needs a mesh-bearing plan")
    if view is None:
        raise ValueError("mesh-bearing plans orient every mode; pass the "
                         "mode's oriented view")
    ax = plan.mesh_axis
    pre_pi = pi is not None
    local = functools.partial(local_phi, plan, mode, eps)
    pi_spec = P(ax) if pre_pi else P()

    def build():
        @functools.partial(
            jax.shard_map, mesh=plan.mesh,
            in_specs=(P(ax), P(ax), P(ax), P(), P(), pi_spec),
            out_specs=P(),
            check_vma=False)              # pallas_call has no rep rule
        def sharded(rows, words, values, B, factors, pi):
            return jax.lax.psum(
                local(rows, words, values, B, factors=factors, pi=pi), ax)

        def run(rows, words, values, B, factors, pi):
            rows, words, values, pi = _pad_stream(
                rows, words, values, _shard_mult(plan, mode), pi=pi)
            return sharded(rows, words, values, B, factors, pi)

        return jax.jit(run)

    fn = ops._cached_executable(("dist_phi", plan, mode, eps, pre_pi),
                                build)
    return fn(view.rows, view.words, view.values, B,
              list(factors) if factors is not None else None, pi)


def shard_views(plan: plan_mod.ExecutionPlan,
                views: dict[int, OrientedView]) -> dict[int, OrientedView]:
    """Place each oriented view row-range-sharded over the plan's mesh.

    The stream is padded once to the plan's shard multiple (the padding
    rule of `sharded_mttkrp`, the Π permutation replicating its final
    entry like the rows) and cut into contiguous per-device slices by a
    `NamedSharding`, so every sweep's `shard_map` finds its slices in
    place instead of resharding a view that sits on one device.
    """
    ax = plan.mesh_axis
    out = {}
    for mode, v in views.items():
        rows, words, values, _ = _pad_stream(v.rows, v.words, v.values,
                                             _shard_mult(plan, mode))
        pad = rows.shape[0] - v.rows.shape[0]
        perm = (jnp.concatenate([v.perm, jnp.broadcast_to(v.perm[-1:],
                                                          (pad,))])
                if pad and v.perm.shape[0] else v.perm)
        place = functools.partial(jax.device_put,
                                  device=NamedSharding(plan.mesh, P(ax)))
        out[mode] = OrientedView(meta=v.meta, mode=mode, rows=place(rows),
                                 words=place(words), values=place(values),
                                 perm=place(perm))
    return out


def sharded_gram(mesh, A: jnp.ndarray) -> jnp.ndarray:
    """AᵀA with the rows of ``A`` sharded over the mesh's first axis and
    the per-device Grams combined by ``psum`` (zero-row padding)."""
    ax = mesh.axis_names[0]
    D = int(mesh.shape[ax])

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(ax),),
                           out_specs=P(), check_vma=False)
        def sharded(A_shard):
            return jax.lax.psum(local_gram(A_shard), ax)

        def run(A):
            pad = (-A.shape[0]) % D
            if pad:
                A = jnp.concatenate(
                    [A, jnp.zeros((pad, A.shape[1]), A.dtype)])
            return sharded(A)

        return jax.jit(run)

    fn = ops._cached_executable(("dist_gram", mesh), build)
    return fn(A)


# ---------------------------------------------------------------------------
# Distributed incremental ingest (sharded COO deltas)
# ---------------------------------------------------------------------------

def sharded_append_delta(at: AltoTensor, coords, values, mesh, *,
                         policy: str = "sum", dims=None,
                         n_partitions: int | None = None,
                         compute_reuse: bool | None = None,
                         invalidate_stale: bool = True) -> AltoTensor:
    """`core.ingest.append_delta` with the delta's linearization sharded
    over ``mesh`` — the distributed ingest entry point for COO deltas
    that arrive row-partitioned across hosts/devices.

    Linearization is the only embarrassingly parallel stage (pure
    per-element bit gather, no collective), so it runs shard-local under
    `shard_map` — the batch is zero-padded to a shard multiple, split
    over the mesh's first axis, and the reassembled words are sliced
    back to the real length before `ingest.append_linearized` runs the
    (inherently global) merge sort. Bitwise identical to the local
    `append_delta`: padding never reaches the merge, and the per-shard
    bit gather is elementwise.
    """
    coords = np.asarray(coords, dtype=np.int32).reshape(-1, len(at.dims))
    new_dims = alto.grown_dims(at.dims, coords, dims)
    D = coords.shape[0]
    if D == 0:
        return ingest_mod.append_delta(
            at, coords, values, policy=policy, dims=new_dims,
            n_partitions=n_partitions, compute_reuse=compute_reuse,
            invalidate_stale=invalidate_stale)
    enc = make_encoding(new_dims)
    ax = mesh.axis_names[0]
    S = int(mesh.shape[ax])
    pad = (-D) % S
    if pad:
        coords = np.concatenate(
            [coords, np.zeros((pad, coords.shape[1]), np.int32)])
    Dp = coords.shape[0]

    def build():
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P(ax),),
                           out_specs=P(ax))
        def sharded(c):
            return alto.linearize(enc, c)

        return jax.jit(sharded)

    fn = ops._cached_executable(("dist_delta_linearize", enc, mesh, Dp),
                                build)
    words = fn(jnp.asarray(coords))[:D]
    return ingest_mod.append_linearized(
        at, words, values, new_dims, policy=policy,
        n_partitions=n_partitions, compute_reuse=compute_reuse,
        invalidate_stale=invalidate_stale)


# ---------------------------------------------------------------------------
# Distributed CP-ALS driver
# ---------------------------------------------------------------------------

def distributed_cp_als(x: SparseTensor | AltoTensor, rank: int, mesh, *,
                       n_iters: int = 50, tol: float = 1e-5, seed: int = 0,
                       n_partitions: int | None = None,
                       backend: str | None = None,
                       interpret: bool | None = None,
                       tune: str = "off", warm_start=None):
    """CP-ALS with MTTKRP and Grams sharded over ``mesh`` (GPipe's sibling
    seam: data-parallel over the nonzero stream, model-replicated factors).

    This IS `core.cpals.cp_als` — same sweep, same host-side float64
    Kolda–Bader fit — run under a mesh-bearing plan (MTTKRP routed to
    `sharded_mttkrp` by `plan.execute_mttkrp`) with `sharded_gram` as the
    sweep's Gram hook. The only deltas from single-device are reduction
    order (shard partials added by psum), so fits match to well under
    1e-3. Returns ``(lam, factors, fits)``.

    Per-shard tile budgets come from the plan layer's corrected
    per-kernel footprints: `make_plan(mesh=...)` divides the VMEM budget
    by the shard count and sizes ``block_m`` against BOTH the oriented
    MTTKRP footprint and the fused Φ footprint (full-rank resident B,
    `plan.phi_oriented_vmem_bytes`), so shard-local blocks stay honest on
    big modes where B dominates. ``tune`` ("off"|"auto"|"force") swaps
    the analytic mesh plan for a measured one: the autotuner times the
    *actual sharded executables* per candidate and persists the winner
    keyed on the shard count (`core.autotune`).
    """
    if isinstance(x, AltoTensor):
        at = x
    else:
        # Device ingest: format generation is a jitted sort on device,
        # and the oriented views the sharded merge consumes come from
        # the shared cache (cpals' plan_mod.build_views) — no host
        # argsort or host→device stream copy anywhere in the chain.
        D = int(mesh.shape[mesh.axis_names[0]])
        at = alto.build_device(x, n_partitions=n_partitions or D)
    plan = plan_mod.make_plan(at.meta, rank, backend=backend,
                              interpret=interpret, mesh=mesh,
                              tune=tune, at=at)
    views = shard_views(plan, plan_mod.build_views(at, plan))
    res = cpals.cp_als(at, rank, n_iters=n_iters, tol=tol, seed=seed,
                       plan=plan, views=views, warm_start=warm_start,
                       gram_fn=functools.partial(sharded_gram, plan.mesh))
    return res.lam, res.factors, res.fits

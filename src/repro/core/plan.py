"""Execution plans: resolve the paper's adaptive heuristics into kernels.

Paper §4.2/§4.3 (Table 1). Invariants: plans are frozen and hashable
(static jit arguments, compiled-executable cache keys); every decision is
made from static `AltoMeta`, never from traced data.

The paper selects a traversal (recursive vs output-oriented) and a Π
policy (PRE vs OTF) per tensor/mode at runtime. On the JAX/TPU target
every such decision must be *static* — jit control flow cannot branch on
data — so this module turns the heuristics plus the tensor's static
metadata (`AltoMeta`) into an :class:`ExecutionPlan`: a frozen, hashable
description of exactly which compiled kernel variant runs for every
(mode, rank) combination, with all block sizes resolved.

The plan answers four questions the call sites used to guess at:

  * **traversal** per mode — `heuristics.choose_traversal` (fiber reuse vs
    the 4-memory-op buffered accumulation cost, §4.2), then for
    output-oriented modes the one-hot-merge vs scratch-carry refinement
    (`heuristics.choose_oriented_variant`: modelled HBM traffic, gated on
    the carry kernel's resident-output VMEM feasibility);
  * **rank blocking** (`r_block`) and **nonzero blocking** (`block_m`) —
    chosen so the Pallas kernel's per-grid-step VMEM footprint fits the
    accelerator budget, from `AltoMeta` (temp_rows, dims, dtype) instead of
    the caller hand-picking tile sizes;
  * **backend** — "pallas" (interpret-mode on CPU, Mosaic on TPU) or
    "reference" (the pure-jnp traversals in `core.mttkrp`, retained as the
    plan's always-available oracle backend);
  * **placement** — a plan built with ``mesh=`` routes every row reduction
    through the sharded oriented merge in `repro.dist.cpd`: the row-sorted
    nonzero stream is cut into per-device contiguous shards, each device
    runs the single-device segment reduction locally, and boundary-run
    carries plus the final rows are combined by ``psum``. Mesh-bearing
    plans force the output-oriented family for every mode (either
    variant — one-hot merge or shard-local scratch carry; row-range
    partitioning needs the row-sorted stream; the recursive traversal's
    partition intervals overlap arbitrarily across devices). Every
    device of a mesh has its own VMEM, so shard-local tiles get the
    whole per-core budget.

Because `ExecutionPlan` is hashable (``jax.sharding.Mesh`` included) it can
travel as a static jit argument and doubles as the key of the
compiled-executable cache in `kernels.ops`.

Two refinements over the original analytic model:

  * **Φ-specific footprints** — the fused CP-APR Φ kernels run at FULL
    rank with the whole (I_mode, R) B operand resident per grid step
    (plus the gathered block rows, and under ALTO-OTF the whole other
    factors); `phi_oriented_vmem_bytes` / `phi_recursive_vmem_bytes`
    account for that and co-constrain `choose_block_m`, closing the
    VMEM model gap the ROADMAP flagged (B resident but unbudgeted).
  * **measured plans** — ``make_plan(..., tune="auto"|"force")`` swaps
    the analytic answer for a measured one: `core.autotune` times every
    feasible candidate (`candidate_mode_plans`, static choice first)
    through the compiled-executable cache and persists winners in a
    versioned on-disk plan store, so later processes get the measured
    plan back with zero timing runs.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core import heuristics
from repro.core import mttkrp as core_mttkrp
from repro.core import telemetry
from repro.core.alto import AltoMeta, AltoTensor, OrientedView, delinearize
from repro.kernels.mttkrp import DEFAULT_BLOCK_M

# VMEM the kernels' blocks may use per grid step. A v5e core has 128 MiB
# of VMEM; the kernels raise Mosaic's scoped limit to
# `kernels.mttkrp.VMEM_LIMIT_BYTES` and the plan budgets below it, leaving
# room for Mosaic's own temporaries. Interpret mode ignores VMEM, but the
# plan sizes identically so the CPU tests exercise the chip's tilings.
VMEM_BYTES = 96 * 1024 * 1024

# Nonzero blocks are 1-D tiles of the lane-dense stream arrays, which XLA
# lays out in 1024-element tiles on the chip: block_m is a multiple of
# 1024, and the recursive kernels always run this smallest block. The
# one-hot kernel's (block_m, block_m) operand caps it above.
MIN_BLOCK_M = DEFAULT_BLOCK_M
MAX_BLOCK_M = 2048
# A rank tile is the whole rank or a multiple of the 128-lane vreg width.
LANES = 128
SUBLANE_BYTES = 32          # one (8, 128) f32 tile row group: 8 rows x 4 B


@dataclasses.dataclass(frozen=True)
class ModePlan:
    """Resolved execution choices for one target mode."""
    mode: int
    traversal: heuristics.Traversal
    r_block: int        # rank tile: the rank, or a 128-multiple dividing it
    block_m: int        # nonzero block (a multiple of MIN_BLOCK_M)
    temp_rows: int      # recursive Temp height (static VMEM bound)
    vmem_bytes: int     # estimated per-grid-step footprint (MTTKRP kernel)
    phi_vmem_bytes: int = 0   # fused Φ kernel footprint (full rank, B resident)


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Out-of-core chunking decision (all ints — hashable, jit-static).

    Present on a plan iff the padded oriented stream plus the resident
    working set overflows the configured device byte budget; the chunked
    executors in `kernels.ops` then stream block-aligned slices of the
    host-resident stream (`core.stream.HostStream`) through device
    memory with cross-chunk carry chains.
    """
    chunk_m: int          # elements per chunk (multiple of every block_m)
    n_chunks: int         # ceil(stream_len / chunk_m) — the executed grid
    device_bytes: int     # the budget the choice was made against
    stream_bytes: int     # in-core working set that overflowed it


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static per-(tensor, rank) kernel routing, hashable for jit/caching."""
    meta: AltoMeta
    rank: int
    backend: str                       # "pallas" | "reference"
    interpret: bool | None             # None = auto (non-TPU -> interpret)
    pi_policy: heuristics.PiPolicy
    modes: tuple[ModePlan, ...]
    # Multi-device placement: shard the oriented row reduction over the
    # first axis of this mesh (None = single device). Mesh is hashable, so
    # mesh-bearing plans remain valid static jit arguments / cache keys.
    mesh: jax.sharding.Mesh | None = None
    # Out-of-core: non-None routes every oriented mode through the
    # chunked executors (the plan forces the carry family then). Default
    # None keeps plans from older stores / callers valid unchanged.
    streaming: StreamPlan | None = None

    def mode_plan(self, mode: int) -> ModePlan:
        return self.modes[mode]

    def traversals(self) -> tuple[str, ...]:
        return tuple(m.traversal.value for m in self.modes)

    @property
    def mesh_axis(self) -> str | None:
        """Mesh axis the row-sorted stream is sharded over (first axis)."""
        return self.mesh.axis_names[0] if self.mesh is not None else None

    @property
    def n_shards(self) -> int:
        """Row-range shard count (1 without a mesh)."""
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.mesh.axis_names[0]])


# ---------------------------------------------------------------------------
# VMEM budgeting
# ---------------------------------------------------------------------------

def _tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a (rows, cols) array in (8·4/itemsize, 128) tiles —
    what Mosaic allocates, not rows·cols·itemsize."""
    sub = SUBLANE_BYTES // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // LANES) * LANES) * itemsize


def _stream_bytes(meta: AltoMeta, block_m: int, n_modes: int) -> int:
    """Index-word blocks (lane-dense, double-buffered) plus the VMEM
    staging tile of ``n_modes`` decoded coordinate rows."""
    return (2 * meta.enc.n_words * block_m * 4
            + _tile_bytes(n_modes, block_m))


def _factor_bytes(meta: AltoMeta, mode: int, cols: int,
                  dtype_bytes: int) -> int:
    """Every other mode's resident (single-buffered) factor tile."""
    return sum(_tile_bytes(I, cols, dtype_bytes)
               for m, I in enumerate(meta.dims) if m != mode)


def _onehot_bytes(block_m: int, dtype_bytes: int) -> int:
    """The (block_m, block_m) one-hot operand and the iota it compares."""
    return block_m * block_m * (4 + dtype_bytes)


def recursive_vmem_bytes(meta: AltoMeta, mode: int, r_block: int,
                         dtype_bytes: int = 4) -> int:
    """Per-grid-step VMEM of the recursive (partition Temp) kernel.

    Word blocks plus the staging tile of all N coordinates, the
    double-buffered (T, r_block) Temp output block, and the resident
    factor tiles of the other modes. Values and staged coordinates live
    in SMEM and are not counted.
    """
    T = meta.temp_rows[mode]
    return (_stream_bytes(meta, MIN_BLOCK_M, meta.enc.ndim)
            + 2 * _tile_bytes(T, r_block, dtype_bytes)
            + _factor_bytes(meta, mode, r_block, dtype_bytes))


def oriented_vmem_bytes(meta: AltoMeta, mode: int, block_m: int,
                        r_block: int, dtype_bytes: int = 4) -> int:
    """Per-grid-step VMEM of the output-oriented one-hot segment kernel.

    Dominated by the (block_m, block_m) one-hot; plus the word blocks and
    coordinate staging, the double-buffered segment-id block, the
    contribution tile, the double-buffered segment-sum output block, and
    the resident factor tiles.
    """
    return (_stream_bytes(meta, block_m, meta.enc.ndim - 1)
            + 2 * block_m * 4
            + _tile_bytes(block_m, r_block, dtype_bytes)
            + _onehot_bytes(block_m, dtype_bytes)
            + 2 * _tile_bytes(block_m, r_block, dtype_bytes)
            + _factor_bytes(meta, mode, r_block, dtype_bytes))


def oriented_carry_vmem_bytes(meta: AltoMeta, mode: int, block_m: int,
                              r_block: int, dtype_bytes: int = 4) -> int:
    """Per-grid-step VMEM of the scratch-carry oriented kernel.

    No (block_m, block_m) one-hot, but the ``(I_mode, r_block)`` output
    tile stays resident (double-buffered) across the whole sequential
    scan, next to the (1, r_block) carry-in and carry-out blocks. Word
    blocks, staging, the contribution tile and the resident factor tiles
    as in the one-hot kernel.
    """
    return (_stream_bytes(meta, block_m, meta.enc.ndim - 1)
            + _tile_bytes(block_m, r_block, dtype_bytes)
            + 2 * _tile_bytes(meta.dims[mode], r_block, dtype_bytes)
            + 4 * _tile_bytes(1, r_block, dtype_bytes)
            + _factor_bytes(meta, mode, r_block, dtype_bytes))


def _phi_operand_bytes(meta: AltoMeta, mode: int, block_m: int, rank: int,
                       dtype_bytes: int, pre_pi: bool) -> int:
    """The Φ kernels' Khatri-Rao operand plus its index decode: the
    double-buffered (block_m, R) Π tile under ALTO-PRE (no words read),
    or the word blocks, staging and whole other factors under ALTO-OTF."""
    if pre_pi:
        return 2 * _tile_bytes(block_m, rank, dtype_bytes)
    return (_stream_bytes(meta, block_m, meta.enc.ndim - 1)
            + _factor_bytes(meta, mode, rank, dtype_bytes))


def phi_oriented_vmem_bytes(meta: AltoMeta, mode: int, block_m: int,
                            rank: int, dtype_bytes: int = 4,
                            pre_pi: bool = False) -> int:
    """Per-grid-step VMEM of the *oriented fused Φ* kernel — full rank.

    The Φ kernel has no rank tiling (the denominator ``<B[i_n,:], krp>``
    needs the full rank per element) and keeps the whole ``(I_mode, R)``
    B operand resident. Term by term: the segment-id block, the
    ``(block_m, block_m)`` one-hot, **resident B**, the contribution
    tile, the double-buffered segment-sum output block, and the Π
    operand (`_phi_operand_bytes`).
    """
    return (2 * block_m * 4
            + _onehot_bytes(block_m, dtype_bytes)
            + _tile_bytes(meta.dims[mode], rank, dtype_bytes)
            + _tile_bytes(block_m, rank, dtype_bytes)
            + 2 * _tile_bytes(block_m, rank, dtype_bytes)
            + _phi_operand_bytes(meta, mode, block_m, rank, dtype_bytes,
                                 pre_pi))


def phi_oriented_carry_vmem_bytes(meta: AltoMeta, mode: int, block_m: int,
                                  rank: int, dtype_bytes: int = 4,
                                  pre_pi: bool = False) -> int:
    """Per-grid-step VMEM of the *scratch-carry fused Φ* kernel.

    Same full-rank accounting as :func:`phi_oriented_vmem_bytes` with the
    one-hot and the segment ids replaced by the carry pattern's resident
    terms: the double-buffered ``(I_mode, R)`` output block next to the
    resident ``(I_mode, R)`` B operand, and the carry-in/out blocks.
    """
    return (_tile_bytes(meta.dims[mode], rank, dtype_bytes)
            + _tile_bytes(block_m, rank, dtype_bytes)
            + 2 * _tile_bytes(meta.dims[mode], rank, dtype_bytes)
            + 4 * _tile_bytes(1, rank, dtype_bytes)
            + _phi_operand_bytes(meta, mode, block_m, rank, dtype_bytes,
                                 pre_pi))


def phi_recursive_vmem_bytes(meta: AltoMeta, mode: int, rank: int,
                             dtype_bytes: int = 4,
                             pre_pi: bool = False) -> int:
    """Per-grid-step VMEM of the *recursive fused Φ* kernel — full rank.

    Word blocks and the staging of all N coordinates (the target mode
    indexes Temp and B), the double-buffered ``(T, R)`` partition Temp,
    resident B, the Π tile or resident other factors, and the kernel's
    three ``(block_m, R)`` scratch tiles (Φ rows, gathered B rows, value
    splats). Nothing here is tunable (Φ runs full rank, at the fixed
    block), so this footprint is advisory — it is reported in the plan
    and used by the per-shard budget checks.
    """
    T = meta.temp_rows[mode]
    if pre_pi:
        operands = 2 * _tile_bytes(MIN_BLOCK_M, rank, dtype_bytes)
    else:
        operands = _factor_bytes(meta, mode, rank, dtype_bytes)
    return (_stream_bytes(meta, MIN_BLOCK_M, meta.enc.ndim)
            + 2 * _tile_bytes(T, rank, dtype_bytes)
            + _tile_bytes(meta.dims[mode], rank, dtype_bytes)
            + operands
            + 3 * _tile_bytes(MIN_BLOCK_M, rank, dtype_bytes))


def rank_tiles(rank: int) -> list[int]:
    """Rank tiles a kernel can run, largest first: the whole rank, then
    every 128-multiple that divides it (a lane-aligned minor block)."""
    return [rank] + [d for d in range(rank - LANES, 0, -LANES)
                     if d % LANES == 0 and rank % d == 0]


def block_sizes() -> list[int]:
    """Nonzero block sizes the plan may emit, largest first."""
    out, bm = [], MAX_BLOCK_M
    while bm >= MIN_BLOCK_M:
        out.append(bm)
        bm //= 2
    return out


def _largest_fitting_tile(model, rank: int, vmem_limit: int) -> int:
    """Largest rank tile whose footprint ``model(rb)`` fits the budget.

    Always returns a tile the kernels can run (`rank_tiles`); if even the
    smallest overflows, the budget is advisory and that tile is returned
    — the kernel still compiles, Mosaic just may refuse its VMEM.
    """
    tiles = rank_tiles(rank)
    for rb in tiles:
        if model(rb) <= vmem_limit:
            return rb
    return tiles[-1]


def choose_rank_block(meta: AltoMeta, mode: int, rank: int,
                      dtype_bytes: int = 4,
                      vmem_limit: int = VMEM_BYTES) -> int:
    """Largest rank tile whose recursive footprint fits VMEM."""
    return _largest_fitting_tile(
        lambda rb: recursive_vmem_bytes(meta, mode, rb, dtype_bytes),
        rank, vmem_limit)


def choose_rank_block_oriented(meta: AltoMeta, mode: int, rank: int,
                               dtype_bytes: int = 4,
                               vmem_limit: int = VMEM_BYTES) -> int:
    """Largest rank tile whose *oriented* footprint fits VMEM.

    Sized at the minimum nonzero block so the rank tile is constrained by
    the resident factor tiles (the term that actually scales with rank),
    not by the recursive kernel's Temp buffer — a mode routed oriented
    never runs that kernel. `choose_block_m` then shrinks the block to
    fit the chosen tile.
    """
    return _largest_fitting_tile(
        lambda rb: oriented_vmem_bytes(meta, mode, MIN_BLOCK_M, rb,
                                       dtype_bytes), rank, vmem_limit)


def choose_rank_block_carry(meta: AltoMeta, mode: int, rank: int,
                            dtype_bytes: int = 4,
                            vmem_limit: int = VMEM_BYTES) -> int:
    """Largest rank tile whose *carry* footprint fits VMEM.

    The carry kernel's resident ``(I_mode, r_block)`` output tile makes
    the rank tile the lever that actually bounds its footprint, so the
    tile is sized at the minimum nonzero block like the oriented sibling.
    """
    return _largest_fitting_tile(
        lambda rb: oriented_carry_vmem_bytes(meta, mode, MIN_BLOCK_M, rb,
                                             dtype_bytes), rank, vmem_limit)


def carry_fits_vmem(meta: AltoMeta, mode: int, rank: int,
                    dtype_bytes: int = 4,
                    vmem_limit: int = VMEM_BYTES) -> bool:
    """True iff the scratch-carry kernel is feasible for this mode at all
    (smallest tiling: the smallest rank tile, ``MIN_BLOCK_M``).

    Unlike the other budgets this one is a hard *routing* gate, not
    advisory: the carry kernel's whole advantage is the VMEM-resident
    output tile, so when ``I_mode`` alone overflows the budget the
    traversal should route to the one-hot merge path instead of
    spilling — `heuristics.choose_oriented_variant` consumes this.
    """
    return oriented_carry_vmem_bytes(meta, mode, MIN_BLOCK_M,
                                     rank_tiles(rank)[-1],
                                     dtype_bytes) <= vmem_limit


# ---------------------------------------------------------------------------
# Out-of-core (HBM) byte models and chunk-size selection
# ---------------------------------------------------------------------------
#
# The VMEM models above size one grid step; these size what the DEVICE as
# a whole must hold. In-core, that is the full padded oriented stream plus
# the chunk-independent residency (factors, output accumulator, Φ's B
# operand, the carry). When it overflows the configured device budget the
# plan goes streaming: only two chunks (double buffer) of the stream are
# in flight at a time. Every model is exact byte accounting —
# `tests/test_heuristics_boundaries.py` pins them term by term.

def stream_elem_bytes(meta: AltoMeta, dtype_bytes: int = 4) -> int:
    """Device bytes per streamed element: words + row + value."""
    return meta.enc.n_words * 4 + 4 + dtype_bytes


def streaming_resident_bytes(meta: AltoMeta, rank: int,
                             dtype_bytes: int = 4) -> int:
    """Chunk-independent device residency of the chunked executors.

    All factors (Σ I·R — the chunk kernels read every other mode's
    factor), the worst-mode (I_max, R) output accumulator, Φ's resident
    (I_max, R) B operand, and the (1,) + (1, R) carry pair.
    """
    factors = sum(meta.dims) * rank * dtype_bytes
    i_max = max(meta.dims)
    out_accum = i_max * rank * dtype_bytes
    b_operand = i_max * rank * dtype_bytes
    carry = 4 + rank * dtype_bytes
    return factors + out_accum + b_operand + carry


def incore_working_set_bytes(meta: AltoMeta, rank: int,
                             dtype_bytes: int = 4) -> int:
    """Device bytes the IN-CORE oriented path holds: the whole padded
    stream plus the chunk-independent residency. The quantity the
    streaming decision compares against the device budget."""
    return (heuristics.stream_len(meta) * stream_elem_bytes(meta,
                                                            dtype_bytes)
            + streaming_resident_bytes(meta, rank, dtype_bytes))


def chunk_hbm_bytes(meta: AltoMeta, chunk_m: int, rank: int,
                    dtype_bytes: int = 4) -> int:
    """Device bytes the chunked executors hold at chunk size ``chunk_m``:
    TWO in-flight chunks (the compute chunk and the prefetched next one)
    plus the chunk-independent residency."""
    return (2 * chunk_m * stream_elem_bytes(meta, dtype_bytes)
            + streaming_resident_bytes(meta, rank, dtype_bytes))


def needs_streaming(meta: AltoMeta, rank: int, device_bytes: int,
                    dtype_bytes: int = 4) -> bool:
    """True iff the in-core working set overflows ``device_bytes``."""
    return incore_working_set_bytes(meta, rank, dtype_bytes) > device_bytes


def chunk_count(meta: AltoMeta, chunk_m: int) -> int:
    """Chunks the executors run: ceil over the partition-padded stream.

    Independent of block_m — the block padding never adds a chunk,
    because chunk_m is a multiple of every block_m and the smallest
    block_m-multiple ≥ Mp is ≤ the smallest chunk_m-multiple ≥ Mp.
    """
    return -(-heuristics.stream_len(meta) // chunk_m)


def choose_chunk_m(meta: AltoMeta, rank: int, device_bytes: int,
                   align: int, dtype_bytes: int = 4) -> int:
    """Largest ``align``-multiple chunk whose double-buffered footprint
    fits ``device_bytes``, capped at the aligned stream length.

    ``align`` is the max block_m across the plan's modes (block_m are
    powers of two, so the max is a common multiple) — chunk boundaries
    then sit on block boundaries for every mode, the bitwise-parity
    precondition. If even one aligned chunk overflows, the budget is
    advisory and one ``align`` chunk is returned (same contract as the
    VMEM choosers: the executor still runs, the device just holds more
    than asked).
    """
    elem = stream_elem_bytes(meta, dtype_bytes)
    resident = streaming_resident_bytes(meta, rank, dtype_bytes)
    avail = device_bytes - resident
    per_chunk = max(0, avail) // (2 * elem)
    chunk = max(align, (per_chunk // align) * align)
    padded = -(-heuristics.stream_len(meta) // align) * align
    return min(chunk, padded)


def default_device_bytes() -> int | None:
    """Process-wide device byte budget: ``$REPRO_DEVICE_BYTES`` or None
    (None = assume device-resident, never stream)."""
    v = os.environ.get("REPRO_DEVICE_BYTES", "")
    return int(v) if v else None


def _mttkrp_vmem_model(traversal: heuristics.Traversal):
    """The MTTKRP footprint function the traversal actually runs."""
    if traversal is heuristics.Traversal.ORIENTED_CARRY:
        return oriented_carry_vmem_bytes
    return oriented_vmem_bytes


def _phi_vmem_model(traversal: heuristics.Traversal):
    """The fused-Φ footprint function the traversal actually runs."""
    if traversal is heuristics.Traversal.ORIENTED_CARRY:
        return phi_oriented_carry_vmem_bytes
    return phi_oriented_vmem_bytes


def choose_block_m(meta: AltoMeta, mode: int, r_block: int,
                   dtype_bytes: int = 4,
                   vmem_limit: int = VMEM_BYTES,
                   rank: int | None = None,
                   pre_pi: bool = False,
                   traversal: heuristics.Traversal =
                   heuristics.Traversal.OUTPUT_ORIENTED) -> int:
    """Largest nonzero block (`block_sizes`) for the oriented kernels.

    The oriented stream is padded to a multiple of block_m by `ops`, so the
    choice is free of divisibility constraints on nnz.  ``traversal``
    selects the footprint model being sized (one-hot merge vs scratch
    carry — the carry kernel swaps the (block_m, block_m) one-hot for a
    resident output tile).  When ``rank`` is given the block must also
    fit the *fused Φ* kernel's footprint for the same traversal
    (:func:`phi_oriented_vmem_bytes` / :func:`phi_oriented_carry_vmem_bytes`
    — full rank, resident B): the same ``ModePlan.block_m`` feeds both
    the MTTKRP and the Φ kernel, so the block is sized for whichever is
    hungrier.  The Φ constraint only applies while it is *satisfiable*
    (fits at ``MIN_BLOCK_M``): on a huge mode the resident ``I_mode·R``
    B term alone can exceed any budget, and shrinking the block cannot
    fix that — Φ spills regardless, so the unsatisfiable constraint must
    not drag the MTTKRP kernel down to the minimum block.  If even
    ``MIN_BLOCK_M`` overflows the budget is advisory and ``MIN_BLOCK_M``
    is returned (the kernel still compiles, just spills — same contract
    as `choose_rank_block`).
    """
    mttkrp_model = _mttkrp_vmem_model(traversal)
    phi_model = _phi_vmem_model(traversal)
    phi_binding = rank is not None and phi_constraint_active(
        meta, mode, rank, dtype_bytes, vmem_limit, pre_pi=pre_pi,
        traversal=traversal)

    def fits(bm: int) -> bool:
        if mttkrp_model(meta, mode, bm, r_block,
                        dtype_bytes) > vmem_limit:
            return False
        if phi_binding and phi_model(
                meta, mode, bm, rank, dtype_bytes,
                pre_pi=pre_pi) > vmem_limit:
            return False
        return True

    for bm in block_sizes():
        if fits(bm):
            return bm
    return MIN_BLOCK_M


def phi_constraint_active(meta: AltoMeta, mode: int, rank: int,
                          dtype_bytes: int = 4,
                          vmem_limit: int = VMEM_BYTES,
                          pre_pi: bool = False,
                          traversal: heuristics.Traversal =
                          heuristics.Traversal.OUTPUT_ORIENTED) -> bool:
    """True iff the fused-Φ footprint can fit the budget at all for this
    mode (at ``MIN_BLOCK_M``) — i.e. the Φ constraint is binding rather
    than vacuous.  An unsatisfiable Φ budget is advisory (the kernel
    spills at any block size) and must not throttle the MTTKRP tiling."""
    return _phi_vmem_model(traversal)(meta, mode, MIN_BLOCK_M, rank,
                                      dtype_bytes,
                                      pre_pi=pre_pi) <= vmem_limit


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

def default_backend() -> str:
    """Pallas/Mosaic on TPU; pure-jnp reference elsewhere (the interpreted
    Pallas path stays available by passing backend="pallas" explicitly)."""
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _mode_plan(meta: AltoMeta, mode: int, rank: int,
               traversal: heuristics.Traversal, r_block: int, block_m: int,
               dtype_bytes: int, pre_pi: bool) -> ModePlan:
    """Assemble a ModePlan with both kernel footprints filled in."""
    if traversal is heuristics.Traversal.RECURSIVE:
        vm = recursive_vmem_bytes(meta, mode, r_block, dtype_bytes)
        phi_vm = phi_recursive_vmem_bytes(meta, mode, rank, dtype_bytes,
                                          pre_pi=pre_pi)
    else:
        vm = _mttkrp_vmem_model(traversal)(meta, mode, block_m, r_block,
                                           dtype_bytes)
        phi_vm = _phi_vmem_model(traversal)(meta, mode, block_m, rank,
                                            dtype_bytes, pre_pi=pre_pi)
    return ModePlan(mode=mode, traversal=traversal, r_block=r_block,
                    block_m=block_m, temp_rows=meta.temp_rows[mode],
                    vmem_bytes=vm, phi_vmem_bytes=phi_vm)


def static_mode_plan(meta: AltoMeta, mode: int, rank: int, *,
                     dtype_bytes: int = 4, vmem_limit: int = VMEM_BYTES,
                     force_oriented: bool = False,
                     force_carry: bool = False,
                     pre_pi: bool = False) -> ModePlan:
    """The analytic-model choice for one mode (the pre-autotune answer).

    The traversal resolves in two stages: the paper's fiber-reuse rule
    picks recursive vs output-oriented (`heuristics.choose_traversal`),
    then an output-oriented mode refines to the one-hot merge or the
    scratch-carry variant by modelled HBM traffic
    (`heuristics.choose_oriented_variant`), gated on the carry kernel's
    resident-output VMEM feasibility (:func:`carry_fits_vmem`).

    ``force_carry`` pins the scratch-carry traversal outright — streaming
    plans require it (the chunked executors ARE the carry scan; the
    carry VMEM gate turns advisory there, as out-of-core has no in-core
    fallback to route to).
    """
    if force_carry:
        traversal = heuristics.Traversal.ORIENTED_CARRY
    else:
        traversal = (heuristics.Traversal.OUTPUT_ORIENTED if force_oriented
                     else heuristics.choose_traversal(meta, mode))
    if not force_carry and heuristics.is_oriented(traversal):
        traversal = heuristics.choose_oriented_variant(
            meta, mode, rank, dtype_bytes,
            carry_feasible=carry_fits_vmem(meta, mode, rank, dtype_bytes,
                                           vmem_limit))
    # Budget the rank tile against the kernel that will actually run:
    # the recursive Temp model would throttle oriented modes (huge
    # partition intervals, or any mesh plan) for no VMEM benefit.
    if traversal is heuristics.Traversal.RECURSIVE:
        rb = choose_rank_block(meta, mode, rank, dtype_bytes, vmem_limit)
    elif traversal is heuristics.Traversal.ORIENTED_CARRY:
        rb = choose_rank_block_carry(meta, mode, rank, dtype_bytes,
                                     vmem_limit)
    else:
        rb = choose_rank_block_oriented(meta, mode, rank, dtype_bytes,
                                        vmem_limit)
    if traversal is heuristics.Traversal.RECURSIVE:
        bm = MIN_BLOCK_M    # the recursive kernels' fixed block
    else:
        bm = choose_block_m(meta, mode, rb, dtype_bytes, vmem_limit,
                            rank=rank, pre_pi=pre_pi, traversal=traversal)
    return _mode_plan(meta, mode, rank, traversal, rb, bm, dtype_bytes,
                      pre_pi)


def candidate_mode_plans(meta: AltoMeta, mode: int, rank: int, *,
                         dtype_bytes: int = 4,
                         vmem_limit: int = VMEM_BYTES,
                         force_oriented: bool = False,
                         pre_pi: bool = False,
                         max_candidates: int | None = None
                         ) -> tuple[ModePlan, ...]:
    """The feasible tiling space for one mode, static choice FIRST.

    Enumerates traversal × ``r_block`` × ``block_m`` and prunes by the
    corrected per-kernel footprints: a candidate survives only if its
    MTTKRP footprint fits the budget AND its fused-Φ footprint
    (:func:`phi_oriented_vmem_bytes`, full-rank resident B) fits too —
    except that the static choice is always kept even when nothing fits
    (some plan must exist; the budget is advisory then, as everywhere).

    The static (analytic-model) choice is element 0 so a capped search
    (``max_candidates``) can never lose it — the measured winner is then
    *never worse than the static model under the measurement*, which is
    the autotuner's acceptance condition.
    """
    static = static_mode_plan(meta, mode, rank, dtype_bytes=dtype_bytes,
                              vmem_limit=vmem_limit,
                              force_oriented=force_oriented, pre_pi=pre_pi)
    out: list[ModePlan] = [static]
    seen = {(static.traversal, static.r_block, static.block_m)}

    def add(traversal, rb, bm):
        key = (traversal, rb, bm)
        if key in seen:
            return
        seen.add(key)
        out.append(_mode_plan(meta, mode, rank, traversal, rb, bm,
                              dtype_bytes, pre_pi))

    traversals = ((heuristics.Traversal.OUTPUT_ORIENTED,
                   heuristics.Traversal.ORIENTED_CARRY) if force_oriented
                  else heuristics.candidate_traversals(meta, mode))
    for traversal in traversals:
        if traversal is heuristics.Traversal.RECURSIVE:
            for rb in rank_tiles(rank):
                if recursive_vmem_bytes(meta, mode, rb,
                                        dtype_bytes) <= vmem_limit:
                    add(traversal, rb, MIN_BLOCK_M)
        else:
            if (traversal is heuristics.Traversal.ORIENTED_CARRY
                    and not carry_fits_vmem(meta, mode, rank, dtype_bytes,
                                            vmem_limit)):
                continue    # hard gate: resident output cannot fit at all
            mttkrp_model = _mttkrp_vmem_model(traversal)
            phi_model = _phi_vmem_model(traversal)
            # Same binding-vs-vacuous rule as choose_block_m: an
            # unsatisfiable Φ budget must not hide the larger MTTKRP
            # blocks from the tuner.
            phi_binding = phi_constraint_active(meta, mode, rank,
                                                dtype_bytes, vmem_limit,
                                                pre_pi=pre_pi,
                                                traversal=traversal)
            for rb in rank_tiles(rank):
                if mttkrp_model(meta, mode, MIN_BLOCK_M, rb,
                                dtype_bytes) > vmem_limit:
                    continue
                for bm in block_sizes():
                    if (mttkrp_model(meta, mode, bm, rb,
                                     dtype_bytes) <= vmem_limit
                            and not (phi_binding and
                                     phi_model(
                                         meta, mode, bm, rank,
                                         dtype_bytes,
                                         pre_pi=pre_pi) > vmem_limit)):
                        add(traversal, rb, bm)
    if max_candidates is not None and len(out) > max_candidates:
        out = out[:max_candidates]
    return tuple(out)


def make_plan(meta: AltoMeta, rank: int, *, backend: str | None = None,
              interpret: bool | None = None, dtype_bytes: int = 4,
              vmem_limit: int = VMEM_BYTES,
              fast_mem_bytes: int = heuristics.DEFAULT_FAST_MEM_BYTES,
              mesh: jax.sharding.Mesh | None = None,
              device_bytes: int | None = None,
              tune: str = "off",
              tune_objective: str = "mttkrp",
              at: "AltoTensor | None" = None,
              search_budget: int | None = None,
              search_seconds: float | None = None,
              search_seed: int = 0,
              store_path=None) -> ExecutionPlan:
    """Resolve heuristics + static meta into a concrete execution plan.

    With ``mesh=`` the plan becomes mesh-bearing: every mode is forced to
    the output-oriented family (the sharded merge partitions the
    row-sorted stream into per-device row ranges; the recursive
    traversal's partition intervals overlap arbitrarily across devices —
    the one-hot-vs-carry refinement still applies per mode, and carry
    shards run the scratch-carry kernel locally under ``shard_map``)
    and the shard-local Pallas tiles are sized against each device's
    own VMEM (see module docstring).

    ``tune`` selects between the analytic model and measured plans
    (`core.autotune`, persisted in the on-disk plan store):

    * ``"off"`` (default) — the static analytic plan, exactly as before;
    * ``"auto"`` — return the stored measured plan if the store has one
      for this (meta, rank, backend, shard count, jax version); else run
      the tuner if the tensor data ``at=`` was provided (and persist the
      winner); else fall back to the static plan;
    * ``"force"`` — like ``"auto"`` but never silently fall back: a store
      miss with no ``at=`` raises, so the caller knows it is NOT running
      a measured plan.
    * ``"search"`` — like ``"auto"`` but a store miss (with ``at=``)
      runs the *budgeted* GA + cost-model search (`core.search`)
      instead of the exhaustive tuner; ``search_budget`` caps the
      timing runs, ``search_seconds`` the measurement wall-clock, and
      ``search_seed`` pins the search's RNG (deterministic candidate
      schedule). Mesh plans fall back to the exhaustive tuner (the
      sharded timing protocol lives there).

    Streaming plans (``device_bytes`` overflow) tune through the search
    engine under every mode but ``"off"`` — ``StreamPlan.chunk_m`` is
    part of the search genome, and the store records/keys the winner
    per device budget.

    ``tune_objective`` names the kernel the measurement ranks by —
    ``"mttkrp"`` (CP-ALS, the default) or ``"phi"`` (CP-APR; `cp_apr`
    passes this) — and is part of the store key: the two objectives
    crown different winners and never overwrite each other.

    A store hit costs **zero timing runs** — the measured plan
    round-trips across processes through the store file
    (``$REPRO_PLAN_CACHE`` or the checkout's ``.cache/plans.json``).
    """
    with telemetry.span("ingest.make_plan"):
        backend = backend or default_backend()
        if backend not in ("pallas", "reference"):
            raise ValueError(f"unknown backend {backend!r}")
        if tune not in ("off", "auto", "force", "search"):
            raise ValueError(f"unknown tune mode {tune!r}")
        if device_bytes is None:
            device_bytes = default_device_bytes()
        streaming_needed = (device_bytes is not None
                            and needs_streaming(meta, rank, device_bytes,
                                                dtype_bytes))
        if streaming_needed and mesh is not None:
            raise ValueError("out-of-core streaming does not compose "
                             "with mesh-sharded plans yet (shard first, "
                             "then size device_bytes per shard)")
        if mesh is not None:
            from repro.dist.meshes import auto_axes
            mesh = auto_axes(mesh)
        if tune != "off":
            from repro.core import autotune
            tuned = autotune.tuned_plan(
                meta, rank, backend=backend, interpret=interpret,
                dtype_bytes=dtype_bytes, vmem_limit=vmem_limit,
                fast_mem_bytes=fast_mem_bytes, mesh=mesh, at=at,
                require=(tune == "force"), objective=tune_objective,
                search=(tune == "search"),
                device_bytes=device_bytes if streaming_needed else None,
                search_budget_runs=search_budget,
                search_budget_s=search_seconds, search_seed=search_seed,
                store_path=store_path)
            if tuned is not None:
                return tuned
        pi_policy = heuristics.choose_pi_policy(
            meta, rank, value_bytes=dtype_bytes,
            fast_mem_bytes=fast_mem_bytes)
        modes = tuple(
            static_mode_plan(meta, n, rank, dtype_bytes=dtype_bytes,
                             vmem_limit=vmem_limit,
                             force_oriented=mesh is not None,
                             force_carry=streaming_needed,
                             pre_pi=pi_policy is heuristics.PiPolicy.PRE)
            for n in range(meta.enc.ndim))
        streaming = None
        if streaming_needed:
            align = max(m.block_m for m in modes)
            cm = choose_chunk_m(meta, rank, device_bytes, align,
                                dtype_bytes)
            streaming = StreamPlan(
                chunk_m=cm, n_chunks=chunk_count(meta, cm),
                device_bytes=device_bytes,
                stream_bytes=incore_working_set_bytes(meta, rank,
                                                      dtype_bytes))
        return ExecutionPlan(meta=meta, rank=rank, backend=backend,
                             interpret=interpret, pi_policy=pi_policy,
                             modes=modes, mesh=mesh, streaming=streaming)


def plan_for(at: AltoTensor, rank: int, **kwargs) -> ExecutionPlan:
    """`make_plan` from a built tensor; tensor data rides along so
    ``plan_for(at, rank, tune="auto")`` can run the measured tuner."""
    kwargs.setdefault("at", at)
    return make_plan(at.meta, rank, **kwargs)


def make_class_plan(sc, **kwargs) -> ExecutionPlan:
    """`make_plan` for a shape class (`core.shapeclass.ShapeClass`).

    The plan resolves against the class's canonical meta, so it is
    CLASS-keyed: every tenant the class admits executes (and, under
    ``tune=``, autotunes/stores — see `autotune.class_plan_key`) through
    this one plan. The canonical meta's ``temp_rows`` are the padded
    class dims, so the VMEM models size scratch for the worst member —
    conservative by construction, never undersized for any tenant.
    A tensor passed via ``at=`` must already carry the canonical meta
    (`shapeclass.canonicalize_tensor`) or the tuner will reject it.
    """
    from repro.core import shapeclass
    return make_plan(shapeclass.canonical_meta(sc), sc.rank, **kwargs)


def build_views(at: AltoTensor, plan: ExecutionPlan,
                route: str | None = None) -> dict[int, OrientedView]:
    """Oriented-traversal copies for exactly the modes the plan routes
    output-oriented — either variant, one-hot merge or scratch carry,
    both consume the same row-sorted view (preserves the single-copy
    property elsewhere).

    Routed through the unified view cache (`core.views`): built once per
    (tensor fingerprint, mode) per process and shared by every driver;
    ``route`` picks the device (`alto.oriented_view_device`, default) or
    host builder — bit-identical, so the cache ignores the route.
    """
    with telemetry.span("ingest.build_views"):
        from repro.core import views as views_mod
        return views_mod.build_views(at, plan, route=route)


def resident_bytes(at: AltoTensor,
                   views: dict[int, OrientedView] | None = None) -> int:
    """Device-resident bytes a decomposition actually holds.

    `AltoTensor.storage_bytes` is the paper's Fig. 12 accounting — index
    + value words per *real* nonzero — which undercounts the working
    set: CP-ALS/CP-APR also hold the padded tail, the partition boxes,
    and one full oriented copy (rows/words/values/perm) per
    output-oriented mode. This sums the actual materialized arrays, so
    `bench_storage` can report the honest footprint next to the paper
    numbers.
    """
    def nbytes(a) -> int:
        return int(a.size) * a.dtype.itemsize

    from repro.core.stream import HostStream
    total = (nbytes(at.words) + nbytes(at.values)
             + nbytes(at.part_start) + nbytes(at.part_end))
    for v in (views or {}).values():
        if isinstance(v, HostStream):
            continue        # host-resident by design, not device bytes
        total += (nbytes(v.rows) + nbytes(v.words) + nbytes(v.values)
                  + nbytes(v.perm))
    return total


# ---------------------------------------------------------------------------
# Plan-directed execution (the single entry point the drivers use)
# ---------------------------------------------------------------------------

def execute_mttkrp(plan: ExecutionPlan, at: AltoTensor,
                   views: dict[int, OrientedView] | None,
                   factors, mode: int) -> jnp.ndarray:
    """MTTKRP for one mode through the plan's kernel choice.

    Falls back to the recursive traversal when the plan says oriented but
    no view was materialized (same contract as `mttkrp_adaptive`).
    Mesh-bearing plans route to the sharded oriented merge in
    `repro.dist.cpd` (shard-local reduction + psum carry merge).
    Streaming plans route to the out-of-core chunked executors
    (`kernels.ops`), which consume the host-resident stream
    (`core.stream.HostStream`) that `build_views` materialized in place
    of a device view.
    """
    faults.inject("plan.dispatch")
    if plan.mesh is not None:
        from repro.dist import cpd as dist_cpd
        return dist_cpd.sharded_mttkrp(plan, at, views, factors, mode)
    mp = plan.modes[mode]
    oriented = (heuristics.is_oriented(mp.traversal)
                and views is not None and mode in views)
    if plan.streaming is not None and oriented:
        from repro.kernels import ops
        if plan.backend == "pallas":
            return ops.mttkrp_oriented_chunked(
                views[mode], factors, chunk_m=plan.streaming.chunk_m,
                block_m=mp.block_m, r_block=mp.r_block,
                interpret=plan.interpret)
        return ops.mttkrp_oriented_chunked_reference(
            views[mode], factors, chunk_m=plan.streaming.chunk_m)
    if plan.backend == "pallas":
        from repro.kernels import ops
        if oriented:
            if mp.traversal is heuristics.Traversal.ORIENTED_CARRY:
                return ops.mttkrp_oriented_carry(views[mode], factors,
                                                 block_m=mp.block_m,
                                                 r_block=mp.r_block,
                                                 interpret=plan.interpret)
            return ops.mttkrp_oriented(views[mode], factors,
                                       block_m=mp.block_m,
                                       r_block=mp.r_block,
                                       interpret=plan.interpret)
        return ops.mttkrp(at, factors, mode, r_block=mp.r_block,
                          interpret=plan.interpret)
    # reference backend: both oriented variants are the same sorted
    # segment_sum — the carry is a kernel-level distinction.
    if oriented:
        return core_mttkrp.mttkrp_oriented(views[mode], factors)
    return core_mttkrp.mttkrp_recursive(at, factors, mode)


def execute_phi(plan: ExecutionPlan, at: AltoTensor,
                view: OrientedView | None, B: jnp.ndarray, mode: int,
                factors=None, pi: jnp.ndarray | None = None,
                eps: float = 1e-10, pre: bool | None = None) -> jnp.ndarray:
    """CP-APR Φ row reduction through the plan's kernel choice.

    Pass ``pi`` (view/ALTO-ordered Khatri-Rao rows) for ALTO-PRE or
    ``factors`` for ALTO-OTF — exactly one, as in `kernels.cpapr_phi`.

    Streaming plans take ``factors`` under BOTH Π policies (a full-stream
    Π is exactly the array streaming avoids; the chunked executor builds
    each chunk's Π rows on device under PRE) — ``pre`` then selects the
    policy explicitly, defaulting to the plan's. ``pre`` is ignored on
    in-core routes, where the pi-vs-factors operand already encodes it.
    """
    faults.inject("plan.dispatch")
    if (pi is None) == (factors is None):
        raise ValueError("pass exactly one of pi= / factors=")
    if plan.mesh is not None:
        from repro.dist import cpd as dist_cpd
        return dist_cpd.sharded_phi(plan, at, view, B, mode,
                                    factors=factors, pi=pi, eps=eps)
    mp = plan.modes[mode]
    oriented = (heuristics.is_oriented(mp.traversal)
                and view is not None)
    if plan.streaming is not None and oriented:
        from repro.kernels import ops
        if factors is None:
            raise ValueError("streaming Φ needs factors= — chunk Π rows "
                             "are built on device per chunk, never as a "
                             "full-stream pi= operand")
        pre_flag = (pre if pre is not None
                    else plan.pi_policy is heuristics.PiPolicy.PRE)
        if plan.backend == "pallas":
            return ops.cpapr_phi_oriented_chunked(
                view, B, factors, pre=pre_flag, eps=eps,
                chunk_m=plan.streaming.chunk_m, block_m=mp.block_m,
                interpret=plan.interpret)
        return ops.cpapr_phi_oriented_chunked_reference(
            view, B, factors, pre=pre_flag, eps=eps,
            chunk_m=plan.streaming.chunk_m)
    if plan.backend == "pallas":
        from repro.kernels import ops
        if oriented:
            if mp.traversal is heuristics.Traversal.ORIENTED_CARRY:
                return ops.cpapr_phi_oriented_carry(
                    view, B, factors=factors, pi=pi, eps=eps,
                    block_m=mp.block_m, interpret=plan.interpret)
            return ops.cpapr_phi_oriented(view, B, factors=factors, pi=pi,
                                          eps=eps, block_m=mp.block_m,
                                          interpret=plan.interpret)
        return ops.cpapr_phi(at, B, mode, factors=factors, pi=pi, eps=eps,
                             interpret=plan.interpret)
    # reference backend: pure-jnp traversals. Under ALTO-PRE the index
    # decode is dead work (the Pallas kernel skips it too): the oriented
    # view already materializes the target rows, so only the OTF path —
    # which rebuilds the Khatri-Rao rows — pays for a delinearize.
    words = view.words if oriented else at.words
    vals = view.values if oriented else at.values
    if pi is None:
        coords = delinearize(plan.meta.enc, words)
        krp = core_mttkrp.krp_rows(coords, factors, mode)
        rows = coords[:, mode]
    else:
        krp = pi
        rows = (view.rows if oriented
                else delinearize(plan.meta.enc, words)[:, mode])
    denom = jnp.maximum(jnp.sum(B[rows] * krp, axis=-1), eps)
    contrib = (vals / denom)[:, None] * krp
    if oriented:
        return core_mttkrp.row_reduce_oriented(view, contrib)
    return core_mttkrp.row_reduce_recursive(at, mode, contrib)

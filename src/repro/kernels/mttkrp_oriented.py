"""Pallas TPU kernel: output-oriented MTTKRP / Φ segment reduction.

The complement of the recursive kernel in `kernels/mttkrp.py` (paper
§4.2, Fig. 8 right): nonzeros arrive permuted into ascending order of the
target-mode row (`core.alto.oriented_view`), so conflict-free updates
become a *sorted segment reduction*. This mirrors the conflict-free
segment-reduction designs of ALTO (arXiv:2102.10245) and Dynasor
(arXiv:2309.09131), adapted to the TPU's no-atomics execution model. The
sorted row stream is cut into `block_m`-element blocks, one grid step
each, and two reductions are offered:

**One-hot merge** (`*_partials_pallas`): a scalar loop writes every
element's contribution row into a VMEM tile (the factor rows are loaded
at coordinates staged in SMEM, `kernels.mttkrp`), and the block's segment
sums are formed by ONE one-hot matmul on the MXU,
``onehot(seg)ᵀ @ contrib``. The segment ids are the run rank of each row
inside its block; they are fixed once the view exists, so the wrapper
computes them outside the kernel (`run_rank_segments`). A row whose run
crosses a block boundary yields one partial sum in each adjacent block;
`ops.segment_merge` scatters every block's sums to their global rows —
the paper's "atomics only at partition boundaries", pull-based.

**Scratch carry** (`*_carry_pallas`, `*_carry_chunk_pallas`): partial
sums ride *through* the sorted-stream scan on a sequential block grid.
The ``(I_n, r_block)`` output tile stays VMEM-resident across the scan;
the element loop keeps the open run's in-block sum ``acc`` and the carry
from earlier blocks ``extra`` in registers and stores ``acc + extra``
into the run's row at every element, so a closed run's row already holds
its total and the open run leaves the block as the carry. The in-core
kernel is the one-chunk case of the chunk kernel: the accumulator and
the carry arrive as inputs (zeros and row −1 for a fresh scan) and leave
as outputs, so an out-of-core stream threads them from chunk to chunk.

Carry-vs-one-hot parity is bit-exact: the in-block sums accumulate in the
same element order as the one-hot matmul, and the carry adds ``acc +
extra`` where the merge adds block partials in block order — the same
sums up to IEEE-commutative swaps (x+y == y+x bitwise), which
`tests/test_oriented_carry.py` pins on adversarial run layouts.

The Φ variants fuse the CP-APR model update (B-row load, denominator dot,
Poisson elementwise update — paper Alg. 5) into the same element loop,
for both Π policies (ALTO-PRE streams Π rows; ALTO-OTF decodes the index
words and loads factor rows).

Invariants: the input stream is row-sorted with length an exact multiple
of block_m (callers pad — `ops` / `dist.cpd`); row ids are global, so
per-shard results combine by psum (`repro.dist.cpd`); all tiling comes
from static, hashable plan metadata.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import AltoEncoding
from repro.kernels.cpapr_phi import phi_row
from repro.kernels.mttkrp import (DEFAULT_BLOCK_M, _for_each, _krp_row,
                                  _stage_coords, compiler_params, resident,
                                  stage_scratch, word_columns)


def run_rank_segments(rows):
    """Run-rank segment ids along the last axis of a sorted row array.

    Shared between the one-hot kernel's wrapper and `ops.segment_merge`:
    the merge's scatter map must reproduce this segmentation bit-for-bit,
    so there is exactly one implementation.
    """
    block_m = rows.shape[-1]
    idx = jax.lax.iota(jnp.int32, block_m)
    prev = jnp.roll(rows, 1, axis=-1)
    is_new = jnp.where(idx == 0, 0, (rows != prev).astype(jnp.int32))
    return jnp.cumsum(is_new, axis=-1)


def _check_stream(M: int, block_m: int, R: int, rb: int) -> None:
    if M % block_m:
        raise ValueError(f"nnz {M} not a multiple of block_m {block_m}")
    if R % rb:
        raise ValueError(f"rank {R} not a multiple of r_block {rb}")


def _fill_contrib(block_m, c_ref, contrib):
    """Phase 1 of every oriented kernel: element j's contribution row
    into row j of the VMEM tile ``c_ref``. Keeping the reduction in a
    separate pass keeps each sum's rounding independent of how the
    contribution was formed (no fused multiply-add across the two)."""
    def body(j, state):
        c_ref[pl.ds(j, 1), :] = contrib(j)
        return state

    _for_each(block_m, body, 0)


def _segment_matmul(seg, contrib):
    """Per-segment sums via one one-hot matmul: (block_m, r_block)."""
    block_m = seg.shape[0]
    onehot = (seg[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block_m, block_m), 1)).astype(contrib.dtype)
    # Contracting the element axis of an element-major one-hot keeps the
    # per-segment sums in element order, bit-for-bit the carry scan's;
    # HIGHEST keeps the MXU from rounding f32 contributions to bf16.
    return jax.lax.dot_general(
        onehot, contrib, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(contrib.dtype)


def _operand_specs(pre_pi, mode, block_m, R, factors, pi, stream_map,
                   const_map):
    """In-specs/args for the Φ Khatri-Rao operand: the streamed Π tile
    (ALTO-PRE) or every other mode's whole factor (ALTO-OTF)."""
    if pre_pi:
        return [pl.BlockSpec((block_m, R), stream_map)], [pi]
    others = [f for m, f in enumerate(factors) if m != mode]
    return [resident(f.shape, const_map) for f in others], others


# ---------------------------------------------------------------------------
# One-hot merge: per-block segment sums, merged outside the kernel
# ---------------------------------------------------------------------------

def _mttkrp_oriented_kernel(enc: AltoEncoding, mode: int, block_m: int,
                            seg_ref, *refs):
    """Grid step: one (nonzero block, rank tile) -> in-block segment sums."""
    W, n_other = enc.n_words, enc.ndim - 1
    word_refs, vals_ref = refs[:W], refs[W]
    factor_refs = refs[W + 1:W + 1 + n_other]
    out_ref, c_ref, cv_ref, cs_ref, sem = refs[W + 1 + n_other:]
    others = [m for m in range(enc.ndim) if m != mode]
    _stage_coords(enc, others, word_refs, cv_ref, cs_ref, sem)
    _fill_contrib(block_m, c_ref,
                  lambda j: vals_ref[j] * _krp_row(j, cs_ref, factor_refs))
    out_ref[0] = _segment_matmul(seg_ref[...], c_ref[...])


def mttkrp_oriented_partials_pallas(enc: AltoEncoding, mode: int,
                                    rows: jnp.ndarray, words: jnp.ndarray,
                                    values: jnp.ndarray, factors,
                                    block_m: int = DEFAULT_BLOCK_M,
                                    r_block: int | None = None,
                                    interpret: bool = True) -> jnp.ndarray:
    """Per-block segment sums: (n_blocks, block_m, R).

    ``rows``/``words``/``values`` must be in oriented (row-sorted) order
    with length a multiple of ``block_m`` (ops pads). Segment slot j of
    block b holds the sum of the j-th distinct-row run inside that block;
    `ops.segment_merge` scatters the slots to global rows and thereby
    merges boundary carries.
    """
    M = words.shape[0]
    R = factors[0].shape[1]
    rb = r_block or R
    _check_stream(M, block_m, R, rb)
    n_blocks = M // block_m
    seg = run_rank_segments(rows.reshape(n_blocks, block_m)).reshape(M)
    others = [f for m, f in enumerate(factors) if m != mode]
    stream = pl.BlockSpec((block_m,), lambda b, r: (b,))

    in_specs = ([stream] * (1 + enc.n_words)                     # seg, words
                + [pl.BlockSpec((block_m,), lambda b, r: (b,),
                                memory_space=pltpu.SMEM)]        # values
                + [resident((f.shape[0], rb), lambda b, r: (0, r))
                   for f in others])
    return pl.pallas_call(
        functools.partial(_mttkrp_oriented_kernel, enc, mode, block_m),
        name="alto_mttkrp_oriented",
        grid=(n_blocks, R // rb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_m, rb), lambda b, r: (b, 0, r)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, block_m, R),
                                       factors[0].dtype),
        scratch_shapes=[pltpu.VMEM((block_m, rb), factors[0].dtype)]
        + stage_scratch(enc.ndim - 1, block_m),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(seg, *word_columns(words), values, *others)


def _phi_oriented_kernel(enc: AltoEncoding, mode: int, eps: float,
                         pre_pi: bool, block_m: int,
                         seg_ref, rows_ref, vals_ref, b_ref, *refs):
    """Grid step: fused Φ update + in-block segment sums (full rank)."""
    n_other = enc.ndim - 1
    if pre_pi:
        pi_ref, out_ref, c_ref = refs
    else:
        W = enc.n_words
        word_refs, factor_refs = refs[:W], refs[W:W + n_other]
        out_ref, c_ref, cv_ref, cs_ref, sem = refs[W + n_other:]
        _stage_coords(enc, [m for m in range(enc.ndim) if m != mode],
                      word_refs, cv_ref, cs_ref, sem)

    def contrib(j):
        krp = (pi_ref[pl.ds(j, 1), :] if pre_pi
               else _krp_row(j, cs_ref, factor_refs))
        b_row = b_ref[pl.ds(rows_ref[j], 1), :]
        return phi_row(vals_ref, j, b_row, krp, eps)

    _fill_contrib(block_m, c_ref, contrib)
    out_ref[0] = _segment_matmul(seg_ref[...], c_ref[...])


def _phi_stream_specs(enc, pre_pi, block_m, B, stream_map, const_map):
    """Shared Φ in-specs: seg/rows/values (+ words under OTF), B."""
    smem = pl.BlockSpec((block_m,), stream_map, memory_space=pltpu.SMEM)
    words = [] if pre_pi else [pl.BlockSpec((block_m,), stream_map)
                               ] * enc.n_words
    return smem, words, resident(B.shape, const_map)


def phi_oriented_partials_pallas(enc: AltoEncoding, mode: int, eps: float,
                                 rows: jnp.ndarray, words: jnp.ndarray,
                                 values: jnp.ndarray, B: jnp.ndarray,
                                 factors=None, pi: jnp.ndarray | None = None,
                                 block_m: int = DEFAULT_BLOCK_M,
                                 interpret: bool = True) -> jnp.ndarray:
    """Per-block Φ segment sums: (n_blocks, block_m, R).

    Pass ``pi`` (oriented-order Khatri-Rao rows) for ALTO-PRE or
    ``factors`` for ALTO-OTF (exactly one). No rank tiling — the
    denominator ``<B[i_n,:], krp>`` needs the full rank per element.
    """
    pre_pi = pi is not None
    if pre_pi == (factors is not None):
        raise ValueError("pass exactly one of pi= / factors=")
    M = words.shape[0]
    R = B.shape[1]
    _check_stream(M, block_m, R, R)
    n_blocks = M // block_m
    seg = run_rank_segments(rows.reshape(n_blocks, block_m)).reshape(M)
    smem, word_specs, b_spec = _phi_stream_specs(
        enc, pre_pi, block_m, B, lambda b: (b,), lambda b: (0, 0))
    op_specs, op_args = _operand_specs(pre_pi, mode, block_m, R, factors,
                                       pi, lambda b: (b, 0),
                                       lambda b: (0, 0))
    scratch = [pltpu.VMEM((block_m, R), B.dtype)]
    if not pre_pi:
        scratch += stage_scratch(enc.ndim - 1, block_m)
    return pl.pallas_call(
        functools.partial(_phi_oriented_kernel, enc, mode, eps, pre_pi,
                          block_m),
        name="alto_phi_oriented",
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_m,), lambda b: (b,)), smem, smem,
                  b_spec] + word_specs + op_specs,
        out_specs=pl.BlockSpec((1, block_m, R), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, block_m, R), B.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(seg, rows, values, B,
      *([] if pre_pi else word_columns(words)), *op_args)


# ---------------------------------------------------------------------------
# Scratch carry: one sequential scan, resident output, chunkable
# ---------------------------------------------------------------------------

def _carry_scan(block_m, first, rows_ref, contrib, c_ref, acc_ref,
                cin_row_ref, cin_val_ref, out_ref, cout_row_ref,
                cout_val_ref, sem):
    """One grid step of the carry scan, shared by MTTKRP and Φ.

    The block's contributions go to the VMEM tile ``c_ref`` first
    (`_fill_contrib`); the scan then reduces the tile's rows in element
    order. At the scan's first block the resident output tile is loaded from
    the accumulator (HBM, aliased onto the output) and the carry from
    ``cin``; the carry then lives in the resident ``cout`` outputs.
    Every element stores its run's running total, so nothing is left to
    flush when the stream ends.
    """
    @pl.when(first)
    def _():
        copy = pltpu.make_async_copy(acc_ref, out_ref, sem)
        copy.start()
        copy.wait()
        cout_row_ref[0] = cin_row_ref[0]
        cout_val_ref[...] = cin_val_ref[...]

    _fill_contrib(block_m, c_ref, contrib)
    zero = jnp.zeros(cout_val_ref.shape, cout_val_ref.dtype)

    def body(j, state):
        run_row, acc, extra = state
        r = rows_ref[j]
        new = r != run_row
        acc = jnp.where(new, zero, acc) + c_ref[pl.ds(j, 1), :]
        extra = jnp.where(new, zero, extra)
        out_ref[pl.ds(r, 1), :] = acc + extra
        return r, acc, extra

    run_row, acc, extra = _for_each(
        block_m, body, (cout_row_ref[0], zero, cout_val_ref[...]))
    cout_row_ref[0] = run_row
    cout_val_ref[...] = acc + extra


def _carry_io(I_n, rb, R, dtype, tile_map):
    """Out-specs/shapes of the carry scan: accumulator tile + carry."""
    specs = [pl.BlockSpec((I_n, rb), tile_map),
             pl.BlockSpec(memory_space=pltpu.SMEM),
             pl.BlockSpec((1, rb), tile_map)]
    shapes = [jax.ShapeDtypeStruct((I_n, R), dtype),
              jax.ShapeDtypeStruct((1,), jnp.int32),
              jax.ShapeDtypeStruct((1, R), dtype)]
    return specs, shapes


def _mttkrp_carry_kernel(enc: AltoEncoding, mode: int, block_m: int,
                         rows_ref, *refs):
    """Grid step: (rank tile r, sorted block b) -> resident (I_n, rb)."""
    W, n_other = enc.n_words, enc.ndim - 1
    word_refs, vals_ref = refs[:W], refs[W]
    cin_row_ref, cin_val_ref = refs[W + 1], refs[W + 2]
    factor_refs = refs[W + 3:W + 3 + n_other]
    acc_ref = refs[W + 3 + n_other]
    (out_ref, cout_row_ref, cout_val_ref, c_ref, cv_ref, cs_ref, sem,
     acc_sem) = refs[W + 4 + n_other:]
    r_tile = pl.program_id(0)
    rb = out_ref.shape[1]
    _stage_coords(enc, [m for m in range(enc.ndim) if m != mode],
                  word_refs, cv_ref, cs_ref, sem)
    if rb != acc_ref.shape[1]:
        acc_ref = acc_ref.at[:, pl.ds(r_tile * rb, rb)]

    def contrib(j):
        return vals_ref[j] * _krp_row(j, cs_ref, factor_refs)

    _carry_scan(block_m, pl.program_id(1) == 0, rows_ref, contrib, c_ref,
                acc_ref, cin_row_ref, cin_val_ref, out_ref, cout_row_ref,
                cout_val_ref, acc_sem)


def mttkrp_oriented_carry_chunk_pallas(enc: AltoEncoding, mode: int,
                                       rows: jnp.ndarray,
                                       words: jnp.ndarray,
                                       values: jnp.ndarray, factors,
                                       out: jnp.ndarray,
                                       carry_row: jnp.ndarray,
                                       carry_val: jnp.ndarray,
                                       block_m: int = DEFAULT_BLOCK_M,
                                       r_block: int | None = None,
                                       interpret: bool = True):
    """One chunk of the scratch-carry MTTKRP scan.

    ``rows/words/values`` are one block_m-multiple slice of the padded
    sorted stream; ``out`` is the running (I_n, R) accumulator (zeros
    for the first chunk); ``carry_row``/``carry_val`` — shapes (1,)
    int32 / (1, R) — are the previous chunk's open run (row −1 + zeros
    for the first). Returns the updated ``(out, carry_row, carry_val)``.
    The grid is (rank tiles, blocks) with the block axis innermost, so
    each rank tile is one sequential scan.
    """
    M = words.shape[0]
    R = factors[0].shape[1]
    rb = r_block or R
    _check_stream(M, block_m, R, rb)
    I_n = enc.dims[mode]
    others = [f for m, f in enumerate(factors) if m != mode]
    smem = pl.BlockSpec((block_m,), lambda r, b: (b,),
                        memory_space=pltpu.SMEM)
    tile = lambda r, b: (0, r)                                 # noqa: E731
    in_specs = ([smem]                                          # rows
                + [pl.BlockSpec((block_m,), lambda r, b: (b,))
                   ] * enc.n_words                              # words
                + [smem,                                        # values
                   pl.BlockSpec(memory_space=pltpu.SMEM),       # carry row
                   pl.BlockSpec((1, rb), tile)]                 # carry val
                + [resident((f.shape[0], rb), tile) for f in others]
                + [pl.BlockSpec(memory_space=pl.ANY)])          # accum
    out_specs, out_shape = _carry_io(I_n, rb, R, out.dtype, tile)
    acc_idx = len(in_specs) - 1
    return pl.pallas_call(
        functools.partial(_mttkrp_carry_kernel, enc, mode, block_m),
        name="alto_mttkrp_carry",
        grid=(R // rb, M // block_m),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_m, rb), out.dtype)]
        + stage_scratch(enc.ndim - 1, block_m)
        + [pltpu.SemaphoreType.DMA(())],
        input_output_aliases={acc_idx: 0},
        compiler_params=compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(rows, *word_columns(words), values, carry_row, carry_val, *others,
      out)


def fresh_carry(I_n: int, R: int, dtype):
    """Accumulator and carry of a scan that starts at the stream head."""
    return (jnp.zeros((I_n, R), dtype), jnp.full((1,), -1, jnp.int32),
            jnp.zeros((1, R), dtype))


def mttkrp_oriented_carry_pallas(enc: AltoEncoding, mode: int,
                                 rows: jnp.ndarray, words: jnp.ndarray,
                                 values: jnp.ndarray, factors,
                                 block_m: int = DEFAULT_BLOCK_M,
                                 r_block: int | None = None,
                                 interpret: bool = True) -> jnp.ndarray:
    """Scratch-carry oriented MTTKRP: sorted stream -> (I_n, R) directly.

    The one-chunk case of `mttkrp_oriented_carry_chunk_pallas`: the
    result is the final row-reduced MTTKRP — there is no partials buffer
    and callers must NOT run `ops.segment_merge` on this path.
    """
    R = factors[0].shape[1]
    out, crow, cval = fresh_carry(enc.dims[mode], R, factors[0].dtype)
    return mttkrp_oriented_carry_chunk_pallas(
        enc, mode, rows, words, values, factors, out, crow, cval,
        block_m=block_m, r_block=r_block, interpret=interpret)[0]


def _phi_carry_kernel(enc: AltoEncoding, mode: int, eps: float,
                      pre_pi: bool, block_m: int,
                      rows_ref, vals_ref, b_ref, cin_row_ref, cin_val_ref,
                      *refs):
    """Grid step: fused Φ update + carry scan, full rank, resident out."""
    n_other = enc.ndim - 1
    if pre_pi:
        (pi_ref, acc_ref, out_ref, cout_row_ref, cout_val_ref, c_ref,
         acc_sem) = refs
    else:
        W = enc.n_words
        word_refs, factor_refs = refs[:W], refs[W:W + n_other]
        acc_ref = refs[W + n_other]
        (out_ref, cout_row_ref, cout_val_ref, c_ref, cv_ref, cs_ref, sem,
         acc_sem) = refs[W + n_other + 1:]
        _stage_coords(enc, [m for m in range(enc.ndim) if m != mode],
                      word_refs, cv_ref, cs_ref, sem)

    def contrib(j):
        krp = (pi_ref[pl.ds(j, 1), :] if pre_pi
               else _krp_row(j, cs_ref, factor_refs))
        b_row = b_ref[pl.ds(rows_ref[j], 1), :]
        return phi_row(vals_ref, j, b_row, krp, eps)

    _carry_scan(block_m, pl.program_id(0) == 0, rows_ref, contrib, c_ref,
                acc_ref, cin_row_ref, cin_val_ref, out_ref, cout_row_ref,
                cout_val_ref, acc_sem)


def phi_oriented_carry_chunk_pallas(enc: AltoEncoding, mode: int,
                                    eps: float,
                                    rows: jnp.ndarray, words: jnp.ndarray,
                                    values: jnp.ndarray, B: jnp.ndarray,
                                    out: jnp.ndarray,
                                    carry_row: jnp.ndarray,
                                    carry_val: jnp.ndarray,
                                    factors=None,
                                    pi: jnp.ndarray | None = None,
                                    block_m: int = DEFAULT_BLOCK_M,
                                    interpret: bool = True):
    """One chunk of the scratch-carry fused Φ scan (full rank).

    Operand contract as `phi_oriented_partials_pallas` (exactly one of
    ``pi``/``factors``; under ALTO-PRE ``pi`` holds THIS CHUNK's Π rows);
    chunk contract as `mttkrp_oriented_carry_chunk_pallas`. Returns the
    updated ``(out, carry_row, carry_val)``.
    """
    pre_pi = pi is not None
    if pre_pi == (factors is not None):
        raise ValueError("pass exactly one of pi= / factors=")
    M = words.shape[0]
    I_n, R = B.shape
    _check_stream(M, block_m, R, R)
    smem, word_specs, b_spec = _phi_stream_specs(
        enc, pre_pi, block_m, B, lambda b: (b,), lambda b: (0, 0))
    op_specs, op_args = _operand_specs(pre_pi, mode, block_m, R, factors,
                                       pi, lambda b: (b, 0),
                                       lambda b: (0, 0))
    in_specs = ([smem, smem, b_spec,                            # rows, vals, B
                 pl.BlockSpec(memory_space=pltpu.SMEM),         # carry row
                 pl.BlockSpec((1, R), lambda b: (0, 0))]        # carry val
                + word_specs + op_specs
                + [pl.BlockSpec(memory_space=pl.ANY)])          # accum
    out_specs, out_shape = _carry_io(I_n, R, R, out.dtype,
                                     lambda b: (0, 0))
    scratch = [pltpu.VMEM((block_m, R), out.dtype)]
    if not pre_pi:
        scratch += stage_scratch(enc.ndim - 1, block_m)
    return pl.pallas_call(
        functools.partial(_phi_carry_kernel, enc, mode, eps, pre_pi,
                          block_m),
        name="alto_phi_carry",
        grid=(M // block_m,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())],
        input_output_aliases={len(in_specs) - 1: 0},
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(rows, values, B, carry_row, carry_val,
      *([] if pre_pi else word_columns(words)), *op_args, out)


def phi_oriented_carry_pallas(enc: AltoEncoding, mode: int, eps: float,
                              rows: jnp.ndarray, words: jnp.ndarray,
                              values: jnp.ndarray, B: jnp.ndarray,
                              factors=None, pi: jnp.ndarray | None = None,
                              block_m: int = DEFAULT_BLOCK_M,
                              interpret: bool = True) -> jnp.ndarray:
    """Scratch-carry fused Φ: sorted stream -> (I_n, R) directly (the
    one-chunk case of `phi_oriented_carry_chunk_pallas`)."""
    I_n, R = B.shape
    out, crow, cval = fresh_carry(I_n, R, B.dtype)
    return phi_oriented_carry_chunk_pallas(
        enc, mode, eps, rows, words, values, B, out, crow, cval,
        factors=factors, pi=pi, block_m=block_m, interpret=interpret)[0]

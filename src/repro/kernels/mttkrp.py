"""Pallas TPU kernel: partitioned MTTKRP over ALTO tensors (paper Alg. 4).

The grid walks each balanced ALTO partition in blocks of
`DEFAULT_BLOCK_M` elements (and one rank tile at a time) and
accumulates that partition's dense
``Temp`` buffer — the local buffer of the paper's recursive traversal —
in a VMEM-resident output block. The pull-based reduction (Alg. 4 lines
14-18) merges partials outside the kernel (see ops.py).

How each step maps onto the TPU core (the same scheme every kernel of
this package uses, so the helpers live here):

  * the index words arrive as ``n_words`` lane-dense 1-D blocks; the
    static shift/or run chain decodes them on the VPU (`_decode`);
  * the decoded coordinates are staged into SMEM by one local DMA
    (`_stage_coords`), because a row address must be a scalar;
  * a scalar loop over the block's elements (`_for_each`) then loads the
    factor rows with dynamic sublane offsets, forms the Khatri-Rao
    product, and read-modify-writes one ``Temp`` row per element. TPUs
    have no atomics and no vector gather/scatter over a large table, so
    the irregular update is a sequence of single-row VMEM accesses;
  * the mode intervals give a *static* Temp height, so the kernel's VMEM
    footprint is known at compile time (`core.plan.recursive_vmem_bytes`).

1-D blocks must be multiples of 1024 elements on the chip (XLA lays 1-D
arrays out in 1024-element tiles); interpret mode accepts any multiple
of `UNROLL`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import AltoEncoding

# The recursive kernels' nonzero block, and the default (and smallest) block
# of the oriented ones.
DEFAULT_BLOCK_M = 1024
# Elements per iteration of the scalar loops (manual unroll: Mosaic only
# lowers fori_loop with unroll=1 or a full unroll).
UNROLL = 8
# Scoped-VMEM limit handed to Mosaic. A v5e core has 128 MiB of VMEM;
# `core.plan.VMEM_BYTES` budgets the kernels' blocks below this limit.
VMEM_LIMIT_BYTES = 112 * 1024 * 1024


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def resident(shape, index_map) -> pl.BlockSpec:
    """A block whose index rarely changes (factor, B or accumulator
    tile): one VMEM buffer instead of the pipeline's two."""
    return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))


def word_columns(words: jnp.ndarray) -> list[jnp.ndarray]:
    """(M, n_words) index words -> n_words lane-dense (M,) columns."""
    return [words[:, w] for w in range(words.shape[1])]


def _decode(enc: AltoEncoding, cols):
    """Static bit-run decode: n_words u32 arrays -> N int32 arrays."""
    out = [jnp.zeros(cols[0].shape, dtype=jnp.uint32)
           for _ in range(enc.ndim)]
    for r in enc.runs:
        chunk = (cols[r.word] >> np.uint32(r.dst_shift)) & np.uint32(r.mask)
        out[r.mode] = out[r.mode] | (chunk << np.uint32(r.src_shift))
    return [c.astype(jnp.int32) for c in out]


def _stage_coords(enc: AltoEncoding, modes, word_refs, cv_ref, cs_ref,
                  sem):
    """Decode the block's coordinates of ``modes`` on the VPU and copy
    them into the SMEM scratch ``cs_ref`` (row i = modes[i])."""
    cols = _decode(enc, [w[...] for w in word_refs])
    for i, m in enumerate(modes):
        cv_ref[pl.ds(i, 1), :] = cols[m][None, :]
    copy = pltpu.make_async_copy(cv_ref, cs_ref, sem)
    copy.start()
    copy.wait()


def stage_scratch(n_modes: int, block_m: int) -> list:
    """Scratch buffers `_stage_coords` needs for ``n_modes`` modes."""
    return [pltpu.VMEM((n_modes, block_m), jnp.int32),
            pltpu.SMEM((n_modes, block_m), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


def _for_each(block_m: int, body, init):
    """``body(j, state) -> state`` for j in [0, block_m), in order."""
    if block_m % UNROLL:
        raise ValueError(f"block_m {block_m} not a multiple of {UNROLL}")

    def step(jj, state):
        for u in range(UNROLL):
            state = body(jj * UNROLL + u, state)
        return state

    return jax.lax.fori_loop(0, block_m // UNROLL, step, init)


def _krp_row(j, cs_ref, factor_refs):
    """Khatri-Rao row of element j: the product of the factor rows at
    its staged coordinates (row i of cs_ref indexes factor_refs[i])."""
    krp = None
    for i, f in enumerate(factor_refs):
        row = f[pl.ds(cs_ref[i, j], 1), :]
        krp = row if krp is None else krp * row
    return krp


def pad_partitions(L: int, block_m: int, cols, values, pi=None):
    """Pad every partition of the ALTO stream to a multiple of block_m.

    ``cols`` are the stream's index-word columns (`word_columns`). Each
    partition's last element is replicated with value 0 (and a zero Π
    row), so the padding stays inside the partition's bounding box and
    contributes nothing. Returns ``(cols, values, pi, chunk)``.
    """
    chunk = values.shape[0] // L
    cp = -(-chunk // block_m) * block_m
    if cp == chunk:
        return cols, values, pi, chunk
    pad = cp - chunk

    def extend(a, fill):
        a = a.reshape((L, chunk) + a.shape[1:])
        tail = (jnp.broadcast_to(a[:, -1:], (L, pad) + a.shape[2:])
                if fill is None else
                jnp.full((L, pad) + a.shape[2:], fill, a.dtype))
        return jnp.concatenate([a, tail], 1).reshape((L * cp,) + a.shape[2:])

    return ([extend(c, None) for c in cols], extend(values, 0),
            None if pi is None else extend(pi, 0), cp)


def _mttkrp_partial_kernel(enc: AltoEncoding, mode: int, start_ref, *refs):
    """Grid step (partition l, rank tile r, block b): Temp_l += block."""
    W, n_other = enc.n_words, enc.ndim - 1
    word_refs, vals_ref = refs[:W], refs[W]
    factor_refs = refs[W + 1:W + 1 + n_other]
    out_ref, cv_ref, cs_ref, sem = refs[W + 1 + n_other:]
    others = [m for m in range(enc.ndim) if m != mode]

    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    _stage_coords(enc, others + [mode], word_refs, cv_ref, cs_ref, sem)
    start = start_ref[pl.program_id(0)]

    def body(j, state):
        contrib = vals_ref[j] * _krp_row(j, cs_ref, factor_refs)
        local = cs_ref[n_other, j] - start        # in [0, temp_rows)
        out_ref[0, pl.ds(local, 1), :] += contrib.astype(out_ref.dtype)
        return state

    _for_each(DEFAULT_BLOCK_M, body, 0)


def mttkrp_partials_pallas(enc: AltoEncoding, mode: int, temp_rows: int,
                           words: jnp.ndarray, values: jnp.ndarray,
                           part_start: jnp.ndarray, factors,
                           r_block: int | None = None,
                           interpret: bool = True) -> jnp.ndarray:
    """Per-partition Temp buffers: (L, temp_rows, R)."""
    block_m = DEFAULT_BLOCK_M
    L = part_start.shape[0]
    R = factors[0].shape[1]
    rb = r_block or R
    if R % rb:
        raise ValueError(f"rank {R} not a multiple of r_block {rb}")
    cols, values, _, chunk = pad_partitions(L, block_m, word_columns(words),
                                            values)
    nbl = chunk // block_m
    others = [f for m, f in enumerate(factors) if m != mode]

    def stream(l, r, b):
        return (l * nbl + b,)

    in_specs = (
        [pl.BlockSpec(memory_space=pltpu.SMEM)]                 # starts
        + [pl.BlockSpec((block_m,), stream)] * enc.n_words      # words
        + [pl.BlockSpec((block_m,), stream,
                        memory_space=pltpu.SMEM)]               # values
        + [resident((f.shape[0], rb), lambda l, r, b: (0, r))
           for f in others])
    return pl.pallas_call(
        functools.partial(_mttkrp_partial_kernel, enc, mode),
        name="alto_mttkrp_recursive",
        grid=(L, R // rb, nbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, temp_rows, rb),
                               lambda l, r, b: (l, 0, r)),
        out_shape=jax.ShapeDtypeStruct((L, temp_rows, R), factors[0].dtype),
        scratch_shapes=stage_scratch(enc.ndim, block_m),
        compiler_params=compiler_params("parallel", "parallel",
                                        "arbitrary"),
        interpret=interpret,
    )(part_start[:, mode], *cols, values, *others)

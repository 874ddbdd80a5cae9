"""Pallas TPU kernel: fused CP-APR Φ model update (paper Alg. 5).

Each `DEFAULT_BLOCK_M` block of a balanced ALTO partition is updated
entirely in VMEM, after `_stage_coords` has decoded its coordinates into
SMEM, in three phases:

1. gather (scalar loop): per element j, the Khatri-Rao row (ALTO-OTF;
   under ALTO-PRE the streamed Π tile already holds it), the B row of
   its target coordinate and its value, splat across the row, go into
   row j of VMEM tiles. The loop only loads from read-only refs and
   stores to distinct rows, so no element waits on another;
2. Poisson update (vector, no loop): `phi_row`'s formula,
   ``(v / max(<B[i_n], krp>, ε)) · krp``, over the whole ``(block_m, R)``
   tile, so the cross-lane sum and the divide run once per vreg rather
   than once per element inside a serial chain;
3. scatter (scalar loop): per element, in element order, the
   read-modify-write of the partition's Temp row. It is the only step
   whose address depends on an earlier element (elements of one fiber
   hit the same row), so it runs alone, in the order that keeps the
   partials' summation order.

Every phase does `phi_row`'s float32 arithmetic in its order, so the
partials are those of a single loop that runs the three steps per element.

This is the kernel the paper reports >99% of CP-APR time in (§5.3);
fusing it removes the (M, R) intermediate round-trips to HBM that
dominate the CPU profile. The loops are the ones the recursive MTTKRP
kernel uses (`kernels.mttkrp`).

No rank tiling here: the denominator ``<B[i_n,:], krp>`` needs the full rank
per element, and R is small in CPD workloads (paper uses R=16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.encoding import AltoEncoding
from repro.kernels.mttkrp import (DEFAULT_BLOCK_M, _for_each, _krp_row,
                                  _stage_coords, compiler_params,
                                  pad_partitions, resident, stage_scratch,
                                  word_columns)


def phi_row(vals_ref, j, b_row, krp, eps):
    """One element's Φ contribution: (v / max(<B[i_n], krp>, ε)) · krp."""
    denom = jnp.maximum(jnp.sum(b_row * krp, axis=-1, keepdims=True), eps)
    return (vals_ref[j] / denom) * krp


def _phi_partial_kernel(enc: AltoEncoding, mode: int, eps: float,
                        pre_pi: bool, start_ref, *refs):
    W, n_other = enc.n_words, enc.ndim - 1
    word_refs, vals_ref, b_ref = refs[:W], refs[W], refs[W + 1]
    n_ops = 1 if pre_pi else n_other
    operand_refs = refs[W + 2:W + 2 + n_ops]     # Π tile or other factors
    out_ref, cv_ref, cs_ref, sem, c_ref, bg_ref, v_ref = refs[W + 2 + n_ops:]
    others = [m for m in range(enc.ndim) if m != mode]
    R = c_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    _stage_coords(enc, others + [mode], word_refs, cv_ref, cs_ref, sem)
    start = start_ref[pl.program_id(0)]

    def gather(j, state):
        if not pre_pi:
            c_ref[pl.ds(j, 1), :] = _krp_row(j, cs_ref, operand_refs)
        bg_ref[pl.ds(j, 1), :] = b_ref[pl.ds(cs_ref[n_other, j], 1), :]
        v_ref[pl.ds(j, 1), :] = jnp.full((1, R), vals_ref[j], v_ref.dtype)
        return state

    _for_each(DEFAULT_BLOCK_M, gather, 0)

    krp = operand_refs[0][...] if pre_pi else c_ref[...]
    c_ref[...] = phi_row(v_ref, ..., bg_ref[...], krp, eps)   # whole block

    def scatter(j, state):
        local = cs_ref[n_other, j] - start        # in [0, temp_rows)
        out_ref[0, pl.ds(local, 1), :] += c_ref[pl.ds(j, 1), :]
        return state

    _for_each(DEFAULT_BLOCK_M, scatter, 0)


def phi_partials_pallas(enc: AltoEncoding, mode: int, temp_rows: int,
                        eps: float, words: jnp.ndarray, values: jnp.ndarray,
                        part_start: jnp.ndarray, B: jnp.ndarray,
                        factors=None, pi: jnp.ndarray | None = None,
                        interpret: bool = True) -> jnp.ndarray:
    """Per-partition Φ partials: (L, temp_rows, R).

    Pass ``pi`` for ALTO-PRE or ``factors`` for ALTO-OTF (exactly one).
    """
    pre_pi = pi is not None
    if pre_pi == (factors is not None):
        raise ValueError("pass exactly one of pi= / factors=")
    block_m = DEFAULT_BLOCK_M
    L = part_start.shape[0]
    R = B.shape[1]
    cols, values, pi, chunk = pad_partitions(L, block_m, word_columns(words),
                                             values, pi=pi)
    nbl = chunk // block_m

    def stream(l, b):
        return (l * nbl + b,)

    in_specs = (
        [pl.BlockSpec(memory_space=pltpu.SMEM)]                 # starts
        + [pl.BlockSpec((block_m,), stream)] * enc.n_words      # words
        + [pl.BlockSpec((block_m,), stream, memory_space=pltpu.SMEM),
           resident(B.shape, lambda l, b: (0, 0))])             # vals, B
    args = [part_start[:, mode], *cols, values, B]
    if pre_pi:
        in_specs.append(pl.BlockSpec((block_m, R),
                                     lambda l, b: (l * nbl + b, 0)))
        args.append(pi)
    else:
        others = [f for m, f in enumerate(factors) if m != mode]
        in_specs += [resident(f.shape, lambda l, b: (0, 0)) for f in others]
        args += others

    return pl.pallas_call(
        functools.partial(_phi_partial_kernel, enc, mode, eps, pre_pi),
        name="alto_phi_recursive",
        grid=(L, nbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, temp_rows, R), lambda l, b: (l, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, temp_rows, R), B.dtype),
        # Φ rows (krp, then C), gathered B rows, value splats
        scratch_shapes=(stage_scratch(enc.ndim, block_m)
                        + [pltpu.VMEM((block_m, R), B.dtype)] * 2
                        + [pltpu.VMEM((block_m, R), values.dtype)]),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*args)

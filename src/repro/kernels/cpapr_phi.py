"""Pallas TPU kernel: fused CP-APR Φ model update (paper Alg. 5).

Per element of each `DEFAULT_BLOCK_M` block of a balanced ALTO partition the
kernel fuses, entirely in VMEM: delinearization → Khatri-Rao row
formation (ALTO-OTF) or Π row load (ALTO-PRE) → B-row load → denominator
dot → elementwise Poisson update → read-modify-write of the partition's
Temp row. This is the kernel the paper reports >99% of CP-APR time in
(§5.3); fusing it removes the (M, R) intermediate round-trips to HBM that
dominate the CPU profile. The element loop is the one the recursive
MTTKRP kernel uses (`kernels.mttkrp`).

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
    out_ref, cv_ref, cs_ref, sem = refs[W + 2 + n_ops:]
    others = [m for m in range(enc.ndim) if m != mode]

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    _stage_coords(enc, others + [mode], word_refs, cv_ref, cs_ref, sem)
    start = start_ref[pl.program_id(0)]

    def body(j, state):
        if pre_pi:
            krp = operand_refs[0][pl.ds(j, 1), :]
        else:
            krp = _krp_row(j, cs_ref, operand_refs)
        row = cs_ref[n_other, j]
        contrib = phi_row(vals_ref, j, b_ref[pl.ds(row, 1), :], krp, eps)
        out_ref[0, pl.ds(row - start, 1), :] += contrib
        return state

    _for_each(DEFAULT_BLOCK_M, body, 0)


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
        scratch_shapes=stage_scratch(enc.ndim, block_m),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(*args)

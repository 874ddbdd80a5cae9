"""Pallas TPU kernel: ALTO delinearization (bit-level scatter, paper Fig. 6b).

Streams the packed multi-word u32 linearized index from HBM through VMEM
tiles and emits int32 coordinates. Pure VPU elementwise work (shifts / ands /
ors over a static run plan), so the kernel is strictly memory-bound — the
point of the paper's compact index is that this stream is 2-4x smaller than
the COO coordinate stream it replaces, and the decode overlaps the loads.

Grid: 1-D over nonzero blocks. Both sides are lane-dense: each index word
arrives as a (block_m,) u32 tile and the coordinates leave as one
(N, block_m) int32 tile of a mode-major (N, M) array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.encoding import AltoEncoding
from repro.kernels.mttkrp import (DEFAULT_BLOCK_M, _decode, compiler_params,
                                  word_columns)


def _delinearize_kernel(enc: AltoEncoding, *refs):
    word_refs, coords_ref = refs[:-1], refs[-1]
    cols = _decode(enc, [w[...] for w in word_refs])
    for m, c in enumerate(cols):
        coords_ref[pl.ds(m, 1), :] = c[None, :]


def delinearize_pallas(enc: AltoEncoding, words: jnp.ndarray,
                       block_m: int = DEFAULT_BLOCK_M,
                       interpret: bool = True) -> jnp.ndarray:
    """(M, n_words) u32 -> (N, M) int32, mode-major. M must be an exact
    multiple of block_m, validated like every other kernel — callers pad
    through the shared `ops.pad_sorted_stream` rule (the `ops.delinearize`
    wrapper does, slicing the tail back off and transposing to (M, N))."""
    M, W = words.shape
    if M % block_m:
        raise ValueError(f"M={M} not a multiple of block_m={block_m}")
    return pl.pallas_call(
        functools.partial(_delinearize_kernel, enc),
        name="alto_delinearize",
        grid=(M // block_m,),
        in_specs=[pl.BlockSpec((block_m,), lambda i: (i,))] * W,
        out_specs=pl.BlockSpec((enc.ndim, block_m), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((enc.ndim, M), jnp.int32),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(*word_columns(words))

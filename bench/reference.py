"""The plain reference: CP-APR outer iterations summed straight from the
COO entries.

It imports nothing of the program under test and takes nothing it made:
its inputs are the COO tensor from `gen` and the initial state the
benchmark draws from the seed. Every Φ is a gather of factor rows and a
segment sum over fixed-size chunks of the COO entries, with no ALTO
stream, no oriented view and no kernel. ``dtype`` lets the same code run
as the precision control: bfloat16, one step below the configuration's
float32.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16            # most COO entries per step of a reduction


def _pad(coords, values):
    """The COO columns zero-padded to whole chunks (value 0 at coordinate
    0, which adds nothing to any sum), and the chunk length."""
    nnz = values.shape[0]
    chunk = min(CHUNK, -(-nnz // 1024) * 1024)
    pad = -nnz % chunk
    cols = tuple(jnp.pad(coords[:, m], (0, pad))
                 for m in range(coords.shape[1]))
    return cols, jnp.pad(values, (0, pad)), chunk


@functools.partial(jax.jit, static_argnames=("mode", "eps", "chunk"))
def _reduce(cols, vals, factors, B, mode: int, eps: float, chunk: int):
    """Σ over entries of v · KRP row scattered to the mode's rows; with
    ``B`` given, v is divided by max(<B[i], KRP row>, eps) first (Φ).
    The chunks' sums are added with compensation (Kahan's TwoSum), so the
    reference's rounding stays that of one chunk, well below the noise of
    the program's float32 sums over whole rows."""
    n_rows, R = factors[mode].shape
    dtype = factors[mode].dtype

    def step(k, acc):
        s, comp = acc
        c = [jax.lax.dynamic_slice_in_dim(a, k * chunk, chunk) for a in cols]
        v = jax.lax.dynamic_slice_in_dim(vals, k * chunk, chunk)
        krp = functools.reduce(operator.mul, (
            factors[m][c[m]] for m in range(len(cols)) if m != mode))
        if B is not None:
            denom = jnp.sum(B[c[mode]] * krp, axis=1)
            v = v / jnp.maximum(denom, jnp.asarray(eps, dtype))
        y = jax.ops.segment_sum(v[:, None] * krp, c[mode],
                                num_segments=n_rows)
        t = s + y
        b = t - s
        return t, comp + ((s - (t - b)) + (y - b))

    zero = jnp.zeros((n_rows, R), dtype)
    s, comp = jax.lax.fori_loop(0, vals.shape[0] // chunk, step, (zero, zero))
    return s + comp


def _cast(coords, values, factors, dtype):
    cols, vals, chunk = _pad(coords, values)
    return (cols, vals.astype(dtype), [jnp.asarray(A, dtype) for A in factors],
            chunk)


def apr_outer(coords, values, lam, factors, outer: int = 1,
              inner: int = 10, eps: float = 1e-10, dtype=jnp.float32):
    """``outer`` CP-APR outer iterations (Alg. 2 with KKT tolerance 0, so
    every one of the ``inner`` multiplicative updates is applied) from
    (λ, factors) with unit column sums. The first iteration makes no
    inadmissible-zero adjustment; later ones add κ = 0.01 where a factor
    entry is below 1e-10 and its last Φ exceeds 1. Returns (λ, factors)."""
    cols, vals, factors, chunk = _cast(coords, values, factors, dtype)
    lam = jnp.asarray(lam, dtype)
    phi_prev = [None] * len(factors)
    for it in range(outer):
        for n in range(len(factors)):
            A = factors[n]
            if it > 0:
                A = A + jnp.where((A < 1e-10) & (phi_prev[n] > 1), 0.01,
                                  0).astype(dtype)
            B = A * lam[None, :]
            for _ in range(inner):
                phi = _reduce(cols, vals, factors, B, mode=n, eps=eps,
                              chunk=chunk)
                B = B * phi
            phi_prev[n] = phi
            lam = jnp.sum(B, axis=0)
            lam = jnp.where(lam > 0, lam, 1).astype(dtype)
            factors[n] = B / lam[None, :]
    return lam, factors


def rel_gap(got, want) -> float:
    """max |got - want| / max |want|, in float64 on the host."""
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def state_gap(lam, factors, lam_ref, factors_ref) -> float:
    """The worst leaf: the largest `rel_gap` over λ and every factor."""
    return max([rel_gap(lam, lam_ref)]
               + [rel_gap(a, b) for a, b in zip(factors, factors_ref)])

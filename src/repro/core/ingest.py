"""Incremental ingest: jitted delta-merge into the resident ALTO stream.

Real workloads mutate the tensor — nonzeros arrive continuously — and a
from-scratch `alto.build_device` per delta batch throws away the one
expensive invariant the resident tensor already holds: its stream is
SORTED. This module keeps it. `append_delta` linearizes the delta batch
in-jit, concatenates it after the resident stream, and runs the SAME
stable multi-word key sort `build_device` uses (`encoding.sort_by_key`)
over the combined stream, then re-derives the partition bounding boxes
and fiber counts inside the same jitted core — zero host callbacks, one
trace per static merge meta (the Dynasor/ReLATE dynamic-relayout regime
from PAPERS.md, on PR 5's device-ingest machinery).

Bit-for-bit parity with the host rebuild (`alto.merge_reference`) falls
out of sort stability: the resident stream is the stable sort of the old
COO, so stably sorting ``[resident stream; delta batch]`` equals stably
sorting the concatenated COO itself — element order, padding, boxes, and
meta all identical to `build(merge_coo(...))`. Duplicate-coordinate
policies preserve that exactness by construction:

* ``"sum"`` keeps every entry (duplicates sit adjacent after the sort
  and accumulate in downstream segment reductions, exactly as `build`
  treats duplicate COO input today) — a pure permutation, trivially
  bitwise.
* ``"last"`` masks all but the final occurrence of each duplicate key to
  value 0 — a pure mask from sorted adjacency, no arithmetic, so there
  is no float-association hazard; writing value 0 acts as a delete.

Real group-summation was deliberately rejected: ``np.add.at``
(sequential) vs a jitted segment-sum (tree) associate float additions
differently, which would break the bit-parity contract every other
subsystem (views cache, chunked executors, Mosaic port) leans on.

Extent growth re-encodes in-jit: when the delta pushes a mode past its
extent, `encoding.make_encoding` may re-assign index bits, so the
resident words are round-tripped ``linearize(new, delinearize(old, w))``
— an exact integer bit transform — before the merge sort.

On top: `grow_factors` seeds warm-start CP solves from a previous
result, padding factor rows when extents expanded, so per-delta latency
is sweeps-from-converged instead of from-scratch (`cpals.cp_als` /
`cpapr.cp_apr` take ``warm_start=``).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alto
from repro.core import encoding as enc_mod
from repro.core import faults
from repro.core import telemetry
from repro.core import views as views_mod
from repro.core.alto import AltoTensor
from repro.core.encoding import AltoEncoding, make_encoding

POLICIES = alto.MERGE_POLICIES


# ---------------------------------------------------------------------------
# The jitted merge core (cached per static merge meta in alto's LRU)
# ---------------------------------------------------------------------------

def _merge_device_fn(old_enc: AltoEncoding, new_enc: AltoEncoding, L: int,
                     M: int, res_len: int, D: int, policy: str,
                     val_dtype, delta_form: str):
    """The cached jitted delta-merge core for one static merge meta.

    ``delta_form`` is "coords" ((D, N) int32, linearized in-jit — the
    local `append_delta` path) or "words" ((D, W) u32 already linearized
    under ``new_enc`` — the sharded ingest path, where linearization ran
    under `shard_map`). ``res_len``/``M`` pin the resident padded/real
    lengths so the trace-once contract keys on the full static shape.
    """
    key = ("merge", old_enc, new_enc, L, M, res_len, D, policy,
           jnp.dtype(val_dtype).name, delta_form)
    N, W = new_enc.ndim, new_enc.n_words
    MD = M + D
    chunk = -(-max(MD, L) // L)
    Mp = chunk * L

    @telemetry.traced("ingest.merge.trace")
    def core(res_words, res_values, delta, delta_values):
        rw = res_words[:M]
        if new_enc != old_enc:
            # Extent growth re-assigned index bits: exact integer
            # round-trip of the resident words into the new layout.
            rw = alto.linearize(new_enc, alto.delinearize(old_enc, rw))
        dw = (delta if delta_form == "words"
              else alto.linearize(new_enc, delta))
        words = jnp.concatenate([rw, dw], axis=0)        # (MD, W)
        values = jnp.concatenate([res_values[:M], delta_values], axis=0)
        # Resident is already sorted; the stable sort of [sorted; delta]
        # IS the stable sort of the concatenated COO (ties resident-
        # first, then delta input order) — the host-parity invariant.
        words, values = enc_mod.sort_by_key(words, values)
        if policy == "last" and MD > 1:
            is_last = jnp.concatenate(
                [jnp.any(words[1:] != words[:-1], axis=-1),
                 jnp.ones((1,), bool)])
            values = jnp.where(is_last, values, jnp.zeros_like(values))
        if Mp > MD:
            # build()'s padding rule: value-0 copies of the last element.
            pad = Mp - MD
            pw = (jnp.zeros((pad, W), jnp.uint32) if MD == 0
                  else jnp.broadcast_to(words[-1:], (pad, W)))
            words = jnp.concatenate([words, pw])
            values = jnp.concatenate(
                [values, jnp.zeros((pad,), values.dtype)])
        # delinearize is linearize's exact inverse, so these coords equal
        # the carried-column coords build() takes its boxes from.
        cc = alto.delinearize(new_enc, words).reshape(L, chunk, N)
        part_start = jnp.min(cc, axis=1).astype(jnp.int32)
        part_end = jnp.max(cc, axis=1).astype(jnp.int32)
        return words, values, part_start, part_end

    return alto._cached_ingest_fn(key, lambda: jax.jit(core))


def _append(at: AltoTensor, delta, delta_values, new_dims: tuple[int, ...],
            delta_form: str, policy: str, n_partitions, compute_reuse,
            invalidate_stale: bool) -> AltoTensor:
    if policy not in POLICIES:
        raise ValueError(f"policy {policy!r}: expected one of {POLICIES}")
    old_enc = at.meta.enc
    new_enc = make_encoding(new_dims)
    L = (at.meta.n_partitions if n_partitions is None
         else max(1, int(n_partitions)))
    if compute_reuse is None:
        # Match the resident tensor's choice (NaN reuse == it was off).
        compute_reuse = not math.isnan(at.meta.fiber_reuse[0])
    M = at.meta.nnz
    D = int(delta.shape[0])
    fn = _merge_device_fn(old_enc, new_enc, L, M, int(at.words.shape[0]),
                          D, policy, at.values.dtype, delta_form)
    # Interruption site: the merge is functional (the resident tensor is
    # never mutated), so a kill here leaves `at` fully serviceable and a
    # retry re-runs the identical jitted program.
    faults.inject("ingest.merge")
    out = fn(at.words, at.values, delta, delta_values)
    new_at = alto.finalize_device(new_enc, M + D, L, *out,
                                  compute_reuse=bool(compute_reuse))
    if invalidate_stale:
        # Surgical: only modes whose content fingerprint moved lose their
        # cached views — a no-op append (empty delta, "sum") drops
        # nothing and the old views keep serving the merged tensor.
        views_mod.invalidate_changed(at, new_at)
    return new_at


def append_delta(at: AltoTensor, coords, values, *, policy: str = "sum",
                 dims: Sequence[int] | None = None,
                 n_partitions: int | None = None,
                 compute_reuse: bool | None = None,
                 invalidate_stale: bool = True) -> AltoTensor:
    """Merge a COO delta batch into ``at`` on device.

    Bit-identical to `alto.merge_reference(at, coords, values, ...)` —
    the from-scratch host rebuild — with the delta linearized, merge-
    sorted, policy-masked, and re-finalized inside one jitted core.
    Extents grow automatically to cover the delta (``dims`` overrides,
    e.g. to pre-reserve headroom so the encoding stays put across many
    appends); ``n_partitions`` defaults to the resident tiling. The new
    tensor's meta counts ``at.nnz + len(values)`` entries — duplicates
    are accumulated ("sum") or masked ("last"), never compacted, keeping
    the merged size static for jit.
    """
    coords = np.asarray(coords, dtype=np.int32).reshape(-1, len(at.dims))
    new_dims = alto.grown_dims(at.dims, coords, dims)
    return _append(at, jnp.asarray(coords),
                   jnp.asarray(values, dtype=at.values.dtype).reshape(-1),
                   new_dims, "coords", policy, n_partitions, compute_reuse,
                   invalidate_stale)


def append_linearized(at: AltoTensor, delta_words, values,
                      dims: Sequence[int], *, policy: str = "sum",
                      n_partitions: int | None = None,
                      compute_reuse: bool | None = None,
                      invalidate_stale: bool = True) -> AltoTensor:
    """`append_delta` for a delta already linearized under
    ``make_encoding(dims)`` — the distributed ingest entry point, where
    linearization ran shard-local under `shard_map` (`dist.cpd.
    sharded_append_delta`). ``dims`` is explicit because the words alone
    don't carry extents; it must cover the resident dims.
    """
    new_dims = alto.grown_dims(at.dims, np.empty((0, len(at.dims))), dims)
    return _append(at, jnp.asarray(delta_words),
                   jnp.asarray(values, dtype=at.values.dtype).reshape(-1),
                   new_dims, "words", policy, n_partitions, compute_reuse,
                   invalidate_stale)


# ---------------------------------------------------------------------------
# Warm-start factor growth (drivers' ``warm_start=`` backing)
# ---------------------------------------------------------------------------

def grow_factors(warm, dims: Sequence[int], rank: int, *, seed: int = 0,
                 dtype=None, positive: bool = False):
    """Adapt a previous solve's factors to (possibly grown) ``dims``.

    ``warm`` is a `CpalsResult`/`CpaprResult`, a ``(lam, factors)``
    tuple, or a bare factor list. Existing rows are kept verbatim (the
    converged state IS the warm start); rows for newly-grown extents are
    drawn from the drivers' seeded init so the fill is deterministic.
    Returns ``(lam, factors)`` with ``lam=None`` when ``warm`` carried no
    weights. Shrinking an extent or changing the rank has no meaningful
    warm state to keep and raises. ``positive=True`` (CP-APR) clamps the
    grown factors positive and re-normalizes columns to unit sum, the
    form the multiplicative updates expect.
    """
    lam = getattr(warm, "lam", None)
    factors = getattr(warm, "factors", None)
    if factors is None:
        if isinstance(warm, tuple) and len(warm) == 2:
            lam, factors = warm
        else:
            factors = warm
    factors = list(factors)
    dims = tuple(int(d) for d in dims)
    if len(factors) != len(dims):
        raise ValueError(f"warm start has {len(factors)} factors for "
                         f"{len(dims)} modes")
    if dtype is None:
        dtype = factors[0].dtype
    fresh = None
    out = []
    for n, (A, I) in enumerate(zip(factors, dims)):
        A = jnp.asarray(A, dtype=dtype)
        if A.ndim != 2 or A.shape[1] != rank:
            raise ValueError(f"warm factor {n} has shape {A.shape}; "
                             f"expected (*, {rank})")
        if A.shape[0] > I:
            raise ValueError(f"mode {n} shrank: warm factor has "
                             f"{A.shape[0]} rows, dims say {I}")
        if A.shape[0] < I:
            if fresh is None:
                from repro.core import cpals  # lazy: drivers import us
                fresh = cpals.init_factors(dims, rank, seed=seed,
                                           dtype=dtype)
            grown = fresh[n][A.shape[0]:I]
            if positive:
                # Small positive mass: perturbs the converged model as
                # little as possible while keeping the MU domain open.
                grown = jnp.maximum(grown, 0.1) / max(1, I)
            A = jnp.concatenate([A, grown], axis=0)
        if positive:
            A = jnp.maximum(A, 1e-10)
            A = A / jnp.sum(A, axis=0, keepdims=True)
        out.append(A)
    if lam is not None:
        lam = jnp.asarray(lam, dtype=dtype)
    return lam, out

"""Jit'd public wrappers around the Pallas kernels, with executable caching.

Paper §4.2/§4.3 kernel entry points. Invariants: oriented entry points
consume a *row-sorted* stream (ascending target-mode row, `ops` pads it to
the block multiple with zero-valued copies of the last element); every
cache key is built from static, hashable metadata only (`AltoMeta`, mode,
tiling, interpret flag), never from traced values; `segment_merge` must
reproduce the kernels' run-rank segmentation bit-for-bit (both call
`mttkrp_oriented.run_rank_segments`) — that is the carry-merge correctness
condition.

On the CPU test host every kernel runs with interpret=True (the Pallas
interpreter traces the kernel body into regular XLA); on TPU the same call
sites compile to Mosaic. `interpret=None` auto-detects.

Every wrapper resolves to a **cached jitted executable** keyed on the
tensor's static metadata (`AltoMeta` is frozen/hashable) plus the static
kernel parameters (mode, block sizes, interpret flag). Before this cache
each call built a fresh closure and `jax.jit` object, so XLA re-traced and
re-compiled the kernel on *every* invocation — per sweep, per mode, per
iteration. Now the first call per (meta, mode, tiling) compiles once and
subsequent calls hit jit's C++ fast path.
"""
from __future__ import annotations

import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core import stream as _stream
from repro.core.alto import AltoTensor, OrientedView
from repro.core.alto import delinearize as _delin_jnp
from repro.core.encoding import AltoEncoding
from repro.core.mttkrp import krp_rows as _krp_rows
from repro.kernels import cpapr_phi as _phi
from repro.kernels import delinearize as _delin
from repro.kernels import mttkrp as _mttkrp
from repro.kernels import mttkrp_oriented as _oriented


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Compiled-executable cache
# ---------------------------------------------------------------------------

_EXEC_CACHE: dict[tuple, Callable] = {}
# One lock for every module-global mutated here (the executable cache and
# the timing counter below): concurrent autotuners / serving drivers were
# racing dict insertions and losing counter increments.
_OPS_LOCK = threading.Lock()


def _cached_executable(key: tuple, build: Callable[[], Callable]) -> Callable:
    """Return the jitted executable for ``key``, building it on first use.

    Thread-safe: the whole check-build-insert runs under the module lock.
    ``build`` only constructs the `jax.jit` wrapper (tracing/compilation
    happens lazily at the first call, outside the lock), so holding the
    lock across it is cheap and keeps the one-entry-per-key contract.
    """
    with _OPS_LOCK:
        fn = _EXEC_CACHE.get(key)
        if fn is None:
            fn = _EXEC_CACHE[key] = build()
        return fn


def cache_size() -> int:
    with _OPS_LOCK:
        return len(_EXEC_CACHE)


def cache_clear() -> None:
    with _OPS_LOCK:
        _EXEC_CACHE.clear()


# ---------------------------------------------------------------------------
# Timing hook (the autotuner's measurement primitive)
# ---------------------------------------------------------------------------

_TIMING_RUNS = 0


def timing_runs() -> int:
    """Number of `median_time` measurements taken in this process.

    `core.autotune` uses this to prove plan-store hits are measurement
    free: loading a persisted plan must leave the counter untouched.
    """
    with _OPS_LOCK:
        return _TIMING_RUNS


def timing_stats(fn: Callable, *args, warmup: int = 1,
                 iters: int = 3) -> tuple[float, float]:
    """(median, IQR) wall-clock seconds of a blocking call, after warmup.

    The autotuner's timing hook on the cached executables: ``fn`` is one
    of the public wrappers above (or any callable ending in a jitted
    call), so the warmup runs absorb compilation + the executable-cache
    fill and the timed iterations hit jit's C++ fast path. Warmup calls
    are run but never timed — they cannot enter the sample at all, so a
    slow first (compiling) call can't skew the statistics. The median is
    the true sample median (middle-pair average for even ``iters``, not
    the upper-middle element), robust against one descheduled run; the
    IQR (Q3 − Q1, nearest-rank quartiles) is the measurement's own
    spread estimate — search fitness comparisons can treat two medians
    closer than their IQRs as a tie instead of crowning noise.

    One call == one measurement for the `timing_runs` counter contract,
    regardless of ``warmup``/``iters``.
    """
    global _TIMING_RUNS
    # Unsynchronized `+= 1` loses updates under concurrent autotuning,
    # which silently breaks the "store hits are measurement-free" proof
    # (a lost increment can mask a real measurement).
    with _OPS_LOCK:
        _TIMING_RUNS += 1
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    n = len(times)
    if n % 2:
        median = times[n // 2]
    else:
        median = 0.5 * (times[n // 2 - 1] + times[n // 2])
    # Nearest-rank quartiles: exact enough for the small n the tuner
    # uses, and degenerate (IQR=0) at n=1 as it should be.
    q1 = times[n // 4]
    q3 = times[min(n - 1, (3 * n) // 4)]
    return median, max(0.0, q3 - q1)


def median_time(fn: Callable, *args, warmup: int = 1,
                iters: int = 3) -> float:
    """Median wall-clock seconds of a blocking call (see `timing_stats`;
    this is the stats' median alone, one counted measurement either way).
    """
    return timing_stats(fn, *args, warmup=warmup, iters=iters)[0]


# ---------------------------------------------------------------------------
# Reductions shared by the kernels (jnp, fused into the cached executables)
# ---------------------------------------------------------------------------

def pull_reduction(partials: jnp.ndarray, part_start_mode: jnp.ndarray,
                   out_dim: int) -> jnp.ndarray:
    """Merge per-partition Temp buffers (Alg. 4 lines 14-18)."""
    L, T, R = partials.shape
    rows = part_start_mode[:, None] + jnp.arange(T)[None, :]
    rows = jnp.minimum(rows, out_dim - 1)
    out = jnp.zeros((out_dim, R), partials.dtype)
    return out.at[rows].add(partials)


def segment_merge(partials: jnp.ndarray, rows: jnp.ndarray,
                  out_dim: int) -> jnp.ndarray:
    """Scatter per-block segment sums to global rows (boundary carry merge).

    ``partials`` is (n_blocks, block_m, R) from the oriented kernel; slot j
    of block b holds the sum of the block's j-th distinct-row run. The
    global row of that run is recovered from the sorted ``rows`` stream
    with the same run-rank prefix scan the kernel used. A row whose run
    spans a block boundary appears as the last segment of one block and
    the first of the next — both scatter to the same output row, which is
    exactly the carry merge ("atomics only at partition boundaries").
    Unused slots carry zero sums and scatter harmlessly to row 0.

    This is the shardable half of the oriented reduction: the scatter-add
    is associative and ``rows`` carries *global* row ids, so applying it to
    each device's contiguous slice of the sorted stream and ``psum``-ing
    the dense outputs yields exactly the single-device result — a run that
    spans a device boundary becomes one partial sum per device, merged by
    the psum the same way in-block boundary carries are merged here.
    `repro.dist.cpd` relies on this to shard CP-ALS/CP-APR row reductions.
    """
    nb, bm, R = partials.shape
    rows_b = rows.reshape(nb, bm)
    seg = _oriented.run_rank_segments(rows_b)              # (nb, bm)
    seg_rows = jnp.zeros((nb, bm), jnp.int32).at[
        jnp.arange(nb)[:, None], seg].set(rows_b)
    out = jnp.zeros((out_dim, R), partials.dtype)
    return out.at[seg_rows.reshape(-1)].add(partials.reshape(nb * bm, R))


def pad_sorted_stream(rows, words, values, mult: int, pi=None):
    """Pad the sorted stream to a multiple of ``mult`` elements.

    The single implementation of the padding rule the carry merge relies
    on (`mttkrp_oriented`'s block grid, `dist.cpd`'s shard cut, the
    `delinearize` wrapper's word-only stream): the final row/words are
    replicated (stream stays sorted, padding joins the final segment)
    with zero values, so padded elements contribute nothing to any
    reduction. ``rows``/``values``/``pi`` may each be None (padding is
    skipped for absent operands — `delinearize` pads words alone).
    An nnz=0 stream has no final row to replicate; it pads with zero
    rows/words instead (still sorted, still value-0), so degenerate
    tenant inputs flow through the same rule instead of crashing on the
    empty ``words[-1:]`` slice. Returns ``(rows, words, values, pi)``.
    """
    M = words.shape[0]
    # An empty stream pads up to one full block (0 is trivially a
    # multiple of mult, but a zero-length stream gives every downstream
    # block grid zero steps).
    pad = mult if M == 0 else (-M) % mult
    if pad == 0:
        return rows, words, values, pi
    if M == 0:
        pad_rows = (None if rows is None
                    else jnp.zeros((pad,), rows.dtype))
        pad_words = jnp.zeros((pad, words.shape[1]), words.dtype)
    else:
        pad_rows = (None if rows is None
                    else jnp.broadcast_to(rows[-1:], (pad,)))
        pad_words = jnp.broadcast_to(words[-1:], (pad, words.shape[1]))
    if rows is not None:
        rows = jnp.concatenate([rows, pad_rows])
    words = jnp.concatenate([words, pad_words])
    if values is not None:
        values = jnp.concatenate(
            [values, jnp.zeros((pad,), values.dtype)])
    if pi is not None:
        pi = jnp.concatenate([pi, jnp.zeros((pad, pi.shape[1]), pi.dtype)])
    return rows, words, values, pi


# ---------------------------------------------------------------------------
# Public kernel entry points
# ---------------------------------------------------------------------------

def delinearize(enc: AltoEncoding, words: jnp.ndarray,
                block_m: int = _delin.DEFAULT_BLOCK_M,
                interpret: bool | None = None) -> jnp.ndarray:
    """ALTO index words -> int32 coordinates (bit-scatter kernel).

    The word stream is padded to the block multiple through the shared
    `pad_sorted_stream` rule (replicated final element — the same rule
    every oriented kernel relies on) and the padded tail is sliced off
    the coordinate output, so the kernel always sees full blocks at the
    caller's requested ``block_m`` instead of silently shrinking it.
    """
    interp = _auto_interpret(interpret)

    def build():
        def run(words):
            _, padded, _, _ = pad_sorted_stream(None, words, None, block_m)
            coords = _delin.delinearize_pallas(enc, padded, block_m=block_m,
                                               interpret=interp)
            return coords[:, :words.shape[0]].T
        return jax.jit(run)

    fn = _cached_executable(("delin", enc, block_m, interp), build)
    return fn(words)


def mttkrp(at: AltoTensor, factors, mode: int,
           r_block: int | None = None,
           interpret: bool | None = None) -> jnp.ndarray:
    """Recursive-traversal MTTKRP: Pallas partials kernel + pull reduction."""
    meta = at.meta
    interp = _auto_interpret(interpret)
    rb = r_block or factors[mode].shape[1]

    faults.inject("ops.exec")

    def build():
        def run(words, values, part_start, factors):
            partials = _mttkrp.mttkrp_partials_pallas(
                meta.enc, mode, meta.temp_rows[mode], words, values,
                part_start, factors, r_block=rb, interpret=interp)
            return pull_reduction(partials, part_start[:, mode],
                                  meta.dims[mode])
        return jax.jit(run)

    fn = _cached_executable(("mttkrp_rec", meta, mode, rb, interp), build)
    return fn(at.words, at.values, at.part_start, list(factors))


def mttkrp_oriented(view: OrientedView, factors,
                    block_m: int = _oriented.DEFAULT_BLOCK_M,
                    r_block: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Output-oriented MTTKRP: Pallas segment kernel + boundary merge."""
    meta = view.meta
    mode = view.mode
    interp = _auto_interpret(interpret)
    rb = r_block or factors[mode].shape[1]

    faults.inject("ops.exec")

    def build():
        def run(rows, words, values, factors):
            rows, words, values, _ = pad_sorted_stream(rows, words, values,
                                                       block_m)
            partials = _oriented.mttkrp_oriented_partials_pallas(
                meta.enc, mode, rows, words, values, factors,
                block_m=block_m, r_block=rb, interpret=interp)
            return segment_merge(partials, rows, meta.dims[mode])
        return jax.jit(run)

    fn = _cached_executable(
        ("mttkrp_ori", meta, mode, block_m, rb, interp), build)
    return fn(view.rows, view.words, view.values, list(factors))


def mttkrp_oriented_carry(view: OrientedView, factors,
                          block_m: int = _oriented.DEFAULT_BLOCK_M,
                          r_block: int | None = None,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Scratch-carry oriented MTTKRP: sequential Pallas scan, no merge.

    The kernel writes the final ``(I_n, R)`` rows directly (resident
    output tile + inter-block carry scratch), so this path materializes
    no ``(n_blocks, block_m, R)`` partials and runs no `segment_merge` —
    the carry-merge work happens inside the scan. Bit-identical to
    `mttkrp_oriented` at the same tiling.
    """
    meta = view.meta
    mode = view.mode
    interp = _auto_interpret(interpret)
    rb = r_block or factors[mode].shape[1]

    faults.inject("ops.exec")

    def build():
        def run(rows, words, values, factors):
            rows, words, values, _ = pad_sorted_stream(rows, words, values,
                                                       block_m)
            return _oriented.mttkrp_oriented_carry_pallas(
                meta.enc, mode, rows, words, values, factors,
                block_m=block_m, r_block=rb, interpret=interp)
        return jax.jit(run)

    fn = _cached_executable(
        ("mttkrp_carry", meta, mode, block_m, rb, interp), build)
    return fn(view.rows, view.words, view.values, list(factors))


def cpapr_phi(at: AltoTensor, B: jnp.ndarray, mode: int,
              factors=None, pi: jnp.ndarray | None = None,
              eps: float = 1e-10,
              interpret: bool | None = None) -> jnp.ndarray:
    """Recursive-traversal fused Φ: Pallas partials kernel + pull reduction."""
    meta = at.meta
    interp = _auto_interpret(interpret)
    pre_pi = pi is not None

    faults.inject("ops.exec")

    def build():
        def run(words, values, part_start, B, factors, pi):
            partials = _phi.phi_partials_pallas(
                meta.enc, mode, meta.temp_rows[mode], eps, words, values,
                part_start, B, factors=factors, pi=pi, interpret=interp)
            return pull_reduction(partials, part_start[:, mode],
                                  meta.dims[mode])
        return jax.jit(run)

    fn = _cached_executable(
        ("phi_rec", meta, mode, eps, pre_pi, interp), build)
    return fn(at.words, at.values, at.part_start, B,
              list(factors) if factors is not None else None, pi)


def cpapr_phi_oriented(view: OrientedView, B: jnp.ndarray,
                       factors=None, pi: jnp.ndarray | None = None,
                       eps: float = 1e-10,
                       block_m: int = _oriented.DEFAULT_BLOCK_M,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Output-oriented fused Φ: Pallas segment kernel + boundary merge."""
    meta = view.meta
    mode = view.mode
    interp = _auto_interpret(interpret)
    pre_pi = pi is not None

    faults.inject("ops.exec")

    def build():
        def run(rows, words, values, B, factors, pi):
            rows, words, values, pi = pad_sorted_stream(rows, words, values,
                                                        block_m, pi=pi)
            partials = _oriented.phi_oriented_partials_pallas(
                meta.enc, mode, eps, rows, words, values, B,
                factors=factors, pi=pi, block_m=block_m, interpret=interp)
            return segment_merge(partials, rows, meta.dims[mode])
        return jax.jit(run)

    fn = _cached_executable(
        ("phi_ori", meta, mode, eps, pre_pi, block_m, interp), build)
    return fn(view.rows, view.words, view.values, B,
              list(factors) if factors is not None else None, pi)


def cpapr_phi_oriented_carry(view: OrientedView, B: jnp.ndarray,
                             factors=None, pi: jnp.ndarray | None = None,
                             eps: float = 1e-10,
                             block_m: int = _oriented.DEFAULT_BLOCK_M,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Scratch-carry fused Φ: sequential Pallas scan, no merge pass."""
    meta = view.meta
    mode = view.mode
    interp = _auto_interpret(interpret)
    pre_pi = pi is not None

    faults.inject("ops.exec")

    def build():
        def run(rows, words, values, B, factors, pi):
            rows, words, values, pi = pad_sorted_stream(rows, words, values,
                                                        block_m, pi=pi)
            return _oriented.phi_oriented_carry_pallas(
                meta.enc, mode, eps, rows, words, values, B,
                factors=factors, pi=pi, block_m=block_m, interpret=interp)
        return jax.jit(run)

    fn = _cached_executable(
        ("phi_carry", meta, mode, eps, pre_pi, block_m, interp), build)
    return fn(view.rows, view.words, view.values, B,
              list(factors) if factors is not None else None, pi)


# ---------------------------------------------------------------------------
# Out-of-core chunked executors (host stream -> device, cross-chunk carry)
# ---------------------------------------------------------------------------
#
# The host loop that drives the chunk kernels in `mttkrp_oriented`: a
# `core.stream.HostStream` is sliced at block_m-aligned chunk boundaries
# and each chunk flows through ONE cached per-chunk-shape jitted
# executable, threading (out, carry_row, carry_val) from chunk to chunk.
# Double buffering: the NEXT chunk's `device_put` is dispatched before the
# current chunk's compute (async on accelerator backends, so copy overlaps
# compute; on the CPU test host it is a plain copy — `docs/known-issues.md`
# carries the timing caveat). At most two chunk lengths exist per stream
# (the full chunk_m and one shorter tail), so the executable cache holds
# at most 2 entries per (meta, mode, tiling) — not one per chunk.

_CHUNK_STATS = {"chunks": 0, "prefetches": 0}


def chunk_stats() -> dict[str, int]:
    """Chunk-executor counters: chunks executed, prefetch puts issued.

    `tests/test_outofcore.py` uses the delta to pin "modeled chunk count
    == executed grid"; `bench_outofcore` reports overlap efficiency."""
    with _OPS_LOCK:
        return dict(_CHUNK_STATS)


def chunk_stats_clear() -> None:
    with _OPS_LOCK:
        for k in _CHUNK_STATS:
            _CHUNK_STATS[k] = 0


def _chunk_bounds(padded_len: int, chunk_m: int) -> list[tuple[int, int]]:
    """Chunk slice bounds over the padded stream (last may be shorter)."""
    return [(s, min(s + chunk_m, padded_len))
            for s in range(0, padded_len, chunk_m)]


def _bump(counter: str, n: int = 1) -> None:
    with _OPS_LOCK:
        _CHUNK_STATS[counter] += n


def mttkrp_oriented_chunked(view, factors, *, chunk_m: int,
                            block_m: int = _oriented.DEFAULT_BLOCK_M,
                            r_block: int | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Out-of-core scratch-carry MTTKRP: host stream -> (I_n, R).

    ``view`` is a `core.stream.HostStream` (or an in-core `OrientedView`,
    adapted on the fly). Bitwise-identical to `mttkrp_oriented_carry` at
    equal tiling: chunk boundaries sit on block boundaries of the same
    padded stream and the open run rides the carry chain across them.
    """
    hs = _stream.ensure_host(view)
    meta, mode = hs.meta, hs.mode
    interp = _auto_interpret(interpret)
    R = factors[0].shape[1]
    rb = r_block or R
    if chunk_m % block_m:
        raise ValueError(f"chunk_m {chunk_m} not a multiple of "
                         f"block_m {block_m}")
    bounds = _chunk_bounds(hs.padded_len(block_m), chunk_m)
    I_n = meta.dims[mode]
    dtype = factors[0].dtype
    factors = [jnp.asarray(f) for f in factors]
    out, crow, cval = _oriented.fresh_carry(I_n, R, dtype)

    nxt = _stream.put_chunk(hs, *bounds[0])
    for i, (s, e) in enumerate(bounds):
        faults.inject("ops.chunk_oom")
        cur = nxt
        if i + 1 < len(bounds):                # prefetch ahead of compute
            nxt = _stream.put_chunk(hs, *bounds[i + 1])
            _bump("prefetches")

        def build(chunk_len=e - s):
            def run(rows, words, values, factors, out, crow, cval):
                return _oriented.mttkrp_oriented_carry_chunk_pallas(
                    meta.enc, mode, rows, words, values, factors,
                    out, crow, cval, block_m=block_m, r_block=rb,
                    interpret=interp)
            return jax.jit(run)

        fn = _cached_executable(
            ("mttkrp_chunk", meta, mode, e - s, block_m, rb, interp),
            build)
        out, crow, cval = fn(*cur, factors, out, crow, cval)
        _bump("chunks")
    return out


def mttkrp_oriented_chunked_reference(view, factors, *,
                                      chunk_m: int) -> jnp.ndarray:
    """Reference-backend chunked MTTKRP: per-chunk jnp scatter-add.

    Same host loop and `device_put` prefetch as the Pallas executor, but
    each chunk is a plain delinearize + Khatri-Rao + ``at[].add``. Not
    bitwise against the in-core reference `segment_sum` (different
    reduction association); agrees to float tolerance.
    """
    hs = _stream.ensure_host(view)
    meta, mode = hs.meta, hs.mode
    R = factors[0].shape[1]
    bounds = _chunk_bounds(hs.padded_len(1), chunk_m)
    dtype = factors[0].dtype
    factors = [jnp.asarray(f) for f in factors]
    out = jnp.zeros((meta.dims[mode], R), dtype)

    nxt = _stream.put_chunk(hs, *bounds[0])
    for i, (s, e) in enumerate(bounds):
        faults.inject("ops.chunk_oom")
        cur = nxt
        if i + 1 < len(bounds):
            nxt = _stream.put_chunk(hs, *bounds[i + 1])
            _bump("prefetches")

        def build(chunk_len=e - s):
            def run(rows, words, values, factors, out):
                coords = _delin_jnp(meta.enc, words)
                krp = _krp_rows(coords, factors, mode)
                return out.at[rows].add(values[:, None] * krp)
            return jax.jit(run)

        fn = _cached_executable(
            ("mttkrp_ref_chunk", meta, mode, e - s), build)
        out = fn(*cur, factors, out)
        _bump("chunks")
    return out


def cpapr_phi_oriented_chunked(view, B: jnp.ndarray, factors, *,
                               pre: bool, eps: float = 1e-10,
                               chunk_m: int,
                               block_m: int = _oriented.DEFAULT_BLOCK_M,
                               interpret: bool | None = None
                               ) -> jnp.ndarray:
    """Out-of-core scratch-carry fused Φ: host stream -> (I_n, R).

    Streaming takes ``factors`` under BOTH Π policies — a precomputed
    full-stream Π is exactly the O(nnz·R) array streaming exists to
    avoid. Under ``pre=True`` each chunk's Π rows are built on device
    inside the per-chunk executable and fed to the ALTO-PRE kernel
    (elementwise-identical to slicing a precomputed Π, so parity with
    the in-core PRE path stays bitwise for CP-APR's non-negative
    factors); ``pre=False`` is plain ALTO-OTF per chunk. The policy's
    cost meaning shifts under streaming: PRE's once-per-outer-iteration
    precompute becomes a per-chunk recompute (`docs/out-of-core.md`).
    """
    hs = _stream.ensure_host(view)
    meta, mode = hs.meta, hs.mode
    interp = _auto_interpret(interpret)
    if chunk_m % block_m:
        raise ValueError(f"chunk_m {chunk_m} not a multiple of "
                         f"block_m {block_m}")
    bounds = _chunk_bounds(hs.padded_len(block_m), chunk_m)
    I_n, R = B.shape
    B = jnp.asarray(B)
    factors = [jnp.asarray(f) for f in factors]
    out, crow, cval = _oriented.fresh_carry(I_n, R, B.dtype)

    nxt = _stream.put_chunk(hs, *bounds[0])
    for i, (s, e) in enumerate(bounds):
        faults.inject("ops.chunk_oom")
        cur = nxt
        if i + 1 < len(bounds):
            nxt = _stream.put_chunk(hs, *bounds[i + 1])
            _bump("prefetches")

        def build(chunk_len=e - s):
            def run(rows, words, values, B, factors, out, crow, cval):
                if pre:
                    coords = _delin_jnp(meta.enc, words)
                    pi = _krp_rows(coords, factors, mode)
                    return _oriented.phi_oriented_carry_chunk_pallas(
                        meta.enc, mode, eps, rows, words, values, B,
                        out, crow, cval, pi=pi, block_m=block_m,
                        interpret=interp)
                return _oriented.phi_oriented_carry_chunk_pallas(
                    meta.enc, mode, eps, rows, words, values, B,
                    out, crow, cval, factors=factors, block_m=block_m,
                    interpret=interp)
            return jax.jit(run)

        fn = _cached_executable(
            ("phi_chunk", meta, mode, eps, pre, e - s, block_m, interp),
            build)
        out, crow, cval = fn(*cur, B, factors, out, crow, cval)
        _bump("chunks")
    return out


def cpapr_phi_oriented_chunked_reference(view, B: jnp.ndarray, factors, *,
                                         pre: bool, eps: float = 1e-10,
                                         chunk_m: int) -> jnp.ndarray:
    """Reference-backend chunked Φ: per-chunk jnp row reduction.

    Tolerance-level (not bitwise) against the in-core reference path,
    like its MTTKRP sibling.
    """
    hs = _stream.ensure_host(view)
    meta, mode = hs.meta, hs.mode
    bounds = _chunk_bounds(hs.padded_len(1), chunk_m)
    I_n, R = B.shape
    B = jnp.asarray(B)
    factors = [jnp.asarray(f) for f in factors]
    out = jnp.zeros((I_n, R), B.dtype)

    nxt = _stream.put_chunk(hs, *bounds[0])
    for i, (s, e) in enumerate(bounds):
        faults.inject("ops.chunk_oom")
        cur = nxt
        if i + 1 < len(bounds):
            nxt = _stream.put_chunk(hs, *bounds[i + 1])
            _bump("prefetches")

        def build(chunk_len=e - s):
            def run(rows, words, values, B, factors, out):
                coords = _delin_jnp(meta.enc, words)
                krp = _krp_rows(coords, factors, mode)
                denom = jnp.maximum(jnp.sum(B[rows] * krp, axis=-1), eps)
                contrib = (values / denom)[:, None] * krp
                return out.at[rows].add(contrib)
            return jax.jit(run)

        fn = _cached_executable(
            ("phi_ref_chunk", meta, mode, eps, e - s), build)
        out = fn(*cur, B, factors, out)
        _bump("chunks")
    return out

"""Unified, cached oriented-view pipeline: (tensor, mode) -> OrientedView.

Every consumer of the oriented traversal — `cp_als`, `cp_apr`, the
autotuner, the distributed drivers — needs the same row-sorted copy of
the stream per (tensor, mode), and before this module each of them
rebuilt it per call (a host argsort + full host→device copy each time).
This is the single materialization point: views are built once per
(tensor fingerprint, mode) per process, routed host-vs-device, and every
caller shares the cached arrays (`plan.build_views` routes through here).

* **Routing** — ``route="device"`` (default) builds with
  `alto.oriented_view_device` (masked bit-extract + one stable
  `lax.sort`, jit-compiled, no host round-trip); ``route="host"`` keeps
  the numpy parity reference. The two are bit-identical (tier-1
  parity-tested), so the cache never keys on the route. The process
  default comes from ``$REPRO_INGEST`` ("device" | "host").

* **Fingerprinting** — the cache key is content-based, not object-based:
  the hashable `AltoMeta` plus two u32 mixing checksums over the word
  stream and the values (bitcast in their NATIVE dtype, so float64
  tensors differing below float32 resolution cannot alias), reduced on
  device and memoized on the tensor object. Two `AltoTensor`s holding
  the same built data (e.g. rebuilt across driver calls) therefore share
  views, while any change to the data re-keys. The digest transfer is
  two scalars — negligible next to the O(nnz) copies it deduplicates.

* **Accounting & bounds** — hits/misses/builds are counted
  (`cache_stats`) so the "one build per (tensor, mode) per process"
  contract is assertable; per-key build latches keep that contract under
  concurrent drivers *without* serializing unrelated requests (a miss
  registers a pending-build event under the global lock, runs the O(nnz)
  build outside it, and re-acquires only to insert — so a cache hit on
  one tensor never blocks behind another tenant's build).
  The cache is LRU-bounded twice over — by entry count
  (``$REPRO_VIEW_CACHE_SIZE``, default 64) and by approximate resident
  bytes (``$REPRO_VIEW_CACHE_BYTES``, default 2 GiB) — because one view
  is a full O(nnz) copy and a count bound alone would let a sweep over
  large tensors pin multiples of device memory. Dropping a tensor does
  not drop its cached views until they age out; call
  :func:`invalidate` to release them eagerly.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading

import jax
import jax.numpy as jnp

from repro.core import alto
from repro.core import faults
from repro.core import stream as stream_mod
from repro.core.alto import AltoTensor, OrientedView
from repro.core.stream import HostStream

DEFAULT_CACHE_SIZE = 64
DEFAULT_CACHE_BYTES = 2 * 1024 ** 3

_CACHE: "collections.OrderedDict[tuple, OrientedView]" = \
    collections.OrderedDict()
_CACHE_BYTES: dict[tuple, int] = {}
_STATS = {"hits": 0, "misses": 0, "builds": 0, "invalidated": 0}
_LOCK = threading.Lock()
# key -> Event set when that key's in-flight build lands (or fails). The
# global lock only guards map bookkeeping; builds run outside it.
_PENDING: dict[tuple, threading.Event] = {}

_FP_ATTR = "_ingest_fingerprint"


def default_route() -> str:
    """Process-wide ingest routing: ``$REPRO_INGEST`` or "device"."""
    route = os.environ.get("REPRO_INGEST", "device")
    if route not in ("device", "host"):
        raise ValueError(f"REPRO_INGEST={route!r}: expected device|host")
    return route


def _limits() -> tuple[int, int]:
    return (int(os.environ.get("REPRO_VIEW_CACHE_SIZE",
                               DEFAULT_CACHE_SIZE)),
            int(os.environ.get("REPRO_VIEW_CACHE_BYTES",
                               DEFAULT_CACHE_BYTES)))


def _view_bytes(v) -> int:
    """Approximate resident bytes of a cache entry — device `OrientedView`
    or host `core.stream.HostStream` (both count against the byte bound;
    a host stream is still an O(nnz) copy of the tensor)."""
    if isinstance(v, HostStream):
        return v.nbytes()
    return sum(int(a.size) * a.dtype.itemsize
               for a in (v.rows, v.words, v.values, v.perm))


@functools.partial(jax.jit, static_argnums=1)
def _u32_mix(x: jnp.ndarray, salt: int) -> jnp.ndarray:
    """Order-sensitive u32 checksum (wrapping arithmetic).

    One jitted program: run op by op, the ``ravel`` of a narrow (M, W)
    array would be its own executable and relayout the array into
    128-lane tiles on a TPU (~40 GB for a 77M-nonzero, two-word stream).
    """
    x = x.ravel().astype(jnp.uint32)
    idx = jnp.arange(x.shape[0], dtype=jnp.uint32)
    mixed = (x ^ (idx * jnp.uint32(0x9E3779B1))) * jnp.uint32(salt)
    return jnp.sum(mixed, dtype=jnp.uint32)


def fingerprint(at: AltoTensor) -> tuple:
    """Content fingerprint of a built tensor, memoized on the object.

    Hashable: (meta, padded length, words checksum, values checksum).
    `AltoMeta` already pins shape/nnz/partitioning; the checksums pin the
    actual stream content — values bitcast in their native width, so no
    precision is discarded before hashing — and distinct tensors with
    identical meta cannot alias each other's views.
    """
    fp = getattr(at, _FP_ATTR, None)
    if fp is None:
        w = _u32_mix(at.words, 0x85EBCA6B)
        # f32 -> (M,) u32; f64 -> (M, 2) u32: ravel covers both widths.
        v_bits = jax.lax.bitcast_convert_type(at.values, jnp.uint32)
        v = _u32_mix(v_bits, 0xC2B2AE35)
        fp = (at.meta, at.words.shape[0], int(w), int(v))
        at._ingest_fingerprint = fp
    return fp


def mode_fingerprint(at: AltoTensor, mode: int) -> tuple:
    """Per-(tensor content, mode) fingerprint — the invalidation unit.

    Deliberately EXCLUDES the partitioning fields of `AltoMeta`
    (n_partitions, temp_rows, fiber_reuse): an oriented view is a pure
    permutation of the padded stream, so re-tiling the same stream under
    a different partition count leaves every cached view valid. Only the
    encoding, the real/padded lengths, the content checksums, and the
    mode participate — which is what lets `invalidate_changed` keep
    untouched entries alive after a re-tile or a no-op append.
    """
    meta, Mp, w, v = fingerprint(at)
    return (meta.enc, meta.nnz, Mp, w, v, int(mode))


def _rebind_meta(key: tuple, entry, at: AltoTensor):
    """Cached entries key on `mode_fingerprint`, which ignores the
    partitioning fields — so a re-tile can HIT an entry built under a
    different `AltoMeta`. The arrays are identical (pure permutation of
    the same stream); only the meta tag is stale. Rebind it lazily,
    storing the rebound entry back so repeated gets with the same tensor
    return the identical object (callers assert `is`-identity)."""
    if entry.meta == at.meta:
        return entry
    entry = dataclasses.replace(entry, meta=at.meta)
    with _LOCK:
        if key in _CACHE:
            _CACHE[key] = entry
    return entry


def _get_or_build(key: tuple, build):
    """Latched cache lookup shared by `get_view` and `get_stream`.

    Thread-safe with per-key build latches (double-checked): the first
    thread to miss a key registers a pending event under the global lock,
    runs the O(nnz) ``build`` *outside* it, then re-acquires to insert
    and release waiters. Concurrent misses on the SAME key wait on the
    event (one build per key — `cache_stats` keeps that assertable),
    while a hit — or a miss — on any OTHER key proceeds immediately
    instead of blocking behind an unrelated tenant's build.
    """
    while True:
        with _LOCK:
            view = _CACHE.get(key)
            if view is not None:
                _STATS["hits"] += 1
                _CACHE.move_to_end(key)
                return view
            event = _PENDING.get(key)
            if event is None:
                # This thread owns the build for `key`.
                _PENDING[key] = threading.Event()
                _STATS["misses"] += 1
                _STATS["builds"] += 1
        if event is not None:
            # Another thread is building this key: wait, then re-check
            # (normally a hit; a failed or instantly-evicted build makes
            # this thread the next builder).
            event.wait()
            continue
        try:
            view = build()
        except BaseException:
            with _LOCK:
                _PENDING.pop(key).set()   # unblock waiters; one re-builds
            raise
        with _LOCK:
            _CACHE[key] = view
            _CACHE_BYTES[key] = _view_bytes(view)
            max_entries, max_bytes = _limits()
            while len(_CACHE) > max(1, max_entries) or (
                    len(_CACHE) > 1
                    and sum(_CACHE_BYTES.values()) > max_bytes):
                old, _ = _CACHE.popitem(last=False)
                _CACHE_BYTES.pop(old, None)
            _PENDING.pop(key).set()
        return view


def get_view(at: AltoTensor, mode: int,
             route: str | None = None) -> OrientedView:
    """The oriented view for ``(at, mode)``: cached, built on miss
    (per-key latched — see `_get_or_build`)."""
    key = ("view", *mode_fingerprint(at, mode))

    def build():
        # Injection here exercises the latch's failed-build contract: the
        # owner's exception releases waiters and the next caller rebuilds.
        faults.inject("views.build")
        route_ = route or default_route()
        return (alto.oriented_view_device(at, mode)
                if route_ == "device" else alto.oriented_view(at, mode))

    return _rebind_meta(key, _get_or_build(key, build), at)


def get_stream(at: AltoTensor, mode: int) -> HostStream:
    """The HOST-resident stream for ``(at, mode)``: cached, built on miss.

    Same cache, latches, counters, and LRU byte/entry bounds as
    `get_view`, under a key tagged "stream" so a tensor decomposed both
    in-core and out-of-core keeps the two representations distinct.
    Eviction is safe mid-flight: the chunked executors slice the numpy
    arrays zero-copy, and numpy refcounting keeps a slice's backing
    buffer alive after the cache entry is dropped (no use-after-evict —
    pinned by `tests/test_outofcore.py`).
    """
    key = ("stream", *mode_fingerprint(at, mode))

    def build():
        faults.inject("views.build")
        return stream_mod.host_stream(at, mode)

    return _rebind_meta(key, _get_or_build(key, build), at)


def build_views(at: AltoTensor, plan, route: str | None = None) -> dict:
    """Cached views for exactly the modes ``plan`` routes oriented
    (either variant — one-hot merge or scratch carry — consumes the same
    row-sorted view). A STREAMING plan materializes host-resident
    `core.stream.HostStream`s instead of device views — same cache, same
    one-build-per-key contract — which the chunked executors consume."""
    from repro.core import heuristics
    if getattr(plan, "streaming", None) is not None:
        return {m.mode: get_stream(at, m.mode)
                for m in plan.modes if heuristics.is_oriented(m.traversal)}
    return {m.mode: get_view(at, m.mode, route=route)
            for m in plan.modes if heuristics.is_oriented(m.traversal)}


def invalidate(at: AltoTensor, modes=None) -> int:
    """Drop cached views/streams of ``at`` — all modes by default, or only
    ``modes`` — returning how many entries were evicted (also accumulated
    in the ``invalidated`` counter). Per-(fingerprint, mode) surgical:
    untouched modes' O(nnz) copies stay cached. For services that release
    a tensor (or re-ingest one mode) and want the stale copies freed
    before LRU aging would get to them."""
    if modes is None:
        modes = range(len(at.dims))
    fps = {mode_fingerprint(at, int(m)) for m in modes}
    with _LOCK:
        dead = [k for k in _CACHE if k[1:] in fps]
        for k in dead:
            del _CACHE[k]
            _CACHE_BYTES.pop(k, None)
        _STATS["invalidated"] += len(dead)
    return len(dead)


def invalidate_changed(old_at: AltoTensor, new_at: AltoTensor) -> int:
    """Surgical post-append invalidation: drop ``old_at``'s cached entries
    only for the modes whose `mode_fingerprint` actually changed between
    the two tensors. A no-op append (empty delta under the "sum" policy)
    or a pure re-tile changes no fingerprints, so nothing is dropped and
    every cached view keeps serving; a content-changing append stales all
    modes' entries (each oriented view permutes the full stream) and they
    are released eagerly instead of aging out of the LRU."""
    stale = [m for m in range(len(old_at.dims))
             if mode_fingerprint(old_at, m) != mode_fingerprint(new_at, m)]
    return invalidate(old_at, modes=stale) if stale else 0


def cache_stats() -> dict[str, int]:
    """Hit/miss/build counters plus current size (copies, not live)."""
    with _LOCK:
        out = dict(_STATS)
        out["size"] = len(_CACHE)
        out["bytes"] = sum(_CACHE_BYTES.values())
    return out


def cache_clear() -> None:
    with _LOCK:
        _CACHE.clear()
        _CACHE_BYTES.clear()
        for k in _STATS:
            _STATS[k] = 0

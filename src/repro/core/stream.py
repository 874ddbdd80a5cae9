"""Host-resident ALTO streams for out-of-core (chunked) execution.

The in-core oriented path (`core.views`) keeps one device-resident
row-sorted copy of the stream per (tensor, mode). For tensors whose
padded stream does not fit the device byte budget (`core.plan`'s
streaming decision) the same copy lives HERE instead: host numpy arrays
— optionally memory-mapped from disk — that the chunked executors in
`kernels.ops` slice into row-sorted chunks and feed through device
memory with double-buffered `jax.device_put` prefetch.

Contracts that make chunking bitwise-exact against the in-core
`oriented_carry` kernels:

* **Same element order.** `host_stream` builds the oriented permutation
  with the identical extract + stable-argsort the in-core builders use
  (`alto.oriented_view` / `oriented_view_device` are bit-identical to
  each other; this is the same numpy path), so element k of the host
  stream is element k of the in-core view.

* **Same padding rule.** The stream is padded once, host-side, to a
  multiple of :data:`STREAM_ALIGN` with `ops.pad_sorted_stream`'s rule —
  replicated final row/words, zero values (an empty stream pads with
  zero rows/words). ``STREAM_ALIGN`` (2048, == ``plan.MAX_BLOCK_M``) is
  a multiple of every legal ``block_m``, and the padded prefix of length
  ``ceil(Mp/block_m)·block_m`` is element-for-element what
  `ops.pad_sorted_stream` would have produced at that ``block_m`` —
  replicated padding is self-similar under truncation. Chunk slicing at
  ``block_m`` multiples therefore cuts the exact block sequence the
  in-core kernel scans.

* **Zero-copy slices.** :meth:`HostStream.chunk` returns numpy views
  (no copy); `jax.device_put` on the slice is the only transfer. Numpy
  refcounting keeps a slice's backing buffer alive even if the cache
  entry that produced it is evicted mid-flight — the no-use-after-evict
  property `tests/test_outofcore.py` pins.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import threading
import zlib

import jax
import numpy as np

from repro.core import encoding as enc_mod
from repro.core import faults
from repro.core.alto import AltoMeta, AltoTensor, OrientedView

# One alignment for every host stream: a multiple of every legal oriented
# block_m (powers of two in [plan.MIN_BLOCK_M, plan.MAX_BLOCK_M]), so one
# padded copy serves any tiling. Must equal plan.MAX_BLOCK_M.
STREAM_ALIGN = 2048


class StreamIntegrityError(RuntimeError):
    """A spilled stream's content checksum does not match its payload —
    a torn multi-file write (crash between `_respill`'s replaces) or
    on-disk corruption. Detected at LOAD time so a wrong stream never
    reaches an executor; recovery is `load_or_rebuild`."""


# Integrity accounting the serving stats surface (instead of log-scraping).
_INTEGRITY_LOCK = threading.Lock()
_INTEGRITY = {"checksum_failures": 0, "rebuilds": 0}


def integrity_stats() -> dict[str, int]:
    with _INTEGRITY_LOCK:
        return dict(_INTEGRITY)


def integrity_stats_clear() -> None:
    with _INTEGRITY_LOCK:
        for k in _INTEGRITY:
            _INTEGRITY[k] = 0


def _integrity_bump(counter: str) -> None:
    with _INTEGRITY_LOCK:
        _INTEGRITY[counter] += 1


def stream_checksum(rows: np.ndarray, words: np.ndarray,
                    values: np.ndarray) -> int:
    """crc32 over the padded payload bytes (rows ‖ words ‖ values).

    One sequential pass at spill/load time — for a memmap-backed stream
    the verify pages the file in once, which is the price of never
    handing a torn generation to the chunked executors.
    """
    c = zlib.crc32(np.ascontiguousarray(rows).tobytes())
    c = zlib.crc32(np.ascontiguousarray(words).tobytes(), c)
    c = zlib.crc32(np.ascontiguousarray(values).tobytes(), c)
    return c & 0xFFFFFFFF


@dataclasses.dataclass
class HostStream:
    """One (tensor, mode) row-sorted stream, host-resident and pre-padded.

    ``length`` is the real (partition-padded) stream length Mp; the
    arrays extend to the next :data:`STREAM_ALIGN` multiple with
    replicated-row / zero-value padding. ``rows`` is int32 ascending,
    ``words`` is (La, W) uint32, ``values`` matches the tensor dtype.
    Arrays may be plain numpy or read-only ``np.memmap`` (disk-backed).
    """
    meta: AltoMeta
    mode: int
    length: int
    rows: np.ndarray
    words: np.ndarray
    values: np.ndarray
    # Content checksum of the padded payload (`stream_checksum`). None for
    # in-memory streams (never at risk of a torn write); spilled streams
    # carry it and `from_memmap` verifies it against the mapped bytes.
    checksum: int | None = None

    def padded_len(self, block_m: int) -> int:
        """Stream length after `ops.pad_sorted_stream` at ``block_m``."""
        if STREAM_ALIGN % block_m:
            raise ValueError(f"block_m {block_m} does not divide "
                             f"STREAM_ALIGN {STREAM_ALIGN}")
        return -(-self.length // block_m) * block_m

    def chunk(self, start: int, stop: int):
        """Zero-copy (rows, words, values) numpy views of [start, stop)."""
        return (self.rows[start:stop], self.words[start:stop],
                self.values[start:stop])

    def nbytes(self) -> int:
        return int(self.rows.nbytes + self.words.nbytes
                   + self.values.nbytes)


def pad_host_stream(rows: np.ndarray, words: np.ndarray,
                    values: np.ndarray, mult: int):
    """Numpy twin of `ops.pad_sorted_stream` (single padding rule).

    Replicates the final row/words with zero values so padded elements
    contribute nothing; an empty stream pads one full ``mult`` block of
    zero rows/words (still sorted, still value-0).
    """
    M = words.shape[0]
    pad = mult if M == 0 else (-M) % mult
    if pad == 0:
        return rows, words, values
    if M == 0:
        pad_rows = np.zeros((pad,), rows.dtype)
        pad_words = np.zeros((pad, words.shape[1]), words.dtype)
    else:
        pad_rows = np.broadcast_to(rows[-1:], (pad,))
        pad_words = np.broadcast_to(words[-1:], (pad, words.shape[1]))
    rows = np.concatenate([rows, pad_rows])
    words = np.concatenate([words, pad_words])
    values = np.concatenate([values, np.zeros((pad,), values.dtype)])
    return rows, words, values


def host_stream(at: AltoTensor, mode: int) -> HostStream:
    """Build the host-resident oriented stream for ``(at, mode)``.

    Same extract + stable argsort as `alto.oriented_view`, kept in numpy
    end to end (no device round-trip for the sorted copy), then padded
    once to the :data:`STREAM_ALIGN` multiple.
    """
    words_np = np.asarray(at.words)
    values_np = np.asarray(at.values)
    rows = enc_mod.extract_mode(at.meta.enc, words_np, mode)
    order = np.argsort(rows, kind="stable")
    rows = np.ascontiguousarray(rows[order].astype(np.int32))
    words = np.ascontiguousarray(words_np[order])
    values = np.ascontiguousarray(values_np[order])
    length = words.shape[0]
    rows, words, values = pad_host_stream(rows, words, values, STREAM_ALIGN)
    return HostStream(meta=at.meta, mode=mode, length=length,
                      rows=np.ascontiguousarray(rows),
                      words=np.ascontiguousarray(words),
                      values=np.ascontiguousarray(values))


def ensure_host(view) -> HostStream:
    """Adapt an in-core `OrientedView` (or pass through a HostStream).

    Lets the chunked executors accept either representation — tests and
    benchmarks chunk existing device views without rebuilding.
    """
    if isinstance(view, HostStream):
        return view
    if isinstance(view, OrientedView):
        rows = np.asarray(view.rows)
        words = np.asarray(view.words)
        values = np.asarray(view.values)
        length = words.shape[0]
        rows, words, values = pad_host_stream(rows, words, values,
                                              STREAM_ALIGN)
        return HostStream(meta=view.meta, mode=view.mode, length=length,
                          rows=rows, words=words, values=values)
    raise TypeError(f"expected HostStream or OrientedView, got "
                    f"{type(view).__name__}")


# ---------------------------------------------------------------------------
# Disk backing (optional): .npy files re-opened as read-only memmaps
# ---------------------------------------------------------------------------

def _respill(hs: HostStream, d: pathlib.Path) -> HostStream:
    """Write ``hs`` into ``d`` atomically and reopen it memory-mapped.

    Two phases: every array is fully written to a ``.tmp`` sibling
    first, then ALL tmps are moved into place with ``os.replace`` —
    readers holding memmaps of the OLD files keep the old inodes alive
    (no torn reads, no SIGBUS from a truncating in-place ``np.save``),
    and a crash anywhere in the write phase leaves the previous
    generation byte-identical on disk (the ``stream.respill`` fault site
    sits between the phases; `tests/test_resilience.py` kills the spill
    there and asserts the old stream still loads and verifies). A crash
    *between replaces* can still tear across files — which is exactly
    what the content checksum (written alongside, verified by
    `from_memmap`) turns from silent corruption into a load-time
    `StreamIntegrityError`.
    """
    d.mkdir(parents=True, exist_ok=True)
    checksum = stream_checksum(hs.rows, hs.words, hs.values)
    payload = {"rows": np.asarray(hs.rows), "words": np.asarray(hs.words),
               "values": np.asarray(hs.values),
               "length": np.asarray([hs.length], np.int64),
               "checksum": np.asarray([checksum], np.int64)}
    tmps = {}
    for name, arr in payload.items():
        tmp = d / f".{name}.tmp.npy"
        np.save(tmp, arr)
        tmps[name] = tmp
    faults.inject("stream.respill")
    for name, tmp in tmps.items():
        os.replace(tmp, d / f"{name}.npy")
    return from_memmap(d, hs.meta, hs.mode)


def to_memmap(hs: HostStream, directory) -> HostStream:
    """Spill ``hs`` to ``directory`` and reopen it memory-mapped.

    Writes ``rows/words/values`` as ``.npy`` plus the real length, and
    returns a HostStream whose arrays are read-only ``np.memmap`` views —
    the OS pages chunks in as the executors slice them, so the host
    working set is bounded by the touched chunks, not the stream.
    """
    return _respill(hs, pathlib.Path(directory))


def from_memmap(directory, meta: AltoMeta, mode: int) -> HostStream:
    """Reopen a spilled stream (`to_memmap`) as read-only memmaps.

    Verifies the stored content checksum against the mapped payload
    before returning — a generation torn across the per-array files
    (crash between `_respill` replaces, disk corruption) raises
    `StreamIntegrityError` here instead of producing a silently wrong
    decomposition downstream. Pre-checksum spills (no ``checksum.npy``)
    load unverified for compatibility.
    """
    faults.inject("stream.memmap_load")
    d = pathlib.Path(directory)
    length = int(np.load(d / "length.npy")[0])
    hs = HostStream(meta=meta, mode=mode, length=length,
                    rows=np.load(d / "rows.npy", mmap_mode="r"),
                    words=np.load(d / "words.npy", mmap_mode="r"),
                    values=np.load(d / "values.npy", mmap_mode="r"))
    cpath = d / "checksum.npy"
    if cpath.exists():
        stored = int(np.load(cpath)[0])
        if faults.fire("stream.checksum") is not None:
            stored ^= 1                       # simulate on-disk corruption
        actual = stream_checksum(hs.rows, hs.words, hs.values)
        if stored != actual:
            _integrity_bump("checksum_failures")
            raise StreamIntegrityError(
                f"spilled stream at {d} fails its checksum "
                f"(stored {stored:#010x}, payload {actual:#010x}) — "
                f"torn write or corruption; rebuild from source "
                f"(stream.load_or_rebuild)")
        hs.checksum = stored
    return hs


def load_or_rebuild(directory, at: AltoTensor, mode: int) -> HostStream:
    """`from_memmap` with the rebuild-from-source recovery rung.

    A checksum-failing (or unreadable) spill is rebuilt from the
    resident tensor — `host_stream` + a fresh atomic spill into the same
    directory — so one torn write costs a re-sort and a re-write, never
    a wrong answer or a dead tensor. The serving runtime counts these
    (``rebuilds`` in `integrity_stats`).
    """
    try:
        return from_memmap(directory, at.meta, mode)
    except (StreamIntegrityError, OSError):
        _integrity_bump("rebuilds")
        return _respill(host_stream(at, mode), pathlib.Path(directory))


def append_stream(hs: HostStream, at_new: AltoTensor) -> HostStream:
    """In-place update path for host/memmap streams after an append.

    Rebuilds the oriented stream for ``hs.mode`` from the merged tensor
    (`core.ingest.append_delta`'s result). A plain-numpy stream returns a
    fresh host-resident one; a memmap-backed stream is re-spilled into
    ITS OWN directory (recovered from ``np.memmap.filename``) via the
    atomic `_respill`, so the out-of-core tensor updates in place on disk
    while executors still slicing the previous generation keep reading
    the old inodes.
    """
    merged = host_stream(at_new, hs.mode)
    if isinstance(hs.words, np.memmap):
        return _respill(merged, pathlib.Path(hs.words.filename).parent)
    return merged


def put_chunk(hs: HostStream, start: int, stop: int):
    """Upload one chunk to device: (rows, words, values) jax arrays.

    `jax.device_put` on the zero-copy numpy slices; on accelerator
    backends the transfers are dispatched asynchronously, so issuing the
    NEXT chunk's put before computing on the current one overlaps copy
    with compute (the double-buffer loop in `kernels.ops`).
    """
    faults.inject("stream.chunk_io")
    rows, words, values = hs.chunk(start, stop)
    return (jax.device_put(rows), jax.device_put(words),
            jax.device_put(values))

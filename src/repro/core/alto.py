"""ALTO tensor: linearized storage, balanced partitioning, traversal views.

Format generation (paper §3.1) = linearize (bit gather), sort by the
linearized index, then impose the balanced partitioning of §4.1. It exists
twice, bit-identically:

* ``build`` / ``oriented_view`` — host-side numpy, the parity reference;
* ``build_device`` / ``oriented_view_device`` — `jax.lax.sort` on the
  packed multi-word key (`encoding.sort_by_key`), jit-compatible with
  zero host callbacks. The paper's Fig. 13 headline (ALTO generation is
  ONE key sort) is what makes this viable on accelerators: the whole
  ingest is a linearize + a stable sort carrying values/coords, so
  nothing upstream of MTTKRP needs a NumPy round-trip and regeneration
  can sit under `jit`/`shard_map` (the prerequisite for dynamic
  relayout à la ReLATE/Dynasor).

The resulting `AltoTensor` is a JAX pytree whose static aux data (encoding,
partition intervals, fiber-reuse stats) drives *trace-time* selection of the
paper's adaptive execution variants — the TPU analogue of the paper's
runtime heuristics (JAX control flow must be static under jit). The static
meta (temp_rows, fiber_reuse) is data-dependent, so the device build ends
with one tiny host transfer — the (L, N) bounding boxes and N fiber
counts, O(L·N) scalars — while the O(nnz) stream never leaves the device.

Partitioning: the sorted nonzero list is cut into L equal-size segments
(perfect workload balance). Each segment's bounding box `T_l` (per-mode
closed intervals) is computed exactly; intervals of different partitions may
overlap (paper Fig. 7) — the pull-based reduction resolves the overlap.
The max interval length per mode is a *static* bound used to size the dense
`Temp` scratch (VMEM tile in the Pallas kernel).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding as enc_mod
from repro.core import telemetry
from repro.core.encoding import AltoEncoding, make_encoding
from repro.sparse.tensor import SparseTensor


# ---------------------------------------------------------------------------
# Device-side bit scatter/gather (jnp) — mirrors encoding.linearize_np.
# ---------------------------------------------------------------------------

def delinearize(enc: AltoEncoding, words: jnp.ndarray) -> jnp.ndarray:
    """(..., n_words) u32 -> (..., N) int32 coordinates (bit scatter)."""
    out = [jnp.zeros(words.shape[:-1], dtype=jnp.uint32)
           for _ in range(enc.ndim)]
    for r in enc.runs:
        chunk = (words[..., r.word] >> np.uint32(r.dst_shift)) & np.uint32(
            r.mask)
        out[r.mode] = out[r.mode] | (chunk << np.uint32(r.src_shift))
    return jnp.stack(out, axis=-1).astype(jnp.int32)


def linearize(enc: AltoEncoding, coords: jnp.ndarray) -> jnp.ndarray:
    """(..., N) int coords -> (..., n_words) u32 index (bit gather)."""
    c = coords.astype(jnp.uint32)
    out = [jnp.zeros(coords.shape[:-1], dtype=jnp.uint32)
           for _ in range(enc.n_words)]
    for r in enc.runs:
        chunk = (c[..., r.mode] >> np.uint32(r.src_shift)) & np.uint32(r.mask)
        out[r.word] = out[r.word] | (chunk << np.uint32(r.dst_shift))
    return jnp.stack(out, axis=-1)


# ---------------------------------------------------------------------------
# AltoTensor pytree
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AltoMeta:
    """Hashable static metadata travelling in the pytree aux."""
    enc: AltoEncoding
    nnz: int                      # real nonzeros (before padding)
    n_partitions: int
    temp_rows: tuple[int, ...]    # per mode: max partition interval length
    fiber_reuse: tuple[float, ...]  # per mode: avg nnz per fiber

    @property
    def dims(self) -> tuple[int, ...]:
        return self.enc.dims


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AltoTensor:
    """Linearized sparse tensor, sorted by ALTO index, padded to L·chunk."""

    meta: AltoMeta
    words: jnp.ndarray        # (Mp, n_words) u32, ascending
    values: jnp.ndarray       # (Mp,)
    part_start: jnp.ndarray   # (L, N) int32 — T_l^s per partition/mode
    part_end: jnp.ndarray     # (L, N) int32 — T_l^e (inclusive)

    def tree_flatten(self):
        return ((self.words, self.values, self.part_start, self.part_end),
                self.meta)

    @classmethod
    def tree_unflatten(cls, meta, leaves):
        return cls(meta, *leaves)

    # convenience ---------------------------------------------------------
    @property
    def dims(self) -> tuple[int, ...]:
        return self.meta.dims

    @property
    def nnz(self) -> int:
        return self.meta.nnz

    @property
    def n_partitions(self) -> int:
        return self.meta.n_partitions

    def coords(self) -> jnp.ndarray:
        return delinearize(self.meta.enc, self.words)

    def storage_bytes(self) -> int:
        """Index + value storage (paper Fig. 12 accounting, real nnz)."""
        idx = self.meta.nnz * self.meta.enc.runtime_index_bits() // 8
        val = self.meta.nnz * self.values.dtype.itemsize
        return idx + val


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class OrientedView:
    """Output-oriented traversal copy for one mode (paper Fig. 8 right).

    Nonzeros permuted into ascending order of the target mode (then ALTO
    order within a row for input locality). Conflict-free updates become a
    sorted segment reduction — the TPU-native form of "atomics only at
    partition boundaries".
    """
    meta: AltoMeta
    mode: int
    rows: jnp.ndarray     # (Mp,) int32 target-mode index, ascending
    words: jnp.ndarray    # (Mp, n_words) u32 permuted ALTO indices
    values: jnp.ndarray   # (Mp,)
    perm: jnp.ndarray     # (Mp,) int32 position in ALTO order (for Π reuse)

    def tree_flatten(self):
        return ((self.rows, self.words, self.values, self.perm),
                (self.meta, self.mode))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(aux[0], aux[1], *leaves)


# ---------------------------------------------------------------------------
# Format generation (host side)
# ---------------------------------------------------------------------------

def fiber_reuse_stats(enc: AltoEncoding, words_np: np.ndarray,
                      nnz: int) -> tuple[float, ...]:
    """Average nonzeros per fiber along each mode (paper §4.2).

    #fibers along mode n = #distinct coordinates with mode-n bits masked
    out of the linearized index. Counted by a masked packed-key sort +
    adjacent-diff (`encoding.count_distinct_np`) — same result as the
    old ``np.unique(axis=0)`` void-view scan, which was the dominant
    ``build(compute_reuse=True)`` cost on large tensors (unique built
    and hashed an (M, W·4)-byte view per mode; the packed sort is one
    u64 argsort-free ``np.sort``).
    """
    masks = enc.mode_masks()           # (N, W)
    out = []
    w = words_np[:nnz]
    for n in range(enc.ndim):
        masked = w & ~masks[n][None, :]
        n_fibers = enc_mod.count_distinct_np(masked) if nnz else 1
        out.append(float(nnz) / max(1, n_fibers))
    return tuple(out)


def build(x: SparseTensor, n_partitions: int = 8,
          compute_reuse: bool = True) -> AltoTensor:
    """ALTO format generation: linearize -> sort -> partition (paper §3.1)."""
    enc = make_encoding(x.dims)
    L = max(1, int(n_partitions))
    words = enc_mod.linearize_np(enc, x.coords)
    order = enc_mod.sort_key_np(words)
    words = words[order]
    values = np.asarray(x.values)[order]
    coords = x.coords[order]          # reordered original coords: cheaper
    M = x.nnz                         # than a delinearization pass

    # Pad to a multiple of L with value-0 copies of the last element so the
    # padded tail stays inside the final partition's bounding box.
    chunk = -(-max(M, L) // L)
    Mp = chunk * L
    if Mp > M:
        pad = Mp - M
        if M == 0:
            pad_words = np.zeros((pad, enc.n_words), dtype=np.uint32)
            pad_coords = np.zeros((pad, enc.ndim), dtype=coords.dtype)
        else:
            pad_words = np.repeat(words[-1:], pad, axis=0)
            pad_coords = np.repeat(coords[-1:], pad, axis=0)
        words = np.concatenate([words, pad_words], axis=0)
        values = np.concatenate(
            [values, np.zeros(pad, dtype=values.dtype)], axis=0)
        coords = np.concatenate([coords, pad_coords], axis=0)
    cc = coords.reshape(L, chunk, enc.ndim)
    part_start = cc.min(axis=1).astype(np.int32)          # (L, N)
    part_end = cc.max(axis=1).astype(np.int32)
    temp_rows = tuple(int((part_end[:, n] - part_start[:, n]).max()) + 1
                      for n in range(enc.ndim))

    reuse = (fiber_reuse_stats(enc, words, M) if compute_reuse
             else tuple(float("nan") for _ in range(enc.ndim)))
    meta = AltoMeta(enc=enc, nnz=M, n_partitions=L, temp_rows=temp_rows,
                    fiber_reuse=reuse)
    return AltoTensor(meta=meta,
                      words=jnp.asarray(words),
                      values=jnp.asarray(values),
                      part_start=jnp.asarray(part_start),
                      part_end=jnp.asarray(part_end))


def oriented_view(at: AltoTensor, mode: int) -> OrientedView:
    """Build the output-oriented permutation for ``mode`` (host side).

    Only the target mode's bit runs are decoded (`encoding.extract_mode`,
    shared with the device path) — a full delinearize just to read one
    column was the old cost here.
    """
    words_np = np.asarray(at.words)
    values_np = np.asarray(at.values)
    rows = enc_mod.extract_mode(at.meta.enc, words_np, mode)
    # stable sort by row keeps ALTO order within each row (input locality)
    order = np.argsort(rows, kind="stable")
    return OrientedView(meta=at.meta, mode=mode,
                        rows=jnp.asarray(rows[order].astype(np.int32)),
                        words=jnp.asarray(words_np[order]),
                        values=jnp.asarray(values_np[order]),
                        perm=jnp.asarray(order.astype(np.int32)))


# ---------------------------------------------------------------------------
# Format generation (device side): jittable linearize -> sort -> partition
# ---------------------------------------------------------------------------

# Jitted ingest cores, keyed on static meta only (encoding, partition
# count, nnz, dtypes) — one trace per meta, then jit's C++ fast path.
# LRU-bounded: a streaming ingest loop sees a distinct nnz (hence key)
# per tensor, and an unbounded map would pin one compiled executable
# per size forever.
_DEVICE_INGEST_FNS: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_DEVICE_INGEST_FNS_MAX = 128
# Concurrent serving drivers ingest in parallel; the OrderedDict
# move_to_end/popitem pair is not atomic, so guard all mutations.
_DEVICE_INGEST_LOCK = threading.Lock()


def _cached_ingest_fn(key: tuple, build):
    with _DEVICE_INGEST_LOCK:
        fn = _DEVICE_INGEST_FNS.get(key)
        if fn is None:
            fn = _DEVICE_INGEST_FNS[key] = build()
        else:
            _DEVICE_INGEST_FNS.move_to_end(key)
        while len(_DEVICE_INGEST_FNS) > _DEVICE_INGEST_FNS_MAX:
            _DEVICE_INGEST_FNS.popitem(last=False)
        return fn


def device_ingest_traces() -> dict[str, int]:
    """Trace counts of the jitted build/view/merge cores (tests pin the
    once-per-meta contract with this; the serving layer pins its
    one-trace-per-shape-class contract with before/after deltas)."""
    c = telemetry.counts()
    return {k: c.get(f"ingest.{k}.trace", 0)
            for k in ("build", "view", "merge")}


def _build_device_fn(enc: AltoEncoding, L: int, M: int, val_dtype):
    """The cached jitted device-build core for one static meta."""
    key = ("build", enc, L, M, jnp.dtype(val_dtype).name)
    N, W = enc.ndim, enc.n_words
    chunk = -(-max(M, L) // L)
    Mp = chunk * L

    @telemetry.traced("ingest.build.trace")
    def core(coords, values):
        words, values = enc_mod.sort_by_key(linearize(enc, coords), values)
        if Mp > M:
            # Same padding rule as build(): value-0 copies of the last
            # element so the tail stays inside the final bounding box.
            pad = Mp - M
            pw = (jnp.zeros((pad, W), jnp.uint32) if M == 0
                  else jnp.broadcast_to(words[-1:], (pad, W)))
            words = jnp.concatenate([words, pw])
            values = jnp.concatenate(
                [values, jnp.zeros((pad,), values.dtype)])
        # delinearize is linearize's exact inverse: these are the input
        # coordinates in stream order, without sorting them along.
        cc = delinearize(enc, words).reshape(L, chunk, N)
        part_start = jnp.min(cc, axis=1).astype(jnp.int32)
        part_end = jnp.max(cc, axis=1).astype(jnp.int32)
        return words, values, part_start, part_end

    return _cached_ingest_fn(key, lambda: jax.jit(core))


def _fiber_count_fn(M: int, Mp: int, W: int):
    """The cached jitted fiber counter: distinct keys of ``words[:M]``
    with one mode's index bits masked out. The mask is an operand, so
    one compiled program serves every mode."""
    def core(words, not_mask):
        return enc_mod.count_distinct(words[:M] & not_mask[None, :])

    return _cached_ingest_fn(("fibers", M, Mp, W), lambda: jax.jit(core))


def fiber_reuse_device(enc: AltoEncoding, words: jnp.ndarray,
                       nnz: int) -> tuple[float, ...]:
    """`fiber_reuse_stats` on device: average nonzeros per fiber along
    each mode, from the sorted (padded) stream's first ``nnz`` words."""
    if nnz == 0:
        return tuple(0.0 for _ in range(enc.ndim))
    fn = _fiber_count_fn(nnz, words.shape[0], enc.n_words)
    not_masks = ~enc.mode_masks()                        # (N, W) u32
    return tuple(float(nnz) / max(1, int(fn(words, jnp.asarray(m))))
                 for m in not_masks)


def finalize_device(enc: AltoEncoding, nnz: int, L: int, words, values,
                    part_start, part_end,
                    compute_reuse: bool) -> AltoTensor:
    """The `AltoTensor` of a device-built stream. The only host transfer
    is the (L, N) bounding boxes and N fiber counts, O(L·N) scalars;
    the O(nnz) stream never leaves the device."""
    ps = np.asarray(part_start)
    pe = np.asarray(part_end)
    temp_rows = tuple(int((pe[:, n] - ps[:, n]).max()) + 1
                      for n in range(enc.ndim))
    reuse = (fiber_reuse_device(enc, words, nnz) if compute_reuse
             else tuple(float("nan") for _ in range(enc.ndim)))
    meta = AltoMeta(enc=enc, nnz=nnz, n_partitions=L, temp_rows=temp_rows,
                    fiber_reuse=reuse)
    return AltoTensor(meta=meta, words=words, values=values,
                      part_start=part_start, part_end=part_end)


def build_device(x: SparseTensor, n_partitions: int = 8,
                 compute_reuse: bool = True) -> AltoTensor:
    """ALTO format generation on device — `build`'s jittable twin.

    linearize (jnp bit gather) → ONE stable multi-word key sort
    (`encoding.sort_by_key`) → reshaped min/max partition bounding
    boxes, all inside a single jitted core with zero host callbacks,
    traced once per (encoding, L, nnz, dtype); fiber counts follow as
    one shared jitted counter per mode (`fiber_reuse_device`).
    Bit-identical to `build` — same element order (stable sort, so
    duplicate linearized keys keep COO input order), same padding, same
    static meta.
    """
    with telemetry.span("ingest.build_device"):
        enc = make_encoding(x.dims)
        L = max(1, int(n_partitions))
        M = x.nnz
        coords = jnp.asarray(x.coords)
        values = jnp.asarray(x.values)
        fn = _build_device_fn(enc, L, M, values.dtype)
        return finalize_device(enc, M, L, *fn(coords, values),
                               compute_reuse=compute_reuse)


def _view_rows_fn(enc: AltoEncoding, mode: int, Mp: int):
    """The cached jitted target-row extraction for one static meta/mode."""
    @telemetry.traced("ingest.view.trace")
    def core(words):
        return enc_mod.extract_mode(enc, words, mode)    # (Mp,) int32

    return _cached_ingest_fn(("view", enc, mode, Mp), lambda: jax.jit(core))


def _view_sort_fn(Mp: int, W: int, val_dtype):
    """The cached jitted row sort, shared by every mode of a stream.

    Only the rows and an iota enter the stable sort (every operand a TPU
    sort carries adds to its compile time); the permutation then gathers
    the words and values."""
    def core(rows, words, values):
        perm0 = jnp.arange(Mp, dtype=jnp.int32)
        rows, perm = jax.lax.sort((rows, perm0), num_keys=1,
                                  is_stable=True)
        words = jnp.stack([words[:, w][perm] for w in range(W)], axis=-1)
        return rows, words, values[perm], perm

    key = ("view_sort", Mp, W, jnp.dtype(val_dtype).name)
    return _cached_ingest_fn(key, lambda: jax.jit(core))


def oriented_view_device(at: AltoTensor, mode: int) -> OrientedView:
    """Output-oriented permutation for ``mode``, built on device.

    Target-mode rows come from a masked bit-extract of the words
    (`encoding.extract_mode` — no full delinearize), then ONE stable
    `lax.sort` of the rows and an iota yields the Π permutation (the
    stable argsort), which gathers the words and values. Stability keeps
    ALTO order within each row — bit-identical to the host
    `oriented_view`, duplicate-coordinate ties included. Jit-compatible,
    zero host callbacks; the extraction is traced once per (encoding,
    mode, Mp), the sort once per (Mp, n_words, dtype).
    """
    Mp, W = at.words.shape
    rows = _view_rows_fn(at.meta.enc, mode, Mp)(at.words)
    rows, words, values, perm = _view_sort_fn(Mp, W, at.values.dtype)(
        rows, at.words, at.values)
    return OrientedView(meta=at.meta, mode=mode, rows=rows, words=words,
                        values=values, perm=perm)


def to_sparse(at: AltoTensor) -> SparseTensor:
    """Back to COO (drops padding)."""
    coords = np.asarray(at.coords())[:at.nnz]
    values = np.asarray(at.values)[:at.nnz]
    return SparseTensor(at.dims, coords, values)


# ---------------------------------------------------------------------------
# Incremental-ingest host reference (core.ingest's parity oracle)
# ---------------------------------------------------------------------------

MERGE_POLICIES = ("sum", "last")


def grown_dims(dims: Sequence[int], coords,
               override: Sequence[int] | None = None) -> tuple[int, ...]:
    """Smallest extents covering ``dims`` and every delta coordinate.

    ``override`` fixes the result explicitly (it must cover both); by
    default extents grow exactly as far as the delta reaches. Extent
    growth can change `make_encoding`'s bit assignment, which is why the
    merge paths re-linearize the resident stream when the encoding
    moves.
    """
    coords = np.asarray(coords)
    need = list(int(d) for d in dims)
    if coords.size:
        mx = coords.reshape(-1, len(need)).max(axis=0)
        need = [max(d, int(m) + 1) for d, m in zip(need, mx)]
    if override is None:
        return tuple(need)
    out = tuple(int(d) for d in override)
    if len(out) != len(need) or any(o < n for o, n in zip(out, need)):
        raise ValueError(f"dims override {out} does not cover required "
                         f"extents {tuple(need)}")
    return out


def merge_coo(x: SparseTensor, coords, values, policy: str = "sum",
              dims: Sequence[int] | None = None) -> SparseTensor:
    """The merged COO an append denotes: resident entries (in stream
    order) followed by the delta batch (in input order), with the
    duplicate policy applied over FULL coordinates (equal linearized
    keys).

    * ``"sum"`` — every entry is kept; after the key sort duplicates sit
      adjacent and accumulate in every downstream reduction (exactly how
      `build` already treats duplicate-coordinate COO input).
    * ``"last"`` — the last-written entry of each duplicate group keeps
      its value and every earlier one is masked to value 0. A pure mask
      (no arithmetic), so the jitted merge reproduces it bit-for-bit;
      value-0 entries are inert in MTTKRP/Φ/likelihood, and writing
      value 0 acts as a delete.

    The entry count is always ``x.nnz + len(values)``: compaction would
    make the merged size data-dependent, which the static-shape jitted
    merge core cannot express.
    """
    if policy not in MERGE_POLICIES:
        raise ValueError(f"policy {policy!r}: expected one of "
                         f"{MERGE_POLICIES}")
    coords = np.asarray(coords, dtype=np.int32).reshape(-1, x.ndim)
    values = np.asarray(values).astype(x.values.dtype, copy=False)
    new_dims = grown_dims(x.dims, coords, dims)
    all_c = np.concatenate([x.coords, coords], axis=0)
    all_v = np.concatenate([x.values, values], axis=0)
    if policy == "last" and all_v.shape[0] > 1:
        enc = make_encoding(new_dims)
        words = enc_mod.linearize_np(enc, all_c)
        order = enc_mod.sort_key_np(words)
        srt = words[order]
        is_last = np.concatenate(
            [np.any(srt[1:] != srt[:-1], axis=-1), [True]])
        keep = np.zeros(all_v.shape[0], dtype=bool)
        keep[order] = is_last
        all_v = np.where(keep, all_v, np.zeros_like(all_v))
    return SparseTensor(new_dims, all_c, all_v)


def merge_reference(at: AltoTensor, coords, values, policy: str = "sum",
                    dims: Sequence[int] | None = None,
                    n_partitions: int | None = None,
                    compute_reuse: bool = True) -> AltoTensor:
    """From-scratch host rebuild of an append — `core.ingest.append_delta`'s
    bit-for-bit parity reference: the standard numpy `build` over
    `merge_coo`'s concatenated COO, under the grown dims. The jitted
    merge's one stable sort of [resident stream; delta batch] must equal
    this stable sort of the same multiset in the same input order —
    stream, values, partition boxes, and meta all bit-identical.
    """
    x = to_sparse(at)
    merged = merge_coo(x, coords, values, policy=policy,
                       dims=grown_dims(x.dims, coords, dims))
    L = at.meta.n_partitions if n_partitions is None else n_partitions
    return build(merged, n_partitions=L, compute_reuse=compute_reuse)

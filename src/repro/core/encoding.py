"""ALTO adaptive linearized encoding (paper §3.1, Figs. 4–6).

Maps N-dimensional coordinates onto a single compact linearized index of
``sum_n ceil(log2 I_n)`` bits (Eq. 1). Bit positions are assigned
most-significant-first by repeatedly splitting the mode with the *largest
remaining extent* ("partition along the longest mode first"); ties break
toward the longer original mode, i.e. within a bit group modes appear in
increasing length order toward the LSB ("shortest mode first"). This is the
paper's adaptive, non-fractal alternative to Z-Morton (Eq. 3).

TPU adaptation: the index is stored as ``n_words`` little-endian uint32
words (1/2/4 words ~ the paper's 32/64/128-bit configurations). TPUs have no
native 64-bit integer datapath, so the word decomposition is explicit and
every bit-gather/scatter lowers to vectorizable u32 shifts/ands/ors.

Linearization ("bit-level gather", Fig. 6a) and delinearization ("bit-level
scatter", Fig. 6b) are run-compressed: consecutive index bits that come from
consecutive bits of the same mode and land in the same word are moved with a
single shift+mask, so the op count is O(#runs) ≤ O(total_bits) and in
practice ~N per word.

Two sorting surfaces live here, one per placement:

* host (`sort_key_np`, `count_distinct_np`) — numpy, the parity
  reference used by `alto.build` / `alto.fiber_reuse_stats`;
* device (`sort_by_key`, `count_distinct`) — `jax.lax.sort` on the same
  packed multi-word key, stable, jit-compatible, carrying arbitrary
  value/coordinate operands through the permutation. This is the paper's
  Fig. 13 claim made jittable: format generation is ONE key sort, so it
  can run on the accelerator inside a traced program.

Both orderings are bit-identical (ascending multi-word unsigned key,
ties by original position) — `alto.build_device` relies on that to be a
drop-in replacement for the host build.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def _bits_for(extent: int) -> int:
    """ceil(log2 extent); modes of length 1 contribute zero bits."""
    return (int(extent) - 1).bit_length() if extent > 1 else 0


@dataclasses.dataclass(frozen=True)
class BitRun:
    """A contiguous run of bits moved between a mode coordinate and a word.

    word:       which u32 word of the linearized index.
    mode:       which tensor mode.
    src_shift:  bit offset of the run inside the mode coordinate.
    dst_shift:  bit offset of the run inside the word.
    length:     run length in bits.
    """
    word: int
    mode: int
    src_shift: int
    dst_shift: int
    length: int

    @property
    def mask(self) -> int:
        return (1 << self.length) - 1


@dataclasses.dataclass(frozen=True)
class AltoEncoding:
    """Static encoding metadata for a tensor shape (host-side, hashable)."""

    dims: tuple[int, ...]
    mode_bits: tuple[int, ...]         # bits per mode
    bit_mode: tuple[int, ...]          # bit b (0 = LSB) -> owning mode
    bit_pos: tuple[int, ...]           # bit b -> bit position inside mode
    runs: tuple[BitRun, ...]           # run-compressed gather/scatter plan

    @property
    def total_bits(self) -> int:
        return len(self.bit_mode)

    @property
    def n_words(self) -> int:
        # Round up to 1/2/4 words like the paper rounds to native word sizes.
        needed = max(1, -(-self.total_bits // WORD_BITS))
        for w in (1, 2, 4):
            if needed <= w:
                return w
        raise ValueError(
            f"ALTO index needs {self.total_bits} bits > 128; "
            "unsupported shape {self.dims}")

    @property
    def ndim(self) -> int:
        return len(self.dims)

    def mode_masks(self) -> np.ndarray:
        """(N, n_words) u32 masks: which index bits belong to each mode."""
        masks = np.zeros((self.ndim, self.n_words), dtype=np.uint64)
        for b, m in enumerate(self.bit_mode):
            masks[m, b // WORD_BITS] |= np.uint64(1) << np.uint64(
                b % WORD_BITS)
        return masks.astype(np.uint32)

    # ---- storage accounting (paper Eqs. 1-3) ----
    def storage_bits_alto(self, word_bits: int = WORD_BITS) -> int:
        """Index bits per nonzero in ALTO (Eq. 1), word-rounded (Eq. 2)."""
        return max(1, -(-self.total_bits // word_bits)) * word_bits

    def runtime_index_bits(self) -> int:
        """Bits per nonzero of the in-memory multi-u32 representation."""
        return self.n_words * WORD_BITS

    def storage_bits_coo(self, word_bits: int = WORD_BITS) -> int:
        """Index bits per nonzero in COO on word-addressed hardware (Eq. 2)."""
        return sum(max(1, -(-_bits_for(I) // word_bits)) * word_bits
                   for I in self.dims)

    def storage_bits_sfc(self) -> int:
        """Index bits per nonzero under a fractal SFC (Z-Morton, Eq. 3)."""
        return self.ndim * max(_bits_for(I) for I in self.dims)


def make_encoding(dims: Sequence[int]) -> AltoEncoding:
    """Build the adaptive bit assignment for a tensor shape."""
    dims = tuple(int(d) for d in dims)
    if not dims or any(d < 1 for d in dims):
        raise ValueError(f"invalid dims {dims}")
    mode_bits = tuple(_bits_for(I) for I in dims)
    total = sum(mode_bits)

    remaining = list(mode_bits)
    # extent of mode n after assigning k of its (high) bits: ceil(I / 2^k)
    def extent(n):
        k = mode_bits[n] - remaining[n]
        return -(-dims[n] // (1 << k))

    order: list[int] = []  # mode owning each bit, MSB first
    for _ in range(total):
        # Largest remaining extent first; ties -> longer original mode;
        # final tie -> lower mode id (deterministic).
        n = max((m for m in range(len(dims)) if remaining[m] > 0),
                key=lambda m: (extent(m), dims[m], -m))
        order.append(n)
        remaining[n] -= 1

    bit_mode = [0] * total
    bit_pos = [0] * total
    taken = [0] * len(dims)  # high bits already assigned per mode
    for i, n in enumerate(order):
        b = total - 1 - i           # global bit position (MSB first)
        bit_mode[b] = n
        bit_pos[b] = mode_bits[n] - 1 - taken[n]
        taken[n] += 1

    # Run-compress: scan LSB->MSB, merge while same mode & word and both
    # source and destination positions advance by one.
    runs: list[BitRun] = []
    b = 0
    while b < total:
        m = bit_mode[b]
        w = b // WORD_BITS
        start_b, start_p = b, bit_pos[b]
        length = 1
        while (b + 1 < total and bit_mode[b + 1] == m
               and (b + 1) // WORD_BITS == w
               and bit_pos[b + 1] == bit_pos[b] + 1):
            b += 1
            length += 1
        runs.append(BitRun(word=w, mode=m, src_shift=start_p,
                           dst_shift=start_b % WORD_BITS, length=length))
        b += 1

    return AltoEncoding(dims=dims, mode_bits=mode_bits,
                        bit_mode=tuple(bit_mode), bit_pos=tuple(bit_pos),
                        runs=tuple(runs))


# ---------------------------------------------------------------------------
# Host-side (numpy) linearize / delinearize — used at format generation time.
# ---------------------------------------------------------------------------

def linearize_np(enc: AltoEncoding, coords: np.ndarray) -> np.ndarray:
    """Bit-level gather: (M, N) int coords -> (M, n_words) u32 index."""
    coords = np.asarray(coords)
    M = coords.shape[0]
    out = np.zeros((M, enc.n_words), dtype=np.uint32)
    c = coords.astype(np.uint32)
    for r in enc.runs:
        chunk = (c[:, r.mode] >> np.uint32(r.src_shift)) & np.uint32(r.mask)
        out[:, r.word] |= chunk << np.uint32(r.dst_shift)
    return out


def delinearize_np(enc: AltoEncoding, words: np.ndarray) -> np.ndarray:
    """Bit-level scatter: (M, n_words) u32 index -> (M, N) int32 coords."""
    words = np.asarray(words, dtype=np.uint32)
    M = words.shape[0]
    out = np.zeros((M, enc.ndim), dtype=np.uint32)
    for r in enc.runs:
        chunk = (words[:, r.word] >> np.uint32(r.dst_shift)) & np.uint32(
            r.mask)
        out[:, r.mode] |= chunk << np.uint32(r.src_shift)
    return out.astype(np.int32)


def sort_key_np(words: np.ndarray) -> np.ndarray:
    """Argsort of multi-word linearized indices (LSW last).

    This is the paper's generation-cost win (Fig. 13): ALTO sorts ONE
    packed key (1-2 words) instead of N coordinate keys. Single-word
    indices take the fast scalar argsort; 64-bit indices combine two u32
    words into one u64 key."""
    W = words.shape[1]
    if W == 1:
        return np.argsort(words[:, 0], kind="stable")
    if W == 2:
        key = (words[:, 1].astype(np.uint64) << np.uint64(32)) \
            | words[:, 0].astype(np.uint64)
        return np.argsort(key, kind="stable")
    # np.lexsort: last key is primary -> most significant word last.
    keys = tuple(words[:, w] for w in range(W))
    return np.lexsort(keys)


def extract_mode(enc: AltoEncoding, words, mode: int):
    """Read ONE mode's coordinate out of the linearized index words.

    Only the target mode's bit runs are touched — no full delinearize —
    so the cost is O(#runs of that mode) shifts/masks instead of
    O(#runs total). Pure ufunc arithmetic: ``words`` may be a numpy
    array (host `alto.oriented_view`) or a jax array
    (`alto.oriented_view_device`) of shape (..., n_words) u32; returns
    (...,) int32. The single shared implementation of the host and
    device row-extraction paths.
    """
    out = words[..., 0] & np.uint32(0)
    for r in enc.runs:
        if r.mode != mode:
            continue
        chunk = (words[..., r.word] >> np.uint32(r.dst_shift)) \
            & np.uint32(r.mask)
        out = out | (chunk << np.uint32(r.src_shift))
    return out.astype(np.int32)


def _pack_u64_np(words: np.ndarray) -> np.ndarray:
    """(M, W<=2) u32 -> (M,) u64 packed key (host side; numpy has u64)."""
    key = words[:, 0].astype(np.uint64)
    if words.shape[1] > 1:
        key |= words[:, 1].astype(np.uint64) << np.uint64(32)
    return key


def count_distinct_np(words: np.ndarray) -> int:
    """Distinct rows of an (M, W) u32 word array: packed-key sort +
    adjacent-diff count.

    Replaces the ``np.unique(axis=0)`` void-view scan that dominated
    ``build(compute_reuse=True)``: ≤2 words collapse to ONE u64 sort
    (the same single-packed-key trick as `sort_key_np`), 4 words to a
    two-u64-key lexsort. Counting needs no stability, only ordering.
    """
    M, W = words.shape
    if M == 0:
        return 0
    if W <= 2:
        key = np.sort(_pack_u64_np(words))
        return 1 + int(np.count_nonzero(key[1:] != key[:-1]))
    lo = _pack_u64_np(words[:, :2])
    hi = _pack_u64_np(words[:, 2:])
    order = np.lexsort((lo, hi))
    lo, hi = lo[order], hi[order]
    return 1 + int(np.count_nonzero(
        (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])))


# ---------------------------------------------------------------------------
# Device-side (jax.lax.sort) key packing + multi-word stable sort.
# ---------------------------------------------------------------------------

def pack_key(words: jnp.ndarray):
    """Packed single-lane device sort key, or None when unpackable.

    One word is its own key; two words pack into u64 only when 64-bit
    lanes exist — ``jax_enable_x64`` on AND a non-TPU backend (TPUs have
    no native 64-bit integer datapath regardless of the x64 flag, and
    with x64 off jnp silently truncates u64). Callers fall back to the
    multi-key paths of :func:`sort_by_key` on None.
    """
    W = words.shape[-1]
    if W == 1:
        return words[..., 0]
    if (W == 2 and jax.config.jax_enable_x64
            and jax.default_backend() != "tpu"):
        return (words[..., 1].astype(jnp.uint64) << jnp.uint64(32)) \
            | words[..., 0].astype(jnp.uint64)
    return None


def _stable_order(cols) -> jnp.ndarray:
    """Stable argsort of the multi-word key whose u32 words are ``cols``
    (LSW first): the permutation :func:`sort_by_key` applies.

    Only the key words and an iota enter the sort — every sorted operand
    costs the TPU sort's compile time, while a gather by the permutation
    is cheap. ≤2 words sort ONCE (a packed u64 key where 64-bit lanes
    exist, else a two-key lexicographic sort, MSW primary — same order);
    wider keys take LSW→MSW stable passes (word-wise LSD radix: each pass
    stably sorts the permutation by one word, so the composition orders by
    the most-significant word with ties resolved by lower words, exactly
    `sort_key_np`'s ``np.lexsort``). The iota makes every pass stable.
    """
    M = cols[0].shape[0]
    iota = jax.lax.iota(jnp.int32, M)
    key = pack_key(jnp.stack(cols, axis=-1)) if len(cols) <= 2 else None
    if key is not None:
        return jax.lax.sort((key, iota), num_keys=1, is_stable=True)[1]
    if len(cols) == 2:
        return jax.lax.sort((cols[1], cols[0], iota), num_keys=2,
                            is_stable=True)[2]
    perm = iota
    for c in cols:                               # LSW -> MSW stable passes
        perm = jax.lax.sort((c[perm], perm), num_keys=1, is_stable=True)[1]
    return perm


def sort_by_key(words: jnp.ndarray, *operands: jnp.ndarray):
    """Stable ascending device sort by the multi-word ALTO key.

    ``words`` is (M, W) u32; ``operands`` are (M,) arrays carried through
    the same permutation (the values). Returns
    ``(sorted_words, *sorted_operands)``. The sort is stable, so
    duplicate full keys keep their input order — the tie rule the
    oriented-view and build parity contracts depend on.
    """
    cols = [words[:, w] for w in range(words.shape[1])]
    perm = _stable_order(cols)
    return (jnp.stack([c[perm] for c in cols], axis=-1),
            *[op[perm] for op in operands])


def count_distinct(words: jnp.ndarray) -> jnp.ndarray:
    """Distinct rows of an (M, W) u32 array, on device (sort + adjacent
    diff — the jittable sibling of :func:`count_distinct_np`). Counting
    needs only the order, so the sort is an unstable lexicographic sort
    of the key words alone."""
    if words.shape[0] == 0:
        return jnp.asarray(0, jnp.int32)
    W = words.shape[1]
    srt = jax.lax.sort(tuple(words[:, w] for w in reversed(range(W))),
                       num_keys=W, is_stable=False)
    neq = jnp.zeros((words.shape[0] - 1,), jnp.bool_)
    for c in srt:
        neq = neq | (c[1:] != c[:-1])
    return jnp.asarray(1, jnp.int32) + jnp.sum(neq, dtype=jnp.int32)


def compare_le_np(words: np.ndarray, bound: np.ndarray) -> np.ndarray:
    """Elementwise multi-word unsigned <= against a single bound."""
    M, W = words.shape
    le = np.ones(M, dtype=bool)
    decided = np.zeros(M, dtype=bool)
    for w in range(W - 1, -1, -1):
        lt = words[:, w] < bound[w]
        gt = words[:, w] > bound[w]
        le = np.where(~decided & gt, False, le)
        decided |= lt | gt
    return le

"""Compulsory work of the two sparse kernels, and the chip's peaks.

A kernel's roofline share is the least time the chip could take for the
work the tensor demands, over the time the kernel took. The work is
counted from the tensor alone, the same whatever traversal or variant
runs: the ALTO stream read once, each factor read once, the output
written once. Oriented-view rows, re-gathered factor rows and Π are the
implementation's cost, not the work. At about 4 FLOP per byte against a
v5e ridge near 240, both kernels are bound by bytes.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def stream_bytes(nnz: int, words: int) -> int:
    """The ALTO stream: ``words`` u32 index words and one f32 value per
    nonzero."""
    return nnz * (F32 * words + F32)


def mttkrp_bytes(dims, nnz: int, words: int, rank: int, mode: int) -> int:
    """One mode's MTTKRP: the stream, every other factor read once, the
    (I_mode, R) output written once."""
    others = sum(I for m, I in enumerate(dims) if m != mode)
    return (stream_bytes(nnz, words) + others * rank * F32
            + dims[mode] * rank * F32)


def phi_bytes(dims, nnz: int, words: int, rank: int, mode: int) -> int:
    """One Φ call: the stream, the N-1 other factors, B read once and Φ
    written once (both I_mode × R)."""
    return (mttkrp_bytes(dims, nnz, words, rank, mode)
            + dims[mode] * rank * F32)


def phi_flops(dims, nnz: int, rank: int) -> int:
    """Per nonzero and rank column: N-2 multiplies for the Khatri-Rao
    row, a multiply and an add for <B row, KRP row>, a multiply by the
    quotient and an add into Φ (the one divide per nonzero is left out)."""
    return nnz * rank * (len(dims) + 2)


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; a device missing from
    the table is an error, not a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def bound_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time for the work: the larger of the two bounds."""
    p = peaks(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])

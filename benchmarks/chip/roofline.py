"""Peaks of each chip, and the least work of each kernel.

``PEAKS`` is keyed by JAX's ``device_kind``; a kind not in it is an
error, never a default. TPU v5e (JAX names it "TPU v5 lite"): Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, HBM
819 GB/s.

A kernel's work is reckoned from the real work it is handed, not from
its padded blocks or its MXU formulation, so that the share reads the
same whatever implements the step. Integer operations are held against
the int8 peak, the highest integer rate published, so that the least
time is a true floor.
"""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e"}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def wedge_fused_work(real_wedges: int) -> tuple:
    """``(int ops, bytes)`` of aggregating ``real_wedges`` wedges by
    endpoint pair: each wedge's two int32 keys read and its two int32
    results (``d - 1`` and ``C(d, 2)``) written, 16 bytes; one key-pair
    match, one representative test and the combine, 4 operations."""
    return 4 * real_wedges, 16 * real_wedges


def bucket_update_work(decrements: int, moves: int) -> tuple:
    """``(int ops, bytes)`` of a decomposition's batched decrease-keys:
    per real decrement its target and amount read and the count read and
    written (16 bytes, 2 operations: the subtract and the new bucket);
    per bucket move the old and new bucket's occupancy updated (8 bytes,
    2 operations)."""
    return 2 * decrements + 2 * moves, 16 * decrements + 8 * moves


def least_time(device_kind: str, ops: float, nbytes: float) -> float:
    """Seconds the chip needs at least: the larger of the integer
    operations over the int8 peak and the bytes over HBM bandwidth."""
    p = peaks(device_kind)
    return max(ops / p["int8_ops"], nbytes / p["hbm_bytes_s"])


def share_pct(device_kind: str, ops: float, nbytes: float,
              kernel_s: float):
    """Roofline share in percent, or None where the kernel never ran."""
    if not kernel_s or kernel_s <= 0:
        return None
    return 100.0 * least_time(device_kind, ops, nbytes) / kernel_s

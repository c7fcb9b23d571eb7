"""Public jit'd wrappers for the Pallas kernels.

``use_pallas`` selects the kernel path; ``interpret=None`` (default)
resolves per backend via ``_interpret_default()``: compiled Mosaic on
TPU, interpret mode everywhere else. This is the engine contract relied
on by ``repro.core.count``/``repro.core.aggregate`` when called with
``engine="pallas"`` — CPU CI runs the identical kernel code in
interpret mode, TPU runs it compiled, and both match the pure-jnp
reference path in ``ref`` bit-for-bit on the integer outputs.

``bucket_min`` is additionally the per-round extract-min of the peeling
engines (``repro.core.peel``): the ``engine="device"`` tip loop calls
it inside a jitted ``lax.while_loop`` with ``use_pallas=True`` (one
reduction per round, no host sync — CI exercises the kernel in
interpret mode, TPU runs compiled Mosaic), while the host
``peel_wings`` loop routes its round minimum through it with the
Pallas path only on the compiled backend (off-TPU the per-round
interpreter overhead dwarfs the reduction, so it serves the XLA ref).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..testing import faults as _faults
from . import ref as _ref
from .bucket_min import bucket_min_pallas
from .bucket_update import (
    MAX_UPDATE_CAP,
    NUM_BUCKETS,
    bit_length,
    bucket_update_pallas,
    bucket_upper_bound,
    lowest_nonempty_bucket,
)
from .butterfly_combine import butterfly_combine_pallas
from .wedge_count import wedge_histogram_pallas
from .wedge_fused import MAX_TILE_CAP, TC, match_tiles_pallas

__all__ = [
    "interpret_default",
    "wedge_histogram",
    "butterfly_combine",
    "bucket_min",
    "bucket_state",
    "bucket_update",
    "match_tiles",
    # kernel-contract constants and pure helpers, re-exported so core/
    # consumes them through this dispatch module instead of importing
    # concrete kernel modules (the layering rule check_layering.py
    # enforces)
    "MAX_UPDATE_CAP",
    "NUM_BUCKETS",
    "MAX_TILE_CAP",
    "TC",
    "bit_length",
    "bucket_upper_bound",
    "lowest_nonempty_bucket",
]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# public alias: the counting engine documents this knob by name
interpret_default = _interpret_default


def _resolve(interpret: Optional[bool]) -> bool:
    return _interpret_default() if interpret is None else interpret


def wedge_histogram(
    keys,
    valid,
    num_buckets: int,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
):
    _faults.maybe_oom("ops.wedge_histogram")
    if use_pallas:
        return wedge_histogram_pallas(
            keys, valid, num_buckets, interpret=_resolve(interpret)
        )
    return _ref.wedge_histogram_ref(keys, valid, num_buckets)


def butterfly_combine(
    d, rep, valid, use_pallas: bool = False, interpret: Optional[bool] = None
):
    _faults.maybe_oom("ops.butterfly_combine")
    if use_pallas:
        return butterfly_combine_pallas(
            d, rep, valid, interpret=_resolve(interpret)
        )
    return _ref.butterfly_combine_ref(d, rep, valid)


def bucket_min(
    counts, alive, use_pallas: bool = False, interpret: Optional[bool] = None
):
    _faults.maybe_oom("ops.bucket_min")
    if use_pallas:
        return bucket_min_pallas(counts, alive, interpret=_resolve(interpret))
    return _ref.bucket_min_ref(counts, alive)


def bucket_state(counts, alive):
    """Masked extract-min plus geometric-bucket occupancy with no
    decrease-key batch: ``(min, bucket_hist)``. Always the jnp
    reference — inside the peeling round loops the same pair comes out
    of the ``bucket_update`` kernel pass for free; this standalone form
    only seeds the carried state before round 0 and re-derives it on
    zero-frontier rounds, both off the per-tile hot path.
    """
    return _ref.bucket_state_ref(counts, alive)


def bucket_update(
    counts,
    alive,
    idx,
    dec,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
):
    """Julienne-style batched decrease-key: apply the (idx, dec) update
    batch to ``counts`` and return ``(new_counts, min over alive,
    geometric-bucket occupancy)`` from the same pass (see
    ``bucket_update``). The kernel path computes in int32: callers keep
    counts below INT32_MAX (the device peeling loops guard it) and get
    the result back in their count dtype. A batch beyond MAX_UPDATE_CAP
    goes through the kernel in MAX_UPDATE_CAP chunks, one pass each;
    the last pass's min and occupancy describe the final counts.
    """
    _faults.maybe_oom("ops.bucket_update")
    if not use_pallas:
        return _ref.bucket_update_ref(counts, alive, idx, dec)
    interp = _resolve(interpret)
    k = idx.shape[0]
    if k <= MAX_UPDATE_CAP:
        new, mn, hist = bucket_update_pallas(
            counts, alive, idx, dec, interpret=interp
        )
        return new.astype(counts.dtype), mn, hist
    chunks = -(-k // MAX_UPDATE_CAP)
    pad = chunks * MAX_UPDATE_CAP - k
    idx_c = jnp.pad(idx.astype(jnp.int32), (0, pad),
                    constant_values=counts.shape[0])
    dec_c = jnp.pad(dec.astype(jnp.int32), (0, pad))

    def step(c, chunk):
        new, mn, hist = bucket_update_pallas(
            c, alive, chunk[0], chunk[1], interpret=interp
        )
        return new, (mn, hist)

    new, (mns, hists) = jax.lax.scan(
        step,
        counts.astype(jnp.int32),
        (idx_c.reshape(chunks, MAX_UPDATE_CAP),
         dec_c.reshape(chunks, MAX_UPDATE_CAP)),
    )
    return new.astype(counts.dtype), mns[-1], hists[-1]


def match_tiles(ka, kb, use_pallas: bool = False,
                interpret: Optional[bool] = None):
    """In-tile endpoint-pair aggregation of ``(n_tiles, T)`` wedge key
    lanes (``engine="fused_pallas"`` hot step; see ``wedge_fused``):
    per lane ``(d - 1, C(d, 2) on group representatives)``."""
    _faults.maybe_oom("ops.match_tiles")
    if use_pallas:
        return match_tiles_pallas(ka, kb, interpret=_resolve(interpret))
    return _ref.match_tiles_ref(ka, kb)

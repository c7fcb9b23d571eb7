"""Pure-jnp oracles for every Pallas kernel in this package.

These are also the ``engine="xla"`` fallbacks dispatched by ``ops`` —
each oracle must stay bit-identical to its kernel's integer outputs
(the parity tests in tests/test_kernels.py and tests/test_engine.py
enforce this on every run).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "wedge_histogram_ref",
    "butterfly_combine_ref",
    "bucket_min_ref",
    "bucket_state_ref",
    "bucket_update_ref",
    "match_tiles_ref",
]


def wedge_histogram_ref(
    keys: jax.Array, valid: jax.Array, num_buckets: int
) -> jax.Array:
    keys = keys.reshape(-1).astype(jnp.int32)
    valid = valid.reshape(-1).astype(jnp.int32)
    safe = jnp.where((keys >= 0) & (keys < num_buckets), keys, num_buckets)
    return (
        jnp.zeros((num_buckets + 1,), jnp.int32)
        .at[safe]
        .add(valid)[:num_buckets]
    )


def butterfly_combine_ref(d: jax.Array, rep: jax.Array, valid: jax.Array):
    """Mirror of the widened kernel: (dm1, c2_lo, c2_hi, total_f32).
    C(d, 2) is exact over the full int32 ``d`` range via the shared
    16-bit-limb multiply (``choose2_limbs``); the int64-truth parity
    check lives in tests/test_kernels.py."""
    from .butterfly_combine import choose2_limbs

    d = d.astype(jnp.int32)
    live = (valid.astype(jnp.int32) > 0) & (d > 0)
    rep = rep.astype(jnp.int32) > 0
    dm1 = jnp.where(live, d - 1, 0)
    lo, hi = choose2_limbs(jnp.where(live & rep, d, 0))
    tot = (
        jnp.sum(lo.astype(jnp.uint32).astype(jnp.float32))
        + jnp.sum(hi.astype(jnp.float32)) * jnp.float32(2.0**32)
    )
    return dm1, lo, hi, tot


def bucket_min_ref(counts: jax.Array, alive: jax.Array) -> jax.Array:
    inf = jnp.int32(np.iinfo(np.int32).max)
    if counts.dtype.itemsize > 4:  # clamp, don't wrap (kernel contract)
        counts = jnp.minimum(counts, jnp.asarray(inf, counts.dtype))
    return jnp.min(
        jnp.where(alive.astype(jnp.int32) > 0, counts.astype(jnp.int32), inf)
    )


def bucket_state_ref(counts: jax.Array, alive: jax.Array):
    """Masked extract-min plus geometric-bucket occupancy, no update —
    ``bucket_update_ref`` with an empty decrease-key batch.

    Returns ``(min, bucket_hist)`` in the ``bucket_min`` clamp contract
    / the ``bucket_update`` histogram contract (``bucket(v) =
    bit_length(max(v, 0))`` over alive entries, ``NUM_BUCKETS`` ranges).
    The range-mode peeling loops use this to seed the carried
    (min, occupancy) state before round 0 and to re-derive it on
    zero-frontier rounds; inside the round loop the same pair comes out
    of the ``bucket_update`` decrease-key pass for free.
    """
    from .bucket_update import NUM_BUCKETS

    inf = jnp.int32(np.iinfo(np.int32).max)
    c32 = counts
    if counts.dtype.itemsize > 4:  # clamp, don't wrap (bucket_min contract)
        c32 = jnp.minimum(counts, jnp.asarray(inf, counts.dtype))
    c32 = c32.astype(jnp.int32)
    live = alive.astype(jnp.int32) > 0
    mn = jnp.min(jnp.where(live, c32, inf))
    v = jnp.maximum(c32, 0)
    bl = jnp.zeros(v.shape, jnp.int32)
    for j in range(31):
        bl = bl + (v >= jnp.int32(1 << j)).astype(jnp.int32)
    hist = (
        jnp.zeros((NUM_BUCKETS,), jnp.int32)
        .at[bl]
        .add(live.astype(jnp.int32))
    )
    return mn, hist


def bucket_update_ref(
    counts: jax.Array,
    alive: jax.Array,
    idx: jax.Array,
    dec: jax.Array,
):
    """Mirror of ``bucket_update.bucket_update_pallas``: one batched
    decrease-key pass returning ``(new_counts, min, bucket_hist)``.

    ``new_counts`` stays in the counts dtype (the kernel is int32-only;
    parity is asserted on int32 inputs). ``min`` follows the
    ``bucket_min`` clamp contract for wider dtypes; ``bucket_hist`` is
    the (32,) occupancy of the geometric ranges ``bucket(v) =
    bit_length(max(v, 0))`` over alive entries. ``idx`` out of
    ``[0, n)`` (the ``n`` sentinel included) drops the update; its
    ``dec`` must be 0-safe anyway. ``dec`` must be nonnegative and
    below 2^31 (the kernel's limb contract).
    """
    from .bucket_update import NUM_BUCKETS

    n = counts.shape[0]
    idx = idx.astype(jnp.int32)
    safe = jnp.where((idx >= 0) & (idx < n), idx, jnp.int32(n))
    new = counts.at[safe].add(-dec.astype(counts.dtype))
    inf = jnp.int32(np.iinfo(np.int32).max)
    c32 = new
    if new.dtype.itemsize > 4:  # clamp, don't wrap (bucket_min contract)
        c32 = jnp.minimum(new, jnp.asarray(inf, new.dtype))
    c32 = c32.astype(jnp.int32)
    live = alive.astype(jnp.int32) > 0
    mn = jnp.min(jnp.where(live, c32, inf))
    v = jnp.maximum(c32, 0)
    bl = jnp.zeros(v.shape, jnp.int32)
    for j in range(31):
        bl = bl + (v >= jnp.int32(1 << j)).astype(jnp.int32)
    hist = (
        jnp.zeros((NUM_BUCKETS,), jnp.int32)
        .at[bl]
        .add(live.astype(jnp.int32))
    )
    return new, mn, hist


def match_tiles_ref(ka: jax.Array, kb: jax.Array):
    """Oracle for ``wedge_fused.match_tiles_pallas``: per-lane
    ``(d - 1, C(d, 2) on group representatives)`` of ``(n_tiles, T)``
    endpoint key lanes, from a dense all-pairs comparison per tile
    instead of MXU panels. Lanes with ``ka < 0`` are invalid."""
    ka = ka.astype(jnp.int32)
    kb = kb.astype(jnp.int32)
    lane = jnp.arange(ka.shape[1], dtype=jnp.int32)
    match = (ka[:, :, None] == ka[:, None, :]) & (
        kb[:, :, None] == kb[:, None, :]
    )
    d = jnp.sum(match, axis=2, dtype=jnp.int32)
    earlier = jnp.sum(
        match & (lane[None, :] < lane[:, None])[None], axis=2,
        dtype=jnp.int32,
    )
    valid = ka >= 0
    rep = valid & (earlier == 0)
    dm1 = jnp.where(valid, d - 1, 0)
    c2 = jnp.where(rep, d * (d - 1) // 2, 0)
    return dm1, c2

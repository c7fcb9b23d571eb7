"""Wedge aggregation strategies (paper §3.1.2).

All strategies group wedges by their endpoint pair (x1, x2) and return
(a) the group size ``d`` gathered back per wedge and (b) a padded table
of distinct groups for endpoint-side butterfly contributions.

SPMD adaptations of the paper's multicore strategies:

  - **sort**: PBBS sample sort -> XLA stable argsort (two-pass lexsort on
    (x2, x1); no wide composite keys needed).
  - **hash**: phase-concurrent linear-probing table with atomic adds ->
    cohort-claiming double-hash table: each probe round does a
    scatter-min "claim" (the SPMD analogue of CAS) followed by a gather
    re-check. All wedges of one key probe an identical slot sequence, so
    they resolve as a cohort. Bounded probes; resolution failure is
    detected and reported so callers can fall back to sort.
  - **histogram**: dense scatter-add over the (x1, x2) key space —
    exact, O(n²) table (the paper's histogramming also pays O(n²)-ish
    space via semisort buckets at worst). Only valid for small n; large
    graphs use hash/sort/batch. On TPU the scatter-add is realized by
    the one-hot MXU kernel in ``repro.kernels.wedge_count``.
  - **batch**: implemented in ``count.py`` (it fuses aggregation with
    butterfly accumulation, as in the paper, where batching cannot
    re-aggregate).

Engine contract: ``aggregate_hash`` and ``aggregate_dense`` accept
``engine="xla"|"pallas"``. Under "pallas" the histogram step (the only
scatter in either strategy) runs through the one-hot MXU kernel
``repro.kernels.wedge_count.wedge_histogram_pallas`` via the
``repro.kernels.ops`` wrapper, which picks interpret mode automatically
off the backend (compiled on TPU, interpreted elsewhere — CI exercises
the kernels in interpret mode). "xla" keeps the scatter-add. Both
engines produce identical int32 counts.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as _kops
from .wedges import Wedges

__all__ = [
    "Groups",
    "aggregate_sort",
    "aggregate_hash",
    "aggregate_dense",
    "AGGREGATIONS",
]

_FREE = jnp.int32(np.iinfo(np.int32).max)


def _histogram(keys: jax.Array, valid: jax.Array, num_buckets: int, engine: str) -> jax.Array:
    """Count ``keys`` (masked by ``valid``) into ``num_buckets`` int32 bins
    on the selected engine. Keys of masked-out entries must already be
    in-range (callers zero them)."""
    if engine == "pallas":
        return _kops.wedge_histogram(
            keys, valid.astype(jnp.int32), num_buckets, use_pallas=True
        )
    if engine != "xla":
        raise ValueError(f"engine must be xla|pallas, got {engine}")
    return (
        jnp.zeros((num_buckets,), jnp.int32)
        .at[keys]
        .add(valid.astype(jnp.int32))
    )


class Groups(NamedTuple):
    """Distinct endpoint-pair groups, padded.

    ``d_per_wedge[w]`` is the multiplicity of wedge w's group (0 for
    invalid wedges). ``(x1, x2, d, valid)`` describe distinct groups.
    ``ok`` is False iff the strategy failed (hash overflow) and the
    caller should fall back.
    """

    d_per_wedge: jax.Array  # (w_cap,)
    x1: jax.Array  # (g_cap,)
    x2: jax.Array  # (g_cap,)
    d: jax.Array  # (g_cap,)
    valid: jax.Array  # (g_cap,) bool
    ok: jax.Array  # () bool


def aggregate_sort(w: Wedges):
    """Sort-based aggregation: one lexicographic ``lax.sort`` on
    (x1, x2) carrying the lane permutation, which gathers the wedge
    payload (centers, edge slots) into sorted order, so no inverse
    permutation or unsort scatter is needed.
    Returns (Groups, sorted Wedges); ``d_per_wedge`` aligns with the
    *sorted* wedges (§Perf-3 iteration 2 — scatter targets are
    order-independent, so callers accumulate from the sorted view).
    """
    w_cap = w.x1.shape[0]
    # Invalid wedges carry x1 == x2 == n_pad sentinel -> sort to the end.
    # Only the keys and a lane permutation go through the sort: the TPU
    # compiler's sort compile time grows with the operand count (six
    # operands took minutes at 64K lanes), and the payload follows by
    # gather.
    sx1, sx2, perm = jax.lax.sort(
        (w.x1, w.x2, jnp.arange(w_cap, dtype=jnp.int32)), num_keys=2
    )
    sy, scs, sss, sval = (
        w.y[perm], w.center_slot[perm], w.second_slot[perm], w.valid[perm]
    )
    prev_same = jnp.concatenate(
        [
            jnp.zeros((1,), jnp.bool_),
            (sx1[1:] == sx1[:-1]) & (sx2[1:] == sx2[:-1]),
        ]
    )
    starts = sval & ~prev_same
    seg = jnp.cumsum(starts.astype(jnp.int32)) - 1  # group id per sorted pos
    seg = jnp.where(sval, seg, w_cap - 1)
    counts = jnp.zeros((w_cap,), jnp.int32).at[seg].add(sval.astype(jnp.int32))
    d_sorted = jnp.where(sval, counts[seg], 0)
    # Group table: one entry per start position.
    g_ids = jnp.where(starts, seg, w_cap - 1)
    gx1 = jnp.full((w_cap,), 0, jnp.int32).at[g_ids].set(jnp.where(starts, sx1, 0))
    gx2 = jnp.full((w_cap,), 0, jnp.int32).at[g_ids].set(jnp.where(starts, sx2, 0))
    n_groups = jnp.sum(starts.astype(jnp.int32))
    gvalid = jnp.arange(w_cap, dtype=jnp.int32) < n_groups
    gd = jnp.where(gvalid, counts, 0)
    groups = Groups(
        d_per_wedge=d_sorted,
        x1=gx1,
        x2=gx2,
        d=gd,
        valid=gvalid,
        ok=jnp.array(True),
    )
    w_sorted = Wedges(
        x1=sx1, x2=sx2, y=sy, center_slot=scs, second_slot=sss, valid=sval
    )
    return groups, w_sorted


def _hash_slots(x1: jax.Array, x2: jax.Array, probe: jax.Array, table_bits: int) -> jax.Array:
    """Double hashing on the endpoint pair; uint32 multiply-mix."""
    a = x1.astype(jnp.uint32)
    b = x2.astype(jnp.uint32)
    h1 = (a * jnp.uint32(0x9E3779B1)) ^ (b * jnp.uint32(0x85EBCA6B))
    h1 = h1 ^ (h1 >> 15)
    h2 = ((a ^ (b << 7) ^ (b >> 3)) * jnp.uint32(0xC2B2AE35)) | jnp.uint32(1)
    slot = h1 + probe.astype(jnp.uint32) * h2
    return (slot & jnp.uint32((1 << table_bits) - 1)).astype(jnp.int32)


def aggregate_hash(
    w: Wedges,
    table_bits: int | None = None,
    max_probes: int = 32,
    engine: str = "xla",
) -> Groups:
    """Cohort-claiming double-hash aggregation.

    The table stores, per slot, the *claimant wedge id* (scatter-min is
    the SPMD stand-in for CAS). Because every wedge of a given key
    probes the identical slot sequence, same-key wedges resolve as a
    cohort to one slot; distinct-key collisions advance to the next
    probe. ``ok`` is False if any wedge remains unresolved (callers
    fall back to sort — paper §3.1.4 discusses strategy fallbacks).
    """
    w_cap = w.x1.shape[0]
    if table_bits is None:
        table_bits = max(4, int(np.ceil(np.log2(max(2 * w_cap, 2)))))
    T = 1 << table_bits
    wid = jnp.arange(w_cap, dtype=jnp.int32)
    claim_id = jnp.where(w.valid, wid, _FREE)

    def body(p, carry):
        owner, slot, resolved = carry
        cand = _hash_slots(w.x1, w.x2, jnp.full((w_cap,), p, jnp.int32), table_bits)
        o = owner[cand]
        o_safe = jnp.minimum(o, w_cap - 1)
        occupied = o != _FREE
        key_match = (w.x1[o_safe] == w.x1) & (w.x2[o_safe] == w.x2)
        res_now = occupied & key_match & ~resolved
        # claim attempt on free slots
        try_claim = ~resolved & ~occupied
        owner = owner.at[cand].min(jnp.where(try_claim, claim_id, _FREE))
        o2 = owner[cand]
        o2_safe = jnp.minimum(o2, w_cap - 1)
        won = try_claim & (o2 != _FREE) & (w.x1[o2_safe] == w.x1) & (w.x2[o2_safe] == w.x2)
        newly = res_now | won
        slot = jnp.where(newly & ~resolved, cand, slot)
        resolved = resolved | newly
        return owner, slot, resolved

    owner0 = jnp.full((T,), _FREE, jnp.int32)
    slot0 = jnp.zeros((w_cap,), jnp.int32)
    resolved0 = ~w.valid  # invalid wedges are trivially resolved
    owner, slot, resolved = jax.lax.fori_loop(
        0, max_probes, body, (owner0, slot0, resolved0)
    )
    ok = jnp.all(resolved)
    add = (w.valid & resolved).astype(jnp.int32)
    counts = _histogram(slot, add, T, engine)
    # counts[slot0=0] may be polluted by invalid wedges' slot 0 default —
    # they add 0, so it is safe.
    d_per_wedge = jnp.where(w.valid, counts[slot], 0)
    own_safe = jnp.minimum(owner, w_cap - 1)
    gvalid = owner != _FREE
    gx1 = jnp.where(gvalid, w.x1[own_safe], 0)
    gx2 = jnp.where(gvalid, w.x2[own_safe], 0)
    gd = jnp.where(gvalid, counts, 0)
    return Groups(
        d_per_wedge=d_per_wedge, x1=gx1, x2=gx2, d=gd, valid=gvalid, ok=ok
    )


def aggregate_dense(w: Wedges, n_pad: int, engine: str = "xla") -> Groups:
    """Exact dense histogram over the (x1, x2) key space. O(n²) table."""
    key = w.x1.astype(jnp.int32) * jnp.int32(n_pad) + w.x2.astype(jnp.int32)
    key = jnp.where(w.valid, key, 0)
    T = n_pad * n_pad
    counts = _histogram(key, w.valid, T, engine)
    d_per_wedge = jnp.where(w.valid, counts[key], 0)
    tkey = jnp.arange(T, dtype=jnp.int32)
    gvalid = counts > 0
    return Groups(
        d_per_wedge=d_per_wedge,
        x1=tkey // n_pad,
        x2=tkey % n_pad,
        d=counts,
        valid=gvalid,
        ok=jnp.array(True),
    )


AGGREGATIONS = ("sort", "hash", "histogram", "batch", "batch_wa")

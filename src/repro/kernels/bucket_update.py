"""Pallas TPU kernel: Julienne-style batched decrease-key.

The peeling frameworks' per-round *update* primitive (Lakhotia et al.
2021; Julienne's bucketed priority structure): apply one round's
aggregated support decrements to the count array and, in the same pass,
re-derive everything the next round's extract-min needs —

  1. ``new_counts = counts - scatter(idx, dec)`` (the decrease-key
     batch; the one-scatter-per-round subtract of the PR 2 engines,
     folded in),
  2. the masked min of the updated counts over ``alive`` (the next
     round's bucket floor — no separate ``bucket_min`` reduction pass),
  3. the occupancy histogram of the O(log n) geometric bucket ranges
     ``[2^k, 2^{k+1})`` (bucket of v = bit_length(v), 32 buckets for
     int32 counts) — the Julienne bucket structure's view of the
     updated array: each decremented element conceptually *moves* from
     its old range to a lower one, and the histogram is the post-move
     occupancy.

Exactness contract: the decrement scatter is realized as one-hot MXU
contractions over four 8-bit limbs of ``dec`` with bf16 operands (every
limb and every one-hot entry is exact in bf16) and f32 accumulation, so
every column sum stays below ``MAX_UPDATE_CAP * 2^8 < 2^24`` — exact —
for update batches of at most ``MAX_UPDATE_CAP`` (4096) entries and
``dec`` anywhere in [0, 2^31). The wrapper enforces the batch bound at
trace time; the peeling loops (``core.pipeline.apply_decrements``)
scatter-add the lanes of a wider batch before its last
``MAX_UPDATE_CAP`` chunk and pass only that chunk. ``idx`` entries equal to
``counts.shape[0]`` (the sentinel) hit no bucket; their ``dec`` must
be 0.

Layout: the count array travels as one ``(1, n)`` row cut into
``(1, TN)`` lane blocks; the update batch as ``(k, 1)`` columns cut into
``(RC, 1)`` row chunks, the grid's second (reduction) axis, so the
one-hot panel ``(RC, TN)`` needs no in-kernel reshape.

Dispatched via ``ops.bucket_update`` with the same backend-aware
interpret default as every kernel here (compiled on TPU, interpreted in
CI). The device peeling engines (``core.peel`` ``engine="device"``)
run it once per sub-round that streams any update, on the
last chunk of its last tile: the round loop reads only that pass's
min and occupancy, so every earlier tile and chunk is an exact int32
scatter-add into the counts. Off-TPU they route that pass to the
reference (the interpreter would dominate the round, same policy as
``peel_wings``'s host extract-min).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

__all__ = [
    "bucket_update_pallas",
    "bit_length",
    "bucket_upper_bound",
    "lowest_nonempty_bucket",
    "MAX_UPDATE_CAP",
    "NUM_BUCKETS",
    "TN",
]

TN = 512  # count-array tile (matches the one-hot panel width)
RC = 512  # update-batch rows per grid step (the one-hot panel height)
NUM_BUCKETS = 32  # geometric ranges for int32 counts: bit_length in [0, 31]
MAX_UPDATE_CAP = 4096  # keeps every f32 limb contraction exact (< 2^24)
_INF = np.int32(np.iinfo(np.int32).max)


def bit_length(v: jax.Array) -> jax.Array:
    """In-graph ``bit_length(max(v, 0))`` — the bucket index of a count
    in the occupancy histogram's geometric ranges (bucket ``k`` holds
    values in ``[2^(k-1), 2^k)``; bucket 0 holds exactly {0})."""
    return jnp.int32(32) - jax.lax.clz(jnp.maximum(v.astype(jnp.int32), 0))


def bucket_upper_bound(k: jax.Array) -> jax.Array:
    """Exclusive upper bound ``2^k`` of geometric bucket ``k``, clamped
    to INT32_MAX for the top bucket (the peeling engines guard counts
    below INT32_MAX, so the clamp still covers every value)."""
    k = k.astype(jnp.int32)
    return jnp.where(k >= 31, _INF, jnp.int32(1) << jnp.minimum(k, 30))


def lowest_nonempty_bucket(hist: jax.Array) -> jax.Array:
    """Index of the lowest non-empty geometric bucket in an occupancy
    histogram (NUM_BUCKETS when all empty) — the Julienne/Lakhotia
    next-range selection, consumed by the range-mode peeling round
    loops. Equals ``bit_length(masked min)`` whenever any entry is
    alive, because the min inhabits the lowest non-empty range."""
    idx = jnp.arange(hist.shape[0], dtype=jnp.int32)
    return jnp.min(jnp.where(hist > 0, idx, jnp.int32(NUM_BUCKETS)))


def _update_kernel(counts_ref, alive_ref, idx_ref, dec_ref,
                   out_ref, mn_ref, hist_ref):
    t = pl.program_id(0)  # count tile
    r = pl.program_id(1)  # update-batch row chunk

    @pl.when((t == 0) & (r == 0))
    def _init():
        mn_ref[...] = jnp.full_like(mn_ref, _INF)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(r == 0)
    def _load():
        out_ref[...] = counts_ref[...]

    # -- 1. decrement scatter: one-hot MXU contraction, 8-bit limbs ---
    idx = idx_ref[...]  # (RC, 1)
    dec = dec_ref[...]  # (RC, 1)
    rows = idx.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, TN), 1) + t * TN
    match = idx == cols
    ones8 = jnp.ones((8, rows), jnp.bfloat16)
    delta = jnp.zeros((1, TN), jnp.int32)
    for shift in (0, 8, 16, 24):
        limb = ((dec >> shift) & jnp.int32(0xFF)).astype(jnp.float32)
        panel = jnp.where(match, limb, jnp.float32(0)).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            ones8, panel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (8, TN); rows identical
        delta = delta + (part[0:1, :].astype(jnp.int32) << shift)
    out_ref[...] = out_ref[...] - delta

    @pl.when(r == pl.num_programs(1) - 1)
    def _finish():
        new = out_ref[...]  # (1, TN)
        alive = alive_ref[...] > 0
        # -- 2. masked min of the updated tile ------------------------
        part_mn = jnp.min(jnp.where(alive, new, _INF)).reshape(1, 1)
        mn_ref[...] = jnp.minimum(mn_ref[...], part_mn)
        # -- 3. occupancy: bucket(v) = bit_length(max(v, 0)) ----------
        v = jnp.maximum(new, 0)
        bl = jnp.zeros((1, TN), jnp.int32)
        for j in range(31):
            bl = bl + (v >= jnp.int32(1 << j)).astype(jnp.int32)
        buckets = jax.lax.broadcasted_iota(jnp.int32, (128, TN), 0)
        onehot = jnp.where(
            (bl == buckets) & alive, jnp.float32(1), jnp.float32(0)
        ).astype(jnp.bfloat16)  # (128, TN): bucket rows x count lanes
        part_h = jax.lax.dot_general(
            jnp.ones((8, TN), jnp.bfloat16), onehot,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (8, 128)
        hist_ref[...] = hist_ref[...] + part_h[0:1, :].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bucket_update_pallas(
    counts: jax.Array,
    alive: jax.Array,
    idx: jax.Array,
    dec: jax.Array,
    interpret: bool = True,
):
    """Batched decrease-key: ``(new_counts, min, bucket_hist)``.

    ``new_counts[i] = counts[i] - sum(dec[idx == i])`` (int32); ``min``
    is the masked minimum of the updated counts over ``alive`` (int32,
    INT32_MAX when none alive); ``bucket_hist`` is the (32,) occupancy
    of the geometric ranges over alive entries. ``idx == counts.shape
    [0]`` is the drop sentinel. Update batches beyond MAX_UPDATE_CAP
    raise (``core.pipeline.apply_decrements`` scatter-adds all but the
    last chunk of such a batch).
    """
    if idx.shape[0] > MAX_UPDATE_CAP:
        raise ValueError(
            f"bucket_update_pallas batch {idx.shape[0]} exceeds "
            f"MAX_UPDATE_CAP {MAX_UPDATE_CAP} — the f32 limb "
            "contractions would lose exactness; apply_decrements "
            "scatter-adds all but the last chunk of a larger batch"
        )
    n = counts.shape[0]
    n_pad = ((n + TN - 1) // TN) * TN
    cp = jnp.pad(counts.astype(jnp.int32), (0, n_pad - n))[None, :]
    ap = jnp.pad(alive.astype(jnp.int32), (0, n_pad - n))[None, :]
    k = idx.shape[0]
    rc = min(RC, ((k + 127) // 128) * 128)
    k_pad = ((k + rc - 1) // rc) * rc
    # padded update lanes target the padded count region (>= n): their
    # delta lands on lanes the wrapper slices off and alive masks out
    ip = jnp.pad(idx.astype(jnp.int32), (0, k_pad - k),
                 constant_values=n_pad)
    ip = jnp.where((ip < 0) | (ip >= n), jnp.int32(n_pad), ip)[:, None]
    dp = jnp.pad(dec.astype(jnp.int32), (0, k_pad - k))[:, None]
    with jax.enable_x64(False):  # Mosaic lowers 32-bit index math only
        out, mn, hist = pl.pallas_call(
            _update_kernel,
            grid=(n_pad // TN, k_pad // rc),
            in_specs=[
                pl.BlockSpec((1, TN), lambda t, r: (0, t)),
                pl.BlockSpec((1, TN), lambda t, r: (0, t)),
                pl.BlockSpec((rc, 1), lambda t, r: (r, 0)),
                pl.BlockSpec((rc, 1), lambda t, r: (r, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, TN), lambda t, r: (0, t)),
                pl.BlockSpec((1, 1), lambda t, r: (0, 0)),
                pl.BlockSpec((1, 128), lambda t, r: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
                jax.ShapeDtypeStruct((1, 128), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")
            ),
            interpret=interpret,
            name="bucket_update",
        )(cp, ap, ip, dp)
    return out[0, :n], mn[0, 0], hist[0, :NUM_BUCKETS]

"""Pallas TPU kernel: wedge histogram via one-hot MXU matmul.

The paper's hottest aggregation step is an atomic-add histogram over
wedge endpoint keys (hash slots or dense keys). TPUs have no fetch-add;
the TPU-native formulation is a *one-hot matrix product*:

    counts[b] = Σ_n [keys[n] == b]  =  (1_{1×T} · onehot_{T×B})[b]

Each grid step materializes a (TK × TB) one-hot tile in VMEM and
contracts it against a ones vector on the MXU, accumulating over key
tiles. This turns random scatter traffic into dense systolic compute —
the hardware-adaptation story of DESIGN.md §2.

Grid: (num_bucket_tiles, num_key_tiles); the key-tile dimension is the
minormost (sequential) axis so each output tile accumulates in place.
Keys enter as a ``(n, 1)`` column so each tile's ``(TK, 1)`` block
broadcasts along the bucket lanes without an in-kernel reshape, and the
one-hot operands are bf16 (0/1 is exact; the f32 accumulation is exact
below 2^24 keys per bucket tile, far above ``TK``).

Engine wiring: ``repro.core.aggregate`` routes the hash-slot and dense
(x1, x2)-key histograms here when the counting engine runs with
``engine="pallas"`` (via ``ops.wedge_histogram``). Work is
O(keys x buckets / tile) — the right trade for hash tables
(buckets ~ 2W) and small dense key spaces; the engine keeps the sort
strategy scatter-free so it never pays this cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wedge_histogram_pallas", "TK", "TB"]

TK = 512  # keys per tile
TB = 512  # buckets per tile  (one-hot tile: 512x512 f32 = 1 MiB VMEM)


def _hist_kernel(keys_ref, valid_ref, out_ref):
    k = pl.program_id(1)
    b0 = pl.program_id(0) * TB
    keys = keys_ref[...]  # (TK, 1) int32
    valid = valid_ref[...]  # (TK, 1) int32 0/1
    cols = jax.lax.broadcasted_iota(jnp.int32, (TK, TB), 1) + b0
    onehot = jnp.where(
        (keys == cols) & (valid > 0), jnp.float32(1), jnp.float32(0)
    ).astype(jnp.bfloat16)  # typed: a bare 1.0 is float64 under x64
    ones = jnp.ones((8, TK), jnp.bfloat16)  # MXU-friendly LHS
    part = jax.lax.dot_general(
        ones,
        onehot,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (8, TB); all rows identical

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part[0:1, :].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def wedge_histogram_pallas(
    keys: jax.Array,
    valid: jax.Array,
    num_buckets: int,
    interpret: bool = True,
) -> jax.Array:
    """Histogram of ``keys`` (int32, any shape flattened) over
    ``[0, num_buckets)``; entries with ``valid == 0`` are skipped.

    Returns int32 counts of shape (num_buckets,).
    """
    if num_buckets <= 0:
        raise ValueError(f"num_buckets must be positive, got {num_buckets}")
    keys = keys.reshape(-1).astype(jnp.int32)
    valid = valid.reshape(-1).astype(jnp.int32)
    n = keys.shape[0]
    n_pad = ((n + TK - 1) // TK) * TK
    b_pad = ((num_buckets + TB - 1) // TB) * TB
    keys = jnp.pad(keys, (0, n_pad - n), constant_values=-1)[:, None]
    valid = jnp.pad(valid, (0, n_pad - n))[:, None]
    grid = (b_pad // TB, n_pad // TK)
    with jax.enable_x64(False):  # Mosaic lowers 32-bit index math only
        out = pl.pallas_call(
            _hist_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TK, 1), lambda b, k: (k, 0)),
                pl.BlockSpec((TK, 1), lambda b, k: (k, 0)),
            ],
            out_specs=pl.BlockSpec((1, TB), lambda b, k: (0, b)),
            out_shape=jax.ShapeDtypeStruct((1, b_pad), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
            name="wedge_count",
        )(keys, valid)
    return out[0, :num_buckets]

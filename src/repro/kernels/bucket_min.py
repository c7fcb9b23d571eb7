"""Pallas TPU kernel: masked min-reduction (bucketing extract-min).

The peeling frameworks' per-round primitive: find the minimum butterfly
count among alive vertices/edges (the SPMD replacement for the
Fibonacci heap's delete-min — DESIGN.md §2/§8, paper §5.4.1). Tiled VPU
reduction with a (1,1) running-min accumulator; Julienne's skip-ahead
over empty buckets is inherent (the min jumps gaps in one reduction).

Dispatched via ``ops.bucket_min`` with the same backend-aware interpret
default as the counting kernels (compiled on TPU, interpreted in CI).
This is the extract-min of the device-resident peeling engine
(``core.peel`` ``engine="device"``): one call per ``lax.while_loop``
round, no host round-trip. Counts wider than int32 are clamped to
INT32_MAX before the reduction (min semantics preserved whenever the
true minimum fits int32 — peeling guards the >= 2^31 case host-side).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

__all__ = ["bucket_min_pallas", "TN"]

TN = 2048
_ROWS = TN // 128  # one block = (_ROWS, 128) lanes
_INF = np.int32(np.iinfo(np.int32).max)


def _min_kernel(counts_ref, alive_ref, out_ref):
    k = pl.program_id(0)
    c = counts_ref[...].astype(jnp.int32)
    alive = alive_ref[...] > 0
    part = jnp.min(jnp.where(alive, c, _INF)).reshape(1, 1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INF)

    out_ref[...] = jnp.minimum(out_ref[...], part)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bucket_min_pallas(
    counts: jax.Array, alive: jax.Array, interpret: bool = True
) -> jax.Array:
    """Min of ``counts`` where ``alive``; INT32_MAX if none. () int32.

    Wider-than-int32 counts are clamped (not wrapped) to INT32_MAX so
    the masked min stays correct while the true minimum fits int32.
    """
    n = counts.shape[0]
    n_pad = ((n + TN - 1) // TN) * TN
    if counts.dtype.itemsize > 4:
        counts = jnp.minimum(counts, jnp.asarray(_INF, counts.dtype))
    rows = n_pad // 128
    cp = jnp.pad(counts.astype(jnp.int32), (0, n_pad - n)).reshape(rows, 128)
    ap = jnp.pad(alive.astype(jnp.int32), (0, n_pad - n)).reshape(rows, 128)
    block = pl.BlockSpec((_ROWS, 128), lambda k: (k, 0))
    with jax.enable_x64(False):  # Mosaic lowers 32-bit index math only
        out = pl.pallas_call(
            _min_kernel,
            grid=(n_pad // TN,),
            in_specs=[block, block],
            out_specs=pl.BlockSpec((1, 1), lambda k: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="bucket_min",
        )(cp, ap)
    return out[0, 0]

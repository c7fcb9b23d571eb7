"""Pallas TPU kernel: wedge-count -> butterfly-contribution transform.

Step 4 of the counting framework (paper Fig. 2): given each wedge's
group multiplicity ``d`` and a group-representative flag, emit

    dm1[i]            = d[i] - 1          (center / edge contributions)
    (c2_lo, c2_hi)[i] = rep[i] ? C(d,2):0 (endpoint contributions,
                                           once per group, 64-bit)

plus per-tile partial sums of choose2 (the global count reduction) so
the host-side total is a cheap O(grid) add. Elementwise VPU work tiled
through VMEM as lane-dense ``(TN // 128, 128)`` blocks; the reduction
keeps a (1,1) accumulator block.

Precision contract: C(d, 2) is computed exactly for the full int32
``d`` range (0 <= d < 2^31) with 16-bit-limb uint32 arithmetic — the
64-bit result is returned as two int32 limbs (``c2_lo`` is the low 32
bits as an int32 bit pattern, ``c2_hi`` the high 32 bits), so group
multiplicities >= 2^16 stay on the kernel instead of tripping an
in-graph exact-path fallback. ``dm1`` is exact int32 (d < 2^31). The
scalar total accumulates in f32 and is exact only below 2^24 — exact
global counts are obtained by recombining the limb arrays in the count
dtype (``repro.core.count._combine_limbs``), which is exactly what
``engine="pallas"`` does; tests compare the scalar with rtol.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["butterfly_combine_pallas", "choose2_limbs", "TN"]

TN = 1024
_ROWS = TN // 128  # one block = (_ROWS, 128) lanes


def choose2_limbs(d: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Exact C(d, 2) = d * (d - 1) / 2 for int32 ``d`` in [0, 2^31), as
    (lo, hi) int32 limbs of the 64-bit result.

    16-bit-limb schoolbook multiply in uint32: no partial product or
    limb sum ever wraps (a, c < 2^15; b, f < 2^16), and the product
    d * (d - 1) is even, so the 64-bit halving is a cross-limb shift.
    Runs identically inside Pallas kernels (VPU uint32 ops) and in
    plain jnp — ``ref.butterfly_combine_ref`` and the ``mode="all"``
    engine share it.
    """
    du = d.astype(jnp.uint32)
    eu = du - jnp.uint32(1)  # callers mask d == 0; wraps harmlessly there
    a, b = du >> 16, du & jnp.uint32(0xFFFF)
    c, f = eu >> 16, eu & jnp.uint32(0xFFFF)
    bf = b * f
    mid = a * f + b * c  # < 2^32: a, c < 2^15 so each term < 2^31
    lo = bf + ((mid & jnp.uint32(0xFFFF)) << 16)
    carry = (lo < bf).astype(jnp.uint32)
    hi = a * c + (mid >> 16) + carry
    c2_lo = (lo >> 1) | ((hi & jnp.uint32(1)) << 31)
    c2_hi = hi >> 1
    return c2_lo.astype(jnp.int32), c2_hi.astype(jnp.int32)


def _u32_as_f32(x: jax.Array) -> jax.Array:
    """f32 value of an int32 array read as uint32, through int32 casts
    only (Mosaic has no uint32 -> float32 conversion)."""
    u = x.astype(jnp.uint32)
    half = (u >> 1).astype(jnp.int32).astype(jnp.float32)
    return half * jnp.float32(2.0) + (u & jnp.uint32(1)).astype(
        jnp.int32
    ).astype(jnp.float32)


def _combine_kernel(d_ref, rep_ref, valid_ref, dm1_ref, lo_ref, hi_ref, tot_ref):
    k = pl.program_id(0)
    d = d_ref[...].astype(jnp.int32)
    rep = rep_ref[...] > 0
    valid = valid_ref[...] > 0
    live = valid & (d > 0)
    dm1 = jnp.where(live, d - 1, 0)
    lo, hi = choose2_limbs(jnp.where(live & rep, d, 0))
    dm1_ref[...] = dm1
    lo_ref[...] = lo
    hi_ref[...] = hi
    part = (
        jnp.sum(_u32_as_f32(lo))
        + jnp.sum(hi.astype(jnp.float32)) * jnp.float32(2.0**32)
    ).reshape(1, 1)

    @pl.when(k == 0)
    def _init():
        tot_ref[...] = jnp.zeros_like(tot_ref)

    tot_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def butterfly_combine_pallas(
    d: jax.Array,
    rep: jax.Array,
    valid: jax.Array,
    interpret: bool = True,
):
    """Returns (dm1 int32 (n,), c2_lo int32 (n,), c2_hi int32 (n,),
    total float32 ()). ``c2_lo``/``c2_hi`` are the 64-bit C(d, 2) limbs
    (lo is the low word's bit pattern)."""
    n = d.shape[0]
    n_pad = ((n + TN - 1) // TN) * TN
    rows = n_pad // 128

    def lanes(x):
        return jnp.pad(x.astype(jnp.int32), (0, n_pad - n)).reshape(rows, 128)

    block = pl.BlockSpec((_ROWS, 128), lambda k: (k, 0))
    plane = jax.ShapeDtypeStruct((rows, 128), jnp.int32)
    with jax.enable_x64(False):  # Mosaic lowers 32-bit index math only
        dm1, lo, hi, tot = pl.pallas_call(
            _combine_kernel,
            grid=(n_pad // TN,),
            in_specs=[block, block, block],
            out_specs=[
                block, block, block, pl.BlockSpec((1, 1), lambda k: (0, 0)),
            ],
            out_shape=[
                plane, plane, plane, jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="butterfly_combine",
        )(lanes(d), lanes(rep), lanes(valid))
    return (
        dm1.reshape(-1)[:n], lo.reshape(-1)[:n], hi.reshape(-1)[:n],
        tot[0, 0],
    )

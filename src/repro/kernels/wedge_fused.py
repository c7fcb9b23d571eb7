"""Pallas TPU kernel: in-VMEM endpoint-pair aggregation of wedge tiles.

The hot step of zero-materialization fused counting
(``engine="fused_pallas"``). The executor (``core.pipeline``) cuts the
flat wedge space into *vertex-aligned* tiles, recovers each tile's
wedges in XLA (the ``wedges_at`` binary search and CSR gathers), and
hands this kernel only the tile's two endpoint key lanes. Per tile the
kernel

  1. aggregates the endpoint-pair groups with an all-pairs key match on
     the MXU: the group multiplicity ``d`` of a lane is the number of
     lanes with the same ``(x1, x2)``, and the lane is its group's
     representative when no earlier lane matches it;
  2. applies the C(d, 2) combine in-register, emitting per lane
     ``dm1 = d - 1`` (the wedge's center / edge contribution) and
     ``c2 = C(d, 2)`` on representatives (the endpoints' contribution,
     0 elsewhere).

Scattering those lanes into the per-vertex / per-edge accumulators, and
summing ``c2`` into the global total, is XLA's job in the executor, so
nothing here scales with n, m or the wedge total W: live memory is the
tile's key lanes and one ``(TC, T)`` match panel.

Tile-alignment invariant (shared with ``wedges.plan_wedge_chunks``):
flat wedge ids follow CSR slot order, so all wedges produced by one
iterating endpoint are contiguous, and every endpoint-pair group lives
entirely inside its iterating endpoint's range. Tile boundaries are
therefore cut only at vertex boundaries: no group ever spans a tile,
and per-tile aggregation is exact.

Layout: keys enter twice, as ``(n_tiles * T, 1)`` columns (blocked
``(T, 1)``) and as ``(n_tiles, 1, T)`` rows (blocked ``(1, 1, T)``), so
the match panel ``column == row`` is a plain broadcast; the per-lane
outputs are lane-dense ``(1, 1, T)`` rows. Invalid lanes carry the keys
``(-1, -2)``, which never equal a real vertex id.

Precision contract (all outputs exact): the match panels are 0/1 in
bf16 with f32 accumulation, so ``d`` and the earlier-match count are
exact for any tile below 2^24 lanes; ``tile_cap <= MAX_TILE_CAP``
(4096) keeps ``d * (d - 1)`` below 2^24 in int32. Enforced at trace
time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["match_tiles_pallas", "MAX_TILE_CAP", "TC"]

MAX_TILE_CAP = 4096  # keeps every in-tile count exact (see contract)
TC = 128  # match-panel rows per step (a (TC, T) panel lives in VMEM)

def _bf16_01(mask):
    """0/1 bf16 panel of a mask: select in f32 (a bf16 select would need
    the mask relaid to bf16's packed tiling) with typed constants (under
    x64 a bare 1.0 is float64, which Mosaic cannot convert)."""
    return jnp.where(mask, jnp.float32(1), jnp.float32(0)).astype(
        jnp.bfloat16
    )


def _match_kernel(acol_ref, bcol_ref, arow_ref, brow_ref, dm1_ref, c2_ref):
    T = arow_ref.shape[-1]
    arow = arow_ref[0]  # (1, T)
    brow = brow_ref[0]
    ones = jnp.ones((8, TC), jnp.bfloat16)

    def body(c, carry):
        d8, lt8 = carry
        c0 = pl.multiple_of(c.astype(jnp.int32) * TC, TC)
        acol = acol_ref[pl.ds(c0, TC), :]  # (TC, 1)
        bcol = bcol_ref[pl.ds(c0, TC), :]
        match = (acol == arow) & (bcol == brow)  # (TC, T): lane j vs i
        j = jax.lax.broadcasted_iota(jnp.int32, (TC, T), 0) + c0
        i = jax.lax.broadcasted_iota(jnp.int32, (TC, T), 1)
        m = _bf16_01(match)
        lt = _bf16_01(match & (j < i))
        dims = (((1,), (0,)), ((), ()))
        d8 = d8 + jax.lax.dot_general(
            ones, m, dims, preferred_element_type=jnp.float32
        )
        lt8 = lt8 + jax.lax.dot_general(
            ones, lt, dims, preferred_element_type=jnp.float32
        )
        return d8, lt8

    zero = jnp.zeros((8, T), jnp.float32)
    d8, lt8 = jax.lax.fori_loop(0, T // TC, body, (zero, zero))
    d = d8[0:1, :].astype(jnp.int32)  # rows identical; exact (< 2^24)
    valid = arow >= 0
    rep = valid & (lt8[0:1, :].astype(jnp.int32) == 0)
    dm1_ref[0] = jnp.where(valid, d - 1, 0)
    c2_ref[0] = jnp.where(rep, (d * (d - 1)) >> 1, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def match_tiles_pallas(ka: jax.Array, kb: jax.Array, interpret: bool = True):
    """Per-lane ``(dm1, c2)`` for a batch of wedge tiles.

    ``ka``/``kb`` are ``(n_tiles, T)`` int32 endpoint keys (x1, x2) of
    each tile's lanes, ``(-1, -2)`` on invalid lanes. Returns two
    ``(n_tiles, T)`` int32 arrays: ``d - 1`` on valid lanes and C(d, 2)
    on each group's first lane (0 elsewhere).
    """
    n_tiles, T = ka.shape
    if T % TC != 0:
        raise ValueError(
            f"tile width must be a multiple of TC={TC}, got {T} — the "
            "match-panel loop requires it (callers pad the planned tile "
            "cap up; see core.pipeline)"
        )
    if T > MAX_TILE_CAP:
        raise ValueError(
            f"tile width {T} exceeds MAX_TILE_CAP {MAX_TILE_CAP} — the "
            "in-tile counts would lose exactness; plan such vertices "
            "onto the XLA tiles of engine='fused'"
        )
    ka = ka.astype(jnp.int32)
    kb = kb.astype(jnp.int32)
    col = pl.BlockSpec((T, 1), lambda t: (t, 0))
    row = pl.BlockSpec((1, 1, T), lambda t: (t, 0, 0))
    lanes = jax.ShapeDtypeStruct((n_tiles, 1, T), jnp.int32)
    with jax.enable_x64(False):  # Mosaic lowers 32-bit index math only
        dm1, c2 = pl.pallas_call(
            _match_kernel,
            grid=(n_tiles,),
            in_specs=[col, col, row, row],
            out_specs=[row, row],
            out_shape=[lanes, lanes],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
            name="wedge_fused",
        )(
            ka.reshape(n_tiles * T, 1),
            kb.reshape(n_tiles * T, 1),
            ka.reshape(n_tiles, 1, T),
            kb.reshape(n_tiles, 1, T),
        )
    return dm1.reshape(n_tiles, T), c2.reshape(n_tiles, T)

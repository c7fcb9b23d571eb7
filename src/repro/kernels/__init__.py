"""Pallas TPU kernels for the butterfly counting/peeling hot paths.

Five kernels cover the paper-identified compute hot spots, each with a
pure-jnp oracle in ``ref`` and a backend-aware dispatcher in ``ops``:

  - ``wedge_count.wedge_histogram_pallas`` — one-hot MXU histogram
    (hash/dense wedge aggregation),
  - ``butterfly_combine.butterfly_combine_pallas`` — d -> (d-1, C(d,2))
    contribution transform (64-bit C(d,2) as two int32 limbs),
  - ``bucket_min.bucket_min_pallas`` — masked min-reduction (peeling
    extract-min),
  - ``bucket_update.bucket_update_pallas`` — Julienne-style batched
    decrease-key: apply one round's support decrements and re-derive
    the masked min + geometric bucket occupancy in the same pass,
  - ``wedge_fused.match_tiles_pallas`` — the aggregation step of
    zero-materialization fused counting: per vertex-aligned wedge tile,
    match endpoint pairs on the MXU and emit each lane's d - 1 and its
    group's C(d, 2) (the executor in ``core.pipeline`` recovers the
    tiles and scatters the lanes, so the global wedge array is never
    materialized).

The counting engine (``repro.core.count`` with ``engine="pallas"`` /
``engine="fused_pallas"``) and the peeling engines (``repro.core.peel``
``engine="device"``) consume them through the ``ops`` wrappers, which
pick interpret mode automatically off the backend.
"""
from .ops import (
    bucket_min,
    bucket_update,
    butterfly_combine,
    interpret_default,
    match_tiles,
    wedge_histogram,
)

__all__ = [
    "bucket_min",
    "bucket_update",
    "butterfly_combine",
    "interpret_default",
    "match_tiles",
    "wedge_histogram",
]

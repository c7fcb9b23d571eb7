"""Job kind ``count``: exact butterfly counts of the whole graph.

One job is ``count_butterflies(g, count_dtype=int64, **args)``, the
program's public entry point, from the edge list to host arrays. The
comparison is exact: the total, and the entries of ``per_u``, ``per_v``
and ``per_edge`` that differ from the reference, each with the limit 0.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import count_reference

LIMITS = {"total_gap": 0, "per_u_wrong": 0, "per_v_wrong": 0,
          "per_edge_wrong": 0}
# the control: the reference with its counts accumulated in float32, the
# native width of the chip's scatter-adds and of its matrix unit's sums
CONTROL = "float32"


def run(g, args: dict):
    import jax.numpy as jnp
    from repro.core import count_butterflies

    return count_butterflies(g, count_dtype=jnp.int64, **args)


def reference(config: dict, edges: np.ndarray, acc: str = "int64") -> dict:
    return count_reference(config["n_u"], config["n_v"], edges, acc=acc)


def _wrong(got, want) -> int:
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def readings(answer, ref: dict) -> dict:
    """The compared numbers of one answer (a result with the fields of
    ``CountResult``, or a reference dict)."""
    get = answer.get if isinstance(answer, dict) else answer._asdict().get
    return {
        "total_gap": int(abs(int(get("total")) - int(ref["total"]))),
        "per_u_wrong": _wrong(get("per_u"), ref["per_u"]),
        "per_v_wrong": _wrong(get("per_v"), ref["per_v"]),
        "per_edge_wrong": _wrong(get("per_edge"), ref["per_edge"]),
    }

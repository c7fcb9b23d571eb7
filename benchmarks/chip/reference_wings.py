"""Plain reference for the benchmark's wing decompositions, in numpy only.

Nothing here imports the program. ``wings_reference`` takes the edge
list as the run hands it to the program and answers per edge, aligned
with its rows.

The per-edge counts are ``reference.count_reference``'s. Then it peels
in sub-rounds: each raises the threshold κ to the least count still
alive and peels every alive edge at or under it. Every butterfly whose
four edges were alive before the sub-round and that holds a peeled edge
is found from each peeled edge ``(u1, v1)`` in it: a second vertex
``u2`` on ``v1``, and a ``v2`` on both ``u1`` and ``u2``, scanned from
the one of the two with the smaller degree and looked up on the other.
A butterfly found from several of its peeled edges is kept once, by its
canonical key ``(min u, max u, min v, max v)``, and each of its edges
that survives the sub-round loses one.

``acc`` names the arithmetic: ``"int64"`` is the reference;
``"bfloat16-counts"`` rounds the per-edge counts to bfloat16: the
lower-precision control, kept here so that the comparison is shown to
catch it. (Rounding each sub-round's decrements instead changes nothing
on condmat: none reaches 256, below which bfloat16 holds integers.)
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import _bit_length, _round_bf16, count_reference

__all__ = ["wings_reference"]


def _csr(rows: np.ndarray, cols: np.ndarray, n: int):
    """``(off, cols, edge ids)`` of the edges grouped by ``rows``."""
    order = np.argsort(rows, kind="stable")
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=off[1:])
    return off, cols[order], order


def _segments(off: np.ndarray, rows: np.ndarray):
    """``(segment, slot)`` of every CSR slot of each row in ``rows``."""
    lens = off[rows + 1] - off[rows]
    seg = np.repeat(np.arange(rows.shape[0]), lens)
    first = np.cumsum(lens) - lens
    return seg, off[rows][seg] + np.arange(seg.shape[0]) - first[seg]


def wings_reference(n_u: int, n_v: int, edges: np.ndarray,
                    acc: str = "int64") -> dict:
    """Wing decomposition: ``numbers`` (one per edge), ``counts`` (its
    per-edge butterflies), ``rounds`` (sub-rounds: distinct thresholds),
    ``step`` (each edge's sub-round, from 0), ``triples`` (each edge's
    candidate triples, ``sum over u2 on v1 of min(deg u1, deg u2)``
    with ``u2 == u1`` included, the space a peeled edge streams), and
    the work of the count updates over all sub-rounds: ``decrements``
    (survivors whose count fell in a sub-round) and ``moves`` (those
    whose geometric bucket, the count's bit length, changed)."""
    if acc not in ("int64", "bfloat16-counts"):
        raise ValueError(f"unknown arithmetic {acc!r}")
    e = np.asarray(edges, np.int64)
    m = e.shape[0]
    if n_u * n_u * n_v * n_v >= 2**63:
        raise ValueError("butterfly keys do not fit 64 bits")
    counts = count_reference(n_u, n_v, e)["per_edge"].astype(np.int64)
    b = _round_bf16(counts) if acc == "bfloat16-counts" else counts.copy()
    u, v = e[:, 0], e[:, 1]
    u_off, u_nbr, u_eid = _csr(u, v, n_u)  # N(u): its v's
    v_off, v_nbr, v_eid = _csr(v, u, n_v)  # N(v): its u's
    deg_u = np.diff(u_off)
    seg, slot = _segments(v_off, v)
    triples = np.bincount(seg, minlength=m,
                          weights=np.minimum(deg_u[u[seg]], deg_u[v_nbr[slot]])
                          ).astype(np.int64)
    key = u * n_v + v
    by_key = np.argsort(key)
    sorted_key = key[by_key]

    alive = np.ones(m, bool)
    numbers = np.zeros(m, np.int64)
    step = np.zeros(m, np.int64)
    kappa = rounds = decrements = moves = 0
    while alive.any():
        kappa = max(kappa, int(b[alive].min()))
        peel = alive & (b <= kappa)
        p_ids = np.flatnonzero(peel)
        numbers[p_ids] = kappa
        step[p_ids] = rounds
        rounds += 1
        # (peeled edge a = (u1, v1), u2 on v1 by edge bb) with bb alive
        seg, slot = _segments(v_off, v[p_ids])
        a, u2, bb = p_ids[seg], v_nbr[slot], v_eid[slot]
        keep = alive[bb] & (u2 != u[a])
        a, u2, bb = a[keep], u2[keep], bb[keep]
        u1, v1 = u[a], v[a]
        small_is_u1 = deg_u[u1] <= deg_u[u2]
        small = np.where(small_is_u1, u1, u2)
        other = np.where(small_is_u1, u2, u1)
        # v2 on the smaller one, looked up on the other
        seg, slot = _segments(u_off, small)
        v2, e_small = u_nbr[slot], u_eid[slot]
        want = other[seg] * n_v + v2
        pos = np.minimum(np.searchsorted(sorted_key, want), m - 1)
        e_other = by_key[pos]
        ok = ((sorted_key[pos] == want) & (v2 != v1[seg])
              & alive[e_small] & alive[e_other])
        seg, v2 = seg[ok], v2[ok]
        e_small, e_other = e_small[ok], e_other[ok]
        lo_u = np.minimum(u1[seg], u2[seg])
        hi_u = np.maximum(u1[seg], u2[seg])
        lo_v = np.minimum(v1[seg], v2)
        hi_v = np.maximum(v1[seg], v2)
        bkey = ((lo_u * n_u + hi_u) * n_v + lo_v) * n_v + hi_v
        _, first = np.unique(bkey, return_index=True)
        quads = np.concatenate([a[seg][first], bb[seg][first],
                                e_small[first], e_other[first]])
        alive &= ~peel
        dec = np.bincount(quads[alive[quads]], minlength=m)
        hit = dec > 0
        before = _bit_length(b[hit])
        b -= dec
        decrements += int(hit.sum())
        moves += int((_bit_length(b[hit]) != before).sum())
    return {"numbers": numbers, "counts": counts, "rounds": rounds,
            "step": step, "triples": triples, "decrements": decrements,
            "moves": moves}

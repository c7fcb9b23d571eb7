"""Dense numpy oracle for butterfly counts (tests + kernel validation).

O(n_u^2 n_v) — only for small graphs.
"""
from __future__ import annotations

import numpy as np

from .graph import BipartiteGraph

__all__ = [
    "adjacency",
    "global_count",
    "per_vertex_counts",
    "per_edge_counts",
    "wing_numbers",
]


def adjacency(g: BipartiteGraph) -> np.ndarray:
    a = np.zeros((g.n_u, g.n_v), dtype=np.int64)
    a[g.edges[:, 0], g.edges[:, 1]] = 1
    return a


def _choose2(x: np.ndarray) -> np.ndarray:
    return x * (x - 1) // 2


def global_count(g: BipartiteGraph) -> int:
    a = adjacency(g)
    m = a @ a.T  # |N(u1) ∩ N(u2)|
    iu = np.triu_indices(g.n_u, k=1)
    return int(_choose2(m[iu]).sum())


def per_vertex_counts(g: BipartiteGraph) -> tuple[np.ndarray, np.ndarray]:
    a = adjacency(g)
    mu = a @ a.T
    np.fill_diagonal(mu, 0)
    per_u = _choose2(mu).sum(axis=1)
    mv = a.T @ a
    np.fill_diagonal(mv, 0)
    per_v = _choose2(mv).sum(axis=1)
    return per_u, per_v


def per_edge_counts(g: BipartiteGraph) -> np.ndarray:
    a = adjacency(g)
    mu = a @ a.T  # (n_u, n_u)
    out = np.zeros(g.m, dtype=np.int64)
    for i, (u, v) in enumerate(g.edges):
        nbrs = np.flatnonzero(a[:, v])
        nbrs = nbrs[nbrs != u]
        out[i] = int((mu[u, nbrs] - 1).sum())
    return out


def wing_numbers(g: BipartiteGraph) -> tuple[np.ndarray, np.ndarray]:
    """Wing number of every edge (aligned with ``g.edges``) and the
    least alive count at the start of each sub-round.

    Counts start from :func:`per_edge_counts`. Each sub-round takes
    κ = max(κ, least alive count) and peels every alive edge at or below
    κ. Every butterfly whose four edges were alive before the sub-round
    and that holds a peeled edge is found once, by its canonical
    ``(u1 < u2, v1 < v2)`` key, and each of its surviving edges loses
    one. The exact rounds are the sub-rounds; a range round opens at
    the first sub-round whose least count reaches ``2 ** bit_length`` of
    the one that opened the round before.
    """
    counts = per_edge_counts(g)
    eid = np.full((g.n_u, g.n_v), -1, dtype=np.int64)
    eid[g.edges[:, 0], g.edges[:, 1]] = np.arange(g.m)
    alive = np.ones(g.m, dtype=bool)
    wing = np.zeros(g.m, dtype=np.int64)
    mins = []
    kappa = 0
    while alive.any():
        mins.append(int(counts[alive].min()))
        kappa = max(kappa, mins[-1])
        peel = alive & (counts <= kappa)
        wing[peel] = kappa
        live = (eid >= 0) & alive[np.maximum(eid, 0)]  # alive before
        keys = []
        for u1, v1 in g.edges[peel]:
            u2s = np.flatnonzero(live[:, v1])
            u2s = u2s[u2s != u1]
            rows, v2 = np.nonzero(live[u2s] & live[u1])
            keep = v2 != v1
            u2, v2 = u2s[rows[keep]], v2[keep]
            lo_u, hi_u = np.minimum(u1, u2), np.maximum(u1, u2)
            lo_v, hi_v = np.minimum(v1, v2), np.maximum(v1, v2)
            keys.append(np.stack([lo_u, hi_u, lo_v, hi_v], axis=1))
        alive &= ~peel
        found = np.unique(np.concatenate(keys), axis=0)
        for ui, vi in ((0, 2), (0, 3), (1, 2), (1, 3)):
            x = eid[found[:, ui], found[:, vi]]
            np.subtract.at(counts, x, alive[x])
    return wing, np.asarray(mins, dtype=np.int64)

"""The benchmark's own graph generator: a fixed copy of the yardstick.

``powerlaw_edges`` is the Chung-Lu bipartite generator of the program's
``data/graphs.py`` (``powerlaw_bipartite``), copied so that a later
change to the program cannot change the graphs the benchmark measures.
Its first draw is the original's, edge for edge; it then tops the draw
up until the published number of *unique* edges is reached, because a
plain draw of ``m`` edges collapses under duplicates.

A configuration fixes the graph's structure (``graph_seed``), as a
deployment's graph is fixed; the run's ``--seed`` only relabels it
(``relabel``): a permutation of the U ids, of the V ids and of the edge
order. Every seed therefore hands the program the same work, in another
layout.
"""
from __future__ import annotations

import numpy as np

__all__ = ["powerlaw_edges", "relabel", "build_edges"]


def _zipf_probs(n: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1))
    return w / w.sum()


def _first_unique(keys: np.ndarray) -> np.ndarray:
    """Indices of the first occurrence of each key, in draw order."""
    _, idx = np.unique(keys, return_index=True)
    return np.sort(idx)


def powerlaw_edges(n_u: int, n_v: int, m_unique: int, alpha_u: float,
                   alpha_v: float, seed: int) -> np.ndarray:
    """``(m_unique, 2)`` int64 unique edges with Zipf expected degrees.

    The first ``m_unique`` draws are exactly ``powerlaw_bipartite(n_u,
    n_v, m_unique, alpha_u, alpha_v, seed)``'s; further batches from the
    same distribution follow until ``m_unique`` distinct edges exist,
    and the first ``m_unique`` distinct edges in draw order are kept.
    """
    if m_unique > n_u * n_v:
        raise ValueError(f"{m_unique} unique edges do not fit {n_u} x {n_v}")
    rng = np.random.default_rng(seed)
    pu, pv = _zipf_probs(n_u, alpha_u), _zipf_probs(n_v, alpha_v)
    us = rng.choice(n_u, size=m_unique, p=pu)
    vs = rng.choice(n_v, size=m_unique, p=pv)
    perm_u = rng.permutation(n_u)
    perm_v = rng.permutation(n_v)
    keys = perm_u[us].astype(np.int64) * n_v + perm_v[vs]
    keys = keys[_first_unique(keys)]
    while keys.shape[0] < m_unique:
        extra = 2 * (m_unique - keys.shape[0]) + 1024
        more = (perm_u[rng.choice(n_u, size=extra, p=pu)].astype(np.int64)
                * n_v + perm_v[rng.choice(n_v, size=extra, p=pv)])
        keys = np.concatenate([keys, more])
        keys = keys[_first_unique(keys)]
    keys = keys[:m_unique]
    return np.stack([keys // n_v, keys % n_v], axis=1)


def _tie_preserving_permutation(deg: np.ndarray,
                                rng: np.random.Generator) -> np.ndarray:
    """A random permutation of ids that keeps the order of the ids within
    each degree class: only which ids a class holds changes."""
    n = deg.shape[0]
    perm = rng.permutation(n)
    members = np.lexsort((np.arange(n), deg))  # by degree, then old id
    targets = np.lexsort((perm, deg))  # by degree, then new id
    out = np.empty(n, np.int64)
    out[members] = perm[targets]
    return out


def relabel(edges: np.ndarray, n_u: int, n_v: int, seed: int) -> np.ndarray:
    """The same graph under the run's labelling: U ids, V ids and the
    edge order permuted from ``seed`` (any integer).

    Ids are permuted within each side so that vertices of equal degree
    keep their relative order. A ranking by degree with ties broken by
    id then meets the same graph in rank space on every seed, so every
    seed hands the program the same work and the same shapes, and only
    the first run of a cell compiles.
    """
    rng = np.random.default_rng(seed % (1 << 64))
    e = np.asarray(edges, np.int64)
    perm_u = _tie_preserving_permutation(np.bincount(e[:, 0], minlength=n_u), rng)
    perm_v = _tie_preserving_permutation(np.bincount(e[:, 1], minlength=n_v), rng)
    e = e[rng.permutation(e.shape[0])]
    return np.stack([perm_u[e[:, 0]], perm_v[e[:, 1]]], axis=1)


def build_edges(config: dict, seed: int) -> np.ndarray:
    """The configuration's graph under the run's labelling."""
    base = powerlaw_edges(
        config["n_u"], config["n_v"], config["m"], config["alpha_u"],
        config["alpha_v"], config["graph_seed"],
    )
    return relabel(base, config["n_u"], config["n_v"], seed)

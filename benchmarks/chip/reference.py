"""Plain references for the benchmark's jobs, in numpy and scipy only.

Nothing here imports the program. Both functions take the edge list as
the run hands it to the program and answer in the same numbering.

``count_reference`` enumerates every wedge once from its first endpoint
in degree order (the highest-degree vertex of the wedge's three,
Chiba-Nishizeki style), groups the wedges by their endpoint pair, and
hands each group of ``d`` wedges its ``C(d, 2)`` butterflies: to both
endpoints and the total, and ``d - 1`` to each centre and to both edges
of each wedge. Every butterfly has exactly one vertex of lowest rank and
is counted once, through that vertex's endpoint pair.

``tips_reference`` builds the peeled side's co-occurrence matrix
``B = A A^T`` with scipy, takes ``C(B[u1, u2], 2)`` as the butterflies a
pair shares, and peels: each round raises the threshold to the least
count still alive, gives every alive vertex at or under it that number,
and subtracts from the survivors what they shared with the peeled ones.

``acc`` names the arithmetic the counts are kept in: ``"int64"`` is the
reference; ``"float32"`` (counts) and ``"bfloat16"`` (each round's
decrements) are the lower-precision controls, kept here so that the
comparison is shown to catch them.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

__all__ = ["count_reference", "tips_reference", "peeled_side"]


def _as_acc(x: np.ndarray, acc: str) -> np.ndarray:
    return x.astype(np.float32 if acc == "float32" else np.int64)


def _segment_add(n: int, idx: np.ndarray, val: np.ndarray) -> np.ndarray:
    out = np.zeros(n, val.dtype)
    np.add.at(out, idx, val)
    return out


def count_reference(n_u: int, n_v: int, edges: np.ndarray,
                    acc: str = "int64") -> dict:
    """Exact butterfly counts: ``total``, ``per_u``, ``per_v`` and
    ``per_edge`` (aligned with the rows of ``edges``, which must be
    unique)."""
    e = np.asarray(edges, np.int64)
    m, n = e.shape[0], n_u + n_v
    src = np.concatenate([e[:, 0], n_u + e[:, 1]])
    dst = np.concatenate([n_u + e[:, 1], e[:, 0]])
    eid = np.concatenate([np.arange(m), np.arange(m)])
    deg = np.bincount(src, minlength=n)
    order = np.lexsort((np.arange(n), -deg))  # degree descending
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    rs, rd = rank[src], rank[dst]
    p = np.lexsort((rd, rs))
    rs, rd, eid = rs[p], rd[p], eid[p]
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rs, minlength=n), out=off[1:])
    comp = rs * n + rd  # ascending
    # wedge x1 - y - x2 with x1 of lowest rank: first slot (x1, y) with
    # y > x1, then every slot (y, x2) with x2 > x1 (a suffix of y's list)
    s1 = np.flatnonzero(rd > rs)
    x1, y = rs[s1], rd[s1]
    lo = np.searchsorted(comp, y * n + x1, side="right")
    cnt = off[y + 1] - lo
    w = int(cnt.sum())
    seg = np.repeat(np.arange(s1.shape[0]), cnt)
    first = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    s2 = lo[seg] + (np.arange(w) - first[seg])
    wx1, wy, wx2 = x1[seg], y[seg], rd[s2]
    keys, inv, d = np.unique(wx1 * n + wx2, return_inverse=True,
                             return_counts=True)
    c2 = _as_acc(d * (d - 1) // 2, acc)
    dm1 = _as_acc(d[inv] - 1, acc)
    per_rank = (_segment_add(n, keys // n, c2) + _segment_add(n, keys % n, c2)
                + _segment_add(n, wy, dm1))
    per_edge = _segment_add(m, eid[s1][seg], dm1) + _segment_add(m, eid[s2], dm1)
    return {
        "total": c2.sum(dtype=c2.dtype),
        "per_u": per_rank[rank[:n_u]],
        "per_v": per_rank[rank[n_u:]],
        "per_edge": per_edge,
    }


def peeled_side(n_u: int, n_v: int, edges: np.ndarray) -> int:
    """The side with fewer wedges that have their endpoints on it
    (U on a tie): 0 for U, 1 for V."""
    e = np.asarray(edges, np.int64)
    du = np.bincount(e[:, 0], minlength=n_u)
    dv = np.bincount(e[:, 1], minlength=n_v)
    w_u = int((dv * (dv - 1) // 2).sum())  # endpoints in U, centres in V
    w_v = int((du * (du - 1) // 2).sum())
    return 0 if w_u <= w_v else 1


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """Non-negative integers rounded to bfloat16's 8 significant bits
    (to nearest, ties to even), back as int64."""
    _, ex = np.frexp(x.astype(np.float64))
    q = np.exp2(np.maximum(ex - 8, 0))
    return (np.round(x / q) * q).astype(np.int64)


def _bit_length(x: np.ndarray) -> np.ndarray:
    return np.frexp(np.maximum(x, 0).astype(np.float64))[1]


def tips_reference(n_u: int, n_v: int, edges: np.ndarray,
                   acc: str = "int64") -> dict:
    """Tip decomposition of the side :func:`peeled_side` picks:
    ``side``, ``numbers`` (one per vertex of that side, in the input's
    numbering), ``counts`` (its per-vertex butterflies), ``rounds``
    (distinct thresholds), and the work of the count updates over all
    rounds: ``decrements`` (survivors whose count fell in a round) and
    ``moves`` (those whose geometric bucket, the count's bit length,
    changed). ``acc="bfloat16"`` rounds each round's decrements to
    bfloat16: the control."""
    e = np.asarray(edges, np.int64)
    side = peeled_side(n_u, n_v, e)
    n_side, n_other = (n_u, n_v) if side == 0 else (n_v, n_u)
    a = sp.csr_matrix(
        (np.ones(e.shape[0], np.int64), (e[:, side], e[:, 1 - side])),
        shape=(n_side, n_other),
    )
    co = (a @ a.T).tocsr()
    co.setdiag(0)
    co.eliminate_zeros()
    share = co.copy()
    share.data = share.data * (share.data - 1) // 2
    share.eliminate_zeros()
    counts = np.asarray(share.sum(axis=1)).ravel().astype(np.int64)
    b = counts.copy()
    alive = np.ones(n_side, bool)
    out = np.zeros(n_side, np.int64)
    kappa, rounds, decrements, moves = 0, 0, 0, 0
    while alive.any():
        kappa = max(kappa, int(b[alive].min()))
        peel = alive & (b <= kappa)
        out[peel] = kappa
        alive &= ~peel
        rounds += 1
        dec = np.asarray(share[np.flatnonzero(peel)].sum(axis=0)).ravel()
        if acc == "bfloat16":
            dec = _round_bf16(dec)
        hit = alive & (dec > 0)
        before = _bit_length(b[hit])
        b -= dec.astype(np.int64)
        decrements += int(hit.sum())
        moves += int((_bit_length(b[hit]) != before).sum())
    return {"side": side, "numbers": out, "counts": counts, "rounds": rounds,
            "decrements": decrements, "moves": moves}

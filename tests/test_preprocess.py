"""Host preprocessing: the CSR sort, the twin-slot map, and the per-slot
wedge counts derived from it, each against a plain composite-key
reference; and the wedge counts computed once per count job."""
import numpy as np
import pytest

import repro.core.count as count_mod
import repro.core.pipeline as pipeline_mod
import repro.core.wedges as wedges_mod
from repro.core import BipartiteGraph, count_butterflies, make_order, preprocess
from repro.core.wedges import host_wedge_counts
from repro.testing import faults


def _random(nu, nv, m, seed, on_duplicate="dedupe"):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e, on_duplicate=on_duplicate)


def _star(leaves):
    e = np.stack([np.zeros(leaves, np.int64), np.arange(leaves)], axis=1)
    return BipartiteGraph(1, leaves, e)


GRAPHS = {
    "random-small": lambda: _random(14, 11, 45, 0),
    "random-dense": lambda: _random(20, 15, 200, 1),
    "random-sparse": lambda: _random(60, 50, 90, 2),
    # most vertices of both sides have no edge
    "isolated": lambda: BipartiteGraph(
        40, 30, np.array([[0, 0], [0, 1], [3, 1], [3, 0], [7, 29]])),
    "lopsided-u": lambda: _random(300, 4, 400, 3),
    "lopsided-v": lambda: _random(3, 250, 300, 4),
    "star": lambda: _star(25),
    "empty": lambda: BipartiteGraph(5, 4, np.zeros((0, 2), np.int64)),
}


def _reference_wedge_counts(rg, direction):
    """Per-slot wedge counts by global binary search: CSR slots are
    sorted by (src, dst), so a search within N(d) is a search of the
    composite key ``src * (n_pad + 1) + dst``."""
    src = rg.edge_src.astype(np.int64)
    dst = rg.neighbors.astype(np.int64)
    n_real = 2 * rg.m
    n_pad1 = np.int64(rg.n_pad + 1)
    off = rg.offsets.astype(np.int64)
    comp = src[:n_real] * n_pad1 + dst[:n_real]
    s, d = src[:n_real], dst[:n_real]
    cnt = np.zeros(src.shape[0], dtype=np.int64)
    if direction == "low":
        ub = np.searchsorted(comp, d * n_pad1 + s, side="right")
        cnt[:n_real] = np.where(
            d > s, off[np.minimum(d, rg.n_pad - 1) + 1] - ub, 0)
    else:
        lb = np.searchsorted(comp, d * n_pad1 + np.minimum(s, d), side="left")
        cnt[:n_real] = lb - off[np.minimum(d, rg.n_pad - 1)]
    return cnt


def _reference_csr(g, order):
    """offsets, neighbors, edge_src, undirected_id as ``np.lexsort``
    over (src, dst) orders the directed slots."""
    rank = np.empty(g.n, dtype=np.int64)
    rank[np.asarray(order)] = np.arange(g.n)
    ru, rv = rank[g.edges[:, 0]], rank[g.edges[:, 1] + g.n_u]
    src, dst = np.concatenate([ru, rv]), np.concatenate([rv, ru])
    uid = np.concatenate([np.arange(g.m), np.arange(g.m)])
    perm = np.lexsort((dst, src))
    return src[perm], dst[perm], uid[perm]


@pytest.mark.parametrize("order", ["degree", "side"])
@pytest.mark.parametrize("direction", ["low", "high"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_host_wedge_counts_match_binary_search(name, direction, order):
    g = GRAPHS[name]()
    rg = preprocess(g, make_order(g, order), order_name=order)
    got = host_wedge_counts(rg, direction)
    want = _reference_wedge_counts(rg, direction)
    assert got.dtype == np.int64 and got.shape == (rg.e_pad,)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_twin_is_the_reversed_slot(name):
    g = GRAPHS[name]()
    rg = preprocess(g, make_order(g, "degree"))
    n_real = 2 * rg.m
    twin = rg.twin[:n_real].astype(np.int64)
    k = np.arange(n_real)
    assert rg.twin.dtype == np.int32 and rg.twin.shape == (rg.e_pad,)
    assert (rg.twin[n_real:] == rg.e_pad).all()
    assert np.array_equal(twin[twin], k)
    assert not (twin == k).any()
    assert np.array_equal(rg.undirected_id[twin], rg.undirected_id[k])
    assert np.array_equal(rg.edge_src[twin], rg.neighbors[:n_real])
    assert np.array_equal(rg.neighbors[twin], rg.edge_src[:n_real])


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("on_duplicate", ["dedupe", "assume_unique"])
def test_preprocess_csr_matches_lexsort(on_duplicate, seed):
    """The composite-key sort orders slots as lexsort((dst, src)),
    including the duplicate edges an ``assume_unique`` graph keeps."""
    g = _random(25, 18, 160, seed, on_duplicate=on_duplicate)
    if on_duplicate == "assume_unique":
        assert np.unique(g.edges, axis=0).shape[0] < g.m  # has duplicates
    order = make_order(g, "degree")
    rg = preprocess(g, order)
    src, dst, uid = _reference_csr(g, order)
    n_real = 2 * g.m
    offsets = np.zeros(rg.n_pad + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=rg.n_pad), out=offsets[1:])
    assert np.array_equal(rg.offsets, offsets)
    assert np.array_equal(rg.neighbors[:n_real], dst)
    assert np.array_equal(rg.edge_src[:n_real], src)
    assert np.array_equal(rg.undirected_id[:n_real], uid)
    assert (rg.neighbors[n_real:] == rg.n_pad).all()
    assert (rg.undirected_id[n_real:] == g.m).all()


def _count_calls(monkeypatch):
    """Wrap every module-level reference to host_wedge_counts."""
    calls = []
    real = wedges_mod.host_wedge_counts

    def counted(rg, direction="low"):
        calls.append(direction)
        return real(rg, direction)

    for mod in (count_mod, pipeline_mod, wedges_mod):
        monkeypatch.setattr(mod, "host_wedge_counts", counted)
    return calls


@pytest.mark.parametrize("cache_opt", [False, True])
@pytest.mark.parametrize("engine", ["fused_pallas", "xla"])
def test_wedge_counts_once_per_count_job(monkeypatch, engine, cache_opt):
    g = _random(30, 20, 200, 5)
    clean = count_butterflies(g, mode="all", engine=engine,
                              cache_opt=cache_opt)
    calls = _count_calls(monkeypatch)
    r = count_butterflies(g, mode="all", engine=engine, cache_opt=cache_opt)
    assert calls == ["high" if cache_opt else "low"]
    assert int(r.total) == int(clean.total)
    assert np.array_equal(r.per_edge, clean.per_edge)


def test_wedge_counts_once_when_the_ladder_descends(monkeypatch):
    """A capacity fault sends the job down a rung: the rungs share the
    one set of wedge counts and the result stays bitwise-equal."""
    g = _random(30, 20, 200, 6)
    clean = count_butterflies(g, mode="all", engine="fused_pallas")
    calls = _count_calls(monkeypatch)
    with faults.inject("capacity_overflow", site="count.fused_pallas",
                       budget=1) as f:
        r = count_butterflies(g, mode="all", engine="fused_pallas")
    assert f.fired > 0
    assert r.report.degraded, r.report.summary()
    assert r.report.attempts[0].outcome == "capacity-overflow"
    assert calls == ["low"]
    for a, b in ((r.total, clean.total), (r.per_u, clean.per_u),
                 (r.per_v, clean.per_v), (r.per_edge, clean.per_edge)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

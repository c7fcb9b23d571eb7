"""Tip/wing decomposition vs a recompute-from-scratch oracle, the
device-resident peeling engine parity suite (engine="device" vs host vs
oracle), and the host Fibonacci heap (paper §5) unit tests."""
import inspect

import jax
import numpy as np
import pytest

from repro.core import BipartiteGraph
from repro.core.fibheap import BucketStructure, FibHeap
from repro.core.oracle import per_edge_counts, per_vertex_counts, wing_numbers
from repro.core.peel import peel_tips, peel_tips_stored, peel_wings
from repro.core.pipeline import record_programs
from repro.data.graphs import powerlaw_bipartite


def rand_graph(nu, nv, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e)


def oracle_tip(g, side):
    n_side = g.n_u if side == 0 else g.n_v
    alive = np.ones(n_side, bool)
    edges = g.edges.copy()
    tip = np.zeros(n_side, np.int64)
    kappa = 0
    while alive.any():
        sub = edges[np.isin(edges[:, side], np.flatnonzero(alive))]
        if len(sub) == 0:
            tip[alive] = kappa
            break
        gg = BipartiteGraph(g.n_u, g.n_v, sub)
        pu, pv = per_vertex_counts(gg)
        c = pu if side == 0 else pv
        cur = np.where(alive, c, np.iinfo(np.int64).max)
        kappa = max(kappa, int(cur.min()))
        peel = alive & (cur <= kappa)
        tip[peel] = kappa
        alive[peel] = False
        edges = edges[~np.isin(edges[:, side], np.flatnonzero(peel))]
    return tip


def oracle_wing(g):
    alive = np.ones(g.m, bool)
    wing = np.zeros(g.m, np.int64)
    kappa = 0
    while alive.any():
        gg = BipartiteGraph(g.n_u, g.n_v, g.edges[alive])
        pe = np.zeros(g.m, np.int64)
        pe[np.flatnonzero(alive)] = per_edge_counts(gg)
        cur = np.where(alive, pe, np.iinfo(np.int64).max)
        kappa = max(kappa, int(cur.min()))
        peel = alive & (cur <= kappa)
        wing[peel] = kappa
        alive[peel] = False
    return wing


def bicliques():
    """K(15,15) + K(20,20): the first peel round releases all 15
    vertices of degree 15 at once (225 level-1 slots)."""
    a = np.stack([np.repeat(np.arange(15), 15),
                  np.tile(np.arange(15), 15)], axis=1)
    b = np.stack([np.repeat(np.arange(20), 20) + 15,
                  np.tile(np.arange(20), 20) + 15], axis=1)
    return BipartiteGraph(35, 35, np.concatenate([a, b]))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("side", [0, 1])
def test_tip_decomposition(seed, side):
    g = rand_graph(10, 8, 30, seed)
    got = peel_tips(g, side=side)
    assert np.array_equal(got.numbers, oracle_tip(g, side))
    assert got.rounds == len(got.round_sizes)


def test_tip_hash_aggregation():
    g = rand_graph(12, 9, 36, 7)
    got = peel_tips(g, side=0, aggregation="hash")
    assert np.array_equal(got.numbers, oracle_tip(g, 0))


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("side", [0, 1])
def test_tip_stored_wedges_variant(seed, side):
    """WPEEL-V (stored wedges, Alg. 7) agrees with PEEL-V + oracle."""
    from repro.core.peel import peel_tips_stored

    g = rand_graph(11, 9, 32, seed)
    a = peel_tips(g, side=side)
    b = peel_tips_stored(g, side=side)
    assert np.array_equal(a.numbers, b.numbers)
    assert np.array_equal(b.numbers, oracle_tip(g, side))


@pytest.mark.parametrize("seed", range(4))
def test_wing_decomposition(seed):
    g = rand_graph(9, 8, 28, seed)
    got = peel_wings(g)
    assert np.array_equal(got.numbers, oracle_wing(g))


@pytest.mark.parametrize("seed", range(6))
def test_wing_numbers_oracle_matches_recompute(seed):
    """``oracle.wing_numbers`` (each butterfly found once per sub-round)
    agrees with the recompute-from-scratch oracle, sub-round for
    sub-round, on the tiny graphs."""
    g = rand_graph(9, 8, 28, seed)
    got, mins = wing_numbers(g)
    assert np.array_equal(got, oracle_wing(g))
    assert len(mins) == peel_wings(g).sub_rounds


def _range_rounds(mins):
    """Bucket rounds of a sub-round trajectory: a round opens where the
    least alive count reaches the bound its last opening set."""
    rounds, hi = 0, 0
    for mn in mins:
        if mn >= hi:
            rounds, hi = rounds + 1, 1 << int(mn).bit_length()
    return rounds


# (Chung-Lu graph, peel_mode, tile_budget): about 0.8-2.5 K edges, whose
# first sub-round alone streams more triples than a tile holds; a
# 2,048-triple tile is a 6,144-lane update batch, wider than
# MAX_UPDATE_CAP, so the sub-round's last one is chunked
WING_CELL_CASES = {
    "cl1100-range": ((200, 150, 1500, 2.1, 1), "range", None),
    "cl1100-exact": ((200, 150, 1500, 2.1, 1), "exact", None),
    "cl800-range-tile128": ((150, 120, 1200, 2.0, 3), "range", 128),
    "cl2500-range": ((400, 300, 3000, 2.2, 4), "range", None),
    "cl2500-exact-tile2048": ((400, 300, 3000, 2.2, 4), "exact", 2048),
}


@pytest.mark.parametrize("case", sorted(WING_CELL_CASES))
def test_wings_cell_call_matches_oracle(case):
    """The benchmark cell's call — device engine, range or exact peel,
    int64 per-edge counts from ``fused_pallas`` — against
    ``oracle.wing_numbers``: the device rung answers with no retry,
    launches only the wing loop and the count, and crosses tiles."""
    (nu, nv, m, alpha, seed), mode, budget = WING_CELL_CASES[case]
    g = powerlaw_bipartite(nu, nv, m, alpha, alpha, seed)
    want, mins = wing_numbers(g)
    with jax.enable_x64(True), record_programs() as programs:
        got = peel_wings(g, engine="device", peel_mode=mode,
                         tile_budget=budget,
                         count_kwargs={"engine": "fused_pallas"})
    names = sorted({p.__name__ for p, _a, _k in programs})
    assert names == ["_peel_wings_device", "run_fused_pallas_program"]
    rep = got.report
    assert rep.requested == rep.final_rung == "device"
    assert all(a.outcome == "ok" and not a.retries and not a.budget_shrinks
               for a in rep.attempts)
    assert got.numbers.dtype == np.int64
    assert np.array_equal(got.numbers, want)
    assert got.sub_rounds == len(mins)
    assert got.rounds == (len(mins) if mode == "exact"
                          else _range_rounds(mins))
    (_p, args, kw), = [p for p in programs
                       if p[0].__name__ == "_peel_wings_device"]
    assert kw["tile_cap"] == (budget or 1024)
    first = per_edge_counts(g) <= mins[0]  # the first sub-round's edges
    assert np.asarray(args[10])[first].sum() > kw["tile_cap"]  # work2


# -- device-resident peeling engine (PR 2) ------------------------------


@pytest.mark.parametrize("seed", range(2))
@pytest.mark.parametrize("side", [0, 1])
@pytest.mark.parametrize("agg", ["sort", "hash"])
def test_device_engine_parity(seed, side, agg):
    """engine="device" tip numbers are bitwise-equal to the host engine
    and the recompute oracle, for both aggregations and both sides."""
    g = rand_graph(10, 8, 30, seed)
    h = peel_tips(g, side=side, aggregation=agg)
    d = peel_tips(g, side=side, aggregation=agg, engine="device")
    assert np.array_equal(h.numbers, d.numbers)
    assert h.rounds == d.rounds
    assert np.array_equal(h.round_sizes, d.round_sizes)
    assert np.array_equal(d.numbers, oracle_tip(g, side))


@pytest.mark.parametrize("side", [0, 1])
def test_device_engine_stored_parity(side):
    """WPEEL-V on device agrees with its host engine and the oracle."""
    for seed in range(2):
        g = rand_graph(11, 9, 32, seed)
        h = peel_tips_stored(g, side=side)
        d = peel_tips_stored(g, side=side, engine="device")
        assert np.array_equal(h.numbers, d.numbers)
        assert h.rounds == d.rounds
        assert np.array_equal(h.round_sizes, d.round_sizes)
        assert np.array_equal(d.numbers, oracle_tip(g, side))


@pytest.mark.parametrize("peel_mode", ["exact", "range"])
@pytest.mark.parametrize("decomp", ["tips", "stored", "wings"])
def test_device_engine_no_per_round_sync(decomp, peel_mode, monkeypatch):
    """The device round loop never host-syncs: with counts precomputed,
    every decomposition performs exactly one jax.device_get (the final
    PeelResult fetch), regardless of round count, in both peel modes."""
    from repro.core import count_butterflies

    g = rand_graph(12, 9, 40, 3)
    if decomp == "wings":
        counts = count_butterflies(g, mode="edge").per_edge
        want = wing_numbers(g)[0]
        run = lambda **kw: peel_wings(g, counts=counts, **kw)  # noqa: E731
    else:
        counts = count_butterflies(g, mode="vertex").per_u
        want = oracle_tip(g, 0)
        fn = peel_tips if decomp == "tips" else peel_tips_stored
        run = lambda **kw: fn(g, counts=counts, side=0, **kw)  # noqa: E731
    calls = []
    orig = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (calls.append(1), orig(x))[1]
    )
    d = run(engine="device", peel_mode=peel_mode)
    assert len(calls) == 1
    assert d.report.final_rung == "device"
    assert d.rounds >= 2  # the loop really ran multiple rounds
    assert np.array_equal(d.numbers, want)


def test_device_engine_frontier_overflow_falls_back():
    """A deliberately tiny max_frontier overflows PEEL-V's level-1
    frontier buffer; the engine must fall back to the host path (never
    silently truncate) and still match the oracle. The latch fires only
    when a round's level-1 expansion exceeds the 128-slot floor (forced
    with a disjoint-biclique graph whose first peel round releases 15
    vertices of degree 15 at once). WPEEL-V has no frontier buffer: its
    device engine stays on device."""
    import repro.core.peel as peel_mod

    g = rand_graph(30, 20, 300, 0)
    want = oracle_tip(g, 0)
    g2 = bicliques()
    want2 = oracle_tip(g2, 0)
    device_returns = []
    orig = peel_mod._peel_tips_device_run

    def spy(*a, **k):
        out = orig(*a, **k)
        device_returns.append(out)
        return out

    peel_mod._peel_tips_device_run = spy
    try:
        ds = peel_tips_stored(g, side=0, engine="device")
        # sanity: without the cap, the device engine handles this graph
        full = peel_tips(g, side=0, engine="device")
        d2 = peel_tips(g2, side=0, engine="device", max_frontier=1)
    finally:
        peel_mod._peel_tips_device_run = orig
    assert device_returns[0] is not None  # stored stays on device
    assert device_returns[1] is not None
    for r in (ds, full):
        assert np.array_equal(r.numbers, want)
    assert device_returns[2] is None  # level-1 overflow -> host fallback
    assert d2.report.final_rung == "host"
    assert np.array_equal(d2.numbers, want2)


def test_stored_hash_overflow_regression():
    """Forced hash-table overflow (4-slot table) in peel_tips_stored:
    the overflow flag must trigger the in-graph sort fallback instead of
    silently subtracting wrong counts. This graph is known to corrupt
    when the flag is discarded (the pre-fix behavior)."""
    g = rand_graph(12, 9, 50, 0)
    want = oracle_tip(g, 0)
    got = peel_tips_stored(g, side=0, aggregation="hash", hash_bits=2)
    assert np.array_equal(got.numbers, want)
    # the non-stored path shares the in-graph fallback
    got2 = peel_tips(g, side=0, aggregation="hash", hash_bits=2)
    assert np.array_equal(got2.numbers, want)


def test_device_engine_hash_overflow_in_graph():
    """Hash overflow inside the device while_loop round also falls back
    to sort in-graph (lax.cond), keeping parity with the oracle."""
    g = rand_graph(12, 9, 50, 0)
    d = peel_tips(
        g, side=0, aggregation="hash", engine="device", hash_bits=2
    )
    assert np.array_equal(d.numbers, oracle_tip(g, 0))


def test_peel_engine_validation():
    g = rand_graph(6, 5, 12, 0)
    with pytest.raises(ValueError, match="engine"):
        peel_tips(g, engine="gpu")
    with pytest.raises(ValueError, match="engine"):
        peel_tips_stored(g, engine="banana")


def test_tip_monotone_under_kappa():
    """Tip numbers are nondecreasing along the peel order."""
    g = rand_graph(15, 12, 60, 11)
    r = peel_tips(g, side=0)
    assert (np.diff([0] + sorted(r.numbers.tolist())) >= 0).all()


# -- the tile stream and its batched decrease-key ----------------------


@pytest.mark.parametrize("shape", [(12, 9, 40, 3), (14, 11, 60, 5)])
@pytest.mark.parametrize("side", [0, 1])
def test_tip_engines_bitwise(side, shape):
    """Both engines of both tip algorithms produce bitwise-identical
    numbers, rounds, and round sizes, equal to the recompute oracle
    (integer scatter sums commute, tiles never split a group)."""
    g = rand_graph(*shape)
    want = oracle_tip(g, side)
    base = peel_tips(g, side=side)
    for engine in ("host", "device"):
        for fn in (peel_tips, peel_tips_stored):
            got = fn(g, side=side, engine=engine)
            assert got.report.final_rung == engine, (fn, engine)
            assert np.array_equal(got.numbers, want), (fn, engine)
            assert got.rounds == base.rounds
            assert np.array_equal(got.round_sizes, base.round_sizes)


def test_fused_subtract_forced_multi_tile():
    """A tiny tile_budget forces the subtract through many tiles per
    round (tile_cap collapses to the single-vertex alignment floor);
    results stay bitwise-equal on both engines."""
    g = rand_graph(14, 11, 60, 5)
    want = oracle_tip(g, 0)
    for engine in ("host", "device"):
        got = peel_tips(g, side=0, engine=engine, tile_budget=1)
        assert np.array_equal(got.numbers, want), engine
        gs = peel_tips_stored(g, side=0, engine=engine, tile_budget=1)
        assert np.array_equal(gs.numbers, want), engine
    wd = peel_wings(g, engine="device", tile_budget=1)
    assert np.array_equal(wd.numbers, peel_wings(g).numbers)


def test_fused_subtract_hash_overflow_in_tile():
    """Forced hash-table overflow (4-slot table) inside the fused tile
    loop falls back to sort in-graph, per tile, on both engines."""
    g = rand_graph(12, 9, 50, 0)
    want = oracle_tip(g, 0)
    for engine in ("host", "device"):
        got = peel_tips(g, side=0, aggregation="hash", engine=engine,
                        hash_bits=2)
        assert np.array_equal(got.numbers, want), engine


def _tip_counts(g, side):
    from repro.core import count_butterflies

    r = count_butterflies(g, mode="vertex")
    return r.per_u if side == 0 else r.per_v


def test_fused_peel_subtract_temp_memory_is_o_tile():
    """The peeling subtract's compiled temp footprint must NOT scale
    with the frontier wedge total: two graphs with ~9x stored-wedge
    totals, the tile budget held fixed across both (the shared
    alignment floor)."""
    import jax.numpy as jnp
    import repro.core.peel as pm

    graphs = {
        "small": rand_graph(2500, 2000, 6000, 11),  # sparse, few wedges
        "big": rand_graph(70, 55, 6000, 11),  # dense, many wedges
    }
    plans = {}
    tile_cap = 128
    for name, g in graphs.items():
        woff, w_u2 = pm._stored_wedge_csr(g, 0)
        rows = np.diff(woff)
        plans[name] = (g, woff, w_u2)
        tile_cap = max(tile_cap, pm._pow2_pad(2 * int(rows.max(initial=0))))
    stats = {}
    for name, (g, woff, w_u2) in plans.items():
        n_side = g.n_u
        st = pm._init_state(jnp.zeros(n_side, jnp.int32), n_side,
                            peel_mode="exact")
        mem = pm._peel_tips_device.lower(
            jnp.asarray(woff, jnp.int32), jnp.asarray(w_u2, jnp.int32),
            jnp.int32(0), st, aggregation="hash", cap1=128,
            tile_cap=tile_cap, n_side=n_side, stored=True,
        ).compile().memory_analysis()
        stats[name] = dict(wedges=int(woff[-1]),
                           temp=int(mem.temp_size_in_bytes))
    ratio_w = stats["big"]["wedges"] / max(stats["small"]["wedges"], 1)
    assert ratio_w >= 8, stats  # the experiment is meaningful
    ratio = stats["big"]["temp"] / max(stats["small"]["temp"], 1)
    assert ratio < 2.0, stats  # O(tile): flat in the frontier wedge total


# -- device wing engine (PEEL-E) ----------------------------------------


@pytest.mark.parametrize("order", ["degree", "side"])
@pytest.mark.parametrize("agg", ["sort", "hash"])
def test_wings_device_parity(order, agg):
    """peel_wings engine="device" is bitwise-equal to the host engine
    and the recompute oracle across aggregation × ranking (the counts
    ordering), for several graphs."""
    for seed in range(2):
        g = rand_graph(10, 8, 30, seed)
        kw = dict(count_kwargs={"order": order}, aggregation=agg)
        h = peel_wings(g, **kw)
        d = peel_wings(g, engine="device", **kw)
        assert np.array_equal(h.numbers, d.numbers), (order, agg, seed)
        assert h.rounds == d.rounds
        assert np.array_equal(h.round_sizes, d.round_sizes)
        assert np.array_equal(d.numbers, oracle_wing(g))


def test_wings_device_hash_overflow_in_graph():
    """Forced hash overflow in the device wing engine's grouped edge
    subtract falls back to sort in-graph and stays oracle-exact."""
    g = rand_graph(9, 8, 28, 1)
    d = peel_wings(g, engine="device", aggregation="hash", hash_bits=2)
    assert np.array_equal(d.numbers, oracle_wing(g))


def test_wings_device_matrix_bitwise():
    """The device wing engine matches the host engine bitwise."""
    g = rand_graph(9, 8, 28, 2)
    h = peel_wings(g)
    d = peel_wings(g, engine="device")
    assert d.report.final_rung == "device"
    assert np.array_equal(h.numbers, d.numbers)
    assert h.rounds == d.rounds
    assert np.array_equal(h.round_sizes, d.round_sizes)


def test_peel_knob_validation():
    g = rand_graph(6, 5, 12, 0)
    with pytest.raises(ValueError, match="aggregation"):
        peel_tips(g, aggregation="histogram")
    with pytest.raises(ValueError, match="aggregation"):
        peel_wings(g, aggregation="batch")
    # one device engine: no option selects another, and only PEEL-V
    # has a frontier buffer to bound
    for fn in (peel_tips, peel_tips_stored, peel_wings):
        params = inspect.signature(fn).parameters
        for gone in ("subtract", "decrease_key", "capacity_schedule"):
            assert gone not in params, (fn.__name__, gone)
        assert ("max_frontier" in params) == (fn is peel_tips)


# -- Fibonacci heap (paper §5) ------------------------------------------


def test_fibheap_ops():
    h = FibHeap()
    h.batch_insert([(5, "a"), (3, "b"), (9, "c")])
    assert h.find_min() == 3
    k, v = h.delete_min()
    assert (k, v) == (3, "b")
    h.batch_insert([(1, "d"), (7, "e")])
    assert h.find_min() == 1
    h.batch_decrease_key([(9, 0)])
    assert h.find_min() == 0
    ks = []
    while len(h):
        ks.append(h.delete_min()[0])
    assert ks == sorted(ks)


def test_fibheap_heapsort_random():
    rng = np.random.default_rng(0)
    keys = rng.permutation(200)[:50]
    h = FibHeap()
    h.batch_insert([(int(k), int(k)) for k in keys])
    out = []
    while len(h):
        out.append(h.delete_min()[0])
    assert out == sorted(int(k) for k in keys)


def test_bucket_structure():
    counts = {0: 5, 1: 5, 2: 2, 3: 9}
    b = BucketStructure(counts)
    k, members = b.pop_min_nonempty()
    assert k == 2 and members == {2}
    b.decrease({3: 1})
    k, members = b.pop_min_nonempty()
    assert k == 1 and members == {3}
    k, members = b.pop_min_nonempty()
    assert k == 5 and members == {0, 1}


# -- bucket-range multi-bucket peeling (peel_mode="range", PR 5) --------


RANGE_GRAPHS = {
    "rand12": lambda: rand_graph(12, 9, 40, 3),
    "rand14": lambda: rand_graph(14, 11, 60, 5),
    "rand20": lambda: rand_graph(20, 15, 120, 4),
    "bicliques": bicliques,
}


@pytest.mark.parametrize("graph", sorted(RANGE_GRAPHS))
def test_range_mode_matrix_bitwise(graph):
    """peel_mode="range" produces bitwise-identical numbers to exact
    peeling on both engines of all three decompositions; rho (bucket
    rounds) never exceeds exact mode's, sub_rounds equals exact mode's
    rho (the re-settle replays the same trajectory), and bucket
    selection agrees between the device engine (consumed occupancy
    histogram) and the host engine (bit length of the min)."""
    g = RANGE_GRAPHS[graph]()
    runs = (
        ("tips", lambda **kw: peel_tips(g, side=0, **kw)),
        ("stored", lambda **kw: peel_tips_stored(g, side=0, **kw)),
        ("wings", lambda **kw: peel_wings(g, **kw)),
    )
    for name, fn in runs:
        exact = fn()
        host_range = None
        for engine in ("host", "device"):
            r = fn(engine=engine, peel_mode="range")
            assert np.array_equal(r.numbers, exact.numbers), (name, engine)
            assert r.rounds <= exact.rounds, (name, engine)
            assert r.sub_rounds == exact.rounds, (name, engine)
            assert len(r.round_sizes) == r.rounds
            assert r.round_sizes.sum() == exact.round_sizes.sum()
            if host_range is None:
                host_range = r
            else:
                assert r.rounds == host_range.rounds, (name, engine)
                assert np.array_equal(r.round_sizes,
                                      host_range.round_sizes), (name, engine)


def test_range_mode_reduces_rounds_on_bench_graph():
    """The acceptance regression: on a peeling benchmark graph, range
    mode's bucket-round count is strictly below exact mode's rho while
    the numbers stay bitwise-identical (geometric buckets span many
    distinct peel values on power-law counts)."""
    from repro.data.graphs import powerlaw_bipartite

    g = powerlaw_bipartite(600, 500, 4_000, seed=7)  # bench peel_small
    counts = _tip_counts(g, 0)
    exact = peel_tips(g, counts=counts, side=0)
    rng_ = peel_tips(g, counts=counts, side=0, peel_mode="range")
    assert np.array_equal(rng_.numbers, exact.numbers)
    assert rng_.sub_rounds == exact.rounds
    assert rng_.rounds < exact.rounds, (rng_.rounds, exact.rounds)
    dev = peel_tips(g, counts=counts, side=0, engine="device",
                    peel_mode="range")
    assert np.array_equal(dev.numbers, exact.numbers)
    assert dev.rounds == rng_.rounds


def test_range_mode_single_sync_and_validation(monkeypatch):
    """Range mode keeps the device engine's one-device_get guarantee
    (the bucket selection consumes the carried histogram — no extra
    host syncs), and bad peel_mode values are rejected."""
    from repro.core import count_butterflies

    g = rand_graph(12, 9, 40, 3)
    counts = count_butterflies(g, mode="vertex").per_u
    calls = []
    orig = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (calls.append(1), orig(x))[1]
    )
    d = peel_tips(g, counts=counts, side=0, engine="device",
                  peel_mode="range")
    assert len(calls) == 1
    assert d.sub_rounds > d.rounds >= 2
    with pytest.raises(ValueError, match="peel_mode"):
        peel_tips(g, peel_mode="banana")


def test_wings_fused_recovery_temp_memory_drops_buffers():
    """With the two-level fused recovery the compiled wing program's
    temp footprint must NOT scale with the O(sum deg^2)-class triple
    totals: same edge count, ~10x denser triple space."""
    import jax.numpy as jnp
    import repro.core.peel as pm
    from repro.core.wedges import degree_sorted_csr

    graphs = {
        "sparse": rand_graph(2500, 2000, 6000, 11),
        "dense": rand_graph(70, 55, 6000, 11),
    }
    stats = {}
    for name, g in graphs.items():
        off, nbr, uid = pm._csr(g)
        m = g.m
        eu, ev, l1, l2 = pm._wing_work_totals(g, off, nbr)
        nbr_ds, uid_ds, degs_ds, cumdeg = degree_sorted_csr(off, nbr, uid)
        args = tuple(
            jnp.asarray(a, jnp.int32)
            for a in (off, nbr, uid, eu, ev, nbr_ds, uid_ds, degs_ds,
                      cumdeg, l1, l2)
        )
        st = pm._init_state(jnp.zeros(m, jnp.int32), m, peel_mode="exact")
        mem = pm._peel_wings_device.lower(
            *args, st, aggregation="sort", m=m, tile_cap=1024,
        ).compile().memory_analysis()
        stats[name] = dict(lvl2=int(l2.sum()),
                           temp=int(mem.temp_size_in_bytes))
    ratio_work = stats["dense"]["lvl2"] / max(stats["sparse"]["lvl2"], 1)
    assert ratio_work >= 8, stats  # the experiment is meaningful
    ratio = stats["dense"]["temp"] / max(stats["sparse"]["temp"], 1)
    assert ratio < 2.0, stats  # O(tile): flat in the triple space

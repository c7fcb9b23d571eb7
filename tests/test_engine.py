"""Fused counting-engine tests: ``engine="pallas"`` (interpret mode on
CPU CI) vs ``engine="xla"`` vs the dense oracle, single-pass
``mode="all"`` equivalence, chunked wedge streaming, and the in-graph
hash-overflow fallback."""
import numpy as np
import pytest

from repro.core import (
    BipartiteGraph,
    count_butterflies,
    count_from_ranked,
    make_order,
    preprocess,
)
from repro.core.oracle import global_count, per_edge_counts, per_vertex_counts
from repro.core.wedges import (
    greedy_vertex_blocks,
    host_wedge_counts,
    plan_wedge_chunks,
)


def rand_graph(nu, nv, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e)


ENGINES = ("xla", "pallas")


@pytest.mark.parametrize("cache_opt", [False, True])
@pytest.mark.parametrize("agg", ["sort", "hash", "histogram"])
def test_pallas_engine_matches_oracle(agg, cache_opt):
    """engine="pallas" (interpret) reproduces the brute-force oracle for
    all of global/vertex/edge in both wedge directions."""
    for seed in range(2):
        g = rand_graph(12, 10, 40, seed)
        want_total = global_count(g)
        pu, pv = per_vertex_counts(g)
        pe = per_edge_counts(g)
        r = count_butterflies(
            g, aggregation=agg, mode="all", engine="pallas",
            cache_opt=cache_opt,
        )
        assert int(r.total) == want_total, (seed, agg, cache_opt)
        assert np.array_equal(r.per_u, pu)
        assert np.array_equal(r.per_v, pv)
        assert np.array_equal(r.per_edge, pe)


@pytest.mark.parametrize("mode", ["global", "vertex", "edge"])
def test_pallas_matches_xla_bitwise(mode):
    g = rand_graph(15, 12, 55, 3)
    rx = count_butterflies(g, mode=mode, engine="xla")
    rp = count_butterflies(g, mode=mode, engine="pallas")
    for field in ("total", "per_u", "per_v", "per_edge"):
        a, b = getattr(rx, field), getattr(rp, field)
        assert (a is None) == (b is None)
        if a is not None:
            assert np.array_equal(a, b), (mode, field)


@pytest.mark.parametrize("engine", ENGINES)
def test_mode_all_equals_three_single_modes(engine):
    """mode="all" is bitwise-identical to the three single-mode calls
    while paying the wedge gather + aggregation once."""
    g = rand_graph(14, 11, 45, 7)
    ra = count_butterflies(g, mode="all", engine=engine)
    rg_ = count_butterflies(g, mode="global", engine=engine)
    rv = count_butterflies(g, mode="vertex", engine=engine)
    re_ = count_butterflies(g, mode="edge", engine=engine)
    assert ra.total.dtype == rg_.total.dtype
    assert int(ra.total) == int(rg_.total)
    assert np.array_equal(ra.per_u, rv.per_u)
    assert np.array_equal(ra.per_v, rv.per_v)
    assert np.array_equal(ra.per_edge, re_.per_edge)


def test_batch_requires_xla_engine():
    """Batch aggregations fuse their own accumulation: kernel/fused
    engines are rejected (mode="all" is supported since PR 3 — see
    tests/test_fused.py)."""
    g = rand_graph(8, 8, 20, 0)
    for engine in ("pallas", "fused", "fused_pallas"):
        with pytest.raises(ValueError, match="engine"):
            count_butterflies(g, aggregation="batch", engine=engine)


@pytest.mark.parametrize("agg", ["sort", "hash"])
@pytest.mark.parametrize("cache_opt", [False, True])
def test_streaming_matches_single_shot(agg, cache_opt):
    g = rand_graph(20, 16, 90, 11)
    r1 = count_butterflies(g, mode="all", aggregation=agg, cache_opt=cache_opt)
    r2 = count_butterflies(
        g, mode="all", aggregation=agg, cache_opt=cache_opt, max_chunk=48
    )
    assert int(r1.total) == int(r2.total) == global_count(g)
    assert np.array_equal(r1.per_u, r2.per_u)
    assert np.array_equal(r1.per_v, r2.per_v)
    assert np.array_equal(r1.per_edge, r2.per_edge)


def test_streaming_pallas_engine():
    g = rand_graph(12, 10, 40, 5)
    r = count_butterflies(
        g, mode="all", engine="pallas", aggregation="sort", max_chunk=32
    )
    assert int(r.total) == global_count(g)
    pu, pv = per_vertex_counts(g)
    assert np.array_equal(r.per_u, pu)
    assert np.array_equal(r.per_v, pv)


def test_streaming_caps_chunk_buffer():
    """The planned per-chunk wedge buffer never exceeds the budget
    (rounded to the 128 pad) unless a single vertex owns more wedges,
    and every chunk's wedge population fits the buffer."""
    g = rand_graph(30, 25, 150, 13)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    wv_slots = host_wedge_counts(rg, "low")
    total = int(wv_slots.sum())
    budget = 128
    assert total > budget  # streaming actually engages on this graph
    bounds, chunk_cap = plan_wedge_chunks(rg, "low", budget)
    n_real = 2 * rg.m
    wv = np.zeros(rg.n_pad, dtype=np.int64)
    np.add.at(wv, rg.edge_src[:n_real].astype(np.int64), wv_slots[:n_real])
    single_vertex_floor = int(wv.max())
    padded = lambda x: ((x + 127) // 128) * 128  # noqa: E731
    assert chunk_cap <= max(padded(budget), padded(single_vertex_floor))
    woff = np.concatenate([[0], np.cumsum(wv)])
    per_chunk = woff[bounds[1:]] - woff[bounds[:-1]]
    assert int(per_chunk.max()) <= chunk_cap
    assert bounds[0] == 0 and bounds[-1] == rg.n_pad
    assert int(per_chunk.sum()) == total


def test_hash_overflow_falls_back_in_graph():
    """A deliberately tiny hash table overflows; the lax.cond fallback
    re-aggregates the same wedges with sort inside the jitted program
    (no host round-trip) and still matches the oracle."""
    g = rand_graph(14, 11, 45, 1)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    out = count_from_ranked(rg, aggregation="hash", hash_bits=2)
    assert int(out) == global_count(g)
    total, bv, be = count_from_ranked(rg, aggregation="hash", mode="all", hash_bits=2)
    assert int(total) == global_count(g)
    pe = per_edge_counts(g)
    assert np.array_equal(np.asarray(be), pe)


def test_pallas_choose2_wide_multiplicities_stay_on_kernel():
    """Group multiplicities >= 2^16 used to trip an in-graph fallback
    to the exact count-dtype path; the widened two-limb combine kernel
    now computes them exactly on the kernel (PR 1 follow-up)."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregate import Groups
    from repro.core.count import _group_choose2

    def groups_with(d_vals, valid_vals):
        n = len(d_vals)
        return Groups(
            d_per_wedge=jnp.zeros((n,), jnp.int32),
            x1=jnp.zeros((n,), jnp.int32),
            x2=jnp.zeros((n,), jnp.int32),
            d=jnp.asarray(d_vals, jnp.int32),
            valid=jnp.asarray(valid_vals, bool),
            ok=jnp.asarray(True),
        )

    with jax.enable_x64(True):
        big = 70_000  # C(big, 2) > int32 max -> needs the high limb
        huge = 1 << 20  # C(huge, 2) ~ 2^39
        g = groups_with([big, 3, huge, 9, 0], [True, True, True, False, False])
        got = np.asarray(_group_choose2(g, jnp.int64, "pallas"))
        want = np.array(
            [big * (big - 1) // 2, 3, huge * (huge - 1) // 2, 0, 0], np.int64
        )
        assert np.array_equal(got, want)
        # and the kernel path agrees bitwise with the exact xla path
        assert np.array_equal(
            got, np.asarray(_group_choose2(g, jnp.int64, "xla"))
        )
        # small multiplicities: likewise bitwise-equal
        g2 = groups_with([5, 2, 1, 0], [True, True, True, False])
        got2 = np.asarray(_group_choose2(g2, jnp.int64, "pallas"))
        assert np.array_equal(
            got2, np.asarray(_group_choose2(g2, jnp.int64, "xla"))
        )


def test_greedy_vertex_blocks_matches_loop_reference():
    """The vectorized sweep reproduces the old per-vertex greedy loop."""

    def reference(wv, n, rows, target):
        bounds = [0]
        acc = 0
        for v in range(n):
            if (v - bounds[-1]) >= rows or (
                acc + wv[v] > target and v > bounds[-1]
            ):
                bounds.append(v)
                acc = 0
            acc += int(wv[v])
        bounds.append(n)
        return np.unique(np.asarray(bounds, dtype=np.int64))

    rng = np.random.default_rng(0)
    for trial in range(20):
        n = int(rng.integers(1, 60))
        wv = rng.integers(0, 50, n).astype(np.int64)
        rows = int(rng.integers(1, 12))
        target = int(rng.integers(1, 200))
        want = reference(wv, n, rows, target)
        got, _ = greedy_vertex_blocks(wv, n, rows=rows, target=target)
        assert np.array_equal(got, want), (trial, n, rows, target)

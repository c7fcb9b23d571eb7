"""Fused-engine (zero-materialization) tests: bitwise parity of
``engine="fused"`` / ``engine="fused_pallas"`` vs ``engine="xla"``
across modes × directions × aggregations (including the in-graph
hash-overflow sort fallback and forced multi-tile grids), the
wedge_fused kernel vs its jnp oracle, the batch ``mode="all"``
single-pass, ``max_chunk="auto"``, the distributed fused tile loop,
and the O(tile)-not-O(W) temp-memory regression via compiled
``memory_analysis()``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BipartiteGraph,
    count_butterflies,
    count_from_ranked,
    make_order,
    preprocess,
)
from repro.core.count import _count_device, _count_stream_device
from repro.core.pipeline import (
    I32_MAX,
    KERNEL_BATCH,
    execute_count_plan,
    narrow_partials,
    plan_count,
    record_programs,
    run_fused_pallas_program,
)
from repro.core.oracle import global_count, per_edge_counts, per_vertex_counts
from repro.core.wedges import (
    auto_chunk_budget,
    device_graph,
    host_wedge_counts,
    plan_wedge_chunks,
    wedges_at,
)
from repro.kernels import ops as kops
from repro.kernels.ops import MAX_TILE_CAP
from repro.kernels import ref as kref
from repro.testing import faults


def rand_graph(nu, nv, m, seed):
    rng = np.random.default_rng(seed)
    e = np.stack([rng.integers(0, nu, m), rng.integers(0, nv, m)], axis=1)
    return BipartiteGraph(nu, nv, e)


def _fields(r):
    return [getattr(r, f) for f in ("total", "per_u", "per_v", "per_edge")]


def assert_bitwise_equal(ra, rb, ctx):
    for f, a, b in zip(("total", "per_u", "per_v", "per_edge"),
                       _fields(ra), _fields(rb)):
        assert (a is None) == (b is None), (ctx, f)
        if a is not None:
            assert np.asarray(a).dtype == np.asarray(b).dtype, (ctx, f)
            assert np.array_equal(a, b), (ctx, f)


@pytest.mark.parametrize("engine", ["fused", "fused_pallas"])
@pytest.mark.parametrize("cache_opt", [False, True])
@pytest.mark.parametrize("mode", ["global", "vertex", "edge", "all"])
def test_fused_matches_xla_bitwise(engine, cache_opt, mode):
    """The fused engines reproduce engine="xla" bit-for-bit on every
    mode × direction, with a forced multi-tile grid (max_chunk far
    below the wedge total)."""
    g = rand_graph(18, 14, 70, 3)
    rx = count_butterflies(g, mode=mode, engine="xla", cache_opt=cache_opt)
    rf = count_butterflies(
        g, mode=mode, engine=engine, cache_opt=cache_opt, max_chunk=48
    )
    assert_bitwise_equal(rx, rf, (engine, cache_opt, mode))


@pytest.mark.parametrize("agg", ["sort", "hash", "histogram"])
@pytest.mark.parametrize("cache_opt", [False, True])
def test_fused_xla_flavor_aggregations(agg, cache_opt):
    """engine="fused" supports tile-local sort/hash/dense aggregation,
    bitwise-equal to the materializing engine and the oracle."""
    for seed in range(2):
        g = rand_graph(14, 11, 45, seed)
        rx = count_butterflies(
            g, mode="all", aggregation=agg, engine="xla", cache_opt=cache_opt
        )
        rf = count_butterflies(
            g, mode="all", aggregation=agg, engine="fused",
            cache_opt=cache_opt, max_chunk=32,
        )
        assert_bitwise_equal(rx, rf, (agg, cache_opt, seed))
        assert int(rf.total) == global_count(g)


def test_fused_hash_overflow_falls_back_in_graph():
    """A deliberately tiny per-tile hash table overflows; the fused
    tile loop's lax.cond sort fallback re-aggregates the same TILE
    in-graph and still matches the oracle."""
    g = rand_graph(14, 11, 45, 1)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    out = count_from_ranked(
        rg, aggregation="hash", engine="fused", max_chunk=32, hash_bits=2
    )
    assert int(out) == global_count(g)
    total, bv, be = count_from_ranked(
        rg, aggregation="hash", engine="fused", mode="all", max_chunk=32,
        hash_bits=2,
    )
    assert int(total) == global_count(g)
    assert np.array_equal(np.asarray(be), per_edge_counts(g))


def _fused_pallas_plan(rg, direction, budget, mode="all"):
    return plan_count(
        rg, mode=mode, direction=direction, budget=budget,
        engine="fused_pallas", wv_slots=host_wedge_counts(rg, direction),
    )


@pytest.mark.parametrize("direction", ["low", "high"])
def test_fused_kernel_matches_ref_bitwise(direction):
    """wedge_fused Pallas kernel (interpret on CPU CI) vs its pure-jnp
    oracle on the key lanes of real multi-tile plans."""
    for seed in range(2):
        g = rand_graph(16, 12, 60, seed)
        rg = preprocess(g, make_order(g, "degree"), order_name="degree")
        dg = device_graph(rg)
        plan = _fused_pallas_plan(rg, direction, 40)
        assert plan.strategy_counts()["kernel"] >= 2  # genuinely multi-tile
        cnt = host_wedge_counts(rg, direction)
        w_off = jnp.asarray(np.concatenate([[0], np.cumsum(cnt)]), jnp.int32)
        tile_cap = dict(plan.capacity)["kernel_tile"]
        flat = plan.tile_flat_bounds()
        wid = flat[:, :1] + np.arange(tile_cap)[None, :]
        valid = wid < flat[:, 1:2]
        w = wedges_at(dg, None, w_off, jnp.asarray(wid.reshape(-1)),
                      jnp.asarray(valid.reshape(-1)), direction)
        ka = jnp.where(w.valid, w.x1, -1).reshape(wid.shape)
        kb = jnp.where(w.valid, w.x2, -2).reshape(wid.shape)
        got = kops.match_tiles(ka, kb, use_pallas=True)
        want = kref.match_tiles_ref(ka, kb)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                seed, direction,
            )


@pytest.mark.parametrize("direction", ["low", "high"])
@pytest.mark.parametrize("mode", ["global", "vertex", "edge", "all"])
def test_fused_pallas_kernel_and_vertex_tiles_match_fused(direction, mode):
    """The restructured fused_pallas program (interpret-mode kernel)
    against engine="fused" bitwise, with a forced multi-tile plan that
    mixes kernel tiles and per-vertex XLA tiles (a kernel cap below the
    heaviest vertices)."""
    g = rand_graph(30, 20, 260, 5)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    dg = device_graph(rg)
    wv = host_wedge_counts(rg, direction)
    with faults.inject("capacity_overflow", site="fused_pallas.plan",
                       budget=40):
        plan = _fused_pallas_plan(rg, direction, 200, mode=mode)
    kinds = plan.strategy_counts()
    assert kinds["kernel"] >= 2 and kinds["vertex"] >= 2, kinds
    got = execute_count_plan(dg, plan)
    want = count_from_ranked(
        rg, mode=mode, engine="fused", cache_opt=direction == "high",
        max_chunk=64,
    )
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(wv.sum()) == plan.total_wedges


def test_fused_pallas_rejects_oversized_tiles():
    """The kernel refuses a tile wider than its exactness bound
    (pointing at engine='fused'), and the fused_pallas planner never
    hands it one: a vertex owning more than MAX_TILE_CAP wedges goes to
    a per-vertex XLA tile of the same program."""
    with pytest.raises(ValueError, match="fused"):
        kops.match_tiles(
            jnp.zeros((1, 2 * MAX_TILE_CAP), jnp.int32),
            jnp.zeros((1, 2 * MAX_TILE_CAP), jnp.int32),
            use_pallas=True,
        )
    # near-complete bipartite core: every iterating endpoint owns far
    # more than MAX_TILE_CAP wedges
    nu, nv = 90, 90
    e = np.stack(
        [np.repeat(np.arange(nu), nv), np.tile(np.arange(nv), nu)], axis=1
    )
    g = BipartiteGraph(nu, nv, e)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    wv = host_wedge_counts(rg, "low")
    n_real = 2 * rg.m
    per_vertex = np.zeros(rg.n_pad, np.int64)
    np.add.at(per_vertex, rg.edge_src[:n_real].astype(np.int64),
              wv[:n_real])
    assert int(per_vertex.max()) > MAX_TILE_CAP
    plan = _fused_pallas_plan(rg, "low", 1 << 18, "global")
    assert dict(plan.capacity)["kernel_tile"] <= MAX_TILE_CAP
    assert plan.strategy_counts().get("vertex", 0) >= 1
    out = count_from_ranked(rg, mode="global", engine="fused_pallas")
    assert int(out) == global_count(g)


def test_fused_pallas_refuses_foreign_tile_kinds():
    """A plan whose tiles are not kernel/vertex tiles (here: sort tiles
    relabelled fused_pallas) is refused, never silently counted as
    zero."""
    g = rand_graph(16, 12, 60, 0)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    plan = plan_count(rg, mode="global", budget=40, engine="fused")
    assert set(plan.tile_aggregation) == {"sort"}
    with pytest.raises(ValueError, match="kernel and vertex tiles only"):
        execute_count_plan(
            device_graph(rg), dataclasses.replace(plan, engine="fused_pallas")
        )


@pytest.mark.parametrize("agg", ["batch", "batch_wa"])
def test_batch_mode_all_equals_single_modes(agg):
    """Batch aggregations now support the single-pass mode="all",
    bitwise-identical to the three single-mode batch runs."""
    g = rand_graph(16, 13, 55, 7)
    ra = count_butterflies(g, aggregation=agg, mode="all")
    rg_ = count_butterflies(g, aggregation=agg, mode="global")
    rv = count_butterflies(g, aggregation=agg, mode="vertex")
    re_ = count_butterflies(g, aggregation=agg, mode="edge")
    assert int(ra.total) == int(rg_.total) == global_count(g)
    assert np.array_equal(ra.per_u, rv.per_u)
    assert np.array_equal(ra.per_v, rv.per_v)
    assert np.array_equal(ra.per_edge, re_.per_edge)
    pu, pv = per_vertex_counts(g)
    assert np.array_equal(ra.per_u, pu)
    assert np.array_equal(ra.per_v, pv)


def test_fused_pallas_wide_dtype_exact_no_warning():
    """The fused_pallas program accumulates in the count dtype, so a
    64-bit count_dtype is exact end to end, with no int32-downgrade
    warning."""
    import warnings as _warnings

    g = rand_graph(10, 8, 25, 2)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    with jax.enable_x64(True):
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            out = count_from_ranked(
                rg, mode="vertex", engine="fused_pallas",
                count_dtype=jnp.int64,
            )
    pu, pv = per_vertex_counts(g)
    bv = np.asarray(out)
    assert bv.dtype == np.int64
    assert np.array_equal(bv[rg.rank_of_u], pu)
    assert np.array_equal(bv[rg.rank_of_v], pv)


def test_fused_pallas_limb_accumulation_across_tiles():
    """Counts accumulate exactly across kernel tiles and launches:
    re-running the same whole-graph tile R times (one per kernel batch
    lane, across several batches) multiplies every count by R exactly,
    in int64 under x64."""
    g = rand_graph(16, 12, 60, 4)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    w_total = int(host_wedge_counts(rg, "low").sum())
    R = KERNEL_BATCH + 3  # two kernel batches, the second one ragged
    with jax.enable_x64(True):
        dg = device_graph(rg)
        tiles = np.zeros((2 * KERNEL_BATCH, 2), np.int32)
        tiles[:R] = (0, w_total)
        out = run_fused_pallas_program(
            dg,
            jnp.asarray(tiles.reshape(2, KERNEL_BATCH, 2)),
            (),
            tile_cap=((w_total + 127) // 128) * 128,
            vertex_caps=(),
            mode="all",
            direction="low",
            dtype=jnp.int64,
        )
    total, vert, edge = (np.asarray(x) for x in out)
    assert vert.dtype == np.int64
    pu, pv = per_vertex_counts(g)
    assert int(total) == R * global_count(g)
    assert np.array_equal(vert[rg.rank_of_u], R * pu)
    assert np.array_equal(vert[rg.rank_of_v], R * pv)
    assert np.array_equal(edge, R * per_edge_counts(g))


def _launched(fn):
    """Run ``fn`` and return the one fused_pallas program it launched,
    as ``(program, args, kwargs)``."""
    with record_programs() as progs:
        out = fn()
    [(prog, args, kw)] = [p for p in progs
                          if p[0] is run_fused_pallas_program]
    return out, prog, args, kw


def _accumulate_scatters(prog, args, kw):
    """Operand dtypes of the scatter-adds under the ``accumulate``
    scope of a fused_pallas program's jaxpr."""
    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for v in e.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        yield from eqns(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        yield from eqns(sub)

    jx = jax.make_jaxpr(lambda *a: prog(*a, **kw))(*args)
    return [str(e.invars[0].aval.dtype) for e in eqns(jx.jaxpr)
            if e.primitive.name == "scatter-add"
            and "accumulate" in str(e.source_info.name_stack)]


@pytest.mark.parametrize("direction", ["low", "high"])
@pytest.mark.parametrize("mode", ["vertex", "edge", "all"])
def test_fused_pallas_narrow_partials_bitwise(direction, mode):
    """int64 counts summed through int32 partials (a kernel batch or a
    vertex tile at a time) equal the per-lane int64 scatter-adds bit
    for bit and the oracle, on a plan mixing kernel and vertex tiles."""
    g = rand_graph(30, 20, 260, 5)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    with jax.enable_x64(True):
        dg = device_graph(rg)
        with faults.inject("capacity_overflow", site="fused_pallas.plan",
                           budget=40):
            plan = plan_count(
                rg, mode=mode, direction=direction, budget=200,
                engine="fused_pallas", dtype="int64",
                wv_slots=host_wedge_counts(rg, direction),
            )
        kinds = plan.strategy_counts()
        assert kinds["kernel"] >= 2 and kinds["vertex"] >= 2, kinds
        got, prog, args, kw = _launched(lambda: execute_count_plan(dg, plan))
        assert kw["narrow"] and kw["vertex_caps"]
        assert "int32" in _accumulate_scatters(prog, args, kw)
        wide = prog(*args, **dict(kw, narrow=False))
    got, wide = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(wide)
    for a, b in zip(got, wide):
        assert a.dtype == b.dtype == np.int64
        assert np.array_equal(np.asarray(a), np.asarray(b))
    pu, pv = per_vertex_counts(g)
    want = {"vertex": [pu, pv], "edge": [per_edge_counts(g)],
            "all": [pu, pv, per_edge_counts(g)]}[mode]
    bv = np.asarray(got[-2] if mode == "all" else got[0])
    have = [bv[rg.rank_of_u], bv[rg.rank_of_v]] if mode != "edge" else []
    if mode != "vertex":
        have.append(np.asarray(got[-1]))
    if mode == "all":
        assert int(got[0]) == global_count(g)
    for a, b in zip(have, want):
        assert np.array_equal(a, b)


def test_fused_pallas_narrow_partials_past_int32():
    """Job counts past 2^31 stay exact through int32 partials while each
    batch stays within its bound: K(2, 4000) (one hub iterates, one
    group of 4000 wedges, C(4000, 2) per tile) run R times, as one
    kernel batch of whole-graph tiles and a vertex-tile class."""
    n = 4000
    e = np.stack([np.repeat([0, 1], n), np.tile(np.arange(n), 2)], axis=1)
    g = BipartiteGraph(2, n, e)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    w_total = int(host_wedge_counts(rg, "low").sum())
    assert w_total == n
    n_vertex = 260
    reps = KERNEL_BATCH + n_vertex
    with jax.enable_x64(True):
        out = run_fused_pallas_program(
            device_graph(rg),
            jnp.asarray(np.tile([0, w_total], (1, KERNEL_BATCH, 1)),
                        jnp.int32),
            (jnp.asarray(np.tile([0, w_total], (n_vertex, 1)), jnp.int32),),
            tile_cap=MAX_TILE_CAP,
            vertex_caps=(MAX_TILE_CAP,),
            mode="all",
            direction="low",
            dtype=jnp.int64,
            narrow=True,
        )
    total, vert, edge = (np.asarray(x) for x in out)
    pu, pv = per_vertex_counts(g)
    assert KERNEL_BATCH * pu.max() <= I32_MAX < int(total)
    assert int(total) == reps * global_count(g)
    assert vert.max() > I32_MAX
    assert np.array_equal(vert[rg.rank_of_u], reps * pu)
    assert np.array_equal(vert[rg.rank_of_v], reps * pv)
    assert np.array_equal(edge, reps * per_edge_counts(g))


def test_narrow_partials_bound():
    """int32 partials only for 64-bit integer accumulators, a per-vertex
    or per-edge mode, kernel batches within the bound (which holds at
    MAX_TILE_CAP), and a largest degree D with D * D < 2^31."""
    assert KERNEL_BATCH * MAX_TILE_CAP * (MAX_TILE_CAP - 1) <= I32_MAX
    assert narrow_partials("int64", "all", MAX_TILE_CAP, 46340)
    assert narrow_partials("uint64", "vertex", 128, 1)
    assert not narrow_partials("int64", "all", MAX_TILE_CAP, 46341)
    assert not narrow_partials("int64", "edge", 4 * MAX_TILE_CAP, 2)
    assert not narrow_partials("int64", "global", 128, 2)
    assert not narrow_partials("int64", "all", 128, None)
    for dtype in ("int32", "float32", "float64"):
        assert not narrow_partials(dtype, "all", 128, 2)


@pytest.mark.parametrize("dtype,mode", [
    ("int32", "all"), ("float32", "all"), ("float64", "vertex"),
    ("int64", "global"),
])
def test_fused_pallas_program_unchanged_without_narrowing(dtype, mode):
    """Accumulators that do not narrow launch ``narrow=False``: every
    scatter-add under ``accumulate`` is in the accumulator dtype, as
    before int32 partials existed."""
    g = rand_graph(30, 20, 260, 5)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    with jax.enable_x64(True):
        out, prog, args, kw = _launched(lambda: count_from_ranked(
            rg, mode=mode, engine="fused_pallas", count_dtype=dtype,
        ))
        assert kw["narrow"] is False
        assert set(_accumulate_scatters(prog, args, kw)) <= {dtype}
    total = np.asarray(jax.tree_util.tree_leaves(out)[0])
    if mode != "vertex":
        assert total == global_count(g)


@pytest.mark.parametrize("degree", [46340, 46341])
def test_fused_pallas_narrows_up_to_degree_46340(degree):
    """K(2, D) counted in int64: the plan records the largest degree D,
    which narrows at 46340 and not at 46341; both are exact."""
    e = np.stack([np.repeat([0, 1], degree),
                  np.tile(np.arange(degree), 2)], axis=1)
    g = BipartiteGraph(2, degree, e)
    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    with jax.enable_x64(True):
        out, prog, args, kw = _launched(lambda: count_from_ranked(
            rg, mode="all", engine="fused_pallas", count_dtype=jnp.int64,
        ))
        assert kw["narrow"] == (degree == 46340)
        if not kw["narrow"]:
            assert set(_accumulate_scatters(prog, args, kw)) == {"int64"}
    total, vert, edge = (np.asarray(x) for x in out)
    c2 = degree * (degree - 1) // 2
    assert int(total) == c2
    assert np.array_equal(vert[rg.rank_of_u], [c2, c2])
    assert (vert[rg.rank_of_v] == degree - 1).all()
    assert (edge == degree - 1).all()


def test_auto_chunk_budget():
    """max_chunk="auto" resolves to a sane positive budget on every
    backend (documented default when memory stats are unavailable) and
    the auto-budgeted engines stay correct."""
    b = auto_chunk_budget()
    assert (1 << 14) <= b <= (1 << 24)
    g = rand_graph(15, 12, 50, 5)
    for engine in ("xla", "fused"):
        r = count_butterflies(
            g, mode="all", engine=engine, max_chunk="auto"
        )
        assert int(r.total) == global_count(g), engine


def test_fused_temp_memory_is_o_tile_not_o_w():
    """The acceptance-criterion regression: the fused path's compiled
    temp footprint must NOT scale with the wedge total W, while the
    materialize-then-aggregate path's does. Two graphs with ~8x wedge
    totals and the same edge count; budgets held fixed."""
    direction, dtype, chunk = "low", jnp.int32, 1 << 12
    m = 6_000
    g_small = rand_graph(2_500, 2_000, m, 11)  # sparse -> few wedges
    g_big = rand_graph(70, 55, m, 11)  # dense -> many wedges
    stats = {}
    for name, g in (("small", g_small), ("big", g_big)):
        rg = preprocess(g, make_order(g, "degree"), order_name="degree")
        dg = device_graph(rg)
        wv = host_wedge_counts(rg, direction)
        w_total = int(wv.sum())
        bounds, chunk_cap = plan_wedge_chunks(
            rg, direction, chunk, wv_slots=wv
        )
        fused = _count_stream_device.lower(
            dg, jnp.asarray(bounds, jnp.int32), chunk_cap=chunk_cap,
            aggregation="hash", mode="all", direction=direction,
            dtype=dtype, engine="xla", hash_bits=None,
        ).compile().memory_analysis()
        w_cap = max(128, ((w_total + 127) // 128) * 128)
        full = _count_device.lower(
            dg, w_cap=w_cap, aggregation="hash", mode="all",
            direction=direction, dtype=dtype, engine="xla",
            hash_bits=None,
        ).compile().memory_analysis()
        stats[name] = dict(
            wedges=w_total,
            fused_temp=int(fused.temp_size_in_bytes),
            full_temp=int(full.temp_size_in_bytes),
        )
    ratio_w = stats["big"]["wedges"] / max(stats["small"]["wedges"], 1)
    assert ratio_w >= 8, stats  # the experiment is meaningful
    ratio_fused = stats["big"]["fused_temp"] / max(
        stats["small"]["fused_temp"], 1
    )
    ratio_full = stats["big"]["full_temp"] / max(
        stats["small"]["full_temp"], 1
    )
    # fused: O(tile) — flat in W (slack for CSR-sized temporaries);
    # materializing: O(W) — tracks the wedge ratio
    assert ratio_fused < 2.0, stats
    assert ratio_full > ratio_w / 2, stats
    assert stats["big"]["fused_temp"] < stats["big"]["full_temp"], stats


def test_distributed_fused_subprocess_multidev():
    """The distributed engine's per-device slices route through the
    shared fused tile loop: 4 forced host devices, fused vs slice
    engines bitwise-equal and oracle-exact (plain Mesh — runs on
    container jax without AxisType)."""
    from repro.core.distributed import launch_device_worker

    code = """
import numpy as np, jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import BipartiteGraph
from repro.core.oracle import global_count, per_vertex_counts
from repro.core.distributed import distributed_count

mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)
e = np.stack([rng.integers(0, 40, 220), rng.integers(0, 30, 220)], axis=1)
g = BipartiteGraph(40, 30, e)
got, rg = distributed_count(g, mesh, mode="global", engine="fused",
                            max_chunk=64)
assert int(got) == global_count(g), (int(got), global_count(g))
a, _ = distributed_count(g, mesh, mode="vertex", engine="fused",
                         max_chunk=64)
b, _ = distributed_count(g, mesh, mode="vertex", engine="slice")
assert np.array_equal(np.asarray(a), np.asarray(b))
pu, pv = per_vertex_counts(g)
ga = np.asarray(a)
assert np.array_equal(ga[rg.rank_of_u], pu)
assert np.array_equal(ga[rg.rank_of_v], pv)
print("DIST_FUSED_OK")
"""
    out = launch_device_worker(code, devices=4, retries=1)
    assert "DIST_FUSED_OK" in out

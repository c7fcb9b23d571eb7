"""Butterfly counting: global, per-vertex, per-edge (paper Algs. 3-4).

Given the group multiplicity ``d`` of each endpoint pair (x1, x2):
  - each endpoint gets C(d, 2) butterflies,
  - each wedge's center gets d - 1,
  - each wedge's two edges get d - 1  (Lemma 4.2).

Counts are accumulated over *rank-space* vertex ids and undirected edge
ids, then mapped back to original (U, V) ids by the public API.

This module is the counting *frontend* of the plan -> execute -> report
pipeline (``core/pipeline.py``): it validates knobs, builds a
:class:`~repro.core.pipeline.WedgePlan` for the tiled engines, hands it
to the shared executors, and interprets the rank-space results back
into a :class:`CountResult`. The tile loop, the aggregation machinery
(including the in-graph hash-overflow sort fallback), the Lemma 4.2
accumulators, and the Pallas tile-kernel dispatch all live in the
pipeline — peeling streams its frontier subtraction through the same
code.

Performance engine
------------------
``engine="xla"`` (default) keeps every step in pure jnp. ``engine=
"pallas"`` routes the two kernel-shaped steps through the Pallas TPU
kernels in ``repro.kernels``:

  - the hash/dense histogram -> ``wedge_histogram_pallas`` (one-hot MXU
    matmul; see ``aggregate._histogram``),
  - the d -> (d - 1, C(d, 2)) transform -> ``butterfly_combine_pallas``
    (64-bit C(d, 2) as two int32 limbs, recombined into the count
    dtype by ``pipeline.combine_limbs`` — exact for the whole int32
    multiplicity range, no fallback path).

Interpret mode is chosen automatically per backend by
``kernels/ops._interpret_default()``: compiled on TPU, interpreted
elsewhere — so CPU CI exercises the same kernel code paths. Exact
totals are obtained by recombining the kernel's per-group C(d, 2)
limbs in the count dtype (the kernel's f32 scalar reduction is
diagnostic only).

Fused engines (zero materialization)
------------------------------------
``engine="fused"`` and ``engine="fused_pallas"`` never materialize the
global wedge array. The flat wedge space is cut into *vertex-aligned*
tiles (``wedges.plan_wedge_chunks`` — flat wedge ids follow CSR slot
order, so every endpoint-pair group lives inside one iterating
endpoint's contiguous range; cutting only at vertex boundaries keeps
per-tile aggregation exact and the per-tile counts additive). Each
tile is generated (the ``wedges_at`` binary-search recovery),
aggregated, combined, accumulated, and DISCARDED inside one program:

  - ``"fused"`` — pure-XLA flavor: the jitted
    ``pipeline.run_count_tiles`` fori_loop (tile-local sort/hash/
    histogram aggregation, same in-graph hash-overflow sort fallback).
    CPU/GPU get the O(tile) memory win with no interpret-mode overhead.
  - ``"fused_pallas"`` — ``pipeline.run_fused_pallas_program``: per
    batch of kernel tiles, XLA recovers the wedges, the
    ``kernels.wedge_fused`` Pallas kernel aggregates them (all-pairs
    match on the MXU + in-register combine), and XLA scatters the
    per-lane contributions. Vertices owning more wedges than the
    kernel tile holds (``MAX_TILE_CAP``) get per-vertex XLA tiles
    inside the same program, aggregated over a dense table keyed by the
    wedge's far endpoint (no sort); the plan summary on the report
    counts both kinds (``kernel:N,vertex:M``).

Both are bitwise-identical to ``engine="xla"`` wherever counts fit the
dtype; peak temp memory is O(tile) instead of O(W) (asserted by the
memory-analysis regression test in tests/test_fused.py).

``aggregation="auto"`` (fused engine) resolves the sort-vs-hash
strategy *per tile* at plan time from the tile's wedge density
(``pipeline.plan_count``); both strategies are exact, so the choice is
bitwise-invisible. Rungs without a tile plan (the ladder's xla/pallas
descent) resolve ``"auto"`` to ``"sort"``.

``mode="all"`` computes global + per-vertex + per-edge counts from ONE
wedge materialization + ONE aggregation (previously three full engine
runs — the wedge gather + sort dominates, so this is a ~3x saving for
callers that want all three views). It now also covers the batch
aggregations (one combined [vertex | edge] scatter per block).

``max_chunk`` bounds peak device memory: an explicit int, or
``"auto"`` to derive the budget from the device memory stats
(``wedges.auto_chunk_budget``; documented default off-accelerator).
For xla/pallas the flat wedge space streams only when the wedge total
exceeds the budget; the fused engines always tile (budget defaults to
auto). Streaming uses a ``fori_loop`` of fixed-size vertex-aligned
chunks, each re-aggregated locally — peak wedge-buffer size is
O(chunk_cap) instead of O(W).

Overflow note: butterfly counts on large graphs exceed int32; enable
x64 (``jax.config.update("jax_enable_x64", True)``) and pass
``count_dtype=jnp.int64`` — the benchmarks do this.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..testing import faults as _faults
from . import pipeline as _pipeline
from . import resilience as _res
from .graph import BipartiteGraph, RankedGraph, preprocess
from .ranking import make_order
from .wedges import (
    DeviceGraph,
    auto_chunk_budget,
    device_graph,
    gather_wedges,
    greedy_vertex_blocks,
    host_wedge_counts,
    shrink_budget,
    slot_wedge_counts,
)

__all__ = [
    "CountResult",
    "count_butterflies",
    "count_from_ranked",
    "count_validator",
    "interpret_counts",
    "default_count_dtype",
    "ENGINES",
    "MODES",
]

ENGINES = ("xla", "pallas", "fused", "fused_pallas")
MODES = _pipeline.MODES

# Degradation ladder per requested engine (resilience.ResiliencePolicy
# descends left to right; every rung is bitwise-identical where it
# applies, so descent changes strategy, never results).
#
# The "sample" entry is the approximate tier's zero-cost rung
# (core/approx.py): NOT part of any exact ladder — an estimate is not
# bitwise-identical to an exact count — but appended below the exact
# rungs when a caller opts into accuracy="approx" (serve/service.py),
# so a deadline too tight for any exact engine still gets a seeded
# sampled answer with error bars instead of a stale result or a typed
# failure. Estimates are explicitly marked (ApproxCount + the
# response's approximate flag); degradation still never silently
# changes what an *exact* answer means.
COUNT_LADDERS = {
    "fused_pallas": ("fused_pallas", "fused", "xla"),
    "fused": ("fused", "xla"),
    "pallas": ("pallas", "xla"),
    "xla": ("xla",),
    "sample": ("sample",),
}

# Pre-pipeline private names, re-bound for compatibility: tests,
# benchmarks, and notebooks grew against ``count._fused_tile_apply``
# and friends before the executor moved into the pipeline. These are
# the pipeline's *public* names (the layering check forbids reaching
# into its privates) — new code should import from ``pipeline``.
_choose2 = _pipeline.choose2
_combine_limbs = _pipeline.combine_limbs
_group_choose2 = _pipeline.group_choose2
_wedge_dm1 = _pipeline.wedge_dm1
_accumulate = _pipeline.accumulate_counts
_fused_tile_apply = _pipeline.tile_apply
_aggregate_and_accumulate = _pipeline.aggregate_and_accumulate
_zero_counts = _pipeline.zero_counts
_fused_tile_step = _pipeline.count_tile_step
_count_stream_device = _pipeline.run_count_tiles


def default_count_dtype():
    """Widest count dtype JAX will actually honor: int64 under x64,
    int32 otherwise.

    Requesting int64 without x64 enabled does not fail — JAX truncates
    to int32 and emits a UserWarning per call site. Callers that want
    "as wide as available" use this instead of hard-coding jnp.int64.
    """
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


class CountResult(NamedTuple):
    """``mode="all"`` populates total, per_u, per_v, and per_edge from a
    single-pass run; single modes populate only their own field."""

    mode: str
    total: Optional[np.ndarray]  # scalar (global / all modes)
    per_u: Optional[np.ndarray]  # (n_u,)
    per_v: Optional[np.ndarray]  # (n_v,)
    per_edge: Optional[np.ndarray]  # (m,) aligned with g.edges rows
    aggregation: str
    order: str
    report: Optional["_res.ExecutionReport"] = None  # resilience audit


@functools.partial(
    jax.jit,
    static_argnames=(
        "w_cap", "aggregation", "mode", "direction", "dtype", "engine",
        "hash_bits",
    ),
)
def _count_device(
    dg: DeviceGraph,
    *,
    w_cap: int,
    aggregation: str,
    mode: str,
    direction: str,
    dtype,
    engine: str = "xla",
    hash_bits: Optional[int] = None,
):
    """Materializing xla/pallas path: gather the whole wedge array
    (W <= budget) and aggregate it in one shot."""
    cnt = slot_wedge_counts(dg, direction)
    w = gather_wedges(dg, cnt, w_cap, direction)
    return _pipeline.aggregate_and_accumulate(
        dg, w, aggregation, mode, dtype, engine, hash_bits
    )


def _batch_bounds(
    wv: np.ndarray, n: int, wedge_aware: bool, rows: int, target: int
) -> tuple[np.ndarray, int]:
    """Vertex-block boundaries for batching.

    simple: fixed ``rows`` vertices per block. wedge-aware: greedy blocks
    of <= rows vertices capped at ~``target`` wedges (paper §3.1.2).
    Both delegate to the vectorized cumsum/searchsorted sweep in
    ``wedges.greedy_vertex_blocks``.
    Returns (boundaries array (n_blocks+1,), max wedges per block).
    """
    return greedy_vertex_blocks(
        wv, n, rows=rows, target=target if wedge_aware else None
    )


@functools.partial(
    jax.jit,
    static_argnames=("chunk_cap", "rows", "mode", "direction", "dtype"),
)
def _count_batch_device(
    dg: DeviceGraph,
    bounds: jax.Array,  # (n_blocks + 1,) vertex boundaries
    *,
    chunk_cap: int,
    rows: int,
    mode: str,
    direction: str,
    dtype,
):
    """Batch aggregation (paper's simple/wedge-aware batching).

    Each block owns the wedges of a contiguous vertex range (wedge ids
    follow CSR order, so the range is contiguous in wedge space). A
    dense (rows, n_pad) table plays the per-worker array of the paper;
    the group-representative trick (scatter-min of wedge ids) replaces
    the serial 'first time I see this endpoint' test.
    """
    cnt = slot_wedge_counts(dg, direction)
    w_off = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt.astype(jnp.int32))]
    )
    n_blocks = bounds.shape[0] - 1
    n_pad = dg.n_pad

    if mode == "global":
        acc0 = jnp.zeros((), dtype)
    elif mode == "vertex":
        acc0 = jnp.zeros((n_pad,), dtype)
    elif mode == "edge":
        acc0 = jnp.zeros((dg.m,), dtype)
    else:  # all: scalar total + one combined [vertex | edge] buffer
        acc0 = (jnp.zeros((), dtype), jnp.zeros((n_pad + dg.m,), dtype))

    def body(i, acc):
        v0 = bounds[i]
        v1 = bounds[i + 1]
        ws = w_off[dg.offsets[v0]]
        we = w_off[dg.offsets[v1]]
        wid = ws + jnp.arange(chunk_cap, dtype=jnp.int32)
        valid = wid < we
        wc = jnp.minimum(wid, jnp.maximum(we - 1, 0))
        e = jnp.searchsorted(w_off, wc, side="right").astype(jnp.int32) - 1
        e = jnp.clip(e, 0, dg.e_pad - 1)
        j = wc - w_off[e]
        y = dg.neighbors[e]
        y_safe = jnp.minimum(y, n_pad - 1)
        if direction == "low":
            x1 = dg.edge_src[e]
            pos = dg.offsets[y_safe + 1] - cnt[e] + j
            x2 = dg.neighbors[jnp.clip(pos, 0, dg.e_pad - 1)]
        else:
            x2 = dg.edge_src[e]
            pos = dg.offsets[y_safe] + j
            x1 = dg.neighbors[jnp.clip(pos, 0, dg.e_pad - 1)]
        pos = jnp.clip(pos, 0, dg.e_pad - 1)
        # Blocks follow the *iterated* endpoint (= edge_src): x1 under
        # "low", x2 under the cache-optimized "high" direction. The
        # table column is the other endpoint.
        if direction == "low":
            row, col = x1 - v0, x2
        else:
            row, col = x2 - v0, x1
        tkey = row * n_pad + col
        tkey = jnp.where(valid, tkey, rows * n_pad)  # OOB -> dropped
        table = jnp.zeros((rows * n_pad,), jnp.int32).at[tkey].add(1)
        lid = jnp.arange(chunk_cap, dtype=jnp.int32)
        rep_t = (
            jnp.full((rows * n_pad,), chunk_cap, jnp.int32).at[tkey].min(lid)
        )
        tkey_safe = jnp.minimum(tkey, rows * n_pad - 1)
        d = jnp.where(valid, table[tkey_safe], 0)
        rep = valid & (rep_t[tkey_safe] == lid)
        dm1 = jnp.where(valid & (d > 0), (d - 1).astype(dtype), 0)
        if mode == "global":
            # explicit cast: under x64 jnp.sum may widen and break the
            # fori_loop carry dtype
            return (acc + jnp.sum(jnp.where(rep, _choose2(d, dtype), 0))).astype(dtype)
        if mode == "vertex":
            g_add = jnp.where(rep, _choose2(d, dtype), 0)
            acc = acc.at[jnp.where(rep, x1, n_pad)].add(g_add)
            acc = acc.at[jnp.where(rep, x2, n_pad)].add(g_add)
            acc = acc.at[jnp.where(valid, y, n_pad)].add(dm1)
            return acc
        if mode == "edge":
            acc = acc.at[dg.undirected_id[e]].add(dm1)
            acc = acc.at[dg.undirected_id[pos]].add(dm1)
            return acc
        # mode == "all": same fused-scatter shape as
        # pipeline.accumulate_counts — one combined [vertex | edge]
        # buffer per block, integer adds commute so the split views are
        # bitwise-identical to the three single-mode batch runs.
        tot, buf = acc
        g_add = jnp.where(rep, _choose2(d, dtype), 0)
        nm = n_pad + dg.m
        oob = jnp.int32(nm)
        idx = jnp.concatenate([
            jnp.where(rep, x1, oob),
            jnp.where(rep, x2, oob),
            jnp.where(valid, y, oob),
            jnp.where(valid, n_pad + dg.undirected_id[e], oob),
            jnp.where(valid, n_pad + dg.undirected_id[pos], oob),
        ])
        upd = jnp.concatenate([g_add, g_add, dm1, dm1, dm1])
        return (
            (tot + jnp.sum(g_add)).astype(dtype),
            buf.at[idx].add(upd),
        )

    out = jax.lax.fori_loop(0, n_blocks, body, acc0)
    if mode == "all":
        tot, buf = out
        return tot, buf[: n_pad], buf[n_pad :]
    return out


def _resolve_chunk_budget(max_chunk) -> Optional[int]:
    """``max_chunk`` knob: None (no streaming for the materializing
    engines; auto for the fused engines), "auto" (device-memory-derived
    budget, see ``wedges.auto_chunk_budget``), or an explicit int."""
    if max_chunk is None:
        return None
    if max_chunk == "auto":
        return auto_chunk_budget()
    return int(max_chunk)


@_pipeline.traced("plan")
def _plan_from_knobs(
    rg: RankedGraph,
    *,
    aggregation: str,
    mode: str,
    direction: str,
    dtype,
    engine: str,
    max_chunk,
    hash_bits: Optional[int],
    wv_slots: Optional[np.ndarray] = None,
) -> Optional["_pipeline.WedgePlan"]:
    """Resolve this module's knob surface into a pipeline counting plan
    — the one place the budget/clamp rules live. Returns None for knob
    combinations that never tile (the materializing xla/pallas path
    under budget, and the self-contained batch aggregations)."""
    if aggregation in ("batch", "batch_wa"):
        return None  # batch fuses its own accumulation: no tile plan
    budget = _resolve_chunk_budget(max_chunk)
    if wv_slots is None:
        wv_slots = host_wedge_counts(rg, direction)
    if engine in ("fused", "fused_pallas"):
        if budget is None:
            budget = auto_chunk_budget()
    else:
        if budget is None or int(wv_slots.sum()) <= budget:
            return None
    return _pipeline.plan_count(
        rg,
        mode=mode,
        direction=direction,
        aggregation=aggregation,
        budget=budget,
        dtype=jnp.dtype(dtype).name,
        hash_bits=hash_bits,
        engine=engine,
        wv_slots=wv_slots,
    )


def count_from_ranked(
    rg: RankedGraph,
    *,
    aggregation: str = "sort",
    mode: str = "global",
    cache_opt: bool = False,
    count_dtype=None,
    batch_rows: int = 8,
    batch_target: int = 1 << 14,
    engine: str = "xla",
    max_chunk=None,
    hash_bits: Optional[int] = None,
):
    """Count butterflies on a preprocessed graph. Returns rank-space
    device arrays (a scalar for global mode; a (total, per-vertex,
    per-edge) triple for ``mode="all"``).

    ``engine="pallas"`` routes the histogram and combine steps through
    the Pallas kernels (interpret mode off-TPU). ``engine="fused"`` /
    ``engine="fused_pallas"`` never materialize the global wedge
    array: a :func:`~repro.core.pipeline.plan_count` plan cuts the
    flat wedge space into vertex-aligned tiles that are generated,
    aggregated, accumulated, and discarded inside one program — peak
    temp memory O(tile), not O(W). ``max_chunk`` bounds the
    tile/stream budget: an int, ``"auto"`` (derived from device memory
    stats), or None (materialize for xla/pallas; auto for the fused
    engines). ``hash_bits`` overrides the hash-table size (testing
    hook for the in-graph overflow fallback).
    """
    if engine not in ENGINES:
        raise ValueError(f"engine must be {'|'.join(ENGINES)}, got {engine}")
    if mode not in MODES:
        raise ValueError(f"mode must be {'|'.join(MODES)}, got {mode}")
    _faults.maybe_oom(f"count.{engine}")
    # slow_rung fault: burn deadline budget at this rung's entry (host
    # side, pre-trace) so budget-aware ladder walks must skip or degrade
    _faults.maybe_slow_rung(f"count.{engine}")
    # hash_overflow fault: shrink the bounded-probe table so the
    # in-graph sort fallback (the ladder's in-program rung) must fire
    hash_bits = _faults.hash_bits_override(f"count.{engine}", hash_bits)
    dtype = count_dtype or jnp.int32
    direction = "high" if cache_opt else "low"
    if aggregation == "auto" and engine not in ("fused", "fused_pallas"):
        # per-tile strategy choice needs a tile plan; the materializing
        # rungs (including the resilience ladder's xla descent) resolve
        # to sort — bitwise-identical, both strategies are exact
        aggregation = "sort"
    dg = device_graph(rg)
    with _pipeline.span("plan"):
        wv_slots = host_wedge_counts(rg, direction)
    if aggregation in ("batch", "batch_wa"):
        if engine != "xla":
            raise ValueError(
                "batch aggregations fuse their own accumulation and do "
                "not route through the Pallas or fused engines; use "
                "engine='xla'"
            )
        with _pipeline.span("plan"):
            # per-vertex wedge counts (by iterating endpoint)
            src = rg.edge_src[: 2 * rg.m]
            wv = np.zeros(rg.n_pad, dtype=np.int64)
            np.add.at(wv, src, wv_slots[: 2 * rg.m])
            bounds, chunk = _batch_bounds(
                wv, rg.n_pad, aggregation == "batch_wa", batch_rows,
                batch_target,
            )
            chunk_cap = max(128, ((chunk + 127) // 128) * 128)
        out = _pipeline.launch(
            _count_batch_device,
            dg,
            jnp.asarray(bounds, jnp.int32),
            chunk_cap=chunk_cap,
            rows=batch_rows,
            mode=mode,
            direction=direction,
            dtype=dtype,
        )
        return out
    plan = _plan_from_knobs(
        rg,
        aggregation=aggregation,
        mode=mode,
        direction=direction,
        dtype=dtype,
        engine=engine,
        max_chunk=max_chunk,
        hash_bits=hash_bits,
        wv_slots=wv_slots,
    )
    if plan is not None:
        return _pipeline.execute_count_plan(dg, plan)
    w_total = int(wv_slots.sum())
    w_cap = max(128, ((w_total + 127) // 128) * 128)
    out, _ok = _pipeline.launch(
        _count_device,
        dg,
        w_cap=w_cap,
        aggregation=aggregation,
        mode=mode,
        direction=direction,
        dtype=dtype,
        engine=engine,
        hash_bits=hash_bits,
    )
    return out


def count_validator(g: BipartiteGraph, mode: str):
    """Result-invariant check for the counting ladder: Σ C(d, 2) over
    endpoint-pair groups with Σ d = W is maximized by one group holding
    all W wedges (convexity), so every count — total, per-vertex,
    per-edge — is bounded by ``ub = C(min(w_u, w_v), 2)`` and
    non-negative. A violating rung result (poisoned tile, corrupted
    scatter) demotes to the next rung instead of being returned. When
    ``ub`` does not fit the result dtype the engines' documented
    wraparound regime is in effect and the check stands down."""
    w_u, w_v = g.wedge_totals()
    w = min(w_u, w_v)
    ub = w * (w - 1) // 2

    def _bad(name, arr):
        arr = np.asarray(arr)
        if arr.size == 0:
            return None
        if ub > int(np.iinfo(arr.dtype).max):
            return None
        lo, hi = int(arr.min()), int(arr.max())
        if lo < 0:
            return f"negative {name} count {lo}"
        if hi > ub:
            return f"{name} count {hi} exceeds the C(W, 2) bound {ub}"
        return None

    def check(host_out):
        if mode == "all":
            total, bv, be = host_out
            for name, arr in (("total", total), ("per-vertex", bv),
                              ("per-edge", be)):
                problem = _bad(name, arr)
                if problem is not None:
                    return problem
            return None
        name = {"global": "total", "vertex": "per-vertex",
                "edge": "per-edge"}[mode]
        return _bad(name, host_out)

    return check


# historical private name, kept for in-tree callers
_count_validator = count_validator


def interpret_counts(
    rg: RankedGraph,
    g: BipartiteGraph,
    mode: str,
    out,
    aggregation: str,
    order: str,
) -> CountResult:
    """Interpret a rank-space engine output (the host-side value a
    counting rung returns) into a :class:`CountResult` in the caller's
    vertex numbering. Split out of :func:`count_butterflies` so the
    serving layer can run the ladder itself (with its own deadline /
    breaker hooks over :func:`count_from_ranked` rungs) and still get
    the same result shape the one-shot entry point produces."""

    def _scatter_vertex(bv: np.ndarray):
        per_u = np.zeros(g.n_u, bv.dtype)
        per_v = np.zeros(g.n_v, bv.dtype)
        per_u[:] = bv[rg.rank_of_u]
        per_v[:] = bv[rg.rank_of_v]
        return per_u, per_v

    if mode == "all":
        total, bv, be = out
        per_u, per_v = _scatter_vertex(np.asarray(bv))
        return CountResult(
            mode, np.asarray(total), per_u, per_v, np.asarray(be),
            aggregation, order,
        )
    if mode == "global":
        return CountResult(
            mode, np.asarray(out), None, None, None, aggregation, order
        )
    if mode == "vertex":
        per_u, per_v = _scatter_vertex(np.asarray(out))
        return CountResult(
            mode, None, per_u, per_v, None, aggregation, order
        )
    return CountResult(
        mode, None, None, None, np.asarray(out), aggregation, order
    )


@_pipeline.traced("count_butterflies")
def count_butterflies(
    g: BipartiteGraph,
    *,
    order: str = "degree",
    aggregation: str = "sort",
    mode: str = "global",
    cache_opt: bool = False,
    count_dtype=None,
    batch_rows: int = 8,
    engine: str = "xla",
    max_chunk=None,
    resilience=None,
) -> CountResult:
    """Public entry point: rank -> plan -> execute -> report.

    Execution runs under the resilience degradation ladder
    (``COUNT_LADDERS``) via :func:`~repro.core.pipeline.execute_ladder`:
    the requested engine is tried first and a capacity overflow (e.g.
    the fused_pallas kernel's tile bound), a RESOURCE_EXHAUSTED
    (retried with a halved ``max_chunk`` budget first), or a
    result-invariant violation descends to the next bitwise-identical
    rung — ``fused_pallas -> fused -> xla``. ``resilience`` accepts
    None/True (default policy), False (disable validation/retries/
    report; rung descent — the engines' documented semantics — still
    applies), or a :class:`~repro.core.resilience.ResiliencePolicy`.
    The returned :class:`CountResult` carries the
    :class:`~repro.core.resilience.ExecutionReport` in ``.report``,
    whose ``.plan`` records the requested engine's tile plan summary
    (tile count, per-tile strategy mix, capacity segments).
    Preprocessing is shared across rungs, so a fallback never repays
    the O(m log m) ranking. The worst-case accumulator preflight
    (:meth:`BipartiteGraph.accumulator_preflight`) raises
    :class:`~repro.core.resilience.AccumulatorOverflowRisk` up front
    when even two-limb int32 accumulation could silently wrap.
    """
    policy = _res.resolve_policy(resilience)
    ordering = make_order(g, order)
    rg = preprocess(g, ordering, order_name=order)
    if policy.validate_results:
        g.accumulator_preflight()
    ladder = COUNT_LADDERS.get(engine, (engine,))
    if aggregation in ("batch", "batch_wa"):
        ladder = (engine,)  # batch fuses its own accumulation: one rung

    def _make_rung(eng):
        def run(shrinks):
            mc = max_chunk
            if shrinks:
                base = _resolve_chunk_budget(mc)
                if base is None:
                    base = auto_chunk_budget()
                mc = shrink_budget(base, shrinks)
            out = count_from_ranked(
                rg,
                aggregation=aggregation,
                mode=mode,
                cache_opt=cache_opt,
                count_dtype=count_dtype,
                batch_rows=batch_rows,
                engine=eng,
                max_chunk=mc,
            )
            return _pipeline.fetch(out)

        return _res.Rung(eng, run)

    # report-only planning pass for the requested engine: what the first
    # rung will execute, recorded on the report before any rung runs
    # (pure host numpy — a failed/degraded rung still reports its plan)
    try:
        plan = _plan_from_knobs(
            rg,
            aggregation=aggregation,
            mode=mode,
            direction="high" if cache_opt else "low",
            dtype=(count_dtype or jnp.int32),
            engine=engine,
            max_chunk=max_chunk,
            hash_bits=None,
        )
    except _res.ResilienceError:
        plan = None

    out, report = _pipeline.execute_ladder(
        "count",
        policy,
        [_make_rung(e) for e in ladder],
        count_validator(g, mode),
        plan=plan,
    )
    res = interpret_counts(rg, g, mode, out, aggregation, order)
    return policy.attach(res, report)

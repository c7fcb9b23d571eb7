"""Butterfly peeling: tip (vertex) and wing (edge) decomposition
(paper §4.3, Algs. 5-7).

Round structure (all engines):
  κ <- max(κ, min butterfly count among alive)   [bucketing extract-min]
  A <- all alive with count <= κ                 [peel whole bucket]
  enumerate wedges/butterflies incident to A     [prefix-sum expansion
                                                  of the CSR — the
                                                  paper's parallel
                                                  wedge retrieval]
  aggregate + subtract contributions             [same sort/hash
                                                  strategies as counting]

The SPMD bucketing replaces the Fibonacci heap (see fibheap.py and
DESIGN.md §8) with a dense masked min-reduction — the semantics of
extract-min + batch decrease-key are preserved; Julienne's
skip-empty-buckets optimization is inherent (min jumps gaps in O(1)
rounds).

Engines
-------
Every decomposition — tips (PEEL-V, Alg. 5), stored-wedge tips
(WPEEL-V, Alg. 7), and wings (PEEL-E, Alg. 6) — supports
``engine="host"|"device"``:

  - **host** — the original host-driven loop: one blocking
    ``jax.device_get`` per round for extract-min + bucket selection,
    numpy prefix-sum wedge expansion, device aggregation/subtraction.
  - **device** — the whole round loop is one jitted
    ``jax.lax.while_loop``; nothing leaves the device until the final
    ``PeelResult`` fetch (one ``device_get`` per decomposition).

Both engines stream each round's frontier wedge space through
iterating-endpoint-aligned tiles that are generated
(``wedges.ragged_slots_at`` recovery), aggregated tile-locally through
the *same* ``count._fused_tile_apply`` machinery as the fused counting
engine (in-graph hash-overflow sort fallback included), subtracted,
and discarded. Peak per-round temp is O(tile) — asserted by the
compiled ``memory_analysis()`` regression in tests — and per-round
device work tracks the *actual* frontier size. Tile boundaries cut
only at peeled-vertex boundaries (``wedges.aligned_tile_end``), the
``plan_wedge_chunks`` invariant, so no endpoint-pair group spans a
tile and the per-tile C(d, 2) subtractions are exact. WPEEL-V
recovers its tiles straight from the stored-wedge CSR (no per-round
frontier buffer); PEEL-V keeps only its level-1 buffer (O(Σ deg_side)
= O(m)) and tiles the dominant level-2 space; PEEL-E recovers its
per-butterfly triple space straight from flat ids via the
degree-sorted CSR (two chained binary searches plus a division —
``wedges.degree_sorted_csr``).

The device engine's decrease-key is Julienne-style and batched
(``pipeline.apply_decrements``): a sub-round's decrements are exact
int32 scatter-adds into the counts, carried as int32 through its tile
stream, except its last update chunk of at most ``MAX_UPDATE_CAP``
lanes, which goes through ``kernels.ops.bucket_update``; that ONE
kernel pass a sub-round yields the next round's masked min and the
O(log n) geometric-bucket occupancy of the final counts, so the round
loop needs no separate extract-min reduction (the carried min seeds
κ). The Pallas kernel runs compiled on TPU; elsewhere the dispatcher
serves the jnp reference (off-TPU the per-round kernel interpreter
would dominate, the same policy as ``peel_wings``'s host extract-min).

Further device-engine knobs:

  - ``tile_budget`` — wedge budget per tile. The default target is
    deliberately small (1024; the planner floors it by the largest
    single-vertex expansion so tiles always align): unlike counting,
    peeling pays the full tile shape every round, so memory-derived
    budgets would dominate tail rounds.
  - ``max_frontier`` (``peel_tips`` only) bounds PEEL-V's level-1
    expansion buffer; a too-small capacity raises an in-graph overflow
    flag and the caller transparently re-runs the host path — never a
    silent truncation. Counts at or beyond INT32_MAX also route to the
    host engine (``bucket_update`` reduces in int32).

The hash-aggregation overflow fallback is **in-graph** for both
engines: ``lax.cond`` re-aggregates the same materialized wedge tile
with sort only when the bounded-probe table actually overflowed (no
host ``bool(ok)`` sync, no silently wrong counts).

Bucket-range multi-bucket peeling (``peel_mode``)
-------------------------------------------------
Every decomposition and engine supports ``peel_mode="exact"|"range"``:

  - **exact** (default) — one round per distinct peel value: the
    classic κ-driven loop above; ρ = number of distinct-value rounds.
  - **range** — Julienne/Lakhotia-style bucket-range rounds ("Parallel
    Peeling of Bipartite Networks", Lakhotia et al. 2021): each round
    selects the **lowest non-empty geometric bucket** ``[2^(k-1), 2^k)``
    and processes it to completion. The device engine's selection
    consumes the O(log n) occupancy histogram that each sub-round's
    one ``bucket_update`` pass already produces; the host engine
    derives the bucket from the masked min's bit length — the two
    selections provably agree, because the min inhabits the lowest
    non-empty range. Final tip/wing numbers are **bitwise-identical**
    to exact peeling: the in-graph *re-settle* iterations within a
    bucket round replay the exact κ trajectory (peel ``<= κ``,
    subtract, advance κ) until the masked min leaves the bucket —
    fall-ins (survivors whose count drops into the active range
    mid-round) are caught by the same test. ``PeelResult.rounds``
    counts bucket rounds — the
    sync/parallel-round metric that range processing slashes on
    high-ρ graphs — and ``PeelResult.sub_rounds`` keeps the re-settle
    iteration count (== exact mode's ρ) so the trade stays measurable
    (``BENCH_peeling.json`` schema v3 records both).

Shared round-loop substrate
---------------------------
Both jitted device engines are thin parameterizations of one substrate
in ``core/pipeline.py``:

  - ``device_round_loop`` — the ``lax.while_loop`` round skeleton:
    carried extract-min, κ update, exact-vs-range round accounting,
    peel-set selection, and the overflow latch, parameterized by an
    ``expand`` callable that turns one round's peel set into count
    decrements.
  - ``stream_tiles`` — the tile ``while_loop``: streams a flat
    per-round id space through fixed-shape tiles
    (iterating-endpoint-aligned for the C(d, 2) tip subtract,
    unaligned for the linear wing subtract), parameterized by a
    per-tile recover/subtract callable; re-derives the carried
    (min, occupancy) on zero-frontier rounds.

Double-count avoidance (paper §4.3.1/§4.3.2): peeled-set members are
processed against a virtual rank order (their id); an element of the
current peel set A is "present" for a lower-id member's enumeration and
"absent" for a higher-id member's.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as _kops
from ..testing import faults as _faults
from . import distributed as _dist  # supervised mesh rung (acyclic:
#   distributed never imports peel — the decomposition callables flow in)
from . import resilience as _res
from .count import count_butterflies, default_count_dtype
from .graph import BipartiteGraph

# The round-loop substrate and the fused tile machinery live in the
# pipeline's execute layer (shared with counting); the pre-pipeline
# private names are re-bound so the engine wrappers below — and the
# tests/benchmarks that grew against them — keep reading naturally.
from .pipeline import (
    I32_MAX as _I32_MAX,
    LoopState as _LoopState,
    execute_ladder as _execute_ladder,
    plan_peel as _plan_peel,
    apply_decrements as _apply_decrements,
    device_round_loop as _device_round_loop,
    fetch as _fetch,
    init_loop_state as _init_state,
    launch as _launch,
    prefix_offsets as _prefix,
    scope as _scope,
    span as _span,
    stream_tiles as _stream_tiles,
    tile_apply as _fused_tile_apply,
    traced as _traced,
)
from .wedges import (
    Wedges,
    _lower_bound_ragged,
    degree_sorted_csr,
    expand_ragged,
    greedy_vertex_blocks,
    ragged_slots_at,
)

__all__ = [
    "PeelResult",
    "peel_tips",
    "peel_tips_stored",
    "peel_wings",
    "peel_validator",
    "PEEL_ENGINES",
    "PEEL_MODES",
]

PEEL_ENGINES = ("host", "device")
PEEL_MODES = ("exact", "range")

# Default tile target of the peeling subtract. Unlike counting — which streams
# the whole wedge space through its tiles ONCE and wants them as large
# as memory allows (auto_chunk_budget) — peeling pays the full tile
# shape EVERY round regardless of the actual frontier size, so the
# default is deliberately small: the planner takes
# max(min(target, total), alignment floor), i.e. effectively the
# 2x-largest-single-vertex alignment floor on real graphs (measured
# ~30x faster than a memory-derived budget on the CPU bench graphs,
# whose tail rounds dominate ρ). Raise ``tile_budget`` for graphs
# whose rounds each release huge frontiers.
_DEFAULT_TILE_TARGET = 1024


class PeelResult(NamedTuple):
    numbers: np.ndarray  # tip number per side-vertex, or wing per edge
    side: Optional[int]  # 0 = U peeled, 1 = V peeled (tips only)
    rounds: int  # ρ: distinct-value rounds (exact) / bucket rounds (range)
    round_sizes: np.ndarray  # peeled per round
    sub_rounds: Optional[int] = None  # range mode: re-settle iterations
    # (== exact mode's ρ); equals ``rounds`` under peel_mode="exact"
    report: Optional["_res.ExecutionReport"] = None  # resilience audit


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenate [s, s+len) ranges — vectorized segment arange."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    ends = np.cumsum(lens)
    idx = np.arange(total, dtype=np.int64)
    seg = np.repeat(np.arange(lens.shape[0], dtype=np.int64), lens)
    base = np.concatenate([[0], ends[:-1]])
    return starts[seg] + idx - base[seg]


def _pow2_pad(x: int, floor: int = 128) -> int:
    c = floor
    while c < x:
        c <<= 1
    return c


@_traced("preprocess")
def _csr(g: BipartiteGraph):
    """Global-id CSR (U ids then V ids), neighbors ascending."""
    n = g.n
    src = np.concatenate([g.edges[:, 0], g.n_u + g.edges[:, 1]])
    dst = np.concatenate([g.n_u + g.edges[:, 1], g.edges[:, 0]])
    uid = np.concatenate([np.arange(g.m), np.arange(g.m)]).astype(np.int64)
    perm = np.lexsort((dst, src))
    src, dst, uid = src[perm], dst[perm], uid[perm]
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=off[1:])
    return off, dst, uid


def _side_and_counts(g, counts, side, count_kwargs):
    """Resolve the peeled side and its per-vertex butterfly counts."""
    w_u, w_v = g.wedge_totals()
    if side is None:
        side = 0 if w_u <= w_v else 1
    if counts is None:
        r = count_butterflies(
            g, mode="vertex", count_dtype=default_count_dtype(),
            **(count_kwargs or {})
        )
        counts = r.per_u if side == 0 else r.per_v
    return side, np.asarray(counts).copy()


def _stored_wedge_csr(g: BipartiteGraph, side: int):
    """All side-oriented wedges keyed by first endpoint (Alg. 7's W_e):
    CSR ``(woff, w_u2)`` with ``w_u2[woff[u]:woff[u+1]]`` the second
    endpoints of u's wedges (u2 != u1). O(Σ deg²_side) space."""
    off, nbr, _ = _csr(g)
    n_side = g.n_u if side == 0 else g.n_v
    base = 0 if side == 0 else g.n_u
    ids = np.arange(n_side) + base
    deg1 = off[ids + 1] - off[ids]
    u1_rep = np.repeat(np.arange(n_side), deg1)
    v_rep = nbr[_ranges(off[ids], deg1)]
    deg2 = off[v_rep + 1] - off[v_rep]
    w_u1 = np.repeat(u1_rep, deg2)
    w_u2 = nbr[_ranges(off[v_rep], deg2)] - base
    keep = w_u2 != w_u1
    w_u1, w_u2 = w_u1[keep], w_u2[keep]
    # CSR over first endpoint (already sorted by construction)
    woff = np.zeros(n_side + 1, dtype=np.int64)
    np.cumsum(np.bincount(w_u1, minlength=n_side), out=woff[1:])
    return woff, w_u2


@_traced("plan")
def _level2_totals(off: np.ndarray, nbr: np.ndarray, base: int,
                   n_side: int) -> np.ndarray:
    """Per-vertex 2-hop expansion totals: w2[u] = Σ_{v in N(u)} deg(v).

    The exact per-round frontier bound of PEEL-V's level-2 space —
    feeds the tile alignment floors and the plan's entity tiles."""
    deg = np.diff(off)
    ids = np.arange(n_side) + base
    d1 = deg[ids]
    w2 = np.zeros(n_side, dtype=np.int64)
    if d1.sum():
        v_rep = nbr[_ranges(off[ids], d1)]
        np.add.at(w2, np.repeat(np.arange(n_side), d1), deg[v_rep])
    return w2


@_scope("subtract")
def _subtract_tile(
    u1: jax.Array,
    u2: jax.Array,
    valid: jax.Array,
    b: jax.Array,
    apply,
    *,
    aggregation: str,
    n_side: int,
    hash_bits: Optional[int] = None,
):
    """Aggregate one tile of (u1, u2) frontier wedge pairs and subtract
    C(d, 2) from B[u2] — the peeling side of the shared fused tile
    machinery (``count._fused_tile_apply``: tile-local sort/hash with
    the in-graph hash-overflow sort fallback). ``apply(b, tgt, dec)``
    applies the grouped decrements — the device loops' batched
    decrease-key (``_apply_decrements``) or the host engines' plain
    scatter — and its result is returned.
    """
    sent = jnp.int32(n_side)
    w = Wedges(
        x1=jnp.where(valid, u1, sent),
        x2=jnp.where(valid, u2, sent),
        y=jnp.where(valid, u1, sent),
        center_slot=u1,
        second_slot=u1,
        valid=valid,
    )

    def consume(_wv, groups):
        d = groups.d.astype(b.dtype)
        # C(d, 2) halved before the product: exact in int32 counts
        # wherever C(d, 2) itself is (it never exceeds B[u2])
        c2 = jnp.where(d % 2 == 0, d // 2 * (d - 1), (d - 1) // 2 * d)
        dec = jnp.where(groups.valid, c2, 0)
        tgt = jnp.where(groups.valid, groups.x2, sent)
        return apply(b, tgt, dec)

    out, _ok = _fused_tile_apply(w, aggregation, consume, "xla", hash_bits)
    return out


def _scatter_decrements(b, tgt, dec):
    """The host engines' subtract: one scatter-add of the decrements."""
    return b.at[tgt].add(-dec)


_subtract_pair_groups = jax.jit(
    lambda u1, u2, valid, b, aggregation, n_pad, hash_bits=None: (
        _subtract_tile(
            u1, u2, valid, b, _scatter_decrements, aggregation=aggregation,
            n_side=n_pad, hash_bits=hash_bits,
        )
    ),
    static_argnames=("aggregation", "n_pad", "hash_bits"),
)


@jax.jit
def _subtract_triples(idx: jax.Array, valid: jax.Array, b: jax.Array):
    """Scatter -1 at idx (flattened butterfly edge triples)."""
    return b.at[jnp.where(valid, idx, b.shape[0])].add(
        -jnp.ones_like(idx, b.dtype)
    )


def _host_subtract_frontier(
    b_dev, u1_w, u2_w, n_side, aggregation, hash_bits, tile_cap
):
    """Host-engine frontier subtract: stream the round's (ascending-u1)
    wedge pairs to the device in u1-aligned tiles — O(tile) device
    temp, one fixed jit shape for the whole decomposition."""
    run_ends = np.flatnonzero(np.diff(u1_w)) + 1
    row_off = np.concatenate([[0], run_ends, [u1_w.size]])
    row_lens = np.diff(row_off)
    vb, _ = greedy_vertex_blocks(row_lens, row_lens.size, target=tile_cap)
    bounds = row_off[vb]
    for ws, we in zip(bounds[:-1], bounds[1:]):
        size = int(we - ws)
        if size == 0:
            continue
        # pad each block to its own pow2 (still <= tile_cap): tail
        # rounds pay their actual size, and the jit cache stays
        # O(log tile_cap) entries
        cap = _pow2_pad(size)
        u1p = np.full(cap, n_side, np.int32)
        u2p = np.full(cap, n_side, np.int32)
        u1p[:size] = u1_w[ws:we]
        u2p[:size] = u2_w[ws:we]
        validp = np.zeros(cap, bool)
        validp[:size] = True
        b_dev = _subtract_pair_groups(
            jnp.asarray(u1p),
            jnp.asarray(u2p),
            jnp.asarray(validp),
            b_dev,
            aggregation=aggregation,
            n_pad=n_side,
            hash_bits=hash_bits,
        )
    return b_dev


def _tip_tile_cap(tile_budget: Optional[int], total: int,
                  floor: int) -> int:
    """Tile shape of the tip engines: the ``tile_budget`` target (at
    most the whole expansion ``total``), floored by the largest
    single-vertex expansion so tiles always align."""
    tb = _DEFAULT_TILE_TARGET if tile_budget is None else int(tile_budget)
    return _pow2_pad(max(min(tb, max(total, 1)), floor))


# ---------------------------------------------------------------------------
# Device-resident tip engine: the pipeline's round-loop substrate with
# 2-hop / stored recovery
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "aggregation", "cap1", "tile_cap", "n_side", "stored", "hash_bits",
        "use_kernel", "peel_mode",
    ),
)
def _peel_tips_device(
    off: jax.Array,  # stored: (n_side+1,) wedge CSR | else (n+1,) graph CSR
    nbr: jax.Array,  # stored: (W,) second endpoints | else (2m,) neighbors
    base: jax.Array,  # () int32 global-id offset of the peeled side
    state: _LoopState,
    *,
    aggregation: str,
    cap1: int,  # level-1 frontier buffer (2-hop engine only)
    tile_cap: int,
    n_side: int,
    stored: bool,
    hash_bits: Optional[int] = None,
    use_kernel: bool = False,
    peel_mode: str = "exact",
):
    """Jitted device round loop (PEEL-V / WPEEL-V): the shared
    ``_device_round_loop`` substrate with the tip decompositions'
    expand callable. Each round's frontier streams through
    ``_stream_tiles`` (tiles recovered via ``ragged_slots_at``,
    boundaries aligned via ``aligned_tile_end``); the subtraction is
    the shared hash/sort aggregation (hash overflow handled in-graph).
    ``overflow`` latches when a round's level-1 expansion exceeds
    ``cap1`` (2-hop engine only); the loop exits immediately and the
    caller re-runs the host path.
    """
    nbr_max = nbr.shape[0] - 1
    want_hist = peel_mode == "range"

    def _tiles(b, alive, roff, recover):
        def tile_fn(bt, wid, tvalid, last):
            with _scope("recover"):
                u1, u2 = recover(wid)
                u2c = jnp.clip(u2, 0, n_side - 1)
                tv = tvalid & (u2 >= 0) & (u2 < n_side) & alive[u2c]
            return _subtract_tile(
                u1.astype(jnp.int32), u2c.astype(jnp.int32), tv, bt,
                lambda c, tgt, dec: _apply_decrements(
                    c, alive, tgt, dec, use_kernel, want_hist, last),
                aggregation=aggregation, n_side=n_side, hash_bits=hash_bits,
            )

        return _stream_tiles(
            b, alive, roff, tile_fn, tile_cap=tile_cap, aligned=True,
            want_hist=want_hist,
        )

    @_scope("recover")
    def expand(args):
        b, alive, _alive_prev, peel = args
        if stored:
            # WPEEL-V: tiles recovered straight from the stored-wedge
            # CSR — no frontier buffer at all
            lens = jnp.where(peel, off[1:] - off[:-1], 0)
            roff = _prefix(lens)
            starts = off[:-1]

            def recover(wid):
                seg, pos = ragged_slots_at(roff, starts, wid)
                return seg, nbr[jnp.clip(pos, 0, nbr_max)]

            b_new, mn2, h2 = _tiles(b, alive, roff, recover)
            return b_new, jnp.array(False), mn2, h2
        # PEEL-V: 2-hop re-enumeration (GET-V-WEDGES). Level 1: peeled
        # u1 -> centers v, materialized (O(m)); level 2: v -> endpoints
        # u2, the dominant space, streams through aligned tiles
        ids = jnp.arange(n_side, dtype=jnp.int32) + base
        lens1 = jnp.where(peel, off[ids + 1] - off[ids], 0)
        seg1, pos1, valid1, tot1 = expand_ragged(off[ids], lens1, cap1)
        v = nbr[jnp.clip(pos1, 0, nbr_max)]
        v = jnp.clip(v, 0, off.shape[0] - 2)
        lens2 = jnp.where(valid1, off[v + 1] - off[v], 0)
        roff2 = _prefix(lens2)
        t2 = jnp.zeros((n_side,), jnp.int32).at[
            jnp.where(valid1, seg1, jnp.int32(n_side))
        ].add(lens2.astype(jnp.int32))
        roff_u = _prefix(t2)
        starts2 = off[v]

        def recover(wid):
            seg2, pos2 = ragged_slots_at(roff2, starts2, wid)
            u1 = seg1[jnp.clip(seg2, 0, cap1 - 1)]
            u2 = nbr[jnp.clip(pos2, 0, nbr_max)] - base
            return u1, u2

        b_new, mn2, h2 = _tiles(b, alive, roff_u, recover)
        ovf = tot1 > cap1
        return jnp.where(ovf, b, b_new), ovf, mn2, h2

    return _device_round_loop(state, expand, peel_mode=peel_mode)


def _peel_tips_device_run(
    g: BipartiteGraph,
    counts: np.ndarray,
    side: int,
    aggregation: str,
    stored: bool,
    max_frontier: Optional[int],
    hash_bits: Optional[int],
    csr,
    tile_budget: Optional[int] = None,
    w2: Optional[np.ndarray] = None,
    peel_mode: str = "exact",
    budget_shrinks: int = 0,
    note: Optional[list] = None,
) -> Optional[PeelResult]:
    """Capacity-plan, run the device loop, fetch once. Returns None
    when the device engine does not apply (empty side, counts beyond
    int32, totals beyond int32 indexing) or the 2-hop engine's level-1
    expansion overflowed its ``max_frontier``-bounded buffer — callers
    fall back to host (the resilience ladder translates the None into
    the typed taxonomy via ``resilience.require_rung``, appending the
    reason to ``note``). ``csr`` is the caller-built ``(woff, w_u2)``
    wedge CSR (stored) or ``(off, nbr)`` graph CSR, shared with the
    host loop so a fallback never rebuilds the dominant preprocessing.
    ``budget_shrinks`` halves the frontier/tile budgets that many times
    (the ladder's RESOURCE_EXHAUSTED re-entry)."""
    note = [] if note is None else note
    n_side = g.n_u if side == 0 else g.n_v
    base = 0 if side == 0 else g.n_u
    if n_side == 0 or int(counts.max(initial=0)) >= _I32_MAX:
        note.append("device engine unavailable: empty side or counts "
                    "beyond int32")
        return None
    with _span("plan"):  # the level-1 buffer and the tile shape
        budget = _I32_MAX if max_frontier is None else int(max_frontier)
        tb = (_DEFAULT_TILE_TARGET if tile_budget is None
              else int(tile_budget))
        if budget_shrinks:
            budget = max(128, budget >> budget_shrinks)
            tb = max(1, tb >> budget_shrinks)
        if stored:
            woff, w_u2 = csr
            total = int(woff[-1])
            if total >= _I32_MAX:
                note.append("device engine unavailable: stored wedge total "
                            "beyond int32 indexing")
                return None
            max_row = int(np.diff(woff).max(initial=0))
            cap1 = 128  # unused by the stored loop
            off_d = jnp.asarray(woff, jnp.int32)
            nbr_d = jnp.asarray(w_u2 if total else np.zeros(1), jnp.int32)
        else:
            off, nbr = csr
            if w2 is None:
                w2 = _level2_totals(off, nbr, base, n_side)
            total = int(w2.sum())
            if total >= _I32_MAX or 2 * g.m >= _I32_MAX:
                note.append("device engine unavailable: expansion totals "
                            "beyond int32 indexing")
                return None
            max_row = int(w2.max(initial=0))
            lvl1 = int(np.diff(off)[base : base + n_side].sum())  # == m
            cap1 = _pow2_pad(min(lvl1, budget))
            off_d = jnp.asarray(off, jnp.int32)
            nbr_d = jnp.asarray(nbr if nbr.size else np.zeros(1),
                                jnp.int32)
        # tiles must fit the largest single-vertex expansion (the
        # alignment floor, like plan_wedge_chunks' single-vertex chunks);
        # the 2x headroom keeps greedy tiles at least half full
        tile_cap = _tip_tile_cap(tb, total, 2 * max_row)
    # counts below INT32_MAX (guarded above) run the int32 kernel in any
    # count dtype; off the compiled backend the reference serves
    use_kernel = not _kops.interpret_default()
    state = _init_state(jnp.asarray(counts), n_side, peel_mode=peel_mode)
    host = _fetch(_launch(
        _peel_tips_device,
        off_d,
        nbr_d,
        jnp.int32(base),
        state,
        aggregation=aggregation,
        cap1=cap1,
        tile_cap=tile_cap,
        n_side=n_side,
        stored=stored,
        hash_bits=hash_bits,
        use_kernel=use_kernel,
        peel_mode=peel_mode,
    ))
    if bool(host.overflow):
        note.append(
            f"bounded frontier buffer overflow (max_frontier budget "
            f"{budget})"
        )
        return None
    rounds = int(host.rounds)
    return PeelResult(
        host.out, side, rounds, host.sizes[:rounds].astype(np.int64),
        sub_rounds=int(host.subr),
    )


def _check_engine(engine: str) -> None:
    if engine not in PEEL_ENGINES:
        raise ValueError(
            f"engine must be {'|'.join(PEEL_ENGINES)}, got {engine}"
        )


def _check_knobs(aggregation: str, peel_mode: str = "exact") -> None:
    if aggregation not in ("sort", "hash"):
        raise ValueError(
            f"peeling aggregation must be sort|hash, got {aggregation}"
        )
    if peel_mode not in PEEL_MODES:
        raise ValueError(
            f"peel_mode must be {'|'.join(PEEL_MODES)}, got {peel_mode}"
        )


class _RoundAccounting:
    """Host-loop round bookkeeping shared by the three host engines —
    the host mirror of the substrate's exact-vs-range accounting.
    Exact mode opens one round per iteration; range mode opens a round
    only when the min leaves the active geometric bucket (the host has
    no carried histogram, so the next range comes from the min's bit
    length — identical to the device selection, see module docstring).
    """

    def __init__(self, peel_mode: str):
        self.range = peel_mode == "range"
        self.rounds = 0
        self.sub_rounds = 0
        self.sizes: list = []
        self._hi = 0

    def open_round(self, mn: int) -> None:
        """Called once per iteration with the pre-peel masked min."""
        self.sub_rounds += 1
        if self.range and mn < self._hi:
            return  # re-settle iteration inside the active bucket
        if self.range:
            self._hi = 1 << int(mn).bit_length()
        self.rounds += 1
        self.sizes.append(0)

    def peeled(self, k: int) -> None:
        self.sizes[-1] += int(k)


def _peel_validator(counts: np.ndarray):
    """Result-invariant validator for the peeling ladders: every peel
    number is the κ of some round's masked min, so the numbers must be
    non-negative and bounded by the max *initial* count. Checked on the
    host-side result only (numpy — never costs a device sync), so a
    poisoned buffer or truncated subtract demotes to the next rung
    instead of escaping as a silent wrong answer. Stands down when the
    initial counts themselves are negative (caller passed garbage the
    engines never promised to interpret)."""
    counts = np.asarray(counts)
    if counts.size == 0 or int(counts.min()) < 0:
        return lambda res: None
    cmax = int(counts.max())

    def validate(res: "PeelResult") -> Optional[str]:
        nums = np.asarray(res.numbers)
        if nums.size == 0:
            return None
        lo, hi = int(nums.min()), int(nums.max())
        if lo < 0:
            return f"negative peel number {lo}"
        if hi > cmax:
            return f"peel number {hi} exceeds max initial count {cmax}"
        return None

    return validate


# public name: the serving layer runs the peeling ladders itself (with
# deadline / breaker hooks) and needs the same result-invariant check
peel_validator = _peel_validator


# ---------------------------------------------------------------------------
# Distributed peeling rung: numpy frontier expansion + partial subtracts
# for the supervised device mesh (distributed.PeelSupervisor). The
# supervisor owns the round loop / checkpointing / recovery; the
# decomposition-specific pieces below are the same enumerations as the
# host engines (byte-for-byte the same index math) factored into
# ``expand(a_ids, alive, peel) -> (owner, payload)`` and
# ``subtract(payload_slice) -> partial`` callables. ``owner`` is the
# ascending iterating-entity id per frontier item — the routing key of
# the entity-range fan-out — and every subtract group is keyed by that
# entity, so per-device partial decrement arrays add exactly.
# ---------------------------------------------------------------------------


def _resolve_devices(devices) -> int:
    """``devices=`` knob: an int mesh width or ``"auto"`` (every
    visible jax device — forced-host devices included)."""
    if devices == "auto":
        return len(jax.devices())
    return int(devices)


def _tips_expand_fn(off, nbr, base, n_side):
    """PEEL-V frontier: 2-hop re-enumeration from the peeled set, the
    distributed twin of ``_peel_tips_host``'s GET-V-WEDGES block."""

    def expand(a_ids, alive, peel):
        ga = a_ids + base
        deg1 = off[ga + 1] - off[ga]
        u1_rep = np.repeat(a_ids, deg1)
        v_rep = nbr[_ranges(off[ga], deg1)]
        deg2 = off[v_rep + 1] - off[v_rep]
        u1_w = np.repeat(u1_rep, deg2)
        u2_w = nbr[_ranges(off[v_rep], deg2)] - base
        ok = alive[u2_w]
        u1_w, u2_w = u1_w[ok], u2_w[ok]
        return u1_w, (u1_w, u2_w)

    return expand


def _stored_expand_fn(woff, w_u2):
    """WPEEL-V frontier: stored-wedge CSR lookup, the distributed twin
    of ``_peel_tips_stored_host``'s per-round block."""

    def expand(a_ids, alive, peel):
        lens = woff[a_ids + 1] - woff[a_ids]
        pos = _ranges(woff[a_ids], lens)
        u1_w = np.repeat(a_ids, lens)
        u2_w = w_u2[pos]
        ok = alive[u2_w]
        u1_w, u2_w = u1_w[ok], u2_w[ok]
        return u1_w, (u1_w, u2_w)

    return expand


def _pair_subtract_fn(n_side, dtype):
    """Tip partial subtract: group one device's (u1, u2) wedge pairs
    and accumulate C(d, 2) per u2 into a dense partial — the numpy
    mirror of ``_subtract_tile``'s consume step, with ``dec`` computed
    in the count dtype so wraparound semantics match the device
    engines bit for bit."""
    dtype = np.dtype(dtype)

    def subtract(payload):
        u1, u2 = payload
        partial = np.zeros(n_side, dtype=dtype)
        if u1.size:
            key = u1.astype(np.int64) * np.int64(n_side) + u2
            uniq, cnt = np.unique(key, return_counts=True)
            d = cnt.astype(dtype)
            dec = d * (d - 1) // 2
            np.add.at(partial, uniq % np.int64(n_side), dec)
        return partial

    return subtract


def _wings_expand_fn(g, off, nbr, uid):
    """PEEL-E frontier: per-butterfly triple location via
    min-degree-side intersections — the distributed twin of
    ``_peel_wings_host``'s level-1/level-2 block. The supervisor clears
    ``alive`` before expanding, so the paper's presence rule
    reconstructs the pre-round mask as ``alive | peel``."""
    n, m = g.n, g.m
    deg = np.diff(off)
    eu = g.edges[:, 0].astype(np.int64)
    ev = (g.edges[:, 1] + g.n_u).astype(np.int64)
    src = np.repeat(np.arange(n), deg)
    comp = src * np.int64(n) + nbr
    empty = np.empty(0, dtype=np.int64)

    def expand(a_ids, alive, peel):
        alive_prev = alive | peel

        def present(x, a):
            return alive_prev[x] & (~peel[x] | (x > a))

        # level 1: (a=(u1,v1), u2 in N(v1))
        u1s, v1s = eu[a_ids], ev[a_ids]
        d1 = deg[v1s]
        a_rep = np.repeat(a_ids, d1)
        u1_rep = np.repeat(u1s, d1)
        v1_rep = np.repeat(v1s, d1)
        pos_b = _ranges(off[v1s], d1)
        u2_rep = nbr[pos_b]
        b_edge = uid[pos_b]
        keep = (u2_rep != u1_rep) & present(b_edge, a_rep)
        a_rep, u1_rep, v1_rep, u2_rep, b_edge = (
            a_rep[keep],
            u1_rep[keep],
            v1_rep[keep],
            u2_rep[keep],
            b_edge[keep],
        )
        if a_rep.size == 0:
            return empty, (np.empty((0, 3), dtype=np.int64),)
        # level 2: scan the smaller of N(u1), N(u2)
        small = np.where(deg[u1_rep] <= deg[u2_rep], u1_rep, u2_rep)
        other = np.where(deg[u1_rep] <= deg[u2_rep], u2_rep, u1_rep)
        d2 = deg[small]
        a2 = np.repeat(a_rep, d2)
        v1_2 = np.repeat(v1_rep, d2)
        b_2 = np.repeat(b_edge, d2)
        oth2 = np.repeat(other, d2)
        pos_s = _ranges(off[small], d2)
        v2 = nbr[pos_s]
        e_small = uid[pos_s]
        # membership: (other, v2) must be an edge
        p = np.searchsorted(comp, oth2 * np.int64(n) + v2)
        p = np.minimum(p, comp.shape[0] - 1)
        hit = comp[p] == oth2 * np.int64(n) + v2
        e_other = uid[p]
        # c = (u1, v2), d_edge = (u2, v2): map small/other back
        small_is_u1 = np.repeat(deg[u1_rep] <= deg[u2_rep], d2)
        c_edge = np.where(small_is_u1, e_small, e_other)
        d_edge = np.where(small_is_u1, e_other, e_small)
        ok = (
            hit
            & (v2 != v1_2)
            & present(c_edge, a2)
            & present(d_edge, a2)
        )
        tri = np.stack([b_2, c_edge, d_edge], axis=1)[ok]
        return a2[ok], (tri,)

    return expand


def _tri_subtract_fn(m, dtype):
    """Wing partial subtract: -1 per still-present edge of each located
    butterfly (the host engine's raw triple scatter), accumulated in
    the count dtype."""
    dtype = np.dtype(dtype)

    def subtract(payload):
        (tri,) = payload
        partial = np.zeros(m, dtype=dtype)
        if tri.size:
            np.add.at(partial, tri.ravel(), dtype.type(1))
        return partial

    return subtract


def _merge_distributed(report: "_res.ExecutionReport", sp) -> None:
    """Fold a :class:`~repro.core.distributed.SupervisedPeel` audit
    into the parent ladder report: rollback count plus one child row
    per mesh device."""
    report.checkpoint_restores += sp.checkpoint_restores
    for child in sp.device_reports:
        report.merge_child(child)


def _peel_tips_host(g, counts, side, aggregation, hash_bits, tile_budget,
                    peel_mode, off, nbr, w2) -> PeelResult:
    """Host tip round loop (PEEL-V's bottom rung): whole-frontier 2-hop
    wedge enumeration with the shared tile subtract."""
    n_side = g.n_u if side == 0 else g.n_v
    base = 0 if side == 0 else g.n_u  # global id offset of peeled side
    tile_cap = _tip_tile_cap(tile_budget, int(w2.sum()),
                             int(w2.max(initial=0)))
    alive = np.ones(n_side, dtype=bool)
    tip = np.zeros(n_side, dtype=counts.dtype)
    b_dev = jnp.asarray(counts)
    kappa = 0
    acct = _RoundAccounting(peel_mode)
    while alive.any():
        cnt_host = np.asarray(_fetch(b_dev))
        cur = np.where(alive, cnt_host, np.iinfo(cnt_host.dtype).max)
        mn = int(cur.min())
        kappa = max(kappa, mn)
        acct.open_round(mn)
        a_ids = np.flatnonzero(alive & (cur <= kappa))
        tip[a_ids] = kappa
        alive[a_ids] = False
        acct.peeled(a_ids.size)
        if not alive.any():
            break
        # -- wedge enumeration from peeled set (GET-V-WEDGES) --
        ga = a_ids + base
        deg1 = off[ga + 1] - off[ga]
        u1_rep = np.repeat(a_ids, deg1)
        v_rep = nbr[_ranges(off[ga], deg1)]
        deg2 = off[v_rep + 1] - off[v_rep]
        u1_w = np.repeat(u1_rep, deg2)
        u2_w = nbr[_ranges(off[v_rep], deg2)] - base
        # keep wedges whose second endpoint is still alive
        ok = alive[u2_w]
        u1_w, u2_w = u1_w[ok], u2_w[ok]
        if u1_w.size == 0:
            continue
        b_dev = _host_subtract_frontier(
            b_dev, u1_w, u2_w, n_side, aggregation, hash_bits, tile_cap,
        )
    return PeelResult(tip, side, acct.rounds, np.asarray(acct.sizes),
                      sub_rounds=acct.sub_rounds)


@_traced("peel_tips")
def peel_tips(
    g: BipartiteGraph,
    counts: Optional[np.ndarray] = None,
    side: Optional[int] = None,
    aggregation: str = "sort",
    count_kwargs: Optional[dict] = None,
    engine: str = "host",
    max_frontier: Optional[int] = None,
    hash_bits: Optional[int] = None,
    tile_budget: Optional[int] = None,
    peel_mode: str = "exact",
    devices=None,
    checkpoint=None,
    round_deadline_s: Optional[float] = None,
    deadline_s: Optional[float] = None,
    resilience=None,
) -> PeelResult:
    """Tip decomposition (PEEL-V, Alg. 5).

    Peels the bipartition producing fewer wedges-as-endpoints unless
    ``side`` is forced. ``counts`` are per-vertex butterfly counts for
    the peeled side (computed if omitted). ``engine="device"`` runs the
    whole round loop on device with one host sync (see module
    docstring); ``max_frontier`` bounds its level-1 buffer (overflow
    falls back to host); ``hash_bits`` overrides the hash-aggregation
    table size (testing hook for the in-graph overflow fallback).

    Both engines stream each round's frontier wedge space through
    iterating-endpoint-aligned tiles — O(tile) peak temp instead of
    O(frontier wedges). ``tile_budget`` sizes the tiles (default: a
    small 1024 target — peeling pays the tile shape every round —
    floored by the largest single-vertex expansion).
    ``peel_mode="range"`` switches to bucket-range rounds (process the
    whole lowest non-empty geometric bucket per round, Lakhotia-style —
    see module docstring): same numbers, ρ counted in bucket rounds,
    re-settle iterations in ``sub_rounds``. Every engine and knob
    setting produces bitwise-identical numbers.

    ``devices=N`` (or ``"auto"`` = every visible jax device) inserts
    the **distributed** rung on top of the ladder: the supervised,
    checkpointable bucket-range round loop of
    :class:`~repro.core.distributed.PeelSupervisor` — coarse bucket
    selection on the host, each range's fine pass fanned out across N
    workers along the plan's entity tiles (``pipeline.plan_partition``),
    per-device partial subtracts reduced exactly. Always runs
    bucket-range rounds (``rounds``/``sub_rounds`` follow
    ``peel_mode="range"`` semantics); numbers are bitwise-identical to
    every single-device engine regardless. ``checkpoint`` persists the
    supervisor's per-round snapshots (a directory path or a
    :class:`~repro.core.checkpoint.CheckpointStore`; default
    in-memory), enabling lost-device rollback and cross-process
    resume; ``round_deadline_s`` overrides the per-round straggler
    deadline (default derived from the plan's wedge totals). A lost
    device triggers restore + elastic re-partition over the survivors;
    losing every device (or a twice-missed deadline) descends the
    ladder to the single-device rungs below.

    ``resilience`` selects the degradation policy (``None``/``True`` =
    default ladder, ``False`` = no validation/retries/report, or a
    :class:`~repro.core.resilience.ResiliencePolicy`); when the report
    is attached, ``result.report`` records the
    ``distributed -> device -> host`` descent path, shrink-retries,
    checkpoint restores, per-device worker rows, and outcomes.
    """
    _check_engine(engine)
    _check_knobs(aggregation, peel_mode)
    policy = _res.resolve_policy(resilience)
    hash_bits = _faults.hash_bits_override("peel_tips", hash_bits)
    side, counts = _side_and_counts(g, counts, side, count_kwargs)
    off, nbr, _ = _csr(g)
    n_side = g.n_u if side == 0 else g.n_v
    base = 0 if side == 0 else g.n_u  # global id offset of peeled side
    # per-vertex 2-hop totals: shared between the device planner and the
    # host tile plan so a device->host fallback never recomputes them
    w2 = _level2_totals(off, nbr, base, n_side)

    def run_device(shrinks: int):
        _faults.maybe_oom("peel_tips.device")
        _faults.maybe_slow_rung("peel_tips.device")
        mf = _faults.capacity_override("peel_tips.device", max_frontier)
        c = _faults.maybe_poison("peel_tips.device", counts)
        notes: list = []
        res = _peel_tips_device_run(
            g, c, side, aggregation, False, mf, hash_bits,
            (off, nbr), tile_budget=tile_budget, w2=w2,
            peel_mode=peel_mode, budget_shrinks=shrinks, note=notes,
        )
        return _res.require_rung(res, notes)

    def run_host(shrinks: int):
        _faults.maybe_oom("peel_tips.host")
        _faults.maybe_slow_rung("peel_tips.host")
        return _peel_tips_host(
            g, counts, side, aggregation, hash_bits, tile_budget,
            peel_mode, off, nbr, w2,
        )

    plan = _plan_peel(
        "peel_tips",
        expansion="peel_tips_2hop",
        engine=engine,
        aggregation=aggregation,
        n_out=n_side,
        dtype=np.asarray(counts).dtype.name,
        capacity=(
            ("max_frontier",
             _I32_MAX if max_frontier is None else int(max_frontier)),
            ("tile_budget",
             _DEFAULT_TILE_TARGET if tile_budget is None
             else int(tile_budget)),
        ),
        hash_bits=hash_bits,
        entity_work=w2,
    )
    dist_audit: list = []

    def run_distributed(shrinks: int):
        _faults.maybe_oom("peel_tips.distributed")
        _faults.maybe_slow_rung("peel_tips.distributed")
        sup = _dist.PeelSupervisor(
            "peel_tips", plan, counts,
            expand=_tips_expand_fn(off, nbr, base, n_side),
            subtract=_pair_subtract_fn(n_side, counts.dtype),
            devices=_resolve_devices(devices),
            checkpoint=checkpoint,
            round_deadline_s=round_deadline_s,
            deadline_s=deadline_s,
        )
        sp = sup.run()
        dist_audit.append(sp)
        return PeelResult(sp.numbers, side, sp.rounds, sp.round_sizes,
                          sub_rounds=sp.sub_rounds)

    rungs = [_res.Rung("host", run_host, shrinkable=False)]
    if engine == "device":
        rungs.insert(0, _res.Rung("device", run_device))
    if devices is not None:
        rungs.insert(
            0, _res.Rung("distributed", run_distributed, shrinkable=False)
        )
    out, report = _execute_ladder(
        "peel_tips", policy, rungs, _peel_validator(counts), plan=plan
    )
    if dist_audit:
        _merge_distributed(report, dist_audit[-1])
    return policy.attach(out, report)


@_traced("peel_tips_stored")
def peel_tips_stored(
    g: BipartiteGraph,
    counts: Optional[np.ndarray] = None,
    side: Optional[int] = None,
    aggregation: str = "sort",
    count_kwargs: Optional[dict] = None,
    engine: str = "host",
    hash_bits: Optional[int] = None,
    tile_budget: Optional[int] = None,
    peel_mode: str = "exact",
    devices=None,
    checkpoint=None,
    round_deadline_s: Optional[float] = None,
    deadline_s: Optional[float] = None,
    resilience=None,
) -> PeelResult:
    """WPEEL-V (paper Alg. 7): store all side-oriented wedges upfront,
    then per round subtract via pure index lookups — O(b)-style work,
    O(Σ deg²_side) = O(αm-class) space (the paper's work/space
    trade-off). One orientation suffices: every butterfly on the peeled
    side U is accounted by its U-endpoint wedge group (Lemma 4.2);
    the paper's W_c store handles the same butterflies from the other
    orientation of its ranked wedge set.

    Knobs as in :func:`peel_tips`. The device engine recovers each
    tile straight from the stored-wedge CSR — no per-round frontier
    buffer exists at all, so there is no ``max_frontier``.
    ``devices``/``checkpoint``/``round_deadline_s`` (the supervised
    distributed rung) and ``resilience`` as in :func:`peel_tips`.
    """
    _check_engine(engine)
    _check_knobs(aggregation, peel_mode)
    policy = _res.resolve_policy(resilience)
    hash_bits = _faults.hash_bits_override("peel_tips_stored", hash_bits)
    side, counts = _side_and_counts(g, counts, side, count_kwargs)
    n_side = g.n_u if side == 0 else g.n_v
    woff, w_u2 = _stored_wedge_csr(g, side)

    def run_device(shrinks: int):
        _faults.maybe_oom("peel_tips_stored.device")
        _faults.maybe_slow_rung("peel_tips_stored.device")
        c = _faults.maybe_poison("peel_tips_stored.device", counts)
        notes: list = []
        res = _peel_tips_device_run(
            g, c, side, aggregation, True, None, hash_bits,
            (woff, w_u2), tile_budget=tile_budget, peel_mode=peel_mode,
            budget_shrinks=shrinks, note=notes,
        )
        return _res.require_rung(res, notes)

    def run_host(shrinks: int):
        _faults.maybe_oom("peel_tips_stored.host")
        _faults.maybe_slow_rung("peel_tips_stored.host")
        return _peel_tips_stored_host(
            counts, side, n_side, aggregation, hash_bits, tile_budget,
            peel_mode, woff, w_u2,
        )

    plan = _plan_peel(
        "peel_tips_stored",
        expansion="peel_tips_stored",
        engine=engine,
        aggregation=aggregation,
        n_out=n_side,
        dtype=np.asarray(counts).dtype.name,
        capacity=(
            ("tile_budget",
             _DEFAULT_TILE_TARGET if tile_budget is None
             else int(tile_budget)),
            ("stored_wedges", int(woff[-1])),
        ),
        hash_bits=hash_bits,
        entity_work=np.diff(woff),
    )
    dist_audit: list = []

    def run_distributed(shrinks: int):
        _faults.maybe_oom("peel_tips_stored.distributed")
        _faults.maybe_slow_rung("peel_tips_stored.distributed")
        sup = _dist.PeelSupervisor(
            "peel_tips_stored", plan, counts,
            expand=_stored_expand_fn(woff, w_u2),
            subtract=_pair_subtract_fn(n_side, counts.dtype),
            devices=_resolve_devices(devices),
            checkpoint=checkpoint,
            round_deadline_s=round_deadline_s,
            deadline_s=deadline_s,
        )
        sp = sup.run()
        dist_audit.append(sp)
        return PeelResult(sp.numbers, side, sp.rounds, sp.round_sizes,
                          sub_rounds=sp.sub_rounds)

    rungs = [_res.Rung("host", run_host, shrinkable=False)]
    if engine == "device":
        rungs.insert(0, _res.Rung("device", run_device))
    if devices is not None:
        rungs.insert(
            0, _res.Rung("distributed", run_distributed, shrinkable=False)
        )
    out, report = _execute_ladder(
        "peel_tips_stored", policy, rungs, _peel_validator(counts), plan=plan
    )
    if dist_audit:
        _merge_distributed(report, dist_audit[-1])
    return policy.attach(out, report)


def _peel_tips_stored_host(counts, side, n_side, aggregation, hash_bits,
                           tile_budget, peel_mode, woff,
                           w_u2) -> PeelResult:
    """Host WPEEL-V round loop (the ladder's bottom rung): per-round
    subtract via stored-wedge index lookups."""
    tile_cap = _tip_tile_cap(tile_budget, int(woff[-1]),
                             int(np.diff(woff).max(initial=0)))
    alive = np.ones(n_side, dtype=bool)
    tip = np.zeros(n_side, dtype=counts.dtype)
    b_dev = jnp.asarray(counts)
    kappa = 0
    acct = _RoundAccounting(peel_mode)
    while alive.any():
        cnt_host = np.asarray(_fetch(b_dev))
        cur = np.where(alive, cnt_host, np.iinfo(cnt_host.dtype).max)
        mn = int(cur.min())
        kappa = max(kappa, mn)
        acct.open_round(mn)
        a_ids = np.flatnonzero(alive & (cur <= kappa))
        tip[a_ids] = kappa
        alive[a_ids] = False
        acct.peeled(a_ids.size)
        if not alive.any():
            break
        # stored-wedge lookup instead of 2-hop re-enumeration
        lens = woff[a_ids + 1] - woff[a_ids]
        pos = _ranges(woff[a_ids], lens)
        u1_w = np.repeat(a_ids, lens)
        u2_w = w_u2[pos]
        ok = alive[u2_w]
        u1_w, u2_w = u1_w[ok], u2_w[ok]
        if u1_w.size == 0:
            continue
        b_dev = _host_subtract_frontier(
            b_dev, u1_w, u2_w, n_side, aggregation, hash_bits, tile_cap,
        )
    return PeelResult(tip, side, acct.rounds, np.asarray(acct.sizes),
                      sub_rounds=acct.sub_rounds)

# ---------------------------------------------------------------------------
# Device-resident wing engine (PEEL-E): triple enumeration in-graph
# ---------------------------------------------------------------------------


@_scope("subtract")
def _subtract_edge_groups(
    tgt3: jax.Array,
    valid3: jax.Array,
    b: jax.Array,
    alive: jax.Array,
    *,
    aggregation: str,
    m: int,
    hash_bits: Optional[int] = None,
    use_kernel: bool = False,
    want_hist: bool = False,
    last=True,
):
    """Aggregate one tile of butterfly edge ids and subtract the group
    multiplicities — the wing-side consumer of the shared fused tile
    machinery. Each of the round's located butterflies contributes -1
    to three still-present edges; grouping by edge id turns the raw
    triple scatter into one subtract per distinct edge (same integer
    sums, so bitwise-equal to the host engine's raw scatter), with the
    in-graph hash-overflow sort fallback. Returns ``(b, min, hist)``
    (min/hist meaningful on the sub-round's ``last`` tile only).
    """
    sent = jnp.int32(m)
    key = jnp.where(valid3, tgt3, sent)
    w = Wedges(
        x1=key,
        x2=key,
        y=key,
        center_slot=tgt3,
        second_slot=tgt3,
        valid=valid3,
    )

    def consume(_wv, groups):
        dec = jnp.where(groups.valid, groups.d.astype(b.dtype), 0)
        tgt = jnp.where(groups.valid, groups.x1, sent)
        return _apply_decrements(b, alive, tgt, dec, use_kernel, want_hist,
                                 last)

    out, _ok = _fused_tile_apply(w, aggregation, consume, "xla", hash_bits)
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "aggregation", "tile_cap", "m", "hash_bits", "use_kernel",
        "peel_mode",
    ),
)
def _peel_wings_device(
    off: jax.Array,  # (n + 1,) graph CSR offsets
    nbr: jax.Array,  # (2m,) neighbors (global ids)
    uid: jax.Array,  # (2m,) undirected edge id per directed slot
    eu: jax.Array,  # (m,) U endpoint (global id) per edge
    ev: jax.Array,  # (m,) V endpoint (global id) per edge
    nbr_ds: jax.Array,  # (2m,) neighbors, degree-sorted within row
    uid_ds: jax.Array,  # (2m,) edge ids matching nbr_ds
    degs_ds: jax.Array,  # (2m,) deg(nbr_ds[p])
    cumdeg: jax.Array,  # (2m,) in-row exclusive prefix of degs_ds
    work1: jax.Array,  # (m,) per-edge level-1 totals (not read)
    work2: jax.Array,  # (m,) per-edge triple-space totals
    state: _LoopState,
    *,
    aggregation: str,
    tile_cap: int,
    m: int,
    hash_bits: Optional[int] = None,
    use_kernel: bool = False,
    peel_mode: str = "exact",
):
    """Jitted device round loop for wing decomposition (PEEL-E, Alg. 6):
    the shared ``_device_round_loop`` substrate with the wing expand
    callable.

    The expand callable is a **two-level fused recovery**: the
    per-butterfly triple space — for each peeled edge a = (u1, v1),
    for each candidate u2 in N(v1), scan the smaller of N(u1)/N(u2)
    for centers v2 — is recovered straight from flat ids with NO
    materialized level-1 or level-2 buffer. A flat triple id inverts
    in O(log) per lane: (1) the per-edge exclusive prefix of the
    static triple totals (``work2``, scattered over this round's peel
    set) locates the edge via ``ragged_slots_at``; (2) inside the
    edge's row of the **degree-sorted** CSR, the candidates u2 with
    ``deg(u2) < deg(u1)`` form a prefix whose ragged inner sizes are
    readable from ``cumdeg`` (one binary search), and the remaining
    candidates all scan exactly ``deg(u1)`` centers (one division) —
    see ``wedges.degree_sorted_csr``. The enumeration covers the same
    candidate multiset as the host engine in a different order, and
    every subtraction is a linear scatter, so results are bitwise
    identical; the paper's Σ min(deg(u), deg(u')) work bound per
    peeled edge is preserved. Per-lane edge membership of (other, v2)
    stays the CSR binary search (``wedges._lower_bound_ragged``).
    ``work1`` is part of the program's signature only: positional
    readers of the launched arguments find ``work2`` at index 10.

    Presence of an edge x w.r.t. the peeled edge a follows the paper's
    id-order tiebreak: alive-before-this-round and (not peeled this
    round or x > a).
    """
    nbr_max = nbr.shape[0] - 1
    deg = off[1:] - off[:-1]
    want_hist = peel_mode == "range"

    @_scope("recover")
    def expand(args):
        b, alive, alive_prev, peel = args

        def present(x, a):
            xc = jnp.clip(x, 0, m - 1)
            return alive_prev[xc] & (~peel[xc] | (x > a))

        # per-edge triple totals are static (work2), so the round's
        # flat triple space is one masked prefix
        roff_tri = _prefix(jnp.where(peel, work2, 0))

        def tile_fn(bt, wid, tvalid, last):
            a2, tp = ragged_slots_at(roff_tri, jnp.zeros((m,), jnp.int32),
                                     wid)
            u1 = eu[a2]
            v1_2 = ev[a2]
            d1 = deg[u1]
            rs = off[v1_2]
            re = off[v1_2 + 1]
            # split N(v1) (degree-sorted) at deg(u2) >= deg(u1)
            q = _lower_bound_ragged(degs_ds, rs, re, d1)
            re1 = jnp.clip(re - 1, 0, nbr_max)
            head_tri = jnp.where(
                q < re,
                cumdeg[jnp.clip(q, 0, nbr_max)],
                cumdeg[re1] + degs_ds[re1],
            )
            in_head = tp < head_tri
            # head: ragged inner sizes — binary search the in-row
            # neighbor-degree prefix (cumdeg[rs] == 0)
            p_head = _lower_bound_ragged(cumdeg, rs, q, tp + 1) - 1
            # tail: deg(u1)-sized blocks — pure arithmetic
            r_tail = tp - head_tri
            d1s = jnp.maximum(d1, 1)
            j_tail = r_tail // d1s
            p1 = jnp.clip(jnp.where(in_head, p_head, q + j_tail), 0, nbr_max)
            i = jnp.where(in_head, tp - cumdeg[p1], r_tail - j_tail * d1s)
            u2 = nbr_ds[p1]
            b_2 = uid_ds[p1]
            kp = tvalid & (u2 != u1) & present(b_2, a2)
            si = d1 <= deg[u2]
            small = jnp.where(si, u1, u2)
            oth = jnp.where(si, u2, u1)
            pos2c = jnp.clip(
                off[small] + jnp.clip(i, 0, jnp.maximum(deg[small] - 1, 0)),
                0, nbr_max,
            )
            v2 = nbr[pos2c]
            e_small = uid[pos2c]
            with _scope("member"):
                # membership: (other, v2) must be an edge — binary
                # search v2 inside N(other)
                lo = off[oth]
                hi = off[oth + 1]
                p = _lower_bound_ragged(nbr, lo, hi, v2)
                pc = jnp.clip(p, 0, nbr_max)
                hit = (p < hi) & (nbr[pc] == v2)
                e_other = uid[pc]
            # c = (u1, v2), d = (u2, v2): map small/other back
            c_edge = jnp.where(si, e_small, e_other)
            d_edge = jnp.where(si, e_other, e_small)
            ok = (
                kp
                & hit
                & (v2 != v1_2)
                & present(c_edge, a2)
                & present(d_edge, a2)
            )
            tgt3 = jnp.concatenate([b_2, c_edge, d_edge])
            ok3 = jnp.concatenate([ok, ok, ok])
            return _subtract_edge_groups(
                tgt3.astype(jnp.int32), ok3, bt, alive,
                aggregation=aggregation, m=m, hash_bits=hash_bits,
                use_kernel=use_kernel, want_hist=want_hist, last=last,
            )

        b_new, mn2, h2 = _stream_tiles(
            b, alive, roff_tri, tile_fn, tile_cap=tile_cap, aligned=False,
            want_hist=want_hist,
        )
        return b_new, jnp.array(False), mn2, h2

    return _device_round_loop(state, expand, peel_mode=peel_mode)


@_traced("preprocess")
def _wing_work_totals(g: BipartiteGraph, off: np.ndarray, nbr: np.ndarray):
    """Per-edge wing expansion totals over the graph CSR: for each
    edge ``a = (u1, v1)``, ``l1[a] = deg(v1)`` (level-1 candidates)
    and ``l2[a] = Σ_{u2 in N(v1)} min(deg(u1), deg(u2))`` — the
    paper's candidate triple-space bound, with the ``u2 == u1`` slot
    included (its lanes mask out per round). The fused recovery
    streams exactly this static space, so the device planner, the
    benchmark gates/memory probes, and the tests all read it from this
    one helper — the totals must never diverge from the engine's
    recovery invariant. Returns ``(eu, ev, l1, l2)`` (endpoints in
    global ids, totals int64)."""
    deg = np.diff(off)
    eu = g.edges[:, 0].astype(np.int64)
    ev = (g.edges[:, 1] + g.n_u).astype(np.int64)
    l1 = deg[ev]
    l2 = np.zeros(g.m, dtype=np.int64)
    if int(l1.sum()):
        a_rep = np.repeat(np.arange(g.m), l1)
        u2 = nbr[_ranges(off[ev], l1)]
        np.add.at(l2, a_rep, np.minimum(deg[eu[a_rep]], deg[u2]))
    return eu, ev, l1, l2


def _peel_wings_device_run(
    g: BipartiteGraph,
    counts: np.ndarray,
    aggregation: str,
    hash_bits: Optional[int],
    csr,
    tile_budget: Optional[int] = None,
    peel_mode: str = "exact",
    budget_shrinks: int = 0,
    note: Optional[list] = None,
    w_totals=None,
) -> Optional[PeelResult]:
    """Plan and run the device wing loop; one ``device_get`` per
    decomposition. Returns None when the device engine does not apply
    (no edges, counts or expansion totals beyond int32) — callers fall
    back to the host loop, reusing ``csr`` (the resilience ladder
    translates the None into the typed taxonomy, appending the reason
    to ``note``; ``budget_shrinks`` is its RESOURCE_EXHAUSTED re-entry
    knob, halving the tile budget). The two-level fused recovery
    inverts flat triple ids directly, so no buffer can overflow."""
    note = [] if note is None else note
    off, nbr, uid = csr
    m = g.m
    if m == 0 or int(counts.max(initial=0)) >= _I32_MAX:
        note.append("device engine unavailable: no edges or counts "
                    "beyond int32")
        return None
    if 2 * m >= _I32_MAX:
        note.append("device engine unavailable: edge slots beyond int32")
        return None
    with _span("plan"):  # the degree-sorted CSR, the tile, the seed state
        eu, ev, l1, l2 = (
            _wing_work_totals(g, off, nbr) if w_totals is None else w_totals
        )
        lvl2 = int(l2.sum())
        if lvl2 >= _I32_MAX:
            note.append("device engine unavailable: expansion totals beyond "
                        "int32 indexing")
            return None
        # the recovery reads in-row neighbor-degree prefixes; every row
        # total must stay int32-addressable
        nbr_ds, uid_ds, degs_ds, cumdeg = degree_sorted_csr(off, nbr, uid)
        if cumdeg.size and int((cumdeg + degs_ds).max(initial=0)) >= _I32_MAX:
            note.append("device engine unavailable: degree-sorted "
                        "prefixes beyond int32 indexing")
            return None
        tb = _DEFAULT_TILE_TARGET if tile_budget is None else int(tile_budget)
        if budget_shrinks:
            tb = max(1, tb >> budget_shrinks)
        tile_cap = _pow2_pad(min(tb, max(lvl2, 1)))
        # counts below INT32_MAX (guarded above) run the int32 kernel in any
        # count dtype; off the compiled backend the reference serves
        use_kernel = not _kops.interpret_default()
        state = _init_state(jnp.asarray(counts), m, peel_mode=peel_mode)
        args = (
            jnp.asarray(off, jnp.int32),
            jnp.asarray(nbr if nbr.size else np.zeros(1), jnp.int32),
            jnp.asarray(uid if uid.size else np.zeros(1), jnp.int32),
            jnp.asarray(eu, jnp.int32),
            jnp.asarray(ev, jnp.int32),
            jnp.asarray(nbr_ds if nbr_ds.size else np.zeros(1), jnp.int32),
            jnp.asarray(uid_ds if uid_ds.size else np.zeros(1), jnp.int32),
            jnp.asarray(degs_ds if degs_ds.size else np.zeros(1), jnp.int32),
            jnp.asarray(cumdeg if cumdeg.size else np.zeros(1), jnp.int32),
            jnp.asarray(l1.astype(np.int32)),
            jnp.asarray(l2.astype(np.int32)),
        )
    host = _fetch(_launch(
        _peel_wings_device,
        *args,
        state,
        aggregation=aggregation,
        tile_cap=tile_cap,
        m=m,
        hash_bits=hash_bits,
        use_kernel=use_kernel,
        peel_mode=peel_mode,
    ))
    rounds = int(host.rounds)
    return PeelResult(
        host.out, None, rounds, host.sizes[:rounds].astype(np.int64),
        sub_rounds=int(host.subr),
    )


@_traced("peel_wings")
def peel_wings(
    g: BipartiteGraph,
    counts: Optional[np.ndarray] = None,
    count_kwargs: Optional[dict] = None,
    engine: str = "host",
    aggregation: str = "sort",
    hash_bits: Optional[int] = None,
    tile_budget: Optional[int] = None,
    peel_mode: str = "exact",
    devices=None,
    checkpoint=None,
    round_deadline_s: Optional[float] = None,
    deadline_s: Optional[float] = None,
    resilience=None,
) -> PeelResult:
    """Wing decomposition (PEEL-E, Alg. 6).

    Butterflies incident to peeled edges are located individually via
    min-degree-side intersections, matching the paper's
    Σ min(deg(u), deg(u')) work bound.

    ``engine="host"`` (membership via binary search on the lexsorted
    directed edge array) keeps the host round loop but routes the
    per-round extract-min through the ``bucket_min`` kernel whenever
    the wing counts fit int32. ``engine="device"`` runs the whole
    decomposition as one jitted ``lax.while_loop`` — a third in-graph
    expansion level enumerates the per-butterfly triples and an
    in-graph CSR binary search replaces the composite-key membership
    probe — with one ``device_get`` per decomposition.
    ``aggregation``/``hash_bits`` select the device engine's grouped
    edge subtract strategy (the host engine's raw triple scatter is
    bitwise-equivalent); ``tile_budget``/``peel_mode`` as in
    :func:`peel_tips`. The device engine recovers the per-butterfly
    triple space straight from flat ids via the degree-sorted CSR
    (``wedges.degree_sorted_csr``) — no level-1/level-2 buffers, so
    there is no ``max_frontier``. Counts at or beyond INT32_MAX or
    expansion totals beyond int32 transparently fall back to the host
    loop.
    ``devices``/``checkpoint``/``round_deadline_s`` (the supervised
    distributed rung, fanning the per-edge triple space out along edge
    tiles) and ``resilience`` as in :func:`peel_tips`.
    """
    _check_engine(engine)
    _check_knobs(aggregation, peel_mode)
    policy = _res.resolve_policy(resilience)
    hash_bits = _faults.hash_bits_override("peel_wings", hash_bits)
    if counts is None:
        r = count_butterflies(
            g, mode="edge", count_dtype=default_count_dtype(),
            **(count_kwargs or {})
        )
        counts = r.per_edge
    counts = np.asarray(counts).copy()
    off, nbr, uid = _csr(g)
    # per-edge triple-space totals: shared between the device planner,
    # the peeling plan's entity tiles, and the distributed fan-out
    w_totals = _wing_work_totals(g, off, nbr)

    def run_device(shrinks: int):
        _faults.maybe_oom("peel_wings.device")
        _faults.maybe_slow_rung("peel_wings.device")
        c = _faults.maybe_poison("peel_wings.device", counts)
        notes: list = []
        res = _peel_wings_device_run(
            g, c, aggregation, hash_bits, (off, nbr, uid),
            tile_budget=tile_budget, peel_mode=peel_mode,
            budget_shrinks=shrinks, note=notes, w_totals=w_totals,
        )
        return _res.require_rung(res, notes)

    def run_host(shrinks: int):
        _faults.maybe_oom("peel_wings.host")
        _faults.maybe_slow_rung("peel_wings.host")
        return _peel_wings_host(g, counts, off, nbr, uid, peel_mode)

    plan = _plan_peel(
        "peel_wings",
        expansion="peel_wings_triples",
        engine=engine,
        aggregation=aggregation,
        n_out=g.m,
        dtype=np.asarray(counts).dtype.name,
        capacity=(
            ("tile_budget",
             _DEFAULT_TILE_TARGET if tile_budget is None
             else int(tile_budget)),
        ),
        hash_bits=hash_bits,
        entity_work=w_totals[3],
    )
    dist_audit: list = []

    def run_distributed(shrinks: int):
        _faults.maybe_oom("peel_wings.distributed")
        _faults.maybe_slow_rung("peel_wings.distributed")
        sup = _dist.PeelSupervisor(
            "peel_wings", plan, counts,
            expand=_wings_expand_fn(g, off, nbr, uid),
            subtract=_tri_subtract_fn(g.m, counts.dtype),
            devices=_resolve_devices(devices),
            checkpoint=checkpoint,
            round_deadline_s=round_deadline_s,
            deadline_s=deadline_s,
        )
        sp = sup.run()
        dist_audit.append(sp)
        return PeelResult(sp.numbers, None, sp.rounds, sp.round_sizes,
                          sub_rounds=sp.sub_rounds)

    rungs = [_res.Rung("host", run_host, shrinkable=False)]
    if engine == "device":
        rungs.insert(0, _res.Rung("device", run_device))
    if devices is not None:
        rungs.insert(
            0, _res.Rung("distributed", run_distributed, shrinkable=False)
        )
    out, report = _execute_ladder(
        "peel_wings", policy, rungs, _peel_validator(counts), plan=plan
    )
    if dist_audit:
        _merge_distributed(report, dist_audit[-1])
    return policy.attach(out, report)


def _peel_wings_host(g, counts, off, nbr, uid, peel_mode) -> PeelResult:
    """Host wing round loop (PEEL-E's bottom rung): per-butterfly
    triple location via min-degree-side intersections and binary-search
    edge membership."""
    n, m = g.n, g.m
    # lexsorted composite keys for edge-membership binary search
    src = np.repeat(np.arange(n), np.diff(off))
    comp = src * np.int64(n) + nbr
    deg = np.diff(off)

    # edge endpoints in global ids
    eu = g.edges[:, 0].astype(np.int64)
    ev = (g.edges[:, 1] + g.n_u).astype(np.int64)

    # bucket_min reduces in int32; counts at/above INT32_MAX would alias
    # its empty sentinel, so such graphs keep the host min. Off-TPU the
    # dispatcher would interpret the kernel tile-by-tile (~15x the cost
    # of the reduction itself per round), so only the compiled backend
    # takes the Pallas path — elsewhere ops.bucket_min serves its XLA
    # reference, preserving the same extract-min contract.
    kernel_min = int(counts.max(initial=0)) < _I32_MAX
    pallas_min = not _kops.interpret_default()

    alive = np.ones(m, dtype=bool)
    wing = np.zeros(m, dtype=counts.dtype)
    b_dev = jnp.asarray(counts)
    kappa = 0
    acct = _RoundAccounting(peel_mode)
    while alive.any():
        if kernel_min:
            # one blocking sync per round: the kernel min and the count
            # buffer come back in a single device_get
            mn_dev = _kops.bucket_min(
                b_dev, jnp.asarray(alive), use_pallas=pallas_min
            )
            mn_np, cnt_host = _fetch((mn_dev, b_dev))
            cnt_host = np.asarray(cnt_host)
            mn = int(mn_np)
        else:
            cnt_host = np.asarray(_fetch(b_dev))
            mn = int(
                np.where(alive, cnt_host, np.iinfo(cnt_host.dtype).max).min()
            )
        kappa = max(kappa, mn)
        acct.open_round(mn)
        a_ids = np.flatnonzero(alive & (cnt_host <= kappa))
        wing[a_ids] = kappa
        in_a = np.zeros(m, dtype=bool)
        in_a[a_ids] = True
        acct.peeled(a_ids.size)

        # presence of edge x w.r.t. peeled edge a (ids break ties):
        #   alive_before[x] and (x not in A or x > a)
        def present(x, a):
            return alive[x] & (~in_a[x] | (x > a))

        # level 1: (a=(u1,v1), u2 in N(v1))
        u1s, v1s = eu[a_ids], ev[a_ids]
        d1 = deg[v1s]
        a_rep = np.repeat(a_ids, d1)
        u1_rep = np.repeat(u1s, d1)
        v1_rep = np.repeat(v1s, d1)
        pos_b = _ranges(off[v1s], d1)
        u2_rep = nbr[pos_b]
        b_edge = uid[pos_b]
        keep = (u2_rep != u1_rep) & present(b_edge, a_rep)
        a_rep, u1_rep, v1_rep, u2_rep, b_edge = (
            a_rep[keep],
            u1_rep[keep],
            v1_rep[keep],
            u2_rep[keep],
            b_edge[keep],
        )
        if a_rep.size:
            # level 2: scan the smaller of N(u1), N(u2)
            small = np.where(deg[u1_rep] <= deg[u2_rep], u1_rep, u2_rep)
            other = np.where(deg[u1_rep] <= deg[u2_rep], u2_rep, u1_rep)
            d2 = deg[small]
            a2 = np.repeat(a_rep, d2)
            u1_2 = np.repeat(u1_rep, d2)
            v1_2 = np.repeat(v1_rep, d2)
            u2_2 = np.repeat(u2_rep, d2)
            b_2 = np.repeat(b_edge, d2)
            oth2 = np.repeat(other, d2)
            pos_s = _ranges(off[small], d2)
            v2 = nbr[pos_s]
            e_small = uid[pos_s]
            # membership: (other, v2) must be an edge
            p = np.searchsorted(comp, oth2 * np.int64(n) + v2)
            p = np.minimum(p, comp.shape[0] - 1)
            hit = comp[p] == oth2 * np.int64(n) + v2
            e_other = uid[p]
            # c = (u1, v2), d2e = (u2, v2): map small/other back
            small_is_u1 = np.repeat(deg[u1_rep] <= deg[u2_rep], d2)
            c_edge = np.where(small_is_u1, e_small, e_other)
            d_edge = np.where(small_is_u1, e_other, e_small)
            ok = (
                hit
                & (v2 != v1_2)
                & present(c_edge, a2)
                & present(d_edge, a2)
            )
            tri = np.stack([b_2, c_edge, d_edge], axis=1)[ok].ravel()
            if tri.size:
                cap = _pow2_pad(tri.size)
                trip = np.full(cap, m, np.int64)
                trip[: tri.size] = tri
                validp = np.zeros(cap, bool)
                validp[: tri.size] = True
                b_dev = _subtract_triples(
                    jnp.asarray(trip), jnp.asarray(validp), b_dev
                )
        alive[a_ids] = False
    return PeelResult(wing, None, acct.rounds, np.asarray(acct.sizes),
                      sub_rounds=acct.sub_rounds)

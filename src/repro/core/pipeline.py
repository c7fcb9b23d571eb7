"""The plan -> execute -> report wedge-pipeline substrate.

Every problem this repo solves — global/per-vertex/per-edge counting
and both peelings — is the same computation: aggregating wedges
incident on subsets of vertices (ParButterfly's core observation).
This module makes that explicit as a three-stage architecture:

  **plan** — a :class:`WedgePlan` is a plain, serializable description
  of a wedge workload: vertex-aligned tile boundaries from the
  aligned-tile planners (``wedges.plan_wedge_chunks``), a per-tile
  aggregation strategy (the sort-vs-hash decision, made at plan time
  from tile density), capacity segments, an expansion-callable id from
  :data:`EXPANSIONS`, and an :class:`AccumulatorSpec`. Plans round-trip
  through dict/JSON, partition across devices
  (:func:`plan_partition`), and plan-equality implies
  execution-equality (planning is pure host numpy on the graph).

  **execute** — ONE shared tile-loop executor family subsumes the
  engines' former private copies: :func:`run_count_tiles` (counting's
  streaming fori_loop), :func:`stream_tiles` (peeling's fused-subtract
  while_loop) and :func:`device_round_loop` (the peeling round
  skeleton).
  Kernels are dispatched ONLY through ``kernels/ops.py`` — this module
  never imports a concrete kernel, and ``count.py`` / ``peel.py``
  never reach past this module's public surface (both enforced by
  ``scripts/check_layering.py``).

  **report** — :func:`execute_ladder` is the single resilience wrapper:
  it runs a degradation ladder under one
  :class:`~repro.core.resilience.ResiliencePolicy` and records the
  plan summary on the resulting
  :class:`~repro.core.resilience.ExecutionReport` (``report.plan``),
  instead of each engine wiring the policy per call site.

Tile-alignment invariant (everything rests on it): flat wedge ids
follow CSR slot order, so every endpoint-pair group lives inside one
iterating endpoint's contiguous range; cutting tiles only at vertex
boundaries means no group ever spans a tile, per-tile C(d, 2)
contributions add exactly, and — because integer adds commute — ANY
vertex-aligned tiling (including any device partition of the tiles)
produces bitwise-identical counts.

``plan_partition(plan, n)`` generalizes the former
``distributed.plan_fused_partition``: it splits a plan's tiles across
``n`` devices greedily by wedge load, returning ``n`` sub-plans whose
tile lists concatenate to the parent's. This is the seam distributed
peeling (ROADMAP item 1) consumes: a peeling round's wedge work,
described as a plan, partitions the same way.

Per-tile sort-vs-hash (the PR 3 standing follow-up)
---------------------------------------------------
``aggregation="auto"`` resolves each tile's strategy at plan time from
its *density* — wedges per endpoint-pair, estimated as the tile's
wedge total over a lower bound on its distinct (x1, x2) pairs (each
directed slot's wedges have pairwise-distinct x2, so
``max_slot_cnt(x1)`` pairs per vertex is certain). Dense tiles (many
wedges collapsing onto few pairs) take the bounded-probe hash table;
sparse tiles (d ~= 1, where the table would be as large as the tile)
take the sort. Both strategies are exact and the hash path keeps its
in-graph sort fallback, so the choice affects speed only — parity
tests assert bitwise-identical counts against forced-sort and
forced-hash runs.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as _kops
from ..testing import faults as _faults
from . import resilience as _res
from .aggregate import Groups, aggregate_dense, aggregate_hash, aggregate_sort
from .graph import RankedGraph
from .tap import (
    DEVICE_SCOPES,
    fetch,
    launch,
    record_programs,
    scope,
    span,
    traced,
)
from .wedges import (
    DeviceGraph,
    Wedges,
    aligned_tile_end,
    host_wedge_counts,
    plan_wedge_chunks,
    slot_wedge_counts,
    wedge_offsets,
    wedges_at,
)

__all__ = [
    # plan
    "AccumulatorSpec",
    "WedgePlan",
    "EXPANSIONS",
    "DENSITY_HASH_THRESHOLD",
    "plan_count",
    "plan_peel",
    "peel_tile_bounds",
    "plan_partition",
    "partition_tile_array",
    # execute: counting
    "choose2",
    "combine_limbs",
    "group_choose2",
    "wedge_dm1",
    "accumulate_counts",
    "tile_apply",
    "aggregate_and_accumulate",
    "zero_counts",
    "count_tile_step",
    "run_count_tiles",
    "lane_counts",
    "vertex_tile_lanes",
    "narrow_partials",
    "run_fused_pallas_program",
    "run_fused_pallas_tiles",
    "plan_strategies",
    "execute_count_plan",
    # the tap (core/tap.py): spans, syncs, device scopes, programs
    "DEVICE_SCOPES",
    "scope",
    "span",
    "traced",
    "fetch",
    "record_programs",
    "launch",
    # execute: peeling substrate
    "I32_MAX",
    "LoopState",
    "prefix_offsets",
    "empty_hist",
    "masked_state",
    "apply_decrements",
    "init_loop_state",
    "stream_tiles",
    "device_round_loop",
    # report
    "execute_ladder",
]

MODES = ("global", "vertex", "edge", "all")
I32_MAX = int(np.iinfo(np.int32).max)

# Plan-time density threshold for ``aggregation="auto"``: a tile whose
# estimated wedges-per-endpoint-pair reaches this takes the hash
# strategy (the bounded-probe table holds ~one entry per distinct pair,
# so high multiplicity amortizes it); below it, sort wins (d ~= 1 makes
# the table as large as the tile with none of the collapse). The value
# is a heuristic starting point for the ROADMAP item 4 autotuner —
# correctness never depends on it.
DENSITY_HASH_THRESHOLD = 4.0

# Expansion-callable registry: a WedgePlan names its wedge recovery by
# id instead of carrying a callable (plans must serialize). The
# executors bind the id back to code: "count_wedges" is the
# ``wedges.wedges_at`` binary-search recovery consumed by
# run_count_tiles / run_fused_pallas_tiles; the peel_* ids name the
# expand callables the decomposition frontends pass into
# device_round_loop (their tile recovery runs through stream_tiles).
EXPANSIONS = {
    "count_wedges": "flat wedge ids -> (x1, x2, y) via wedges_at",
    "peel_tips_2hop": "peeled vertices -> 2-hop wedge pairs (PEEL-V)",
    "peel_tips_stored": "peeled vertices -> stored-wedge CSR rows "
                        "(WPEEL-V)",
    "peel_wings_triples": "peeled edges -> butterfly edge triples via "
                          "the degree-sorted CSR (PEEL-E)",
}


# ---------------------------------------------------------------------------
# Plan layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AccumulatorSpec:
    """What a plan's executor accumulates into: the count mode, the
    result dtype (by name — specs serialize), and the output extents
    (``n_pad`` for vertex counts, ``m`` for edge counts, ``n_out`` for
    peel numbers)."""

    mode: str  # global | vertex | edge | all (counting); numbers (peel)
    dtype: str  # numpy dtype name, e.g. "int32"
    n_pad: int = 0
    m: int = 0
    n_out: int = 0

    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


@dataclasses.dataclass(frozen=True)
class WedgePlan:
    """A serializable description of one wedge workload.

    For tiled counting plans (``kind="count"``), ``bounds`` are the
    vertex-aligned tile boundaries in rank space, ``tile_wedges[i]``
    the exact wedge total of tile ``i``, ``tile_aggregation[i]`` its
    resolved strategy, ``chunk_cap`` the fixed per-tile buffer size,
    and ``w_start`` the flat wedge id of ``bounds[0]`` (nonzero only
    for partition sub-plans). Peeling plans (``kind="peel_*"``) are
    *envelope* plans: they carry the expansion id, the accumulator
    spec, and the capacity segments the run wrappers planned — the
    exact per-round tile boundaries are data-dependent (the frontier),
    so they are cut in-graph by ``stream_tiles``/``aligned_tile_end``
    against the same invariant.

    ``capacity`` is a tuple of ``(name, value)`` segments: every
    statically-planned buffer the executor allocates (tile caps,
    frontier caps), recorded so a plan documents its memory envelope,
    and for ``engine="fused_pallas"`` the graph's largest degree
    (``max_degree``), which bounds a vertex tile's partial sums
    (:func:`narrow_partials`).
    """

    kind: str  # count | peel_tips | peel_tips_stored | peel_wings
    expansion: str  # EXPANSIONS id
    direction: str  # low | high
    engine: str  # xla | pallas | fused | fused_pallas | device | host
    aggregation: str  # requested: sort | hash | histogram | auto
    tile_aggregation: tuple  # per-tile resolved strategy (tiled plans)
    bounds: tuple  # (n_tiles + 1,) vertex boundaries (tiled plans)
    tile_wedges: tuple  # (n_tiles,) wedges per tile (tiled plans)
    chunk_cap: int  # fixed per-tile wedge-buffer size
    w_start: int  # flat wedge id of bounds[0] (partition sub-plans)
    capacity: tuple  # ((name, value), ...) planned buffer segments
    budget: int  # requested wedge budget the planner honored
    hash_bits: Optional[int]
    accumulator: AccumulatorSpec

    def __post_init__(self):
        if self.expansion not in EXPANSIONS:
            raise ValueError(
                f"unknown expansion id {self.expansion!r}; known: "
                f"{sorted(EXPANSIONS)}"
            )
        if len(self.tile_wedges) != max(len(self.bounds) - 1, 0):
            raise ValueError(
                "tile_wedges must have one entry per bounds interval"
            )
        if self.tile_aggregation and (
            len(self.tile_aggregation) != len(self.tile_wedges)
        ):
            raise ValueError(
                "tile_aggregation must be empty or one entry per tile"
            )

    @property
    def n_tiles(self) -> int:
        return len(self.tile_wedges)

    @property
    def total_wedges(self) -> int:
        return int(sum(self.tile_wedges))

    def tile_flat_bounds(self) -> np.ndarray:
        """Per-tile ``[start, end)`` in flat wedge-id space,
        ``(n_tiles, 2)`` int64 — what the device partition ships."""
        pref = np.concatenate(
            [[0], np.cumsum(np.asarray(self.tile_wedges, np.int64))]
        )
        pref += int(self.w_start)
        return np.stack([pref[:-1], pref[1:]], axis=1)

    def strategy_counts(self) -> dict:
        """{strategy: tile count} over the resolved per-tile choices."""
        out: dict = {}
        for s in self.tile_aggregation:
            out[s] = out.get(s, 0) + 1
        return out

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # asdict leaves tuples as tuples; normalize to lists so the
        # dict is exactly what json round-trips through
        return json.loads(json.dumps(d))

    @classmethod
    def from_dict(cls, d: dict) -> "WedgePlan":
        d = dict(d)
        acc = d.pop("accumulator")
        return cls(
            accumulator=AccumulatorSpec(**acc),
            tile_aggregation=tuple(d.pop("tile_aggregation")),
            bounds=tuple(d.pop("bounds")),
            tile_wedges=tuple(d.pop("tile_wedges")),
            capacity=tuple(
                (str(k), int(v)) for k, v in d.pop("capacity")
            ),
            **d,
        )

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "WedgePlan":
        return cls.from_dict(json.loads(s))

    def summary(self) -> str:
        """One line for the ExecutionReport audit trail."""
        parts = [
            f"{self.kind}/{self.expansion}",
            f"engine={self.engine}",
            f"mode={self.accumulator.mode}",
            f"agg={self.aggregation}",
        ]
        if self.n_tiles:
            sc = self.strategy_counts()
            mix = ",".join(f"{k}:{v}" for k, v in sorted(sc.items()))
            parts.append(
                f"tiles={self.n_tiles}({mix}) cap={self.chunk_cap} "
                f"wedges={self.total_wedges}"
            )
        if self.capacity:
            parts.append(
                "caps=" + ",".join(f"{k}={v}" for k, v in self.capacity)
            )
        return " ".join(parts)


def _round_up(x: int, to: int) -> int:
    return ((max(int(x), 1) + to - 1) // to) * to


def _pow2_at_least(x: int, floor: int = 128) -> int:
    return max(int(floor), 1 << (max(int(x), 1) - 1).bit_length())


def _tile_pair_floor(rg: RankedGraph, wv_slots: np.ndarray) -> np.ndarray:
    """Per-vertex lower bound on distinct (x1, x2) endpoint pairs: the
    wedges of one directed slot (x1 -> y) all have distinct x2, so
    vertex x1 contributes at least ``max_e cnt[e]`` distinct pairs —
    the certain part of the density denominator."""
    n_real = 2 * rg.m
    mx = np.zeros(rg.n_pad, dtype=np.int64)
    if n_real:
        np.maximum.at(
            mx, rg.edge_src[:n_real].astype(np.int64), wv_slots[:n_real]
        )
    return mx


def _kernel_split_bounds(wv: np.ndarray, kernel_cap: int, target: int):
    """Vertex-aligned tiles for ``engine="fused_pallas"``: runs of
    vertices owning at most ``kernel_cap`` wedges pack greedily into
    kernel tiles of at most ``min(kernel_cap, target)`` wedges (a single
    vertex above the target gets a solo tile); every heavier vertex
    gets a solo ``"vertex"`` tile. Returns ``(bounds, is_kernel)`` —
    vertex boundaries and one flag per tile."""
    n = wv.shape[0]
    heavy = wv > kernel_cap
    woff = np.concatenate([[0], np.cumsum(wv)])
    run_end = np.concatenate(
        [np.flatnonzero(heavy[1:] != heavy[:-1]) + 1, [n]]
    )
    target = min(int(kernel_cap), int(target))
    bounds, is_kernel = [0], []
    b, r = 0, 0
    while b < n:
        while run_end[r] <= b:
            r += 1
        nxt = b + 1
        if not heavy[b]:
            nxt = int(np.searchsorted(woff, woff[b] + target, "right")) - 1
            nxt = min(max(nxt, b + 1), int(run_end[r]))
        bounds.append(nxt)
        is_kernel.append(not heavy[b])
        b = nxt
    return np.asarray(bounds, np.int64), np.asarray(is_kernel, bool)


@traced("plan")
def plan_count(
    rg: RankedGraph,
    *,
    mode: str = "global",
    direction: str = "low",
    aggregation: str = "sort",
    budget: int,
    dtype="int32",
    hash_bits: Optional[int] = None,
    engine: str = "fused",
    density_threshold: float = DENSITY_HASH_THRESHOLD,
    wv_slots: Optional[np.ndarray] = None,
) -> WedgePlan:
    """Plan a tiled counting workload: vertex-aligned tile boundaries
    (``wedges.plan_wedge_chunks`` under ``budget``), exact per-tile
    wedge totals, and the per-tile aggregation strategy.

    ``aggregation="auto"`` resolves sort-vs-hash per tile from the
    density estimate (see module docstring); any other value is applied
    uniformly. ``engine="fused_pallas"`` splits the tiling: vertices
    owning at most ``MAX_TILE_CAP`` wedges (the ``wedge_fused`` kernel's
    exactness bound) go to ``"kernel"`` tiles of at most
    ``min(MAX_TILE_CAP, budget)`` wedges; each heavier vertex gets a
    solo ``"vertex"`` tile, aggregated in XLA over a dense table keyed
    by its other endpoint (see :func:`run_fused_pallas_program`).
    Planning is deterministic pure-numpy on (graph, knobs) — the
    property the plan tests pin down.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be {'|'.join(MODES)}, got {mode}")
    if aggregation not in ("sort", "hash", "histogram", "auto"):
        raise ValueError(
            "plan_count aggregation must be sort|hash|histogram|auto, "
            f"got {aggregation}"
        )
    if wv_slots is None:
        wv_slots = host_wedge_counts(rg, direction)
    n_real = 2 * rg.m
    wv = np.zeros(rg.n_pad, dtype=np.int64)
    if n_real:
        np.add.at(
            wv, rg.edge_src[:n_real].astype(np.int64), wv_slots[:n_real]
        )
    split = engine == "fused_pallas"
    if split:
        kernel_cap = _faults.capacity_override(
            "fused_pallas.plan", _kops.MAX_TILE_CAP
        )
        bounds, is_kernel = _kernel_split_bounds(
            wv, int(kernel_cap), int(budget)
        )
    else:
        bounds, chunk_cap = plan_wedge_chunks(
            rg, direction, int(budget), wv_slots=wv_slots
        )
    voff = np.concatenate([[0], np.cumsum(wv)])
    tile_wedges = (voff[bounds[1:]] - voff[bounds[:-1]]).astype(np.int64)
    capacity = ()
    if split:
        tile_aggregation = tuple(
            "kernel" if k else "vertex" for k in is_kernel
        )
        chunk_cap = _pow2_at_least(
            int(tile_wedges[~is_kernel].max(initial=1))
        )
        k_max = int(tile_wedges[is_kernel].max(initial=1))
        capacity = (
            ("kernel_tile", _round_up(k_max, _kops.TC)),
            ("max_degree", int(np.diff(rg.offsets).max(initial=0))),
        )
    elif aggregation == "auto":
        mx = _tile_pair_floor(rg, wv_slots)
        moff = np.concatenate([[0], np.cumsum(mx)])
        pair_floor = np.maximum(moff[bounds[1:]] - moff[bounds[:-1]], 1)
        density = tile_wedges / pair_floor
        tile_aggregation = tuple(
            "hash" if d >= density_threshold else "sort" for d in density
        )
    else:
        tile_aggregation = (aggregation,) * int(tile_wedges.shape[0])
    return WedgePlan(
        kind="count",
        expansion="count_wedges",
        direction=direction,
        engine=engine,
        aggregation=aggregation,
        tile_aggregation=tile_aggregation,
        bounds=tuple(int(b) for b in bounds),
        tile_wedges=tuple(int(w) for w in tile_wedges),
        chunk_cap=int(chunk_cap),
        w_start=0,
        capacity=capacity + (("chunk_cap", int(chunk_cap)),),
        budget=int(budget),
        hash_bits=hash_bits,
        accumulator=AccumulatorSpec(
            mode=mode,
            dtype=np.dtype(
                dtype if isinstance(dtype, str) else jnp.dtype(dtype).name
            ).name,
            n_pad=rg.n_pad,
            m=rg.m,
        ),
    )


def peel_tile_bounds(
    entity_work, n_tiles: int = 64
) -> tuple:
    """Cut entity-aligned coarse tiles over a peeling decomposition's
    static per-entity expansion totals (per-vertex 2-hop totals for
    tips, stored-wedge row lengths for WPEEL-V, per-edge triple totals
    for wings).

    Unlike counting tiles — per-round buffers the executor streams —
    peeling tiles are the *partition granularity*: each tile is a
    contiguous run of iterating-entity ids with its summed worst-case
    expansion work, and ``plan_partition`` balances whole tiles across
    devices. Entity alignment is the same invariant as the counting
    planner's vertex alignment: every subtract group is keyed by its
    iterating entity, so no group spans a tile (or a device) and the
    per-device partial decrements add exactly.

    Boundaries come from ``n_tiles`` equal-work quantiles of the work
    prefix sum (deduplicated — a single heavy entity gets a solo tile).
    Returns ``(bounds, tile_wedges)`` tuples ready for
    :class:`WedgePlan`.
    """
    work = np.asarray(entity_work, dtype=np.int64)
    n = int(work.shape[0])
    if n == 0:
        return (), ()
    coff = np.concatenate([[0], np.cumsum(work)])
    total = int(coff[-1])
    k = max(1, min(int(n_tiles), n))
    if total == 0:
        # no expansion work anywhere: uniform entity-count tiles keep
        # the partition well-defined (devices still get entity ranges)
        cuts = np.unique(
            np.linspace(0, n, k + 1).astype(np.int64)
        )
    else:
        targets = (np.arange(1, k) * total) / k
        cuts = np.searchsorted(coff, targets, side="left")
        cuts = np.unique(np.concatenate([[0], cuts, [n]]))
    bounds = tuple(int(b) for b in cuts)
    tile_wedges = tuple(
        int(coff[bounds[i + 1]] - coff[bounds[i]])
        for i in range(len(bounds) - 1)
    )
    return bounds, tile_wedges


@traced("plan")
def plan_peel(
    kind: str,
    *,
    expansion: str,
    engine: str,
    aggregation: str,
    n_out: int,
    dtype="int32",
    capacity: Sequence = (),
    budget: int = I32_MAX,
    hash_bits: Optional[int] = None,
    entity_work=None,
    coarse_tiles: int = 64,
) -> WedgePlan:
    """Plan for a peeling decomposition: the expansion id, accumulator
    spec, planned capacity segments — and, when the frontend passes its
    static per-entity expansion totals as ``entity_work``, real coarse
    tile bounds (:func:`peel_tile_bounds`) so ``plan_partition`` can
    split the decomposition across devices. Fine per-round tile
    boundaries remain data-dependent (the frontier) and stay in-graph
    (``stream_tiles``/``aligned_tile_end``); the coarse tiles are the
    entity-aligned partition granularity the distributed supervisor
    fans out over."""
    if entity_work is not None:
        bounds, tile_wedges = peel_tile_bounds(entity_work, coarse_tiles)
    else:
        bounds, tile_wedges = (), ()
    return WedgePlan(
        kind=kind,
        expansion=expansion,
        direction="low",
        engine=engine,
        aggregation=aggregation,
        tile_aggregation=(),
        bounds=bounds,
        tile_wedges=tile_wedges,
        chunk_cap=0,
        w_start=0,
        capacity=tuple((str(k), int(v)) for k, v in capacity),
        budget=int(budget),
        hash_bits=hash_bits,
        accumulator=AccumulatorSpec(
            mode="numbers",
            dtype=np.dtype(
                dtype if isinstance(dtype, str) else jnp.dtype(dtype).name
            ).name,
            n_out=int(n_out),
        ),
    )


def plan_partition(plan: WedgePlan, n: int) -> list:
    """Split a tiled plan across ``n`` devices: contiguous tile runs,
    boundaries placed greedily so each device's wedge load approaches
    the ideal share (the wedge-aware batching heuristic promoted to the
    partition strategy, as in the former ``plan_fused_partition``).

    Tiles are never split — they are vertex-aligned (entity-aligned for
    peeling plans), so assigning each whole tile to one device
    preserves the invariant that no endpoint-pair group spans a device,
    and the per-device partial counts add exactly (bitwise — integer
    adds commute). Returns ``n`` sub-plans whose ``tile_flat_bounds()``
    concatenate to the parent's; devices beyond the tile count get
    empty plans. A plan with no tiles at all (an empty workload, or a
    peeling plan built without ``entity_work``) partitions into ``n``
    empty sub-plans — every device sees an empty tile list, not an
    error.
    """
    n = max(int(n), 1)
    if plan.n_tiles == 0:
        return [dataclasses.replace(plan) for _ in range(n)]
    tw = np.asarray(plan.tile_wedges, np.int64)
    pref = np.concatenate([[0], np.cumsum(tw)])
    total = int(pref[-1])
    ideal = total / n
    cuts = [0]
    for d in range(1, n):
        c = int(np.searchsorted(pref, d * ideal, side="left"))
        cuts.append(min(max(c, cuts[-1]), plan.n_tiles))
    cuts.append(plan.n_tiles)
    parts = []
    for d in range(n):
        t0, t1 = cuts[d], cuts[d + 1]
        if t1 > t0:
            bounds = plan.bounds[t0 : t1 + 1]
        else:
            bounds = (plan.bounds[min(t0, len(plan.bounds) - 1)],)
        parts.append(dataclasses.replace(
            plan,
            bounds=bounds,
            tile_wedges=plan.tile_wedges[t0:t1],
            tile_aggregation=(
                plan.tile_aggregation[t0:t1]
                if plan.tile_aggregation else ()
            ),
            w_start=int(plan.w_start + pref[t0]),
        ))
    return parts


def partition_tile_array(parts: Sequence[WedgePlan]):
    """Pack partitioned sub-plans into the device-sharded tile format:
    ``(tiles (n_dev, max_tiles, 2) int32, tile_cap)`` — flat wedge-id
    ``[start, end)`` per tile, rows padded with empty ``(0, 0)`` tiles
    (the ``distributed_count_fn`` contract)."""
    per_dev = [p.tile_flat_bounds() for p in parts]
    max_tiles = max(1, max(t.shape[0] for t in per_dev))
    tiles = np.zeros((len(parts), max_tiles, 2), np.int64)
    for d, t in enumerate(per_dev):
        tiles[d, : t.shape[0]] = t
    tile_cap = max(p.chunk_cap for p in parts)
    return tiles.astype(np.int32), int(tile_cap)


# ---------------------------------------------------------------------------
# Execute layer: counting primitives (Lemma 4.2 accumulation)
# ---------------------------------------------------------------------------


def choose2(d: jax.Array, dtype) -> jax.Array:
    dd = d.astype(dtype)
    return dd * (dd - 1) // 2


def combine_limbs(lo: jax.Array, hi: jax.Array, dtype) -> jax.Array:
    """Recombine the combine kernel's 64-bit C(d, 2) limbs into
    ``dtype``. With a 64-bit count dtype this is exact for the full
    int32 multiplicity range; sub-64-bit dtypes keep the low word's
    bit pattern (values that need more than 32 bits need a 64-bit
    ``count_dtype``, same as every other engine)."""
    if jnp.dtype(dtype).itemsize >= 8:
        return lo.astype(jnp.uint32).astype(dtype) + (hi.astype(dtype) << 32)
    return lo.astype(dtype)


def group_choose2(groups: Groups, dtype, engine: str) -> jax.Array:
    """Per-group C(d, 2) endpoint contributions, in ``dtype``."""
    if engine == "pallas":
        # The widened kernel emits C(d, 2) as two int32 limbs — exact
        # for the whole int32 multiplicity range, so no in-graph
        # exact-path fallback is needed (dispatch through kernels/ops).
        _, lo, hi, _ = _kops.butterfly_combine(
            groups.d,
            jnp.ones_like(groups.d),
            groups.valid.astype(jnp.int32),
            use_pallas=True,
        )
        return combine_limbs(lo, hi, dtype)
    return jnp.where(groups.valid, choose2(groups.d, dtype), 0)


def wedge_dm1(w: Wedges, groups: Groups, dtype, engine: str) -> jax.Array:
    """Per-wedge d - 1 center/edge contributions, in ``dtype``."""
    d = groups.d_per_wedge
    if engine == "pallas":
        dm1, _, _, _ = _kops.butterfly_combine(
            d, jnp.zeros_like(d), w.valid.astype(jnp.int32), use_pallas=True
        )
        return dm1.astype(dtype)
    return jnp.where(w.valid & (d > 0), (d - 1).astype(dtype), 0)


def accumulate_counts(
    dg: DeviceGraph,
    w: Wedges,
    groups: Groups,
    mode: str,
    dtype,
    engine: str = "xla",
):
    """Turn group multiplicities into butterfly counts (Lemma 4.2).

    ``mode="all"`` returns the (total, per-vertex, per-edge) triple from
    the same shared (dm1, C(d, 2)) intermediates — the single-pass path.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be {'|'.join(MODES)}, got {mode}")
    dm1 = (
        wedge_dm1(w, groups, dtype, engine)
        if mode in ("vertex", "edge", "all")
        else None
    )
    g_add = (
        group_choose2(groups, dtype, engine)
        if mode in ("global", "vertex", "all")
        else None
    )

    def _global():
        # Every group of d wedges = C(d,2) butterflies, each counted once
        # thanks to the rank filter.
        return jnp.sum(g_add).astype(dtype)

    def _vertex():
        bv = jnp.zeros((dg.n_pad,), dtype)
        bv = bv.at[groups.x1].add(g_add)
        bv = bv.at[groups.x2].add(g_add)
        # centers: w.y holds an out-of-range sentinel for invalid wedges;
        # JAX scatter drops OOB updates.
        bv = bv.at[w.y].add(dm1)
        return bv

    def _edge():
        be = jnp.zeros((dg.m,), dtype)
        be = be.at[dg.undirected_id[w.center_slot]].add(dm1)
        be = be.at[dg.undirected_id[w.second_slot]].add(dm1)
        return be

    if mode == "global":
        return _global()
    if mode == "vertex":
        return _vertex()
    if mode == "edge":
        return _edge()
    # mode == "all": one fused scatter-add over a combined
    # [vertex | edge] buffer — the five single-mode scatters collapse to
    # one device pass, which is where the single-pass speedup on top of
    # the shared gather+aggregation comes from. Integer adds commute, so
    # the split views are bitwise-identical to the single-mode results.
    nm = dg.n_pad + dg.m
    oob = jnp.int32(nm)  # JAX scatter drops out-of-bounds updates
    idx = jnp.concatenate([
        jnp.where(w.valid, w.y, oob),
        jnp.where(w.valid, dg.n_pad + dg.undirected_id[w.center_slot], oob),
        jnp.where(w.valid, dg.n_pad + dg.undirected_id[w.second_slot], oob),
        groups.x1,
        groups.x2,
    ])
    upd = jnp.concatenate([dm1, dm1, dm1, g_add, g_add])
    buf = jnp.zeros((nm,), dtype).at[idx].add(upd)
    return jnp.sum(g_add).astype(dtype), buf[: dg.n_pad], buf[dg.n_pad :]


def tile_apply(
    w: Wedges,
    aggregation: str,
    consume,
    engine: str = "xla",
    hash_bits: Optional[int] = None,
    dense_n: Optional[int] = None,
):
    """Aggregate ONE generated wedge tile and hand it to ``consume``.

    ``consume(wedges, groups)`` turns the tile's endpoint-pair groups
    into whatever the caller accumulates — butterfly counts here, the
    C(d, 2) frontier *subtraction* in peeling's fused tile loop (the
    machinery is shared so both sides keep the identical aggregation
    semantics). For ``aggregation="hash"`` the overflow fallback is
    in-graph: a ``lax.cond`` re-aggregates the *same* materialized tile
    with the sort strategy only when the bounded-probe table failed,
    instead of a host-side ``bool(ok)`` sync + pipeline re-run.
    ``dense_n`` sizes the ``histogram`` strategy's key space (counting
    passes ``dg.n_pad``; peeling does not use histogram).

    Returns ``(consume(...), ok)``.
    """
    if aggregation == "sort":
        groups, ws = aggregate_sort(w)
        return consume(ws, groups), jnp.array(True)
    if aggregation == "histogram":
        groups = aggregate_dense(w, dense_n, engine=engine)
        return consume(w, groups), jnp.array(True)
    if aggregation == "hash":
        groups = aggregate_hash(w, table_bits=hash_bits, engine=engine)

        def _hash_path(_):
            return consume(w, groups)

        def _sort_path(_):
            g2, ws = aggregate_sort(w)
            return consume(ws, g2)

        out = jax.lax.cond(groups.ok, _hash_path, _sort_path, None)
        return out, groups.ok
    raise ValueError(f"bad aggregation {aggregation}")


def aggregate_and_accumulate(
    dg: DeviceGraph,
    w: Wedges,
    aggregation: str,
    mode: str,
    dtype,
    engine: str,
    hash_bits: Optional[int] = None,
):
    """Aggregate one (chunk of the) wedge stream and accumulate counts."""
    return tile_apply(
        w,
        aggregation,
        lambda wv, gv: accumulate_counts(dg, wv, gv, mode, dtype, engine),
        engine,
        hash_bits,
        dense_n=dg.n_pad,
    )


def zero_counts(dg: DeviceGraph, mode: str, dtype):
    by_mode = {
        "global": lambda: jnp.zeros((), dtype),
        "vertex": lambda: jnp.zeros((dg.n_pad,), dtype),
        "edge": lambda: jnp.zeros((dg.m,), dtype),
    }
    if mode == "all":
        return tuple(by_mode[m]() for m in ("global", "vertex", "edge"))
    return by_mode[mode]()


def count_tile_step(
    dg: DeviceGraph,
    cnt: Optional[jax.Array],
    w_off: jax.Array,
    ws: jax.Array,
    we: jax.Array,
    *,
    chunk_cap: int,
    aggregation: str,
    mode: str,
    direction: str,
    dtype,
    engine: str = "xla",
    hash_bits: Optional[int] = None,
):
    """Generate -> aggregate -> accumulate ONE vertex-aligned wedge
    tile ``[ws, we)`` and discard it — the fused counting step shared
    by the streaming executor here and the distributed per-device loop
    (``distributed``). The aggregation core (including the in-graph
    hash-overflow sort fallback) is :func:`tile_apply`, which the
    peeling engines' fused frontier subtract also streams through. The
    tile-alignment invariant of ``plan_wedge_chunks`` guarantees no
    endpoint-pair group spans the tile, so the per-tile counts add
    exactly."""
    wid = ws + jnp.arange(chunk_cap, dtype=jnp.int32)
    valid = wid < we
    w = wedges_at(dg, cnt, w_off, wid, valid, direction)
    return aggregate_and_accumulate(
        dg, w, aggregation, mode, dtype, engine, hash_bits
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_cap", "aggregation", "mode", "direction", "dtype", "engine",
        "hash_bits",
    ),
)
def run_count_tiles(
    dg: DeviceGraph,
    bounds: jax.Array,  # (n_blocks + 1,) vertex boundaries
    strategies: Optional[jax.Array] = None,  # (n_blocks,) 0=sort 1=hash
    *,
    chunk_cap: int,
    aggregation: str,
    mode: str,
    direction: str,
    dtype,
    engine: str = "xla",
    hash_bits: Optional[int] = None,
):
    """THE shared counting tile-loop executor: a fori_loop over
    vertex-aligned tiles of the flat wedge space, each re-materialized
    via ``wedges_at`` into a fixed (chunk_cap,) buffer, aggregated
    locally, accumulated, and discarded — all inside one jitted
    program. Peak wedge memory is O(chunk_cap) instead of O(W);
    per-tile counts add exactly because groups never span an
    iterating-vertex boundary (see ``plan_wedge_chunks``). This is both
    the ``max_chunk`` streaming path and the ``engine="fused"`` hot
    loop (which always routes through it, regardless of wedge total).

    ``strategies`` carries a mixed plan's per-tile sort-vs-hash choice
    as a traced operand (0 = sort, 1 = hash): the tile is generated
    once and a ``lax.cond`` selects the aggregation. ``None`` (every
    uniform plan) compiles the exact single-strategy program the
    pre-plan engine ran — bitwise- and cache-identical."""
    cnt = slot_wedge_counts(dg, direction)
    w_off = wedge_offsets(cnt)
    n_blocks = bounds.shape[0] - 1
    acc0 = zero_counts(dg, mode, dtype)

    def body(i, carry):
        acc, ok = carry
        v0 = bounds[i]
        v1 = bounds[i + 1]
        ws = w_off[dg.offsets[v0]]
        we = w_off[dg.offsets[v1]]
        if strategies is None:
            out, ok_i = count_tile_step(
                dg, cnt, w_off, ws, we,
                chunk_cap=chunk_cap, aggregation=aggregation, mode=mode,
                direction=direction, dtype=dtype, engine=engine,
                hash_bits=hash_bits,
            )
        else:
            wid = ws + jnp.arange(chunk_cap, dtype=jnp.int32)
            valid = wid < we
            w = wedges_at(dg, cnt, w_off, wid, valid, direction)
            out, ok_i = jax.lax.cond(
                strategies[i] == 1,
                lambda wt: aggregate_and_accumulate(
                    dg, wt, "hash", mode, dtype, engine, hash_bits
                ),
                lambda wt: aggregate_and_accumulate(
                    dg, wt, "sort", mode, dtype, engine, hash_bits
                ),
                w,
            )
        acc = jax.tree_util.tree_map(
            lambda a, o: (a + o).astype(a.dtype), acc, out
        )
        return acc, ok & ok_i

    return jax.lax.fori_loop(0, n_blocks, body, (acc0, jnp.array(True)))


def lane_counts(dg: DeviceGraph, w: Wedges, dm1: jax.Array, c2: jax.Array,
                mode: str, acc):
    """Add per-wedge-lane contributions to the accumulators ``acc``
    (Lemma 4.2): ``c2`` (C(d, 2) on each group's representative lane,
    0 elsewhere) to the endpoints x1, x2 and the total, ``dm1`` (d - 1)
    to the center y and the wedge's two edges. Invalid lanes carry the
    out-of-range sentinels of ``wedges_at`` and zero contributions."""
    if mode == "global":
        return acc + jnp.sum(c2).astype(acc.dtype)

    def vertex(bv):
        for x in (w.x1, w.x2):  # None: the caller adds that endpoint
            if x is not None:
                bv = bv.at[x].add(c2)
        return bv.at[w.y].add(dm1)

    def edge(be):
        return (be.at[dg.undirected_id[w.center_slot]].add(dm1)
                .at[dg.undirected_id[w.second_slot]].add(dm1))

    if mode == "vertex":
        return vertex(acc)
    if mode == "edge":
        return edge(acc)
    tot, bv, be = acc
    return tot + jnp.sum(c2).astype(tot.dtype), vertex(bv), edge(be)


KERNEL_BATCH = 16  # wedge_fused tiles per kernel launch

# Within one kernel tile a vertex or an edge takes at most
# tile_cap * (tile_cap - 1): each lane adds it at most d - 1 <= tile_cap
# - 1 (as the center or an edge of the wedge) or (d - 1) / 2 (as an
# endpoint, C(d, 2) over the group's d lanes). A batch of the widest
# tiles therefore fits an int32 partial.
assert KERNEL_BATCH * _kops.MAX_TILE_CAP * (_kops.MAX_TILE_CAP - 1) <= I32_MAX


def narrow_partials(dtype, mode: str, tile_cap: int,
                    max_degree: Optional[int]) -> bool:
    """Whether :func:`run_fused_pallas_program` may sum each kernel
    batch and vertex tile into int32 partials before adding them to
    64-bit integer accumulators — exact when no partial can reach 2^31.
    A kernel batch: see the bound above. A vertex tile (one iterating
    vertex ``it``, degrees at most ``max_degree`` = D): the other
    endpoint takes one group's C(d, 2) <= C(D, 2), the center y and the
    edge (it, y) at most D - 1 lanes of d - 1 <= D - 1 each, the edge
    (y, other) one lane; ``it`` itself takes an int64 sum. D * D < 2^31
    covers them all. The total is always summed in the accumulator's
    dtype; ``mode="global"`` has nothing to scatter."""
    dt = np.dtype(dtype)
    return (
        mode != "global"
        and dt.kind in "iu"
        and dt.itemsize == 8
        and KERNEL_BATCH * tile_cap * (tile_cap - 1) <= I32_MAX
        and max_degree is not None
        and max_degree * max_degree <= I32_MAX
    )


def _partials(dg: DeviceGraph, mode: str, dtype):
    """Zeroed int32 partials of the accumulators; the total stays in
    ``dtype`` (it sums every group, which no static bound holds)."""
    part = zero_counts(dg, mode, jnp.int32)
    return (jnp.zeros((), dtype),) + part[1:] if mode == "all" else part


def _widen(acc, part):
    """Add the partials densely into the accumulators, once."""
    return jax.tree_util.tree_map(
        lambda a, p: a + p.astype(a.dtype), acc, part
    )


def vertex_tile_lanes(w: Wedges, n_pad: int, direction: str, dtype):
    """Per-lane ``(dm1, c2)`` of a tile holding ONE iterating vertex's
    wedges: its endpoint-pair groups are keyed by the other endpoint
    alone, so a dense ``n_pad`` table counts them (scatter-add) and a
    scatter-min of lane ids marks each group's first lane — exact, and
    no sort (whose TPU compile time grows steeply with size)."""
    other = w.x2 if direction == "low" else w.x1  # sentinel n_pad: dropped
    lane = jnp.arange(other.shape[0], dtype=jnp.int32)
    cnt = jnp.zeros((n_pad,), jnp.int32).at[other].add(1)
    first = jnp.full((n_pad,), other.shape[0], jnp.int32).at[other].min(lane)
    safe = jnp.minimum(other, n_pad - 1)
    d = jnp.where(w.valid, cnt[safe], 0)
    rep = w.valid & (first[safe] == lane)
    dm1 = jnp.where(w.valid, d - 1, 0).astype(dtype)
    return dm1, jnp.where(rep, choose2(d, dtype), 0)


@functools.partial(
    jax.jit,
    static_argnames=("tile_cap", "vertex_caps", "mode", "direction",
                     "dtype", "narrow"),
)
def run_fused_pallas_program(
    dg: DeviceGraph,
    kernel_tiles: jax.Array,  # (n_batches, KERNEL_BATCH, 2) flat ranges
    vertex_tiles: tuple,  # per capacity class: (n_c, 2) flat ranges
    *,
    tile_cap: int,
    vertex_caps: tuple,  # lane buffer of each vertex-tile class
    mode: str,
    direction: str,
    dtype,
    narrow: bool = False,
):
    """The ``engine="fused_pallas"`` program: a fori_loop over batches
    of kernel tiles — each tile's wedges recovered in XLA
    (``wedges_at``), aggregated by the ``wedge_fused`` kernel, and its
    lanes scattered into the accumulators — then one fori_loop per
    capacity class of vertex tiles (vertices owning more wedges than
    the kernel tile holds, :func:`vertex_tile_lanes`). Live memory is
    O(KERNEL_BATCH x tile_cap + max(vertex_caps) + n_pad); nothing of
    size W is ever built. Its phases are named ``offsets``, ``recover``,
    ``match`` and ``accumulate`` (:data:`DEVICE_SCOPES`).

    ``narrow`` (where :func:`narrow_partials` allows it) scatters each
    batch or vertex tile into int32 partials and adds them densely into
    the 64-bit accumulators: a TPU has no 64-bit integer unit, so a
    64-bit scatter-add is emulated and costs several times a 32-bit one.
    A vertex tile's iterating endpoint takes one sum of its lanes."""
    with scope("offsets"):
        cnt = slot_wedge_counts(dg, direction)
        w_off = wedge_offsets(cnt)
        acc = zero_counts(dg, mode, dtype)

    @scope("recover")
    def recover(tb, cap):
        lanes = tb[:, :1] + jnp.arange(cap, dtype=jnp.int32)[None, :]
        valid = lanes < tb[:, 1:2]
        return wedges_at(
            dg, cnt, w_off, lanes.reshape(-1), valid.reshape(-1), direction
        )

    def kernel_batch(i, acc):
        w = recover(kernel_tiles[i], tile_cap)
        shape = (kernel_tiles.shape[1], tile_cap)
        with scope("match"):
            dm1, c2 = _kops.match_tiles(
                jnp.where(w.valid, w.x1, -1).reshape(shape),
                jnp.where(w.valid, w.x2, -2).reshape(shape),
                use_pallas=True,
            )
        with scope("accumulate"):
            if narrow:  # the kernel's lanes are int32 already
                return _widen(acc, lane_counts(
                    dg, w, dm1.reshape(-1), c2.reshape(-1), mode,
                    _partials(dg, mode, dtype),
                ))
            return lane_counts(
                dg, w, dm1.reshape(-1).astype(dtype),
                c2.reshape(-1).astype(dtype), mode, acc,
            )

    if kernel_tiles.shape[0]:
        acc = jax.lax.fori_loop(0, kernel_tiles.shape[0], kernel_batch, acc)
    it = "x1" if direction == "low" else "x2"  # a vertex tile's one vertex
    for tiles, cap in zip(vertex_tiles, vertex_caps):

        def vertex_tile(i, acc, tiles=tiles, cap=cap):
            w = recover(tiles[i][None, :], cap)
            with scope("match"):
                dm1, c2 = vertex_tile_lanes(
                    w, dg.n_pad, direction, jnp.int32 if narrow else dtype
                )
            with scope("accumulate"):
                if not narrow:
                    return lane_counts(dg, w, dm1, c2, mode, acc)
                acc = _widen(acc, lane_counts(
                    dg, w._replace(**{it: None}), dm1, c2, mode,
                    _partials(dg, mode, dtype),
                ))
                if mode == "edge":
                    return acc
                # lane 0 holds the vertex unless the tile is empty (its
                # sentinel then drops a sum of 0)
                own = jnp.sum(c2, dtype=dtype)

                def add_own(bv):
                    return bv.at[getattr(w, it)[0]].add(own)

                if mode == "vertex":
                    return add_own(acc)
                return acc[0], add_own(acc[1]), acc[2]

        acc = jax.lax.fori_loop(0, tiles.shape[0], vertex_tile, acc)
    return acc


def run_fused_pallas_tiles(dg: DeviceGraph, plan: WedgePlan):
    """Execute a ``engine="fused_pallas"`` plan. A kernel tile wider
    than the kernel's exactness bound raises
    :class:`~repro.core.resilience.CapacityOverflow` (the resilience
    ladder then descends to ``engine="fused"``)."""
    tile_cap = dict(plan.capacity).get("kernel_tile", 0)
    max_tile = _faults.capacity_override(
        "count.fused_pallas", _kops.MAX_TILE_CAP
    )
    if tile_cap > max_tile:
        raise _res.CapacityOverflow(
            f"engine='fused_pallas' kernel tile {tile_cap} exceeds the "
            f"kernel's exactness bound {max_tile}; use engine='fused'"
        )
    kinds = np.asarray(plan.tile_aggregation)
    other = set(kinds.tolist()) - {"kernel", "vertex"}
    if other:
        raise ValueError(
            f"engine='fused_pallas' runs kernel and vertex tiles only, "
            f"got {sorted(other)}"
        )
    with span("plan"):
        flat = plan.tile_flat_bounds()
        ktiles = flat[kinds == "kernel"]
        pad = -len(ktiles) % KERNEL_BATCH
        ktiles = np.concatenate([ktiles, np.zeros((pad, 2), np.int64)])
        vtiles = flat[kinds == "vertex"]
        classes = np.asarray(
            [_pow2_at_least(int(s)) for s in vtiles[:, 1] - vtiles[:, 0]],
            np.int64,
        )
        caps = tuple(int(c) for c in np.unique(classes))
        tile_cap = tile_cap or _kops.TC
        acc = plan.accumulator
        narrow = narrow_partials(acc.dtype, acc.mode, tile_cap,
                                 dict(plan.capacity).get("max_degree"))
    out = launch(
        run_fused_pallas_program,
        dg,
        jnp.asarray(ktiles.reshape(-1, KERNEL_BATCH, 2), jnp.int32),
        tuple(jnp.asarray(vtiles[classes == c], jnp.int32) for c in caps),
        tile_cap=tile_cap,
        vertex_caps=caps,
        mode=acc.mode,
        direction=plan.direction,
        dtype=acc.jnp_dtype(),
        narrow=narrow,
    )
    # value-level poison hook: the program's concrete output, outside
    # any jit, so the sentinel can never leak into a compilation cache
    return _faults.maybe_poison("count.fused_pallas", out)


def plan_strategies(plan: WedgePlan) -> Optional[jax.Array]:
    """Resolve a plan's per-tile strategy list for the executor:
    ``None`` for uniform plans (the executor compiles the exact
    single-strategy program) or an int8 device array (0 = sort,
    1 = hash) for mixed plans."""
    kinds = set(plan.tile_aggregation)
    if len(kinds) <= 1:
        return None
    if not kinds <= {"sort", "hash"}:
        raise ValueError(
            f"mixed tile strategies must be sort/hash, got {sorted(kinds)}"
        )
    return jnp.asarray(
        [1 if s == "hash" else 0 for s in plan.tile_aggregation],
        jnp.int8,
    )


def execute_count_plan(dg: DeviceGraph, plan: WedgePlan):
    """Execute a counting plan on its device graph and return the
    rank-space counts (a scalar / array / triple per the accumulator
    mode). ``engine="fused_pallas"`` dispatches the Pallas tile program
    (:func:`run_fused_pallas_tiles`); everything else streams through
    :func:`run_count_tiles`."""
    if plan.kind != "count":
        raise ValueError(f"not a counting plan: kind={plan.kind!r}")
    if plan.engine == "fused_pallas":
        return run_fused_pallas_tiles(dg, plan)
    strategies = plan_strategies(plan)
    uniform = (
        plan.tile_aggregation[0] if plan.tile_aggregation else "sort"
    )
    out, _ok = launch(
        run_count_tiles,
        dg,
        jnp.asarray(plan.bounds, jnp.int32),
        strategies,
        chunk_cap=plan.chunk_cap,
        aggregation=uniform if strategies is None else "sort",
        mode=plan.accumulator.mode,
        direction=plan.direction,
        dtype=plan.accumulator.jnp_dtype(),
        engine="xla" if plan.engine in ("fused", "xla") else plan.engine,
        hash_bits=plan.hash_bits,
    )
    return out


# ---------------------------------------------------------------------------
# Execute layer: the peeling round-loop substrate
# ---------------------------------------------------------------------------


class LoopState(NamedTuple):
    """Carry of the jitted device round loops (both decompositions)."""

    b: jax.Array  # counts (peeled side / per edge)
    alive: jax.Array  # bool mask
    out: jax.Array  # tip / wing numbers
    kappa: jax.Array  # () int32 peel threshold
    rounds: jax.Array  # () int32 — bucket rounds under range mode
    subr: jax.Array  # () int32 re-settle iterations (== rounds, exact)
    sizes: jax.Array  # (n_out,) int32 peeled per round
    overflow: jax.Array  # () bool capacity latch
    mn: jax.Array  # () int32 carried masked min
    hist: jax.Array  # (NUM_BUCKETS,) carried occupancy, or (0,) unused
    hi: jax.Array  # () int32 active bucket's exclusive upper bound


def prefix_offsets(lens: jax.Array) -> jax.Array:
    """Exclusive-prefix flat id space over per-segment lengths."""
    return jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(lens.astype(jnp.int32)),
    ])


def empty_hist(want_hist: bool) -> jax.Array:
    """Carried-occupancy placeholder: a real (NUM_BUCKETS,) histogram
    slot when range mode consumes it, a zero-length array otherwise —
    keeping the unused histogram OUT of the while_loop carry is what
    lets XLA dead-code-eliminate the reference path's bit-length
    scatter under ``peel_mode="exact"`` (loop state is always live)."""
    return jnp.zeros((_kops.NUM_BUCKETS if want_hist else 0,), jnp.int32)


def masked_state(b: jax.Array, alive: jax.Array, want_hist: bool):
    """Masked extract-min (+ occupancy when consumed) in the
    ``bucket_min``/``bucket_update`` contracts — seeds the carried
    state before round 0 and re-derives it on zero-frontier rounds."""
    if want_hist:
        return _kops.bucket_state(b, alive)
    return _kops.bucket_min(b, alive, use_pallas=False), empty_hist(False)


def apply_decrements(b, alive, tgt, dec, use_kernel, want_hist=False,
                     last=True):
    """Apply one aggregated update batch to the count array: the
    Julienne-style batched decrease-key of the device round loops.

    Returns the decremented counts, the next round's masked min, and
    (when ``want_hist``, i.e. range mode) the geometric-bucket
    occupancy. Only the pass the round loop consumes runs
    ``kernels.ops.bucket_update``: ``last`` (a traced bool, or True for
    a sub-round's only batch) marks the sub-round's final batch, whose
    last ``MAX_UPDATE_CAP``-lane chunk goes through the kernel, so its
    min and occupancy describe the sub-round's final counts. Every
    other batch, and every earlier chunk of the final one, is an exact
    int32 scatter-add (the counts stay below INT32_MAX: the device
    loops guard it). Returns ``(new_counts, min, hist)``; min and hist
    are placeholders unless ``last`` (hist zero-length unless
    ``want_hist`` — see :func:`empty_hist`).
    """
    with scope("bucket_update"):
        tgt = tgt.astype(jnp.int32)
        dec = dec.astype(jnp.int32)
        cap = _kops.MAX_UPDATE_CAP
        head = (tgt.shape[0] - 1) // cap * cap  # lanes before the last chunk

        def passed(c):
            if head:
                c = c.at[tgt[:head]].add(-dec[:head])
            nc, mn, hist = _kops.bucket_update(
                c, alive, tgt[head:], dec[head:], use_pallas=use_kernel
            )
            if not want_hist:
                # dropped before it reaches the loop carry -> XLA DCEs
                # the reference path's histogram under exact mode; the
                # kernel computes it in-register either way
                hist = empty_hist(False)
            return nc, mn, hist

        def scattered(c):
            return (c.at[tgt].add(-dec), jnp.int32(I32_MAX),
                    empty_hist(want_hist))

        c32 = b.astype(jnp.int32)
        if last is True:
            nb, mn, hist = passed(c32)
        else:
            nb, mn, hist = jax.lax.cond(last, passed, scattered, c32)
        return nb.astype(b.dtype), mn, hist


def init_loop_state(b0: jax.Array, n_out: int, *,
                    peel_mode: str) -> LoopState:
    """Round-0 carry for :func:`device_round_loop` (shared by the run
    wrappers, the benchmarks' memory-analysis probes, and tests)."""
    alive0 = jnp.ones((n_out,), jnp.bool_)
    mn0, hist0 = masked_state(b0, alive0, peel_mode == "range")
    return LoopState(
        b=b0,
        alive=alive0,
        out=jnp.zeros((n_out,), b0.dtype),
        kappa=jnp.int32(0),
        rounds=jnp.int32(0),
        subr=jnp.int32(0),
        sizes=jnp.zeros((n_out,), jnp.int32),
        overflow=jnp.array(False),
        mn=mn0,
        hist=hist0,
        hi=jnp.int32(0),
    )


def stream_tiles(b, alive, roff, tile_fn, *, tile_cap: int, aligned: bool,
                 want_hist: bool):
    """Stream the flat per-round id space ``[0, roff[-1])`` through
    fixed-shape tiles — the fused-subtract while_loop shared by every
    decomposition. ``tile_fn(b, wid, tvalid, last) -> (b, mn, hist)``
    recovers and subtracts one tile; ``last`` is the traced flag of
    the sub-round's final tile, the one whose min and occupancy the
    stream returns (:func:`apply_decrements`). ``aligned`` cuts tile
    boundaries at segment boundaries (``aligned_tile_end`` — required
    when the consumer's per-group C(d, 2) must not split); unaligned
    tiles advance by the full ``tile_cap`` (linear subtracts split
    exactly). The stream carries the counts as int32 and casts them
    back once at its end. Returns ``(b, mn, hist)`` with the
    zero-frontier carried state re-derived via :func:`masked_state`.
    """
    total = roff[-1]
    dtype = b.dtype
    with scope("bucket_update"):
        b = b.astype(jnp.int32)

    @scope("recover")
    def tcond(c):
        return c[1] < total

    def tbody(c):
        bt, ts, _mn, _h = c
        with scope("recover"):
            if aligned:
                te = aligned_tile_end(roff, ts, tile_cap)
            else:
                te = jnp.minimum(ts + jnp.int32(tile_cap), total)
            wid = ts + jnp.arange(tile_cap, dtype=jnp.int32)
        out_b, mn, h = tile_fn(bt, wid, wid < te, te >= total)
        return out_b, te, mn, h

    b, _, mn, hist = jax.lax.while_loop(
        tcond, tbody,
        (b, jnp.int32(0), jnp.int32(I32_MAX), empty_hist(want_hist)),
    )
    with scope("bucket_update"):
        b = b.astype(dtype)
    # zero-tile rounds still need the post-peel carried state
    with scope("select"):
        mn, hist = jax.lax.cond(
            total > 0,
            lambda _: (mn, hist),
            lambda _: masked_state(b, alive, want_hist),
            None,
        )
    return b, mn, hist


def device_round_loop(state: LoopState, expand, *, peel_mode: str):
    """The jitted round-loop skeleton shared by the tips and wings
    device engines: extract-min (the min carried out of the previous
    sub-round's ``bucket_update`` pass), κ update, exact-vs-range round
    accounting, peel-set selection/assignment, and the overflow latch.

    ``expand((b, alive, alive_prev, peel)) -> (b, ovf, mn, hist)``
    turns one round's peel set into count decrements (the only part
    the decompositions differ on).

    Range mode (``peel_mode="range"``): a new bucket round starts
    whenever the masked min has left the active range ``[.., hi)``;
    the next range is the lowest non-empty geometric bucket, read from
    the carried ``bucket_update`` occupancy histogram. Iterations
    *within* a bucket round are the in-graph re-settle: they replay
    the exact κ trajectory, so the assigned numbers are
    bitwise-identical to exact mode — only the round accounting
    (``rounds``, ``sizes``) is per bucket.
    """
    dtype = state.b.dtype
    want_hist = peel_mode == "range"

    @scope("select")
    def cond(st):
        return jnp.any(st.alive) & ~st.overflow

    def body(st):
        with scope("select"):  # extract-min, κ, accounting, peel set
            mn = st.mn
            kappa = jnp.maximum(st.kappa, mn)
            rounds, hi = st.rounds, st.hi
            if peel_mode == "range":
                new_bucket = mn >= hi
                k_sel = _kops.lowest_nonempty_bucket(st.hist)
                hi = jnp.where(new_bucket, _kops.bucket_upper_bound(k_sel),
                               hi)
                rounds = rounds + new_bucket.astype(jnp.int32)
            else:
                rounds = rounds + 1
            subr = st.subr + 1
            peel = st.alive & (st.b <= kappa.astype(dtype))
            out = jnp.where(peel, kappa.astype(dtype), st.out)
            alive_prev = st.alive
            alive = st.alive & ~peel
            # explicit dtype: under x64 jnp.sum promotes to int64 and the
            # scatter into the int32 sizes buffer would downcast-warn
            sizes = st.sizes.at[rounds - 1].add(
                jnp.sum(peel, dtype=jnp.int32))
            any_alive = jnp.any(alive)

        def _last_round(args):
            # nothing left alive: the subtract would be a masked no-op
            # (the host loops' `if not alive.any(): break`)
            return (args[0], jnp.array(False), jnp.int32(I32_MAX),
                    empty_hist(want_hist))

        b, ovf_i, mn_next, hist_next = jax.lax.cond(
            any_alive, expand, _last_round, (st.b, alive, alive_prev, peel),
        )
        with scope("select"):
            overflow = st.overflow | ovf_i
        return LoopState(
            b, alive, out, kappa, rounds, subr, sizes,
            overflow, mn_next, hist_next, hi,
        )

    return jax.lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# Report layer
# ---------------------------------------------------------------------------


def execute_ladder(
    workload: str,
    policy: "_res.ResiliencePolicy",
    rungs,
    validate=None,
    plan: Optional[WedgePlan] = None,
    estimator: Optional[str] = None,
):
    """The single resilience wrapper of the pipeline: run a degradation
    ladder under ``policy`` and stamp the plan summary onto the
    resulting :class:`~repro.core.resilience.ExecutionReport`
    (``report.plan``) — engines call this once instead of wiring
    ``policy.execute`` per call site. ``estimator`` records the
    approximate tier's parameters (``report.estimator``) when the
    ladder computes an estimate rather than an exact result. Returns
    ``(result, report)``."""
    out, report = policy.execute(workload, rungs, validate)
    if plan is not None:
        report.plan = (
            plan.summary() if isinstance(plan, WedgePlan) else str(plan)
        )
    if estimator is not None:
        report.estimator = estimator
    return out, report

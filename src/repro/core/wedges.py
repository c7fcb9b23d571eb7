"""Vectorized rank-filtered wedge retrieval (paper Alg. 2 GET-WEDGES).

The paper's nested parallel-for over (vertex, neighbor, 2nd-neighbor) is
re-thought for SPMD hardware as a *flat wedge index space*:

  - every directed edge slot ``e = (x1 -> y)`` contributes
    ``cnt[e] = |{x2 in N(y) : rank(x2) > rank(x1)}|`` wedges when
    ``rank(y) > rank(x1)`` (and 0 otherwise),
  - a global prefix sum over ``cnt`` assigns each wedge a dense id
    ``w in [0, W)``,
  - wedge ``w`` is materialized with two gathers and one binary search:
    ``e = upper_bound(w_off, w) - 1``, ``j = w - w_off[e]``.

This gives O(1) span per wedge and O(αm) work with degree-style
orderings — the same bounds as the paper — while being fully
vectorizable on VPU/MXU hardware. The exponential search of the paper
(adjacency suffix length) becomes a batched binary search.

``direction="low"`` iterates from the lowest-ranked endpoint (paper
default); ``direction="high"`` iterates from the highest-ranked endpoint
(the Wang et al. cache optimization, paper §3.1.4) — the wedge *set* is
identical, the access pattern differs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .graph import RankedGraph

__all__ = [
    "DeviceGraph",
    "Wedges",
    "DEFAULT_CHUNK_BUDGET",
    "auto_chunk_budget",
    "shrink_budget",
    "device_graph",
    "slot_wedge_counts",
    "host_wedge_counts",
    "wedge_capacity",
    "wedge_offsets",
    "wedges_at",
    "gather_wedges",
    "expand_ragged",
    "ragged_slots_at",
    "aligned_tile_end",
    "degree_sorted_csr",
    "greedy_vertex_blocks",
    "plan_wedge_chunks",
]

# Streaming/tile wedge budget used when the device exposes no memory
# stats (the CPU host platform returns None): 2^18 wedges ~ 16 MiB of
# per-tile working set at _BYTES_PER_WEDGE — small enough to stay
# cache-friendly (measured fastest-region on the CPU bench graphs; see
# BENCH_fused.json), large enough to amortize per-tile overhead.
DEFAULT_CHUNK_BUDGET = 1 << 18

# Per-wedge working-set estimate for one live tile: six int32 wedge
# vectors (x1, x2, y, center_slot, second_slot, valid) plus roughly one
# same-sized copy for the aggregation temporaries (sorted wedges or the
# ~2x hash table + probe state) -> 6 * 4 B * ~2.7 rounded to 64.
_BYTES_PER_WEDGE = 64


@functools.lru_cache(maxsize=None)
def auto_chunk_budget(
    fraction: float = 0.125,
    default: int = DEFAULT_CHUNK_BUDGET,
    lo: int = 1 << 14,
    hi: int = 1 << 24,
) -> int:
    """Derive the streaming/tile wedge budget from the device's memory
    stats (``max_chunk="auto"``): a ``fraction`` of the free bytes on
    device 0, divided by the per-wedge working-set estimate, clamped to
    [lo, hi]. Platforms without memory stats (CPU host platform returns
    None) get the documented ``DEFAULT_CHUNK_BUDGET``.

    The result feeds jit-static tile shapes (``chunk_cap``, bounds
    length), so it must not wobble with live allocator state: the
    free-byte reading is snapshotted once per process (lru_cache) and
    quantized down to a power of two."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend-specific, optional API
        stats = None
    if not stats:
        return default
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        return default
    free = max(int(limit) - int(stats.get("bytes_in_use", 0)), 0)
    raw = int(min(hi, max(lo, (free * fraction) // _BYTES_PER_WEDGE)))
    return 1 << (raw.bit_length() - 1)  # quantize: stable jit shapes


def shrink_budget(budget: int, shrinks: int, floor: int = 128) -> int:
    """Halve ``budget`` ``shrinks`` times, floored — the resilience
    ladder's RESOURCE_EXHAUSTED re-entry schedule (each retry re-plans
    tiles/chunks with this tightened budget; the pow2 floor matches
    the planners' alignment floors, so a fully-shrunk budget is still
    a valid plan input)."""
    return max(int(floor), int(budget) >> max(0, int(shrinks)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceGraph:
    """RankedGraph arrays on device. All int32, statically shaped.

    ``n`` and ``m`` (real vertex / undirected edge counts) are static
    pytree aux data so jitted engine code can use them as shapes.
    """

    offsets: jax.Array  # (n_pad + 1,)
    neighbors: jax.Array  # (e_pad,)
    edge_src: jax.Array  # (e_pad,)
    undirected_id: jax.Array  # (e_pad,)
    side_of: jax.Array  # (n_pad,) int8
    n: int  # static: real vertex count
    m: int  # static: real undirected edge count

    @property
    def n_pad(self) -> int:
        return self.side_of.shape[0]

    @property
    def e_pad(self) -> int:
        return self.neighbors.shape[0]

    def tree_flatten(self):
        children = (
            self.offsets,
            self.neighbors,
            self.edge_src,
            self.undirected_id,
            self.side_of,
        )
        return children, (self.n, self.m)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n=aux[0], m=aux[1])


def device_graph(rg: RankedGraph) -> DeviceGraph:
    return DeviceGraph(
        offsets=jnp.asarray(rg.offsets, jnp.int32),
        neighbors=jnp.asarray(rg.neighbors, jnp.int32),
        edge_src=jnp.asarray(rg.edge_src, jnp.int32),
        undirected_id=jnp.asarray(rg.undirected_id, jnp.int32),
        side_of=jnp.asarray(rg.side_of, jnp.int8),
        n=rg.n,
        m=rg.m,
    )


class Wedges(NamedTuple):
    """A padded batch of wedges (x1, x2, y): endpoints x1 < x2, center y.

    ``center_slot`` is the directed-edge slot of (x1 -> y) under
    ``direction="low"`` (resp. (x2 -> y) under "high");
    ``second_slot`` is the neighbor-array position of x2 within N(y)
    (resp. x1), i.e. the directed edge (y -> x2). Both index
    ``undirected_id`` for per-edge butterfly scatter.
    ``valid`` masks padding.
    """

    x1: jax.Array
    x2: jax.Array
    y: jax.Array
    center_slot: jax.Array
    second_slot: jax.Array
    valid: jax.Array


def _upper_bound_ragged(values: jax.Array, lo: jax.Array, hi: jax.Array, x: jax.Array) -> jax.Array:
    """Batched upper_bound: for each i, first index in sorted
    ``values[lo[i]:hi[i]]`` strictly greater than ``x[i]`` (absolute idx).

    O(log e_pad) span; fully vectorized (replaces the paper's per-edge
    exponential search).
    """
    steps = max(1, int(np.ceil(np.log2(max(int(values.shape[0]), 2)))) + 1)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) >> 1
        take = (lo_ < hi_) & (values[mid] <= x)
        new_lo = jnp.where(take, mid + 1, lo_)
        new_hi = jnp.where((lo_ < hi_) & ~take, mid, hi_)
        return new_lo, new_hi

    lo_f, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo_f


def _lower_bound_ragged(values: jax.Array, lo: jax.Array, hi: jax.Array, x: jax.Array) -> jax.Array:
    """First index with values[idx] >= x (absolute)."""
    steps = max(1, int(np.ceil(np.log2(max(int(values.shape[0]), 2)))) + 1)

    def body(_, carry):
        lo_, hi_ = carry
        mid = (lo_ + hi_) >> 1
        take = (lo_ < hi_) & (values[mid] < x)
        new_lo = jnp.where(take, mid + 1, lo_)
        new_hi = jnp.where((lo_ < hi_) & ~take, mid, hi_)
        return new_lo, new_hi

    lo_f, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo_f


def slot_wedge_counts(dg: DeviceGraph, direction: str = "low") -> jax.Array:
    """Per directed-edge-slot wedge counts (device). int32 (e_pad,)."""
    src = dg.edge_src
    dst = dg.neighbors
    lo = dg.offsets[jnp.minimum(dst, dg.n_pad - 1)]
    hi = dg.offsets[jnp.minimum(dst, dg.n_pad - 1) + 1]
    real = (src < dg.n) & (dst < dg.n)
    if direction == "low":
        # e = (x1 -> y), need rank(y) > rank(x1); eligible x2 in N(y)
        # with x2 > x1: suffix of the ascending adjacency list.
        eligible = real & (dst > src)
        ub = _upper_bound_ragged(dg.neighbors, lo, hi, src)
        cnt = hi - ub
    elif direction == "high":
        # e = (x2 -> y) from the *highest* endpoint: wedge (x1, x2, y)
        # with x1 < min(x2, y). Eligible x1 in N(y) with x1 < min(src,dst):
        # prefix of the adjacency list. Every wedge is produced exactly
        # once: x2 and y are determined by the directed edge.
        eligible = real
        lb = _lower_bound_ragged(dg.neighbors, lo, hi, jnp.minimum(src, dst))
        cnt = lb - lo
    else:
        raise ValueError(f"direction must be low|high, got {direction}")
    return jnp.where(eligible, cnt, 0).astype(jnp.int32)


def host_wedge_counts(rg: RankedGraph, direction: str = "low") -> np.ndarray:
    """Numpy mirror of slot_wedge_counts, for capacity planning. O(m).

    Adjacency lists are ascending, so for a slot ``(s -> d)`` its twin
    slot ``(d -> s)`` splits ``N(d)``: the neighbours above ``s`` follow
    the twin, those below ``s`` precede it. Each binary search of the
    device version is a lookup here.
    """
    n_real = 2 * rg.m
    s = rg.edge_src[:n_real].astype(np.int64)
    d = rg.neighbors[:n_real].astype(np.int64)
    twin = rg.twin[:n_real].astype(np.int64)
    off = rg.offsets.astype(np.int64)
    cnt = np.zeros(rg.e_pad, dtype=np.int64)
    if direction == "low":
        # |{x2 in N(y) : x2 > x1}| for slots with y > x1
        cnt[:n_real] = np.where(d > s, off[d + 1] - twin - 1, 0)
    else:
        # |{x1 in N(y) : x1 < min(x2, y)}|: the neighbours of y below x2
        # when x2 < y, else all of y's neighbours ranked below y
        below = np.bincount(s[d < s], minlength=rg.n_pad)
        cnt[:n_real] = np.where(s < d, twin - off[d], below[d])
    return cnt


def wedge_capacity(rg: RankedGraph, direction: str = "low", pad: int = 128) -> int:
    """Exact wedge total, padded. Host-side, O(m)."""
    w = int(host_wedge_counts(rg, direction).sum())
    return max(pad, ((w + pad - 1) // pad) * pad)


def wedge_offsets(cnt: jax.Array) -> jax.Array:
    """Exclusive prefix sum over per-slot wedge counts: (e_pad + 1,)."""
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt.astype(jnp.int32))]
    )


def wedges_at(
    dg: DeviceGraph,
    cnt: Optional[jax.Array],
    w_off: jax.Array,
    wid: jax.Array,
    valid: jax.Array,
    direction: str = "low",
) -> Wedges:
    """Materialize wedges for an arbitrary array of flat wedge ids.

    Used by the single-device path (contiguous ids), the batch
    aggregation (per-block chunks), and the shard_map distributed engine
    (per-device slices of the global wedge space). ``cnt`` may be None:
    per-slot wedge counts are then recovered as w_off[e+1] - w_off[e]
    (the distributed engine passes only the precomputed prefix array —
    EXPERIMENTS.md §Perf-3).
    """
    idx_t = jnp.int32
    total = w_off[-1]
    wc = jnp.clip(wid.astype(idx_t), 0, jnp.maximum(total - 1, 0))
    e = jnp.searchsorted(w_off, wc, side="right").astype(idx_t) - 1
    e = jnp.clip(e, 0, dg.e_pad - 1)
    j = wc - w_off[e]
    cnt_e = (w_off[e + 1] - w_off[e]) if cnt is None else cnt[e]
    y = dg.neighbors[e]
    y_safe = jnp.minimum(y, dg.n_pad - 1)
    if direction == "low":
        x1 = dg.edge_src[e]
        # eligible x2 = suffix of N(y) of length cnt[e]
        pos = dg.offsets[y_safe + 1] - cnt_e + j
        x2 = dg.neighbors[jnp.clip(pos, 0, dg.e_pad - 1)]
    elif direction == "high":
        x2 = dg.edge_src[e]
        # eligible x1 = prefix of N(y) of length cnt[e]
        pos = dg.offsets[y_safe] + j
        x1 = dg.neighbors[jnp.clip(pos, 0, dg.e_pad - 1)]
    else:
        raise ValueError(f"direction must be low|high, got {direction}")
    pos = jnp.clip(pos, 0, dg.e_pad - 1)
    sent = jnp.int32(dg.n_pad)
    return Wedges(
        x1=jnp.where(valid, x1, sent),
        x2=jnp.where(valid, x2, sent),
        y=jnp.where(valid, y, sent),
        center_slot=jnp.where(valid, e, dg.e_pad - 1),
        second_slot=jnp.where(valid, pos, dg.e_pad - 1),
        valid=valid,
    )


def gather_wedges(
    dg: DeviceGraph,
    cnt: jax.Array,
    w_cap: int,
    direction: str = "low",
) -> Wedges:
    """Materialize the flat wedge space (device, static shape (w_cap,))."""
    w_off = wedge_offsets(cnt)
    wid = jnp.arange(w_cap, dtype=jnp.int32)
    valid = wid < w_off[-1]
    return wedges_at(dg, cnt, w_off, wid, valid, direction)


def ragged_slots_at(
    roff: jax.Array, starts: jax.Array, wid: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Recover (segment, absolute position) for arbitrary flat ragged ids.

    ``roff`` is the exclusive prefix sum of the segment lengths (the flat
    ragged id space), ``starts[i]`` the absolute start of segment ``i``'s
    range. Flat id ``w`` belongs to segment ``seg`` with
    ``roff[seg] <= w < roff[seg + 1]`` at absolute position
    ``starts[seg] + w - roff[seg]``. Ids are clamped into
    ``[0, roff[-1])`` — callers mask invalid lanes themselves.

    This is the tile-sliced core of :func:`expand_ragged`: the fused
    peeling subtract calls it once per frontier tile (``wid`` =
    ``ts + arange(tile_cap)``) so no round ever materializes the full
    frontier expansion.
    """
    total = roff[-1]
    kc = jnp.minimum(wid.astype(jnp.int32), jnp.maximum(total - 1, 0))
    seg = jnp.searchsorted(roff, kc, side="right").astype(jnp.int32) - 1
    seg = jnp.clip(seg, 0, starts.shape[0] - 1)
    pos = starts[seg] + kc - roff[seg]
    return seg, pos


def aligned_tile_end(
    roff: jax.Array, ts: jax.Array, tile_cap: int
) -> jax.Array:
    """Largest segment boundary in ``roff`` at most ``ts + tile_cap``.

    In-graph greedy tile planning for the fused peeling subtract: tiles
    of the per-round frontier wedge space must cut only at iterating-
    endpoint boundaries (the ``plan_wedge_chunks`` invariant — no
    endpoint-pair group may span a tile, or its C(d, 2) contribution
    would split inexactly). Callers guarantee ``tile_cap`` is at least
    the largest single segment (host-planned from exact per-vertex
    totals), which makes every returned boundary strictly advance past
    ``ts`` whenever ``ts`` is itself a boundary below ``roff[-1]``.
    """
    i32_max = np.int32(np.iinfo(np.int32).max)
    tgt = ts.astype(jnp.int32) + jnp.int32(min(int(tile_cap), int(i32_max)))
    # saturate on int32 wrap: the saturated target still exceeds every
    # boundary (totals are < 2^31 by the planners' guards), and the
    # resulting tile is then strictly shorter than tile_cap
    tgt = jnp.where(tgt < ts, i32_max, tgt)
    ub = jnp.searchsorted(roff, tgt, side="right").astype(jnp.int32) - 1
    return roff[jnp.clip(ub, 0, roff.shape[0] - 1)]


def expand_ragged(
    starts: jax.Array, lens: jax.Array, cap: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Flatten ragged ranges ``[starts[i], starts[i] + lens[i])`` into a
    fixed ``(cap,)`` buffer — the device analogue of the host prefix-sum
    expansion used by the peeling round loop (``peel._ranges``).

    Flat slot ``k`` belongs to segment ``seg[k]`` (via searchsorted on
    the exclusive prefix sum of ``lens``) at absolute position ``pos[k]``
    inside that segment's range. ``valid`` masks slots beyond the true
    total; ``total`` is returned so callers can detect capacity overflow
    (``total > cap``) in-graph instead of silently truncating.

    Returns ``(seg, pos, valid, total)`` — all int32 except bool valid.
    """
    lens = lens.astype(jnp.int32)
    roff = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)]
    )
    total = roff[-1]
    k = jnp.arange(cap, dtype=jnp.int32)
    valid = k < total
    seg, pos = ragged_slots_at(roff, starts, k)
    return seg, pos, valid, total


def degree_sorted_csr(
    off: np.ndarray, nbr: np.ndarray, uid: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Re-sort every CSR row by neighbor degree and attach the in-row
    neighbor-degree prefix — the O(m)-space index that lets the fused
    wing subtract recover its per-butterfly triple space from flat ids
    in O(log) per lane (no materialized level-1/level-2 buffers).

    For a peeled edge ``a = (u1, v1)`` the paper's PEEL-E scans, per
    candidate ``u2 in N(v1)``, the smaller of ``N(u1)``/``N(u2)`` —
    so edge ``a``'s triple space has ragged inner sizes
    ``min(deg(u1), deg(u2))``. With ``N(v1)`` sorted by ``deg(u2)``,
    those sizes become a monotone head (``deg(u2) < deg(u1)``, prefix
    readable from ``cumdeg``) followed by a constant tail
    (``deg(u1)`` each, pure arithmetic): a flat offset inverts with one
    binary search over ``degs``, one over ``cumdeg``, and a division.
    Row order is irrelevant to correctness — every subtraction is a
    linear scatter over the same multiset of candidates.

    Returns ``(nbr_ds, uid_ds, degs_ds, cumdeg)``: the permuted
    neighbor/edge-id arrays, ``degs_ds[p] = deg(nbr_ds[p])``, and the
    *in-row exclusive* prefix sum of ``degs_ds`` (int64 — callers
    guard the int32 range before shipping to device).
    """
    deg = np.diff(off)
    src = np.repeat(np.arange(deg.shape[0]), deg)
    order = np.lexsort((nbr, deg[nbr], src))
    nbr_ds, uid_ds = nbr[order], uid[order]
    degs_ds = deg[nbr_ds].astype(np.int64)
    excl = np.concatenate([[0], np.cumsum(degs_ds)])  # global, (2m + 1,)
    cumdeg = excl[:-1] - np.repeat(excl[off[:-1]], deg)
    return nbr_ds, uid_ds, degs_ds, cumdeg


def greedy_vertex_blocks(
    wv: np.ndarray,
    n: int,
    rows: Optional[int] = None,
    target: Optional[int] = None,
) -> tuple[np.ndarray, int]:
    """Greedy vertex-aligned block boundaries over per-vertex wedge counts.

    Each block spans at most ``rows`` vertices (when given) and at most
    ``target`` wedges (when given; a single vertex whose wedge count
    already exceeds the target gets a solo block — the block size is
    then that vertex's wedge count). Host-side, O(n_blocks log n) via
    cumsum + searchsorted — this replaces the O(n) interpreted-Python
    per-vertex sweep the batch aggregation used to run per count call.

    Returns (boundaries (n_blocks + 1,) int64, max wedges per block).
    """
    wv = np.asarray(wv[:n], dtype=np.int64)
    woff = np.concatenate([[0], np.cumsum(wv)])
    bounds = [0]
    b = 0
    while b < n:
        nxt = n
        if target is not None:
            # largest v with sum(wv[b:v]) <= target
            nxt = int(np.searchsorted(woff, woff[b] + target, side="right")) - 1
        if rows is not None:
            nxt = min(nxt, b + rows)
        nxt = min(max(nxt, b + 1), n)
        bounds.append(nxt)
        b = nxt
    bounds = np.asarray(bounds, dtype=np.int64)
    per_block = woff[bounds[1:]] - woff[bounds[:-1]]
    return bounds, int(per_block.max(initial=1))


def plan_wedge_chunks(
    rg: RankedGraph,
    direction: str = "low",
    max_chunk: int = 1 << 18,
    pad: int = 128,
    wv_slots: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, int]:
    """Vertex-aligned streaming chunks of the flat wedge space.

    Flat wedge ids follow CSR slot order, so all wedges produced by one
    iterating endpoint (``edge_src``: x1 under "low", x2 under "high")
    are contiguous — and every group (x1, x2) lives entirely inside its
    iterating endpoint's range. Cutting the stream only at vertex
    boundaries therefore keeps aggregation exact per chunk: no group
    ever spans two chunks, so per-chunk butterfly contributions add.

    Returns (vertex boundaries (n_blocks + 1,), chunk_cap). ``chunk_cap``
    is the fixed per-chunk wedge-buffer size (rounded up to ``pad``); it
    equals ~``max_chunk`` unless a single vertex owns more wedges than
    the budget, in which case that vertex's count is the floor.
    """
    if wv_slots is None:
        wv_slots = host_wedge_counts(rg, direction)
    n_real = 2 * rg.m
    wv = np.zeros(rg.n_pad, dtype=np.int64)
    np.add.at(wv, rg.edge_src[:n_real].astype(np.int64), wv_slots[:n_real])
    bounds, chunk = greedy_vertex_blocks(wv, rg.n_pad, target=int(max_chunk))
    chunk_cap = max(pad, ((chunk + pad - 1) // pad) * pad)
    return bounds, chunk_cap

"""Distributed butterfly counting with shard_map (DESIGN.md §2, §4).

Mapping of the paper onto an SPMD mesh:

  - The flat wedge index space is partitioned into per-device slices
    whose boundaries are *vertex-aligned* and *wedge-balanced* — the
    paper's wedge-aware batching promoted to the cross-chip partition
    strategy. Vertex alignment guarantees every endpoint-pair group is
    device-local (all wedges anchored at x1 live on x1's device), so
    local aggregation is exact and the only communication is the final
    count combine.
  - Each device consumes its wedge slice through the SAME fused tile
    loop as the single-device ``engine="fused"`` path
    (``pipeline.count_tile_step``): vertex-aligned sub-tiles of the
    device slice are generated (binary search over the replicated
    prefix array), aggregated locally (sort strategy), accumulated, and
    discarded — per-device peak wedge memory is O(tile), never
    O(W / n_dev). ``engine="slice"`` keeps the old behavior of
    materializing + aggregating the full local slice at once.
  - Contributions are combined with one ``psum`` (global counts) or a
    ``psum`` over the dense count vector (per-vertex / per-edge). On a
    multi-pod mesh the psum spans all axes, lowering to hierarchical
    all-reduce: in-pod ICI reduction then cross-pod combine.

The graph CSR is replicated (real deployments of this engine would
additionally shard the adjacency of very large graphs; the wedge space —
the O(αm) object that dominates — is what we partition).

Tile-alignment invariant: both the cross-device partition AND the
in-device tiles are cut only at iterating-vertex boundaries (shared
with ``wedges.plan_wedge_chunks``), so no endpoint-pair group ever
spans a tile or a device — per-tile and per-device contributions add
exactly and the engines agree bitwise.
"""
from __future__ import annotations

import concurrent.futures as _cf
import dataclasses
import os
import subprocess
import sys
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..testing import faults as _faults
from . import checkpoint as _ckpt
from . import pipeline as _pipeline  # shared hot path + partition seam
from .aggregate import aggregate_sort
from .graph import BipartiteGraph, RankedGraph, preprocess
from .ranking import make_order
from .resilience import (
    Deadline,
    DeadlineExceeded,
    DeviceLost,
    ExecutionReport,
    RungAttempt,
    RungUnavailable,
    StragglerTimeout,
)
from .wedges import (
    auto_chunk_budget,
    device_graph,
    greedy_vertex_blocks,
    host_wedge_counts,
    slot_wedge_counts,
    wedge_offsets,
    wedges_at,
)

__all__ = [
    "plan_partition",
    "plan_fused_partition",
    "distributed_count",
    "distributed_count_fn",
    "launch_device_worker",
    "SupervisedPeel",
    "PeelSupervisor",
]

DIST_ENGINES = ("fused", "slice")

# Prepended to every worker payload: lets the chaos matrix kill, hang,
# or delay a specific launch attempt from the parent via the
# environment, before the worker imports jax (so a "lost device" looks
# exactly like a dead or wedged XLA client process, and a "slow" device
# like a straggling one — it still answers, just late).
_WORKER_FAULT_PREAMBLE = """\
import os as _os
_slow = _os.environ.pop("REPRO_FAULT_DEVICE_SLOW", None)
if _slow:
    import time as _time
    _time.sleep(float(_slow))
_mode = _os.environ.pop("REPRO_FAULT_DEVICE_LOSS", None)
if _mode == "hang":
    import time as _time
    _time.sleep(3600)
elif _mode:
    _os._exit(13)
"""


def launch_device_worker(
    code: str,
    *,
    devices: int = 1,
    device_index: int = 0,
    timeout_s: float = 540.0,
    retries: int = 1,
    backoff_s: float = 0.5,
    env: Optional[dict] = None,
    deadline_s: Optional[float] = None,
) -> str:
    """Run a Python worker payload against a forced ``devices``-wide
    CPU host platform, with bounded retry + exponential backoff and a
    per-attempt timeout — the per-device dispatch path of the
    resilience layer.

    This is a CPU simulation harness for tests, never a chip path: the
    child is pinned to ``JAX_PLATFORMS=cpu`` (so it can never claim an
    accelerator its parent holds) and gets
    ``--xla_force_host_platform_device_count`` appended to any
    ``XLA_FLAGS`` already set, plus the repro ``src`` dir on
    ``PYTHONPATH``; extra ``env`` entries overlay that. Each attempt asks the fault harness
    (:func:`repro.testing.faults.worker_env`) whether an armed
    ``device_loss`` fault should kill or hang this launch — a
    ``times=1`` fault consumes itself on the first attempt, so the
    retry runs clean and results stay bitwise-identical. A nonzero
    exit or a timeout burns one attempt; after ``retries`` extra
    attempts the failure surfaces as :class:`DeviceLost` carrying the
    failed ``device_index``, the attempt count, and the last stderr
    tail — never a silent empty result. Returns the worker's stdout.

    ``deadline_s`` bounds the *whole* dispatch (all attempts plus
    backoffs) for deadline-aware callers: each attempt's timeout is
    clamped to the remaining budget, backoff sleeps never overrun it,
    and an exhausted budget raises
    :class:`~repro.core.resilience.DeadlineExceeded` (the budget ran
    out — the device may be fine) rather than :class:`DeviceLost`.
    """
    src_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    base_env = dict(os.environ)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={int(devices)}",
    ]))
    base_env["PYTHONPATH"] = src_root
    if env:
        base_env.update(env)
    base_env.pop("REPRO_FAULT_DEVICE_LOSS", None)
    base_env.pop("REPRO_FAULT_DEVICE_SLOW", None)
    payload = _WORKER_FAULT_PREAMBLE + code
    attempts = int(retries) + 1
    last_detail = ""
    deadline = (
        None if deadline_s is None
        else Deadline(float(deadline_s))
    )
    for attempt in range(attempts):
        attempt_timeout = timeout_s
        if deadline is not None:
            remaining = deadline.remaining_s()
            if remaining <= 0:
                raise deadline.exceeded(
                    f"device worker {device_index}: dispatch budget "
                    f"{deadline.budget_s:.3f}s exhausted after "
                    f"{attempt} attempt(s); last: {last_detail or 'none'}"
                )
            attempt_timeout = min(timeout_s, remaining)
        attempt_env = _faults.worker_env(
            dict(base_env), device=device_index
        )
        try:
            out = subprocess.run(
                [sys.executable, "-c", payload],
                env=attempt_env,
                capture_output=True,
                text=True,
                timeout=attempt_timeout,
            )
        except subprocess.TimeoutExpired:
            last_detail = f"timed out after {attempt_timeout}s"
        else:
            if out.returncode == 0:
                return out.stdout
            last_detail = (
                f"exit code {out.returncode}; stderr tail: "
                f"{out.stderr[-2000:]}"
            )
        if attempt + 1 < attempts and backoff_s > 0:
            pause = backoff_s * (2 ** attempt)
            if deadline is not None:
                pause = min(pause, max(0.0, deadline.remaining_s()))
            time.sleep(pause)
    raise DeviceLost(
        f"device worker {device_index} failed after {attempts} "
        f"attempt(s): {last_detail}",
        device=device_index,
        attempts=attempts,
    )


def _vertex_loads(rg: RankedGraph, direction: str):
    """Per-vertex wedge loads (by iterating endpoint) and their prefix
    sum over rank space — the shared host-planning inputs."""
    cnt = host_wedge_counts(rg, direction)
    src = rg.edge_src[: 2 * rg.m]
    wv = np.zeros(rg.n_pad + 1, dtype=np.int64)
    np.add.at(wv, src, cnt[: 2 * rg.m])
    voff = np.concatenate([[0], np.cumsum(wv[: rg.n_pad])])
    return wv[: rg.n_pad], voff


def _device_vertex_starts(voff: np.ndarray, n_pad: int, n_dev: int):
    """Greedy wedge-balanced vertex boundaries, one range per device."""
    total = int(voff[-1])
    ideal = total / max(n_dev, 1)
    starts = [0]
    for d in range(1, n_dev):
        # first vertex boundary with cumulative wedges >= d * ideal
        b = int(np.searchsorted(voff, d * ideal, side="left"))
        starts.append(min(b, n_pad))
    starts.append(n_pad)
    return np.asarray(starts, dtype=np.int64)


def plan_partition(rg: RankedGraph, n_dev: int, direction: str = "low"):
    """Wedge-balanced, vertex-aligned device partition (host planning).

    Returns (w_start (n_dev,), w_cap) where device d owns global wedge
    ids [w_start[d], w_start[d+1]) padded to the common capacity w_cap.
    Greedy boundary placement: walk vertices, cut when the running wedge
    load reaches the ideal share — the wedge-aware batching heuristic.
    """
    _, voff = _vertex_loads(rg, direction)
    starts = _device_vertex_starts(voff, rg.n_pad, n_dev)
    w_start = voff[starts]
    per_dev = np.diff(w_start)
    cap = int(per_dev.max(initial=1))
    cap = max(128, ((cap + 127) // 128) * 128)
    return w_start.astype(np.int32), cap


def plan_fused_partition(
    rg: RankedGraph,
    n_dev: int,
    direction: str = "low",
    max_chunk="auto",
):
    """Per-device vertex-aligned tile plan for the fused engine.

    The whole flat wedge space is tiled once by the pipeline planner
    (``pipeline.plan_count`` — at most ``max_chunk`` wedges per tile,
    ``"auto"`` -> ``wedges.auto_chunk_budget``, cut only at vertex
    boundaries), then the tile list is split across devices greedily by
    wedge load (``pipeline.plan_partition``). Both cuts respect the
    tile-alignment invariant, so per-tile aggregation stays exact and
    the per-device partials add bitwise.

    Returns ``(tiles (n_dev, max_tiles, 2) int32, tile_cap)``: flat
    wedge-id [start, end) per tile, rows padded with empty (0, 0)
    tiles; ``tile_cap`` is the common padded per-tile buffer size.
    """
    budget = (
        auto_chunk_budget() if max_chunk in (None, "auto") else int(max_chunk)
    )
    plan = _pipeline.plan_count(
        rg, mode="global", direction=direction, aggregation="sort",
        budget=budget, engine="fused",
    )
    parts = _pipeline.plan_partition(plan, n_dev)
    return _pipeline.partition_tile_array(parts)


def distributed_count_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    w_cap: int,
    mode: str,
    direction: str = "low",
    dtype=jnp.int32,
    precomputed_offsets: bool = False,
    combine: str = "all",
    engine: str = "slice",
):
    """Build the jitted shard_mapped counting step for a mesh.

    The default keeps the historical low-level contract
    (``engine="slice"``: per-device slice bounds); the end-to-end
    ``distributed_count`` passes ``engine="fused"`` with tile-style
    bounds.

    ``engine="fused"``: the returned function takes
    (dg, tiles[, w_off]) where ``tiles`` is an (n_dev, max_tiles, 2)
    int32 array of per-tile [start, end) flat wedge ids (from
    ``plan_fused_partition``), sharded over the flattened mesh axes;
    each device runs the shared fused tile loop (generate ->
    sort-aggregate -> accumulate -> discard per tile; ``w_cap`` is the
    per-TILE buffer size). ``engine="slice"``: takes (dg, w_bounds[,
    w_off]) with w_bounds (n_dev, 2) and materializes + aggregates the
    whole local slice at once (``w_cap`` = per-device slice buffer).
    ``dg`` is replicated in both cases.

    ``precomputed_offsets``: pass the global wedge-prefix array as a
    replicated input instead of recomputing the O(e_pad · log deg)
    rank-filtered counts *per device* — the §Perf-3 fix (the prefix is a
    byproduct of host partition planning anyway).
    ``combine``: "all" -> psum (replicated counts); "scatter" ->
    psum_scatter (vertex-mode counts stay sharded over devices — halves
    the wire bytes and the production deployment keeps them sharded).
    """
    if engine not in DIST_ENGINES:
        raise ValueError(
            f"engine must be {'|'.join(DIST_ENGINES)}, got {engine}"
        )
    axes = tuple(axis_names)
    repl = P()
    sharded = P(axes)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))

    def _local_counts(dg, bounds, cnt, w_off):
        if engine == "fused":
            n_tiles = bounds.shape[1]
            acc0 = _pipeline.zero_counts(dg, mode, dtype)

            def body(i, acc):
                out, _ok = _pipeline.count_tile_step(
                    dg, cnt, w_off, bounds[0, i, 0], bounds[0, i, 1],
                    chunk_cap=w_cap, aggregation="sort", mode=mode,
                    direction=direction, dtype=dtype, engine="xla",
                )
                return jax.tree_util.tree_map(
                    lambda a, o: (a + o).astype(a.dtype), acc, out
                )

            return jax.lax.fori_loop(0, n_tiles, body, acc0)
        start = bounds[0, 0]
        end = bounds[0, 1]
        wid = start + jnp.arange(w_cap, dtype=jnp.int32)
        valid = wid < end
        w = wedges_at(dg, cnt, w_off, wid, valid, direction)
        groups, w = aggregate_sort(w)
        return _pipeline.accumulate_counts(dg, w, groups, mode, dtype)

    def _count(dg, bounds, cnt, w_off):
        out = _local_counts(dg, bounds, cnt, w_off)
        if combine == "scatter" and mode in ("vertex", "edge"):
            pad = (-out.shape[0]) % n_dev
            out = jnp.pad(out, (0, pad))
            return jax.lax.psum_scatter(
                out, axes, scatter_dimension=0, tiled=True
            )
        return jax.lax.psum(out, axes)

    if precomputed_offsets:
        def local(dg, bounds, w_off):
            return _count(dg, bounds, None, w_off)

        in_specs = (repl, sharded, repl)
    else:
        def local(dg, bounds):
            cnt = slot_wedge_counts(dg, direction)
            w_off = wedge_offsets(cnt)
            return _count(dg, bounds, cnt, w_off)

        in_specs = (repl, sharded)

    out_specs = sharded if combine == "scatter" and mode != "global" else repl
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def distributed_count(
    g: BipartiteGraph,
    mesh: Mesh,
    axis_names: Optional[Sequence[str]] = None,
    *,
    order: str = "degree",
    mode: str = "global",
    cache_opt: bool = False,
    count_dtype=None,
    precomputed_offsets: bool = True,
    combine: str = "all",
    engine: str = "fused",
    max_chunk="auto",
):
    """End-to-end distributed counting on an existing mesh.

    ``engine="fused"`` (default) streams each device's wedge slice
    through vertex-aligned tiles of at most ``max_chunk`` wedges
    (``"auto"`` derives the budget from device memory stats) — per-
    device peak temp memory O(tile). ``engine="slice"`` materializes
    the whole per-device slice (the pre-fused behavior). Both produce
    bitwise-identical counts.
    """
    axis_names = tuple(axis_names or mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    direction = "high" if cache_opt else "low"
    ordering = make_order(g, order)
    rg = preprocess(g, ordering, order_name=order)
    if engine == "fused":
        bounds, cap = plan_fused_partition(
            rg, n_dev, direction, max_chunk=max_chunk
        )
    else:
        w_start, cap = plan_partition(rg, n_dev, direction)
        bounds = np.stack(
            [w_start[:-1], w_start[1:]], axis=1
        ).astype(np.int32)
    dg = device_graph(rg)
    fn = distributed_count_fn(
        mesh,
        axis_names,
        w_cap=cap,
        mode=mode,
        direction=direction,
        dtype=count_dtype or jnp.int32,
        precomputed_offsets=precomputed_offsets,
        combine=combine,
        engine=engine,
    )
    sharding = NamedSharding(mesh, P(axis_names))
    bounds_dev = jax.device_put(jnp.asarray(bounds), sharding)
    dg_repl = jax.device_put(dg, NamedSharding(mesh, P()))
    if precomputed_offsets:
        cnt_host = host_wedge_counts(rg, direction)
        w_off = np.concatenate([[0], np.cumsum(cnt_host)]).astype(np.int32)
        w_off_dev = jax.device_put(
            jnp.asarray(w_off), NamedSharding(mesh, P())
        )
        out = fn(dg_repl, bounds_dev, w_off_dev)
    else:
        out = fn(dg_repl, bounds_dev)
    return out, rg


# ---------------------------------------------------------------------------
# Distributed peeling: the supervised, checkpointable round loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SupervisedPeel:
    """Result of one supervised distributed peeling run — the peel
    numbers plus the recovery audit the frontend folds into its
    :class:`~repro.core.resilience.ExecutionReport`."""

    numbers: np.ndarray
    rounds: int  # bucket rounds (range-mode ρ)
    round_sizes: np.ndarray
    sub_rounds: int  # re-settle iterations (== exact-mode ρ)
    checkpoint_restores: int
    device_reports: List[ExecutionReport]
    devices_initial: int
    devices_final: int
    resumed_from_round: int  # 0 = fresh start


@dataclasses.dataclass
class _RoundState:
    """Mutable supervisor state between checkpoints."""

    b: np.ndarray  # remaining support (counts)
    alive: np.ndarray  # bool per entity
    out: np.ndarray  # peel numbers assigned so far
    kappa: int
    hi: int  # exclusive bound of the active geometric bucket
    rounds: int
    subr: int
    sizes: list


class PeelSupervisor:
    """The distributed peeling round loop: coarse bucket selection on
    the host, per-range fine passes fanned out across a worker mesh,
    one checkpoint per committed round, and elastic recovery.

    Round structure (Lakhotia-style two-phase, extending PR 5's range
    mode): the **coarse phase** reads the geometric occupancy of the
    remaining support and opens the lowest non-empty bucket
    ``[2^(k-1), 2^k)``; the **fine phase** re-settles that bucket to
    completion — peel every alive entity with support ≤ κ, fan the
    frontier's subtract work out across the devices, reduce the
    per-device partial decrements, advance κ — until the masked min
    leaves the bucket. This replays exactly the κ trajectory of the
    single-device engines (`peel._RoundAccounting`), so the numbers
    are bitwise-identical by construction, not by luck.

    Fan-out goes through ``pipeline.plan_partition`` over the peeling
    plan's coarse entity tiles: device *i* owns a contiguous entity
    range, every frontier item is routed by its **iterating entity**
    (the peeled vertex for tips, the peeled edge for wings), and since
    every subtract group is keyed by that entity, no group spans a
    device — integer partial decrements add exactly in any order.

    Recovery ladder, every path bitwise-identical or typed:

      - **DeviceLost** (a worker dies mid-round): drop the device,
        re-run ``plan_partition`` over the survivors, restore the last
        committed :class:`~repro.core.checkpoint.RoundCheckpoint`, and
        replay the round. Counted in ``checkpoint_restores``.
      - **Straggler** (a device misses the per-round deadline derived
        from the plan's wedge totals): re-dispatch its sub-plan to a
        free worker and keep the first completion — both compute the
        same integers, so whichever answers first is the answer.
      - **Repeated failure**: a second consecutive deadline miss
        raises :class:`~repro.core.resilience.StragglerTimeout`; all
        devices lost raises
        :class:`~repro.core.resilience.RungUnavailable`. Both descend
        the caller's resilience ladder to the single-device engines —
        never a silent partial decomposition.

    The decomposition-specific pieces come in as two callables:
    ``expand(a_ids, alive, peel) -> (owner, payload)`` enumerates one
    round's frontier (``owner`` ascending iterating-entity ids;
    ``payload`` a tuple of equal-length arrays), and
    ``subtract(payload_slice) -> partial`` turns one device's slice
    into a dense decrement array. Both are plain numpy — exact integer
    arithmetic, bitwise-equal to the jitted single-device subtracts.
    """

    def __init__(
        self,
        workload: str,
        plan,
        counts: np.ndarray,
        *,
        expand: Callable,
        subtract: Callable,
        devices: int,
        checkpoint=None,
        round_deadline_s: Optional[float] = None,
        deadline_s: Optional[float] = None,
    ):
        self.workload = workload
        self.plan = plan
        self.counts = np.asarray(counts)
        self.expand = expand
        self.subtract = subtract
        self.devices = int(devices)
        if self.devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        if isinstance(checkpoint, _ckpt.CheckpointStore):
            self.store = checkpoint
        elif checkpoint is None:
            self.store = _ckpt.CheckpointStore()
        else:
            self.store = _ckpt.CheckpointStore(directory=str(checkpoint))
        # per-round deadline from the plan's static expansion totals:
        # generous (never fires on a healthy CPU worker at bench scale)
        # but bounded, so a wedged worker can't stall the run for the
        # full subprocess timeout the way a 3600 s hang would
        if round_deadline_s is None:
            round_deadline_s = max(5.0, 1e-6 * float(plan.total_wedges))
        self.round_deadline_s = float(round_deadline_s)
        # overall run budget for deadline-aware callers (the serving
        # layer): the countdown starts at run(), clamps every per-round
        # straggler deadline, and raises DeadlineExceeded (degradable —
        # the ladder descends to a cheaper rung) when it runs out
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self._deadline: Optional[Deadline] = None
        self.plan_hash = _ckpt.plan_hash(plan)
        self._stats = {
            d: {"rounds": 0, "redispatch": 0, "lost": 0}
            for d in range(self.devices)
        }

    # -- partition ----------------------------------------------------

    def _entity_ranges(self, live: list) -> list:
        """Contiguous entity range per live device via plan_partition
        over the surviving device count (the elastic re-partition)."""
        parts = _pipeline.plan_partition(self.plan, len(live))
        ranges = []
        for p in parts:
            if p.n_tiles:
                ranges.append((int(p.bounds[0]), int(p.bounds[-1])))
            else:
                ranges.append((0, 0))
        return ranges

    # -- worker task --------------------------------------------------

    def _device_task(self, round_ix: int, d: int, payload):
        site = f"distributed.peel.round{round_ix}.dev{d}"
        _faults.maybe_device_loss(site, device=d)
        _faults.maybe_slow(site, device=d)
        return self.subtract(payload)

    # -- fine-pass fan-out with straggler re-dispatch -----------------

    def _round_budget_s(self) -> float:
        """Per-round straggler deadline, clamped to the remaining
        overall ``deadline_s`` budget when one is active; an exhausted
        budget raises :class:`DeadlineExceeded` (degradable — the
        caller's ladder descends instead of waiting out a round the
        query can no longer afford)."""
        if self._deadline is None:
            return self.round_deadline_s
        remaining = self._deadline.remaining_s()
        if remaining <= 0:
            raise self._deadline.exceeded(
                f"{self.workload}: run budget "
                f"{self._deadline.budget_s:.3f}s exhausted mid-round"
            )
        return min(self.round_deadline_s, remaining)

    def _fanout(self, pool, round_ix: int, live: list, ranges: list,
                owner: np.ndarray, payload: tuple) -> list:
        slices = {}
        for i, d in enumerate(live):
            lo, hi = ranges[i]
            s = int(np.searchsorted(owner, lo, side="left"))
            e = int(np.searchsorted(owner, hi, side="left"))
            slices[d] = tuple(a[s:e] for a in payload)
        primary = {
            d: pool.submit(self._device_task, round_ix, d, slices[d])
            for d in live
        }
        fut_dev = {f: d for d, f in primary.items()}
        pending = dict(primary)
        dups: dict = {}
        results: dict = {}
        deadline = time.monotonic() + self._round_budget_s()
        while pending:
            waitset = [
                f
                for d in pending
                for f in (pending[d], dups.get(d))
                if f is not None
            ]
            timeout = max(0.0, deadline - time.monotonic())
            done, _ = _cf.wait(
                waitset, timeout=timeout,
                return_when=_cf.FIRST_COMPLETED,
            )
            progressed = False
            for f in done:
                d = fut_dev[f]
                if d in results:
                    continue  # the twin already answered
                # first completion wins; a raising future (DeviceLost)
                # surfaces here and the run loop handles recovery
                results[d] = f.result()
                self._stats[d]["rounds"] += 1
                pending.pop(d, None)
                dups.pop(d, None)
                progressed = True
            if progressed:
                deadline = time.monotonic() + self._round_budget_s()
                continue
            if time.monotonic() < deadline:
                continue
            # deadline passed, nothing finished: every still-pending
            # device is a straggler — re-dispatch once to a free
            # worker slot; a second miss is a typed failure
            for d in sorted(pending):
                if d in dups:
                    raise StragglerTimeout(
                        f"{self.workload}: device {d} missed the "
                        f"{self.round_deadline_s:.3f}s round deadline "
                        f"twice (round {round_ix})",
                        device=d,
                        deadline_s=self.round_deadline_s,
                    )
                nf = pool.submit(
                    self._device_task, round_ix, d, slices[d]
                )
                dups[d] = nf
                fut_dev[nf] = d
                self._stats[d]["redispatch"] += 1
            deadline = time.monotonic() + self._round_budget_s()
        # fixed ascending-device reduction order (immaterial for the
        # integer sums, deterministic for everything else)
        return [results[d] for d in sorted(results)]

    # -- the round loop -----------------------------------------------

    def _capture(self, st: _RoundState) -> None:
        self.store.save(_ckpt.RoundCheckpoint.capture(
            plan_hash=self.plan_hash,
            round_index=st.rounds,
            sub_rounds=st.subr,
            kappa=st.kappa,
            bucket_hi=st.hi,
            support=st.b,
            alive=st.alive,
            numbers=st.out,
            round_sizes=st.sizes,
        ))

    def _restore(self) -> _RoundState:
        cp = self.store.restore(self.plan_hash)
        b, alive, out = cp.arrays()
        return _RoundState(
            b=b, alive=alive, out=out, kappa=cp.kappa, hi=cp.bucket_hi,
            rounds=cp.round_index, subr=cp.sub_rounds,
            sizes=list(cp.round_sizes),
        )

    def _bucket_round(self, pool, st: _RoundState, live: list,
                      ranges: list) -> None:
        """One coarse bucket + its fine re-settle passes, mutating
        ``st``. Raises DeviceLost/StragglerTimeout without committing —
        the caller restores the last checkpoint."""
        imax = np.iinfo(st.b.dtype).max
        round_ix = st.rounds
        mn = int(np.where(st.alive, st.b, imax).min())
        st.kappa = max(st.kappa, mn)
        # coarse phase: the masked min's bit length names the lowest
        # non-empty geometric bucket [2^(k-1), 2^k) — identical to the
        # device engines' occupancy-histogram selection (PR 5)
        st.hi = 1 << int(mn).bit_length()
        st.rounds += 1
        st.sizes.append(0)
        while True:
            st.subr += 1
            peel = st.alive & (st.b <= st.kappa)
            a_ids = np.flatnonzero(peel)
            st.out[a_ids] = st.kappa
            st.alive[a_ids] = False
            st.sizes[-1] += int(a_ids.size)
            if not st.alive.any():
                return
            owner, payload = self.expand(a_ids, st.alive, peel)
            if owner.size:
                partials = self._fanout(
                    pool, round_ix, live, ranges, owner, payload
                )
                for p in partials:
                    st.b -= p.astype(st.b.dtype, copy=False)
            mn = int(np.where(st.alive, st.b, imax).min())
            if mn >= st.hi:
                return  # min left the bucket: round committed
            st.kappa = max(st.kappa, mn)

    def run(self) -> SupervisedPeel:
        n_out = int(self.counts.shape[0])
        resumed_from = 0
        if self.store.latest() is not None:
            # cross-process resume: continue from the stored snapshot
            st = self._restore()
            self.store.restores -= 1  # construction-time, not recovery
            resumed_from = st.rounds
        else:
            st = _RoundState(
                b=self.counts.copy(),
                alive=np.ones(n_out, dtype=bool),
                out=np.zeros(n_out, dtype=self.counts.dtype),
                kappa=0, hi=0, rounds=0, subr=0, sizes=[],
            )
            self._capture(st)  # round-0 snapshot anchors first rollback
        live = list(range(self.devices))
        ranges = self._entity_ranges(live)
        restores = 0
        self._deadline = (
            None if self.deadline_s is None else Deadline(self.deadline_s)
        )
        pool = _cf.ThreadPoolExecutor(
            max_workers=self.devices + 1,
            thread_name_prefix="peel-dev",
        )
        try:
            while st.alive.any():
                if (self._deadline is not None
                        and self._deadline.expired()):
                    # the committed rounds live in the checkpoint store;
                    # a re-run with more budget resumes, doesn't restart
                    raise self._deadline.exceeded(
                        f"{self.workload}: run budget "
                        f"{self._deadline.budget_s:.3f}s exhausted after "
                        f"{st.rounds} committed round(s)"
                    )
                try:
                    self._bucket_round(pool, st, live, ranges)
                except DeviceLost as e:
                    d = e.device if e.device in live else live[0]
                    live.remove(d)
                    self._stats[d]["lost"] += 1
                    if not live:
                        raise RungUnavailable(
                            f"{self.workload}: all {self.devices} mesh "
                            f"devices lost (last: device {d}; "
                            f"{restores} checkpoint restores)"
                        ) from e
                    st = self._restore()
                    restores += 1
                    ranges = self._entity_ranges(live)
                    continue
                self._capture(st)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
        return SupervisedPeel(
            numbers=st.out,
            rounds=st.rounds,
            round_sizes=np.asarray(st.sizes),
            sub_rounds=st.subr,
            checkpoint_restores=restores,
            device_reports=self._device_reports(live),
            devices_initial=self.devices,
            devices_final=len(live),
            resumed_from_round=resumed_from,
        )

    def _device_reports(self, live: list) -> List[ExecutionReport]:
        reports = []
        for d in range(self.devices):
            s = self._stats[d]
            outcome = "device-lost" if s["lost"] else "ok"
            rep = ExecutionReport(
                workload=f"{self.workload}@dev{d}",
                requested="worker",
            )
            rep.attempts.append(RungAttempt(
                rung=f"dev{d}",
                outcome=outcome,
                detail=(
                    f"rounds={s['rounds']} "
                    f"redispatches={s['redispatch']} losses={s['lost']}"
                ),
                retries=s["redispatch"],
            ))
            rep.final_rung = f"dev{d}" if d in live else None
            reports.append(rep)
        return reports

"""Paper Table 4 + Figs. 12-13: tip/wing decomposition runtimes across
wedge-aggregation methods; reports ρ (peeling complexity) per graph.

``write_json`` additionally produces the machine-readable
``BENCH_peeling.json`` trajectory (schema v3) comparing:

  - the host round loop vs the device-resident ``engine="device"``
    while_loop (wall time, round count ρ, blocking host syncs), with
    the device tip and wing programs' compiled peak-temp-memory bytes
    per (graph, algo) beside the frontier wedge total they stream;
  - **exact vs bucket-range rounds** (``peel_mode=`` axis, schema v3):
    every row records both ρ (bucket rounds under range mode) and the
    re-settle iteration count ``sub_rounds``; the derived
    ``range_rho_reduction`` per (graph, algo) is the measured
    Lakhotia-style round-count win, and ``range_bitwise_equal``
    asserts the numbers stayed bitwise-identical.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit

from repro.core import count_butterflies
from repro.core.count import default_count_dtype
from repro.core.peel import (
    _csr,
    _init_state,
    _level2_totals,
    _peel_tips_device,
    _peel_wings_device,
    _pow2_pad,
    _stored_wedge_csr,
    _tip_tile_cap,
    _wing_work_totals,
    peel_tips,
    peel_tips_stored,
    peel_wings,
)
from repro.core.wedges import degree_sorted_csr
from repro.data.graphs import powerlaw_bipartite

PEEL_GRAPHS = {
    "peel_small": lambda: powerlaw_bipartite(600, 500, 4_000, seed=7),
    "peel_medium": lambda: powerlaw_bipartite(3_000, 2_500, 18_000, seed=8),
}

# Off-TPU the device rows run no interpret-mode kernel (the dispatcher
# serves the jnp reference), so they are gated only by total expansion
# work; the in-loop hash table is skipped visibly, never silently.
WORK_BUDGET = 1 << 22


def _tip_lvl2(g, side: int) -> int:
    """Worst-case level-2 expansion total used for row gating (mirrors
    the device planner): Σ other-side deg²."""
    du, dv = g.degrees()
    other = du if side == 1 else dv
    return int((other.astype(np.int64) ** 2).sum())


def _device_row_ok(g, side, agg):
    if jax.default_backend() == "tpu":
        return True, ""
    if agg != "sort":
        return False, "interpret-mode budget (in-loop hash table)"
    lvl2 = _tip_lvl2(g, side)
    if lvl2 > WORK_BUDGET:
        return False, f"work budget (lvl2={lvl2})"
    return True, ""


def _wings_row_ok(g):
    """Gated by the total streamed triple work: the device planner's
    own per-edge totals (`peel._wing_work_totals`), summed."""
    if jax.default_backend() == "tpu":
        return True, ""
    off, nbr, _ = _csr(g)
    lvl2 = int(_wing_work_totals(g, off, nbr)[3].sum())
    if lvl2 > WORK_BUDGET:
        return False, f"work budget (triple space lvl2={lvl2})"
    return True, ""


def _count_host_syncs(fn):
    """Run ``fn`` counting blocking ``jax.device_get`` calls."""
    calls = {"n": 0}
    orig = jax.device_get

    def counted(x):
        calls["n"] += 1
        return orig(x)

    jax.device_get = counted
    try:
        out = fn()
    finally:
        jax.device_get = orig
    return out, calls["n"]


def _time_warm(fn, repeats: int = 1) -> float:
    """Best-of-N timing with no extra warmup call — callers have
    already executed ``fn`` once (the sync-count run compiles and warms
    the jit caches), so each row runs the decomposition twice total."""
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _tip_inputs(g):
    rv = count_butterflies(g, mode="vertex", count_dtype=default_count_dtype())
    side = 0 if g.wedge_totals()[0] <= g.wedge_totals()[1] else 1
    return side, np.asarray(rv.per_u if side == 0 else rv.per_v)


def _device_temp_bytes(g, side: int, stored: bool) -> dict:
    """Compiled peak-temp bytes of the device tip program (same tile
    planning as ``peel._peel_tips_device_run``)."""
    n_side = g.n_u if side == 0 else g.n_v
    base = 0 if side == 0 else g.n_u
    if stored:
        woff, w_u2 = _stored_wedge_csr(g, side)
        lvl2 = int(woff[-1])
        cap1 = 128
        off_d = jnp.asarray(woff, jnp.int32)
        nbr_d = jnp.asarray(w_u2 if lvl2 else np.zeros(1), jnp.int32)
        max_row = int(np.diff(woff).max(initial=0))
    else:
        off, nbr, _ = _csr(g)
        w2 = _level2_totals(off, nbr, base, n_side)
        lvl2 = int(w2.sum())
        cap1 = _pow2_pad(int(np.diff(off)[base : base + n_side].sum()))
        off_d = jnp.asarray(off, jnp.int32)
        nbr_d = jnp.asarray(nbr, jnp.int32)
        max_row = int(w2.max(initial=0))
    tile_cap = _tip_tile_cap(None, lvl2, 2 * max_row)
    dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    st = _init_state(jnp.zeros(n_side, dtype), n_side, peel_mode="exact")
    mem = _peel_tips_device.lower(
        off_d, nbr_d, jnp.int32(base), st, aggregation="sort", cap1=cap1,
        tile_cap=tile_cap, n_side=n_side, stored=stored,
    ).compile().memory_analysis()
    return {
        "frontier_wedges": lvl2,
        "tile_cap": int(tile_cap),
        "temp_bytes": int(mem.temp_size_in_bytes),
    }


def _wings_temp_bytes(g) -> dict:
    """Compiled peak-temp bytes of the device wing program: the
    two-level fused recovery holds no buffer sized by the O(Σ deg²)
    triple space (same planning as ``peel._peel_wings_device_run``)."""
    from repro.core.peel import _DEFAULT_TILE_TARGET

    off, nbr, uid = _csr(g)
    m = g.m
    eu, ev, l1, l2 = _wing_work_totals(g, off, nbr)
    lvl2 = int(l2.sum())
    nbr_ds, uid_ds, degs_ds, cumdeg = degree_sorted_csr(off, nbr, uid)
    tile_cap = _pow2_pad(min(_DEFAULT_TILE_TARGET, max(lvl2, 1)))
    dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    st = _init_state(jnp.zeros(m, dtype), m, peel_mode="exact")
    args = tuple(
        jnp.asarray(a if np.asarray(a).size else np.zeros(1), jnp.int32)
        for a in (off, nbr, uid, eu, ev, nbr_ds, uid_ds, degs_ds, cumdeg,
                  l1, l2)
    )
    mem = _peel_wings_device.lower(
        *args, st, aggregation="sort", m=m, tile_cap=tile_cap,
    ).compile().memory_analysis()
    return {
        "frontier_wedges": lvl2,
        "tile_cap": int(tile_cap),
        "temp_bytes": int(mem.temp_size_in_bytes),
    }


def write_json(path, graphs=("peel_small",), repeats: int = 1) -> dict:
    """Peeling engine trajectory (schema v3): per (graph, algo, engine,
    aggregation, peel_mode) wall time, rounds (bucket rounds under
    ``peel_mode="range"``), re-settle ``sub_rounds``, and host-sync
    count; compiled peak-temp bytes of the device programs per (graph,
    algo); the derived range-mode ρ reduction (with a bitwise-parity
    check against the exact rows).
    Wall times exclude the butterfly counting pass (counts are
    precomputed once per graph — the decomposition loop is what the
    engines differ on). ``path=None`` builds the payload without
    writing a file."""
    payload: dict = {
        "schema": "bench_peeling/v3",
        "backend": jax.default_backend(),
        "graphs": {},
        "runs": [],
        "memory": [],
        "derived": {},
        "skipped": [],
    }

    def add_row(gname, algo, engine, agg, res, syncs, wall,
                peel_mode="exact"):
        payload["runs"].append({
            "graph": gname,
            "algo": algo,
            "engine": engine,
            "aggregation": agg,
            "peel_mode": peel_mode,
            "rounds": int(res.rounds),
            "sub_rounds": int(
                res.rounds if res.sub_rounds is None else res.sub_rounds
            ),
            "max_number": int(res.numbers.max(initial=0)),
            "host_syncs": syncs,
            "wall_s": wall,
        })

    def skip(gname, algo, engine, agg, reason):
        payload["skipped"].append({
            "graph": gname,
            "algo": algo,
            "engine": engine,
            "aggregation": agg,
            "reason": reason,
        })

    range_info: dict = {}

    def range_rows(gname, algo, run_host, run_device, device_ok,
                   ref_res):
        """One host + one default-device ``peel_mode="range"`` row,
        plus the derived ρ-reduction bookkeeping vs the exact rows."""
        res, syncs = _count_host_syncs(run_host)
        t = _time_warm(run_host, repeats=repeats)
        add_row(gname, algo, "host", "sort", res, syncs, t,
                peel_mode="range")
        equal = bool(np.array_equal(res.numbers, ref_res.numbers))
        rng_rounds = int(res.rounds)
        ok, reason = device_ok
        if ok:
            dres, syncs = _count_host_syncs(run_device)
            t = _time_warm(run_device, repeats=repeats)
            add_row(gname, algo, "device", "sort", dres, syncs, t,
                    peel_mode="range")
            equal = equal and bool(
                np.array_equal(dres.numbers, ref_res.numbers)
            )
            rng_rounds = int(dres.rounds)
        else:
            skip(gname, algo, "device", "sort", reason)
        range_info[f"{gname}/{algo}"] = {
            "exact_rho": int(ref_res.rounds),
            "range_rho": rng_rounds,
            "range_rho_reduction": int(ref_res.rounds) / max(rng_rounds, 1),
            "range_sub_rounds": int(res.sub_rounds),
            "range_bitwise_equal": equal,
        }

    for gname in graphs:
        g = PEEL_GRAPHS[gname]()
        side, counts = _tip_inputs(g)
        payload["graphs"][gname] = {
            "n_u": g.n_u, "n_v": g.n_v, "m": g.m, "side": side,
        }
        for algo, fn in (
            ("peel_tips", peel_tips),
            ("peel_tips_stored", peel_tips_stored),
        ):
            ref_res = None  # host sort exact run: parity ref
            for engine in ("host", "device"):
                for agg in ("sort", "hash"):
                    if engine == "device":
                        ok, reason = _device_row_ok(g, side, agg)
                        if not ok:
                            skip(gname, algo, engine, agg, reason)
                            continue
                    run = lambda: fn(  # noqa: E731
                        g, counts=counts, side=side, aggregation=agg,
                        engine=engine,
                    )
                    res, syncs = _count_host_syncs(run)  # also warms jit
                    if engine == "host" and agg == "sort":
                        ref_res = res
                    t = _time_warm(run, repeats=repeats)
                    add_row(gname, algo, engine, agg, res, syncs, t)
            # peel_mode="range": bucket rounds, bitwise-equal numbers
            range_rows(
                gname, algo,
                lambda: fn(g, counts=counts, side=side, engine="host",
                           peel_mode="range"),
                lambda: fn(g, counts=counts, side=side, engine="device",
                           peel_mode="range"),
                _device_row_ok(g, side, "sort"),
                ref_res,
            )
            payload["memory"].append({
                "graph": gname,
                "algo": algo,
                **_device_temp_bytes(g, side, algo == "peel_tips_stored"),
            })

        # PEEL-E: host loop + the device engine
        re_ = count_butterflies(
            g, mode="edge", count_dtype=default_count_dtype()
        )
        ecounts = np.asarray(re_.per_edge)
        run = lambda: peel_wings(g, counts=ecounts)  # noqa: E731
        wres, syncs = _count_host_syncs(run)
        t = _time_warm(run, repeats=repeats)
        add_row(gname, "peel_wings", "host", "sort", wres, syncs, t)
        ok, reason = _wings_row_ok(g)
        if ok:
            run = lambda: peel_wings(  # noqa: E731
                g, counts=ecounts, engine="device")
            res, syncs = _count_host_syncs(run)
            t = _time_warm(run, repeats=repeats)
            add_row(gname, "peel_wings", "device", "sort", res, syncs, t)
        else:
            skip(gname, "peel_wings", "device", "sort", reason)
        range_rows(
            gname, "peel_wings",
            lambda: peel_wings(g, counts=ecounts, engine="host",
                               peel_mode="range"),
            lambda: peel_wings(g, counts=ecounts, engine="device",
                               peel_mode="range"),
            _wings_row_ok(g),
            wres,
        )
        payload["memory"].append({
            "graph": gname,
            "algo": "peel_wings",
            **_wings_temp_bytes(g),
        })

    payload["derived"] = range_info
    if path:
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    return payload


def resilience_rows(graphs=("peel_small",), repeats: int = 3) -> dict:
    """Ladder-overhead audit rows for the peeling ladder: ``peel_tips``
    with the default resilience policy (validation + report) vs
    ``resilience=False``, min-of-``repeats`` warm wall time each, plus
    one injected transient-OOM smoke run proving the device rung's
    shrink-retry carries the decomposition. Counts are precomputed so
    the rows time the decomposition loop, not the counting pass."""
    from repro.testing import faults

    rows = {}
    for gname in graphs:
        g = PEEL_GRAPHS[gname]()
        side, counts = _tip_inputs(g)

        def best(fn):
            fn()  # warm the jit caches: we time the ladder, not XLA
            ts = []
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            return min(ts)

        t_on = best(lambda: peel_tips(
            g, counts=counts, side=side, engine="device"))
        t_off = best(lambda: peel_tips(
            g, counts=counts, side=side, engine="device",
            resilience=False))
        with faults.inject("oom", site="peel_tips.device", times=1):
            r = peel_tips(g, counts=counts, side=side, engine="device")
        rows[gname] = {
            "workload": "peel_tips/device",
            "ladder_enabled_s": t_on,
            "ladder_disabled_s": t_off,
            "overhead_pct": (
                100.0 * (t_on - t_off) / t_off if t_off > 0 else None
            ),
            "fault_smoke": r.report.summary(),
            "fault_smoke_retries": r.report.retries,
        }
    return rows


def append_resilience_rows(path: str, graphs=("peel_small",),
                           repeats: int = 3) -> None:
    """Read-modify-write the additive ``resilience`` key (schema stays
    ``bench_peeling/v3`` — the rows are an overlay, not a new version)."""
    with open(path) as f:
        payload = json.load(f)
    payload["resilience"] = resilience_rows(graphs=graphs, repeats=repeats)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    for gname, row in payload["resilience"].items():
        emit(
            f"peel_tips/{gname}/resilience_overhead",
            row["ladder_enabled_s"] * 1e6,
            f"disabled={row['ladder_disabled_s'] * 1e6:.1f}us,"
            f"overhead={row['overhead_pct']:.2f}%",
        )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", nargs="*", default=list(PEEL_GRAPHS))
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write the BENCH_peeling.json engine trajectory",
    )
    ap.add_argument("--faults", action="store_true",
                    help="append the resilience-overhead rows to --json")
    args = ap.parse_args(argv)
    # one sweep: the JSON payload is the source of truth, CSV rows are
    # derived from it (no second run of the decompositions)
    payload = write_json(args.json, graphs=tuple(args.graphs))
    for r in payload["runs"]:
        emit(
            f"{r['algo']}/{r['graph']}/{r['aggregation']}/{r['engine']}/"
            f"{r['peel_mode']}",
            r["wall_s"] * 1e6,
            f"rho={r['rounds']},sub={r['sub_rounds']},"
            f"max={r['max_number']},syncs={r['host_syncs']}",
        )
    for s in payload["skipped"]:
        emit(
            f"{s['algo']}/{s['graph']}/{s['aggregation']}/{s['engine']}",
            -1.0,
            f"SKIPPED:{s['reason']}",
        )
    for row in payload["memory"]:
        emit(
            f"{row['algo']}/{row['graph']}/temp_bytes",
            0.0,
            f"temp={row['temp_bytes']},"
            f"frontier_wedges={row['frontier_wedges']}",
        )
    if args.faults and args.json:
        append_resilience_rows(args.json, graphs=tuple(args.graphs))


if __name__ == "__main__":
    main()

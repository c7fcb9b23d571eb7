"""Run counting, peeling and serving once on one TPU chip, and check it.

    python chip_smoke.py                 # one chip, every phase
    python chip_smoke.py --four-chips    # distributed_count on a 4-chip mesh

Each phase goes through the entry points a user calls
(``count_butterflies``, ``peel_tips``, ``peel_wings``,
``ButterflyService``) on a seeded power-law bipartite graph, and fails
the run unless:

  * the resilience report shows the requested rung answering, with no
    descent, retry or budget shrink;
  * every device program the phase launched, compiled for the chip,
    holds the Mosaic kernels the phase is meant to run (the
    ``tpu_custom_call`` ops of ``compile().as_text()``);
  * the result agrees bitwise with an independent path (``engine=
    "fused"``, the host peeling engine, or the dense ``core/oracle``).

Progress goes to stdout, one line per fact; the last line is the JSON
device record. Without a TPU (or without the repository beside this
file) it exits nonzero and prints no record.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


# The count graph: side sizes of the smaller KONECT graphs of the
# paper's evaluation. 4 M drawn edges (3.3 M unique) is cut from 5 M
# for time: the three counting phases take about 710 s on one v5e at
# 4 M, and scale with the wedges (1.4x at 5 M), which would leave the
# peeling and serving phases no room in the smoke's 20-minute budget.
N_U, N_V, M = 500_000, 200_000, 4_000_000
# Tile budget of the sort-based engines ("fused", "pallas"): their TPU
# compile time grows steeply with the tile size.
CHUNK = 1 << 16
# Peeling and serving graph, cut from the same generator (side sizes in
# the count graph's ratio). Device peeling runs one round per distinct
# count value (1,160 tip and 1,637 wing rounds here), and the device
# and host decompositions of both kinds, and the service's, must finish
# with a margin in the time the counting phases leave.
PEEL_N_U, PEEL_N_V, PEEL_M = 12_500, 5_000, 50_000


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--m", type=int, default=M,
                   help="edges drawn for the count graph")
    p.add_argument("--four-chips", action="store_true",
                   help="run only distributed_count on 4 chips and its "
                        "single-chip comparison")
    return p.parse_args()


# --------------------------------------------------------------------------
# what ran: compile times and the Mosaic kernels of recorded programs
# --------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


def kernels_of(programs) -> set:
    """Names of the Mosaic kernels in the chip-compiled text of each
    recorded ``(program, args, kwargs)``."""
    names = set()
    for fn, args, kwargs in programs:
        text = fn.lower(*args, **kwargs).compile().as_text()
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                m = re.search(r'op_name="[^"]*/(\w+)/pallas_call', line)
                names.add(m.group(1) if m else "?")
    return names


class Phase:
    """Times one phase (wall and XLA compile seconds) and prints them."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = _COMPILE_S[0]
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            say(f"phase {self.name}: wall_s={time.perf_counter() - self.t0}"
                f" compile_s={_COMPILE_S[0] - self.c0}")
        return False


def require_kernels(phase: str, programs, want: set) -> None:
    found = kernels_of(programs)
    say(f"{phase}: programs={len(programs)} kernels={sorted(found)}")
    check(want <= found, f"{phase}: kernels {sorted(want - found)} missing "
                         f"from the compiled programs")


def require_no_descent(phase: str, report) -> None:
    say(f"{phase}: {report.summary().splitlines()[0]}")
    check(report.final_rung == report.requested,
          f"{phase}: ran {report.final_rung}, asked {report.requested}")
    for a in report.attempts:
        check(a.outcome == "ok" and a.retries == 0 and a.budget_shrinks == 0,
              f"{phase}: attempt {a}")


def same(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


def require_same_counts(phase: str, r, ref) -> None:
    for f in ("total", "per_u", "per_v", "per_edge"):
        check(same(getattr(r, f), getattr(ref, f)),
              f"{phase}: {f} differs from the reference")
    say(f"{phase}: parity ok (total, per_u, per_v, per_edge bitwise)")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def graph_facts(name: str, g, direction: str, seed: int, drawn: int) -> None:
    from repro.core.graph import preprocess
    from repro.core.ranking import make_order
    from repro.core.wedges import host_wedge_counts

    rg = preprocess(g, make_order(g, "degree"), order_name="degree")
    w = host_wedge_counts(rg, direction)
    csr = 4 * (rg.offsets.size + 3 * rg.neighbors.size + rg.side_of.size)
    say(f"graph {name}: powerlaw_bipartite seed={seed} n_u={g.n_u} "
        f"n_v={g.n_v} m_drawn={drawn} m={g.m} wedges_{direction}="
        f"{int(w.sum())} csr_bytes={csr} wedge_prefix_bytes="
        f"{4 * (w.size + 1)}")
    return int(w.sum())


def phase_count(g) -> None:
    import jax.numpy as jnp

    from repro.core import count_butterflies
    from repro.core.pipeline import record_programs

    kw = dict(mode="all", order="degree", cache_opt=True,
              count_dtype=jnp.int64)
    with Phase("count/fused_pallas"), record_programs() as p:
        r = count_butterflies(g, engine="fused_pallas", **kw)
    require_no_descent("count/fused_pallas", r.report)
    say(f"count/fused_pallas: {r.report.plan}")
    require_kernels("count/fused_pallas", p, {"wedge_fused"})
    del p
    total = int(r.total)
    check(int(r.per_u.sum()) + int(r.per_v.sum()) == int(r.per_edge.sum())
          == 4 * total, "count: sum(per_u)+sum(per_v) == sum(per_edge) "
                        "== 4*total violated")
    say(f"count: total={total} max_per_u={int(r.per_u.max())} "
        f"max_per_v={int(r.per_v.max())} max_per_edge="
        f"{int(r.per_edge.max())}; identities ok")
    with Phase("count/fused"):
        ref = count_butterflies(g, engine="fused", max_chunk=CHUNK, **kw)
    require_no_descent("count/fused", ref.report)
    require_same_counts("count/fused_pallas vs fused", r, ref)
    with Phase("count/pallas"), record_programs() as p:
        rp = count_butterflies(g, engine="pallas", max_chunk=CHUNK, **kw)
    require_no_descent("count/pallas", rp.report)
    require_kernels("count/pallas", p, {"butterfly_combine"})
    require_same_counts("count/pallas vs fused", rp, ref)


def narrow(counts, what: str):
    """int32 counts when they fit (the kernels reduce in int32)."""
    import numpy as np

    top = int(counts.max(initial=0))
    if top < np.iinfo(np.int32).max:
        say(f"{what}: max count {top} fits int32; peeling with int32 counts")
        return counts.astype(np.int32)
    say(f"{what}: max count {top} does NOT fit int32; peeling keeps "
        f"{counts.dtype} counts")
    return counts


def phase_peel(g):
    """Peels ``g`` on the device and host engines; returns its total,
    the peeled tip side and the host tip numbers."""
    import jax.numpy as jnp

    from repro.core import count_butterflies
    from repro.core.peel import peel_tips, peel_wings
    from repro.core.pipeline import record_programs

    w_u, w_v = g.wedge_totals()
    side = 0 if w_u <= w_v else 1
    with Phase("peel/counts"):
        rc = count_butterflies(g, mode="all", engine="fused", max_chunk=CHUNK,
                               count_dtype=jnp.int64)
    require_no_descent("peel/counts", rc.report)
    say(f"peel/counts: total={int(rc.total)} max_per_v="
        f"{int(rc.per_v.max())} max_per_edge={int(rc.per_edge.max())}")
    counts = narrow(rc.per_u if side == 0 else rc.per_v, "peel_tips")
    with Phase("peel_tips/device"), record_programs() as p:
        d = peel_tips(g, counts=counts, side=side, engine="device",
                      peel_mode="range")
    require_no_descent("peel_tips/device", d.report)
    require_kernels("peel_tips/device", p, {"bucket_update"})
    del p
    with Phase("peel_tips/host"):
        h = peel_tips(g, counts=counts, side=side, engine="host",
                      peel_mode="range")
    check(same(d.numbers, h.numbers), "peel_tips: device != host numbers")
    say(f"peel_tips: side={side} rounds={d.rounds} sub_rounds="
        f"{d.sub_rounds} max_tip={int(d.numbers.max())}; parity ok "
        f"(device == host bitwise)")

    ecounts = narrow(rc.per_edge, "peel_wings")
    with Phase("peel_wings/device"), record_programs() as p:
        wd = peel_wings(g, counts=ecounts, engine="device", peel_mode="range")
    require_no_descent("peel_wings/device", wd.report)
    require_kernels("peel_wings/device", p, {"bucket_update"})
    del p
    with Phase("peel_wings/host"):
        wh = peel_wings(g, counts=ecounts, engine="host", peel_mode="range")
    check(same(wd.numbers, wh.numbers), "peel_wings: device != host numbers")
    say(f"peel_wings: rounds={wd.rounds} sub_rounds={wd.sub_rounds} "
        f"max_wing={int(wd.numbers.max())}; parity ok (device == host "
        f"bitwise)")
    return int(rc.total), side, h.numbers


def phase_oracle(g):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import count_butterflies
    from repro.core.oracle import global_count, per_edge_counts
    from repro.core.oracle import per_vertex_counts
    from repro.core.peel import peel_wings
    from repro.core.pipeline import record_programs
    from repro.kernels import ops

    total = global_count(g)
    pu, pv = per_vertex_counts(g)
    pe = per_edge_counts(g)
    runs = [("fused_pallas", "sort", {"wedge_fused"}),
            ("pallas", "hash", {"wedge_count", "butterfly_combine"})]
    for engine, agg, want in runs:
        name = f"oracle/{engine}/{agg}"
        with Phase(name), record_programs() as p:
            r = count_butterflies(g, mode="all", engine=engine,
                                  aggregation=agg, count_dtype=jnp.int64)
        require_no_descent(name, r.report)
        require_kernels(name, p, want)
        check(int(r.total) == total and np.array_equal(r.per_u, pu)
              and np.array_equal(r.per_v, pv)
              and np.array_equal(r.per_edge, pe),
              f"{name}: differs from core/oracle")
        say(f"{name}: total={total}; oracle parity ok")
    # the host wing engine's per-round extract-min is the one caller of
    # the bucket_min kernel: record each call as a program to compile
    counts = pe.astype(np.int32)
    real_min = ops.bucket_min
    mins = []

    def recorded_min(b, alive, use_pallas=False, interpret=None):
        mins.append((jax.jit(real_min, static_argnames=("use_pallas",)),
                     (b, alive), {"use_pallas": use_pallas}))
        return real_min(b, alive, use_pallas=use_pallas, interpret=interpret)

    ops.bucket_min = recorded_min
    try:
        with Phase("oracle/peel_wings/host"):
            h = peel_wings(g, counts=counts, engine="host")
    finally:
        ops.bucket_min = real_min
    require_no_descent("oracle/peel_wings/host", h.report)
    check(mins and all(kw["use_pallas"] for _f, _a, kw in mins),
          "oracle/peel_wings/host: extract-min did not take the kernel")
    require_kernels("oracle/peel_wings/host", mins[:1], {"bucket_min"})
    d = peel_wings(g, counts=counts, engine="device")
    require_no_descent("oracle/peel_wings/device", d.report)
    check(same(d.numbers, h.numbers), "oracle peel_wings: device != host")
    say(f"oracle/peel_wings/host: {len(mins)} bucket_min calls; parity ok "
        f"(host == device bitwise)")


def phase_service(peel_g, peel_total, side, tips):
    """One service holding two graphs. ``wide``, the complete bipartite
    graph K(2, 70000), has C(70000, 2) butterflies, beyond int32: its
    exact count checks the service's count width against the closed
    form. The peeling graph gets an approx-tier count and a tip
    decomposition, checked against the peeling phases. Count queries
    name no engine: the service's default must be ``fused_pallas``."""
    import numpy as np

    from repro.core import BipartiteGraph
    from repro.core.pipeline import record_programs
    from repro.serve.service import ButterflyService, Query

    b = 70_000
    wide = BipartiteGraph(2, b, np.stack(
        [np.repeat(np.arange(2), b), np.tile(np.arange(b), 2)], axis=1))
    queries = [
        # each of wide's two iterating vertices owns 70000 wedges, more
        # than a kernel tile holds: per-vertex XLA tiles, no kernel
        ("count/exact", Query("wide"), set(), b * (b - 1) // 2),
        ("count/approx", Query("peel", accuracy="approx"), {"wedge_fused"},
         peel_total),
        # the resident tip inputs are counted, then peeled
        ("peel_tips", Query("peel", kind="peel_tips", engine="device",
                            peel_mode="range"),
         {"wedge_fused", "bucket_update"}, None),
    ]
    with ButterflyService(workers=1, queue_cap=4,
                          refine_approx=False) as svc:
        with Phase("service/register"):
            svc.register("wide", wide)
            svc.register("peel", peel_g)
        for name, q, want, ref_total in queries:
            phase = f"service/{name}"
            with Phase(phase), record_programs() as p:
                resp = svc.query(q)
            rep = resp.service
            say(f"{phase}: {rep.summary()}")
            check(rep.cache == "miss", f"{phase}: served from cache")
            require_no_descent(phase, resp.execution)
            check(not rep.approximate, f"{phase}: answered approximately")
            require_kernels(phase, p, want)
            del p
            if q.kind == "count":
                got = int(resp.result.total)
                check(resp.execution.requested == "fused_pallas",
                      f"{phase}: default engine {resp.execution.requested}")
                check(got == ref_total, f"{phase}: total {got} != "
                                        f"{ref_total}")
                say(f"{phase}: total={got} (int32 max "
                    f"{np.iinfo(np.int32).max}); parity ok")
            else:
                check(resp.result.side == side
                      and np.array_equal(resp.result.numbers, tips),
                      f"{phase}: tip numbers differ from the host engine")
                say(f"{phase}: side={side}; parity ok (tip numbers equal "
                    f"the host engine's)")


def phase_four_chips(g):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import count_butterflies
    from repro.core.distributed import distributed_count

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, JAX "
                                   f"sees {len(jax.devices())}")
    mesh = jax.make_mesh((4,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:4])
    # combine="all": XLA's TPU backend cannot rewrite an int64
    # reduce-scatter into 32-bit words, so exact int64 counts all-reduce
    with Phase("four_chips/distributed_count"):
        out, rg = distributed_count(g, mesh, mode="vertex", combine="all",
                                    cache_opt=True, count_dtype=jnp.int64,
                                    max_chunk=CHUNK)
        out.block_until_ready()
    devs = sorted(d.id for d in out.sharding.device_set)
    shards = sorted(s.device.id for s in out.addressable_shards)
    say(f"four_chips: output sharding device set={devs} "
        f"shard devices={shards}")
    check(len(devs) == 4 and len(set(shards)) == 4,
          "four_chips: output does not span four devices")
    bv = np.asarray(out)[: rg.n_pad]
    with Phase("four_chips/single_chip"):
        r = count_butterflies(g, mode="vertex", engine="fused_pallas",
                              cache_opt=True, count_dtype=jnp.int64)
    require_no_descent("four_chips/single_chip", r.report)
    check(same(bv[rg.rank_of_u], r.per_u) and same(bv[rg.rank_of_v], r.per_v),
          "four_chips: distributed per-vertex counts != single chip")
    say("four_chips: parity ok (per_u, per_v bitwise vs one chip)")


def main() -> None:
    args = parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {devices[0].platform} devices only")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"the repository is not beside this script ({e})")
    from repro.data.graphs import powerlaw_bipartite
    from repro.launch.cache import enable_compilation_cache

    jax.config.update("jax_enable_x64", True)
    say(f"compile cache: {enable_compilation_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind} count={len(devices)}")
    t_all = time.perf_counter()

    with Phase("graph"):
        g = powerlaw_bipartite(N_U, N_V, args.m, seed=args.seed)
        graph_facts("count", g, "high", args.seed, args.m)
    if args.four_chips:
        phase_four_chips(g)
    else:
        phase_count(g)
        del g
        peel_g = powerlaw_bipartite(PEEL_N_U, PEEL_N_V, PEEL_M,
                                    seed=args.seed + 1)
        graph_facts("peel (cut)", peel_g, "low", args.seed + 1, PEEL_M)
        peel_total, side, tips = phase_peel(peel_g)
        small = powerlaw_bipartite(200, 150, 1500, seed=args.seed + 2)
        graph_facts("oracle (cut)", small, "low", args.seed + 2, 1500)
        phase_oracle(small)
        phase_service(peel_g, peel_total, side, tips)
    say(f"total: wall_s={time.perf_counter() - t_all} "
        f"compile_s={_COMPILE_S[0]}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
